"""Pallas-vs-JAX e2e parity distribution on the real chip (VERDICT r3 #5).

Runs N (default 12) fresh-nonce, warmed, diff-32 end-to-end solves
through the ``jax`` and the ``pallas`` backends — the same nonce set for
both, so the comparison is paired — and reports median / p90 wall-clock
per backend plus the pallas/jax median ratio.  The acceptance bar from
the verdict: pallas median <= 1.2x jax median (the kernel is a
production path, not a showpiece).

Usage: python scripts/parity_pallas.py [N] [--difficulty NIBBLES]
Writes per-solve lines to stderr and ONE summary JSON line to stdout.
"""

from __future__ import annotations

import json
import os
import statistics
import sys
import time

sys.path.insert(0, ".")


def solve_times(backend, label: str, nonces, difficulty: int):
    from distpow_tpu.models import puzzle

    t0 = time.time()
    backend.warmup([len(nonces[0])], [0, 1, 2, 3, 4])
    print(f"[parity] {label} warmup: {time.time() - t0:.1f}s one-time",
          file=sys.stderr)
    times = []
    for nonce in nonces:
        t0 = time.time()
        secret = backend.search(nonce, difficulty, list(range(256)))
        dt = time.time() - t0
        assert secret is not None
        assert puzzle.check_secret(nonce, secret, difficulty)
        print(f"[parity] {label} {nonce.hex()}: {dt:.3f}s "
              f"secret={secret.hex()}", file=sys.stderr)
        times.append(dt)
    return times


def main() -> None:
    difficulty = 8
    positional = []
    argv = iter(sys.argv[1:])
    for a in argv:
        if a == "--difficulty":
            difficulty = int(next(argv))
        elif not a.startswith("-"):
            positional.append(a)
    n = int(positional[0]) if positional else 12
    if "--cpu" in sys.argv:
        # CPU smoke path: pin the CPU explicitly (same rule as
        # __graft_entry__ / tests/conftest.py) BEFORE any backend touch,
        # so the smoke never opens a chip.
        import jax
        import jax.extend.backend as _jeb

        jax.config.update("jax_platforms", "cpu")
        _jeb.clear_backends()

    # deterministic fresh nonces — NOT the three round-3 ones (13579bdf,
    # 2468ace0, 3579bdf1), so the distribution can't inherit their luck
    import hashlib

    nonces = [hashlib.sha256(b"parity-r4-%d" % i).digest()[:4]
              for i in range(n)]

    from distpow_tpu.runtime.watchdog import WATCHDOG

    def _bail(stale: float) -> None:
        print(f"ABORT: no device progress for {stale:.0f}s; partial "
              f"results above stand", file=sys.stderr)
        os._exit(1)

    WATCHDOG.start(420.0, on_hang=_bail)

    import jax

    from distpow_tpu.runtime.compile_cache import enable as _enable_cache

    _enable_cache()

    from distpow_tpu.backends import JaxBackend, get_backend

    # interpret mode off-TPU so the script CPU-smokes (tiny n, low
    # difficulty); the acceptance numbers only mean anything on the chip.
    # The devices() probe runs inside an active() section so a hung
    # device converts to the watchdog bail instead of a silent hang.
    with WATCHDOG.active():
        on_tpu = jax.devices()[0].platform == "tpu"
    summary = {"n": n, "difficulty_nibbles": difficulty,
               "platform": jax.devices()[0].platform}
    jt = solve_times(JaxBackend(batch_size=1 << 21), "jax", nonces,
                     difficulty)
    pt = solve_times(get_backend("pallas", batch_size=1 << 21,
                                 interpret=not on_tpu),
                     "pallas", nonces, difficulty)
    import math

    for label, ts in (("jax", jt), ("pallas", pt)):
        ts_sorted = sorted(ts)
        p90_idx = min(len(ts) - 1, math.ceil(0.9 * len(ts)) - 1)  # nearest rank
        summary[label] = {
            "median_s": round(statistics.median(ts), 3),
            "p90_s": round(ts_sorted[p90_idx], 3),
            "mean_s": round(statistics.fmean(ts), 3),
            "solves_s": [round(t, 3) for t in ts],
        }
    summary["pallas_over_jax_median"] = round(
        summary["pallas"]["median_s"] / summary["jax"]["median_s"], 3)
    summary["parity_bar_1p2x"] = summary["pallas_over_jax_median"] <= 1.2
    WATCHDOG.stop()
    print(json.dumps(summary))


if __name__ == "__main__":
    main()
