"""Bit-exact check: Pallas kernel step vs a pure-Python hashlib oracle,
on the current backend (run on real TPU; interpret mode has its own
tests in tests/test_pallas.py).

For each model this drives the kernel step over several launch windows
at several difficulties — hit and miss cases — recomputing the expected
uint32 first-hit flat index with hashlib on the host, then runs one
``pallas`` backend end-to-end solve against the Python reference search.
This is the hardware half of the kernel test strategy: the tile *math*
is hashlib-pinned eagerly in tests/test_pallas.py; what only the chip
can prove is the Mosaic-compiled integration — packing words through
SMEM, the grid accumulation, the int32 min domain.  (The fused XLA
step is NOT the oracle here: for sha512 its compile is impractical on
this backend — >30 min, the very gap the kernel exists to close.)

Usage: python scripts/check_pallas_parity.py [model ...]
       (default: sha512 sha384 — the round-4 additions)
Prints one PARITY_OK line per model or dies with the mismatch.
"""

from __future__ import annotations

import hashlib
import sys
import time

sys.path.insert(0, ".")

WIDTH = 2
TBC = 256
CHUNKS = 512  # x 256 thread bytes = 2^17 candidates per launch window


def oracle_first_hits(mname: str, nonce: bytes, chunk0: int, batch: int,
                      difficulties) -> dict:
    """Expected kernel results for EVERY difficulty in one enumeration:
    min flat index whose digest has >= d trailing zero nibbles, else
    SENTINEL.  The candidate set of a window is identical across
    difficulty passes — only the threshold changes — so one hashlib
    sweep serves all of them (advisor r4: the difficulty-outer loop
    recomputed up to 2^17 digests per window three times, minutes of
    host time inside a fragile TPU session)."""
    from distpow_tpu.models.puzzle import new_hash
    from distpow_tpu.ops.search_step import SENTINEL

    log_tbc = TBC.bit_length() - 1
    want = sorted(difficulties)
    hits = {d: SENTINEL for d in want}
    missing = list(want)  # ascending: hits[d] found => all below found
    for f in range(batch):
        chunk = (chunk0 + (f >> log_tbc)) & 0xFFFFFFFF
        tb = f & (TBC - 1)
        secret = bytes([tb]) + (chunk & (256 ** WIDTH - 1)).to_bytes(
            WIDTH, "little")
        # new_hash, not getattr(hashlib, ...): blake2b_256 is a
        # PARAMETERIZED constructor with no hashlib attribute name
        h = new_hash(mname)
        h.update(nonce + secret)
        hexd = h.hexdigest()
        tz = len(hexd) - len(hexd.rstrip("0"))
        while missing and tz >= missing[0]:
            hits[missing.pop(0)] = f
        if not missing:
            break
    return hits


def check_model(mname: str) -> None:
    import jax.numpy as jnp

    from distpow_tpu.models import puzzle
    from distpow_tpu.ops.md5_pallas import build_pallas_search_step

    nonce = b"\x13\x57\x9b\xdf"
    batch = CHUNKS * TBC
    difficulties = (1, 3, 5)
    windows = (0, 1, 255, 4096, 65535, 2**16 - CHUNKS)
    # one host sweep per window covers all three difficulty passes
    t0 = time.time()
    oracle_tbl = {
        c0: oracle_first_hits(mname, nonce, c0, batch, difficulties)
        for c0 in windows
    }
    print(f"[parity] {mname}: oracle table for {len(windows)} windows "
          f"in {time.time() - t0:.0f}s host time", file=sys.stderr)
    for difficulty in difficulties:
        t0 = time.time()
        pstep = build_pallas_search_step(
            nonce, WIDTH, difficulty, 0, TBC, CHUNKS, mname
        )
        for chunk0 in windows:
            p = int(pstep(jnp.uint32(chunk0)))
            x = oracle_tbl[chunk0][difficulty]
            assert p == x, (
                f"{mname}: kernel/oracle divergence at difficulty="
                f"{difficulty} chunk0={chunk0}: pallas={p:#x} oracle={x:#x}"
            )
        print(f"[parity] {mname} d={difficulty}: 6 windows identical "
              f"({time.time() - t0:.0f}s incl. compile)", file=sys.stderr)

    from distpow_tpu.backends import get_backend

    backend = get_backend("pallas", hash_model=mname, batch_size=1 << 17)
    t0 = time.time()
    secret = backend.search(nonce, 3, list(range(256)))
    oracle = puzzle.python_search(nonce, 3, list(range(256)), algo=mname)
    assert secret == oracle, (
        f"{mname}: e2e secret {secret!r} != oracle {oracle!r}"
    )
    print(f"PARITY_OK {mname} e2e_secret={secret.hex()} "
          f"solve_s={time.time() - t0:.2f}")


def main() -> None:
    import jax

    from distpow_tpu.runtime.compile_cache import enable as _enable_cache

    _enable_cache()
    print(f"devices: {jax.devices()}", file=sys.stderr)
    models = sys.argv[1:] or ["sha512", "sha384"]
    for mname in models:
        check_model(mname)


if __name__ == "__main__":
    main()
