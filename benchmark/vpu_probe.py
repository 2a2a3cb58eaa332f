"""Check the int32 vector peak of ``roofline.PEAKS`` against the chip.

    python3 benchmark/vpu_probe.py

A copy of ``bench.py measured_vpu_roofline``: independent uint32
rotate-add chains over a 2^21-element vector, with md5's shifts and
constants so nothing folds, four chains per element.  It prints the
rate counted as ``roofline.py`` counts md5 (a rotate is one op, so two
ops per link) and counted in the shifts and ors a chip without a rotate
executes (four per link).  Both must read below the peak; the second
is the tighter test of it.  Not part of a benchmark run.
"""

from __future__ import annotations

import json
import os
import sys
import time

sys.path[0] = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

from benchmark import roofline  # noqa: E402

N = 1 << 21
CHAINS = 4
LINKS = 64


def measure(min_seconds: float = 2.0) -> dict:
    import jax
    import jax.numpy as jnp

    @jax.jit
    def run(seed, reps):
        x = jax.lax.broadcasted_iota(jnp.uint32, (N,), 0) + seed
        chains = tuple(x + jnp.uint32((i * 0x9E3779B9) & 0xFFFFFFFF)
                       for i in range(CHAINS))

        def body(_, chains):
            out = []
            for ci, y in enumerate(chains):
                for j in range(LINKS):
                    s = roofline.MD5_S[(j + 17 * ci) % 64]
                    y = (((y << s) | (y >> (32 - s)))
                         + jnp.uint32(roofline.MD5_K[j]))
                out.append(y)
            return tuple(out)

        chains = jax.lax.fori_loop(0, reps, body, chains)
        acc = chains[0]
        for y in chains[1:]:
            acc = acc ^ y
        return acc[0]

    int(run(jnp.uint32(1), 1))  # compile
    reps = 64
    while True:
        t0 = time.perf_counter()
        int(run(jnp.uint32(2), reps))
        dt = time.perf_counter() - t0
        if dt >= min_seconds or reps >= 1 << 20:
            break
        reps = max(reps * 2, int(reps * min_seconds / max(dt, 1e-3)) + 1)
    links = N * reps * CHAINS * LINKS
    kind = jax.devices()[0].device_kind
    peak = roofline.vpu_int32_peak(kind)
    return {"device_kind": kind, "seconds": dt, "reps": reps,
            "ops_per_s_rotate_as_1": 2 * links / dt,
            "ops_per_s_rotate_as_3": 4 * links / dt,
            "peak": peak,
            "share_rotate_as_1": 2 * links / dt / peak,
            "share_rotate_as_3": 4 * links / dt / peak}


if __name__ == "__main__":
    print(json.dumps(measure()))
