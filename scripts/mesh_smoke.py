#!/usr/bin/env python
"""Deterministic mesh-lane smoke (scripts/ci.sh --mesh-smoke).

Boots 4 virtual CPU devices (pre-backend-init, via the version-portable
compat shim) and drives the kernel-lane launch planner end to end on
the simulated mesh:

* the planner's auto ranking picks the ``mesh`` lane for a real
  scheduler solve and ``sched.lane_launches.mesh`` counts the serving;
* the mesh-lane secret is byte-identical to the pure-python oracle
  (first-hit parity across the sharded span);
* the solo route gains the same mesh through
  ``persistent_step_builder`` and agrees with the oracle too;
* ``search.mesh_devices`` reports the full simulated span.

Prints one JSON summary line on stdout (details to stderr); exit 0 on
success — the shape scripts/chaos_smoke.py established for CI lanes.
"""

from __future__ import annotations

import json
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from distpow_tpu.parallel import compat  # noqa: E402

N_DEVICES = int(os.environ.get("MESH_SMOKE_DEVICES", "4"))
compat.request_cpu_devices(N_DEVICES)

from distpow_tpu.models import puzzle  # noqa: E402
from distpow_tpu.parallel.search import persistent_search  # noqa: E402
from distpow_tpu.runtime.metrics import REGISTRY  # noqa: E402
from distpow_tpu.sched.engine import BatchingScheduler  # noqa: E402
from distpow_tpu.sched.lanes import persistent_step_builder  # noqa: E402

NTZ = 3
THREADS = list(range(256))


def main() -> int:
    import jax

    devices = len(jax.devices())
    assert devices == N_DEVICES, (
        f"expected {N_DEVICES} simulated CPU devices, backend has "
        f"{devices} — compat.request_cpu_devices ran too late?"
    )

    # scheduler route: auto ranking on a multi-device CPU host must
    # pick the mesh lane, and the answer must match the oracle
    before = REGISTRY.get("sched.lane_launches.mesh")
    eng = BatchingScheduler(hash_model="md5", batch_size=1 << 12,
                            max_slots=4)
    try:
        sched_secrets = {}
        for seed in (0x21, 0x22):
            nonce = bytes([seed, 0xA5])
            got = eng.search(nonce, NTZ, THREADS)
            want = puzzle.python_search(nonce, NTZ, THREADS)
            assert got == want, (
                f"mesh-lane scheduler diverged from oracle for nonce "
                f"{nonce.hex()}: {got!r} != {want!r}"
            )
            sched_secrets[nonce.hex()] = got.hex()
    finally:
        eng.close()
    mesh_launches = REGISTRY.get("sched.lane_launches.mesh") - before
    assert mesh_launches > 0, (
        "scheduler served zero launches on the mesh lane — planner "
        "fell back to xla on a multi-device host"
    )

    # solo route: the persistent step builder binds the mesh
    # persistent step for the same span
    nonce = b"\x23\xa5\x5a"
    builders = []

    def sb(operands):
        builders.append(persistent_step_builder(operands))
        return builders[-1]

    res = persistent_search(nonce, NTZ, THREADS, batch_size=1 << 12,
                            step_builder=sb)
    assert builders[0] is not None, \
        "persistent builder declined a 4-device host"
    want = puzzle.python_search(nonce, NTZ, THREADS)
    assert res is not None and res.secret == want, (
        f"mesh persistent route diverged from oracle: "
        f"{getattr(res, 'secret', None)!r} != {want!r}"
    )

    gauge = REGISTRY.get("search.mesh_devices")
    assert gauge == devices, (
        f"search.mesh_devices gauge {gauge} != device count {devices}"
    )

    print(json.dumps({
        "devices": devices,
        "mesh_launches": mesh_launches,
        "sched_secrets": sched_secrets,
        "persistent_secret": res.secret.hex(),
        "mesh_devices_gauge": gauge,
        "ok": True,
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
