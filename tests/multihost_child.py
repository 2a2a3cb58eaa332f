"""Child process for the 2-process jax.distributed multi-host test.

Usage: python multihost_child.py <process_id> <coordinator_port>

Each of the two processes owns 4 virtual CPU devices; together they form
one 8-device global mesh.  The mesh solve's ``lax.pmin`` found-index
collective must cross the process boundary for either process to learn
the result (the winning candidate is pinned to the upper thread-byte
half, i.e. process 1's devices).  Run by tests/test_multihost.py.
"""

import os
import sys

pid = int(sys.argv[1])
port = sys.argv[2]
# pin the CPU before the first backend use (same pattern as
# tests/conftest.py): this child must never open the chip; XLA_FLAGS is
# read at backend initialization
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.distributed.initialize(
    coordinator_address=f"127.0.0.1:{port}", num_processes=2, process_id=pid
)
assert len(jax.local_devices()) == 4, jax.local_devices()
assert len(jax.devices()) == 8, jax.devices()

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from distpow_tpu.models import puzzle  # noqa: E402
from distpow_tpu.models.registry import get_hash_model  # noqa: E402
from distpow_tpu.parallel.mesh_search import (  # noqa: E402
    make_mesh,
    mesh_persistent_factory,
    pallas_mesh_persistent_factory,
)
from distpow_tpu.parallel.search import persistent_search  # noqa: E402

# nonce chosen so the FIRST solution in enumeration order is
# (tb=214, chunk=empty->width probe) — tb 214 lives on global device
# 214 // 32 = 6, owned by process 1 (tests/test_multihost.py verified
# the oracle offline)
NONCE = bytes.fromhex("045a")
mesh = make_mesh(jax.devices())


def mesh_search(nonce, model=None, factory=mesh_persistent_factory,
                **kwargs):
    """One solve of the full partition through the search driver, every
    width past the probe on the global mesh."""
    model = model or get_hash_model("md5")
    return persistent_search(
        nonce, 2, list(range(256)), model=model, batch_size=1 << 12,
        step_builder=lambda operands: factory(operands, mesh, **kwargs))


res = mesh_search(NONCE)
assert res is not None
assert puzzle.check_secret(NONCE, res.secret, 2)
print(f"RESULT pid={pid} secret={res.secret.hex()} tb={res.thread_byte}",
      flush=True)

# a solve through the chained pallas mesh step (interpret mode on the
# CPU mesh).  Different nonce on purpose: NONCE's first solution is
# width-0 (empty chunk), which both factories serve via the shared
# single-device probe — it would never consult the kernel.  0x000c has
# NO width-0 solution and its first width-1 solution is (tb=144,
# chunk=1) — verified against the hashlib oracle — so the result comes
# from the KERNEL's tile grid, tb=144 lives on global device 4 (process
# 1), and only the kernel's pmin-ed global flat index crossing the
# process boundary can deliver it to process 0.
NONCE_P = bytes.fromhex("000c")
res_p = mesh_search(NONCE_P, factory=pallas_mesh_persistent_factory,
                    interpret=True)
assert res_p is not None
assert puzzle.check_secret(NONCE_P, res_p.secret, 2)
assert res_p.secret == bytes([144, 1]), res_p.secret.hex()
print(f"PALLAS pid={pid} secret={res_p.secret.hex()} "
      f"tb={res_p.thread_byte}", flush=True)

# the sponge family through the distributed mesh.  Width-0 first
# solutions are served by the shared single-device probe (same trap the
# PALLAS leg documents above), so the nonce must have NONE: sha3_256
# of 0x000a has no width-0 solution and its first solution in
# reference chunk-major order is (chunk=1, tb=204) — verified against
# the hashlib oracle over iter_candidates — on global device
# 204 // 32 = 6, owned by process 1, so both processes reporting it
# proves the structurally-different model (pad10*1, XOR-absorb,
# 50-limb state) rides the cross-process pmin collective
NONCE_S = bytes.fromhex("000a")
res_s = mesh_search(NONCE_S, model=get_hash_model("sha3_256"))
assert res_s is not None
assert puzzle.check_secret(NONCE_S, res_s.secret, 2, "sha3_256")
assert res_s.secret == bytes([204, 1]), res_s.secret.hex()
print(f"SHA3 pid={pid} secret={res_s.secret.hex()} "
      f"tb={res_s.thread_byte}", flush=True)
