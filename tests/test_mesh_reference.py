"""The four-chip worker's served forms against the plain reference, and
the mesh work they count.

``PallasMeshBackend`` (the kernel in interpret mode, over four of the
conftest's virtual CPU devices) drives the relaunch driver; the mesh
persistent lane (``sched/lanes.py persistent_step_builder``) drives the
persistent one.  Each must return the reference's first solution
(``models/puzzle.py python_search``) in both sharding regimes, and
count every candidate but the one-device width-0 probe's under
``search.mesh_hashes`` as well as ``search.hashes``.
"""

import random

import pytest

from distpow_tpu.backends import PallasMeshBackend
from distpow_tpu.models import puzzle
from distpow_tpu.models.registry import get_hash_model
from distpow_tpu.parallel.search import persistent_search
from distpow_tpu.runtime.metrics import REGISTRY
from distpow_tpu.runtime.spans import SPANS
from distpow_tpu.sched.lanes import LaneCaps, persistent_step_builder

NTZ = 3
N_DEV = 4
BATCH = 1 << 12
REGIMES = {
    # the full partition, 64 thread bytes a device, as the cell serves it
    "tb-split": list(range(256)),
    # fewer thread bytes than devices: each device takes a chunk span
    "chunk-split": [6, 7],
}


def _nonces(seed: int, n: int = 2):
    """Seeded 4-byte nonces; at seed 2500 the full partition's first
    solutions lie on the third and fourth device."""
    rng = random.Random(seed)
    return [bytes(rng.randrange(256) for _ in range(4)) for _ in range(n)]


def _position(secret: bytes, tbs) -> int:
    """Candidates a reference-order search of ``tbs`` covers up to and
    including ``secret``."""
    return int.from_bytes(secret[1:], "little") * len(tbs) \
        + tbs.index(secret[0]) + 1


def _pallas_mesh(nonce, tbs):
    backend = PallasMeshBackend(batch_size=BATCH, mesh_devices=N_DEV,
                                interpret=True)
    return backend.search(nonce, NTZ, tbs)


def _mesh_persistent(nonce, tbs):
    builder = persistent_step_builder(
        nonce, NTZ, tbs[0], len(tbs), get_hash_model("md5"),
        caps=LaneCaps(platform="cpu", n_devices=N_DEV))
    assert builder is not None
    res = persistent_search(nonce, NTZ, tbs, batch_size=BATCH,
                            step_builder=builder)
    return None if res is None else res.secret


DRIVERS = {"search": _pallas_mesh, "persistent_search": _mesh_persistent}


@pytest.mark.parametrize("regime", list(REGIMES))
@pytest.mark.parametrize("driver", list(DRIVERS))
def test_mesh_serving_matches_the_reference_and_counts_mesh_work(
        driver, regime):
    tbs = REGIMES[regime]
    for i, nonce in enumerate(_nonces(2500)):
        tried = []
        want = puzzle.python_search(nonce, NTZ, tbs,
                                    on_progress=tried.append)
        hashes0 = REGISTRY.get("search.hashes")
        mesh0 = REGISTRY.get("search.mesh_hashes")
        SPANS.reset()
        with SPANS.bind(9000 + i, "w-mesh"):
            got = DRIVERS[driver](nonce, tbs)
        assert got == want, (nonce.hex(), got, want)
        assert _position(got, tbs) == tried[0]
        hashes = REGISTRY.get("search.hashes") - hashes0
        mesh = REGISTRY.get("search.mesh_hashes") - mesh0
        # the width-0 probe: one candidate per thread byte, on one device
        assert mesh == hashes - len(tbs) > 0
        segs = [s for s in SPANS.spans_for(9000 + i)
                if s["name"] == "search.segment"]
        assert [s["attrs"]["devices"] for s in segs] == \
            [1] + [N_DEV] * (len(segs) - 1)
