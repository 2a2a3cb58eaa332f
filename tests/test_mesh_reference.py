"""The device backends' served forms against the plain reference, and
the mesh work they count.

``pallas-mesh`` (the kernel in interpret mode, over four of the
conftest's virtual CPU devices), ``pallas`` (the same on one device),
``jax-mesh`` (the XLA mesh step on four) and the mesh persistent lane
(``sched/lanes.py persistent_step_builder``) all drive the persistent
driver.  Each must return the reference's first solution
(``models/puzzle.py python_search``) in both sharding regimes, halt the
one dispatch chained behind its hit, and count every candidate but the
one-device width-0 probe's under ``search.mesh_hashes`` as well as
``search.hashes`` — none on one device.  The chained Pallas mesh step's own contract
(halt behind a hit, the segment of a hit, the reference-order first of
several solutions in one dispatch) is checked at small segments.
"""

import hashlib
import random

import jax
import numpy as np
import pytest

from distpow_tpu.backends import PallasMeshBackend, get_backend
from distpow_tpu.models import puzzle
from distpow_tpu.models.registry import get_hash_model
from distpow_tpu.ops.search_step import CHAIN_CLEAR, CHAIN_HALT, HALT
from distpow_tpu.parallel.mesh_search import (
    make_mesh,
    pallas_mesh_persistent_factory,
)
from distpow_tpu.parallel.search import RequestOperands, persistent_search
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


def _pallas_one_device(nonce, tbs):
    backend = get_backend("pallas", batch_size=BATCH, interpret=True)
    return backend.search(nonce, NTZ, tbs)


def _jax_mesh(nonce, tbs):
    backend = get_backend("jax-mesh", batch_size=BATCH, mesh_devices=N_DEV)
    return backend.search(nonce, NTZ, tbs)


def _mesh_persistent(nonce, tbs):
    builders = []

    def builder(operands):
        builders.append(persistent_step_builder(
            operands, caps=LaneCaps(platform="cpu", n_devices=N_DEV)))
        return builders[-1]

    res = persistent_search(nonce, NTZ, tbs, batch_size=BATCH,
                            step_builder=builder)
    assert builders[0] is not None
    return None if res is None else res.secret


# driver -> (serve, devices its steps span)
DRIVERS = {"pallas_mesh": (_pallas_mesh, N_DEV),
           "persistent_search": (_mesh_persistent, N_DEV),
           "pallas": (_pallas_one_device, 1),
           "jax_mesh": (_jax_mesh, N_DEV)}


@pytest.mark.parametrize("regime", list(REGIMES))
@pytest.mark.parametrize("driver", list(DRIVERS))
def test_mesh_serving_matches_the_reference_and_counts_mesh_work(
        driver, regime):
    tbs = REGIMES[regime]
    serve, n_dev = DRIVERS[driver]
    for i, nonce in enumerate(_nonces(2500)):
        tried = []
        want = puzzle.python_search(nonce, NTZ, tbs,
                                    on_progress=tried.append)
        hashes0 = REGISTRY.get("search.hashes")
        mesh0 = REGISTRY.get("search.mesh_hashes")
        halted0 = REGISTRY.get("search.halted_dispatches")
        SPANS.reset()
        with SPANS.bind(9000 + i, "w-mesh"):
            got = serve(nonce, tbs)
        assert got == want, (nonce.hex(), got, want)
        assert _position(got, tbs) == tried[0]
        hashes = REGISTRY.get("search.hashes") - hashes0
        mesh = REGISTRY.get("search.mesh_hashes") - mesh0
        # the hit lies past the width-0 probe, so the one dispatch in
        # flight behind it is chained to it and halts
        assert len(got) > 1
        assert REGISTRY.get("search.halted_dispatches") - halted0 == 1
        # the width-0 probe: one candidate per thread byte, on one device
        assert hashes > len(tbs)
        assert mesh == (hashes - len(tbs) if n_dev > 1 else 0)
        segs = [s for s in SPANS.spans_for(9000 + i)
                if s["name"] == "search.segment"]
        assert [s["attrs"]["devices"] for s in segs] == \
            [1] + [n_dev] * (len(segs) - 1)


# The chained Pallas mesh step at small segments: 1024 candidates a
# device (one interpret-mode tile), so a width-2 dispatch of 2^15
# candidates from chunk 256 runs 8 segments of 4096.  Each nonce's
# solutions at NTZ in that dispatch, by flat index (hashlib): the first
# lies past segment 0.
SEG_LOCAL = 1 << 10
SEG_GLOBAL = SEG_LOCAL * N_DEV
DISPATCH = 1 << 15
CHUNK0 = 256
FIRST_PAST_SEGMENT_0 = {
    "tb-split": bytes.fromhex("8c3bf4a9"),
    # also solved at 24740, which a split of the whole dispatch into
    # four device spans puts in device 3's segment 0
    "chunk-split": bytes.fromhex("fc2cd547"),
}


def _solutions(nonce, tbs, n=DISPATCH):
    """Flat indices in ``[0, n)`` of the width-2 dispatch at CHUNK0
    whose candidates solve ``nonce`` (hashlib)."""
    tbc = len(tbs)
    out = []
    for f in range(n):
        secret = bytes([tbs[0] + f % tbc]) \
            + (CHUNK0 + f // tbc).to_bytes(2, "little")
        if hashlib.md5(nonce + secret).hexdigest().endswith("0" * NTZ):
            out.append(f)
    return out


def _chained_pallas_builder(operands):
    return pallas_mesh_persistent_factory(
        operands, make_mesh(jax.devices()[:N_DEV]), interpret=True,
        segment_candidates=SEG_LOCAL)


def _operands(nonce, tbs):
    return RequestOperands(nonce, NTZ, tbs[0], len(tbs),
                           get_hash_model("md5"))


@pytest.mark.parametrize("regime", list(REGIMES))
def test_pallas_mesh_persistent_step_chained_halt(regime):
    """A hit in segment ``s`` returns the dispatch's global first index
    and ``s + 1`` segments; the dispatches chained behind it return
    ``[HALT, 0]``; ``CHAIN_HALT`` stops a dispatch at segment 0."""
    tbs = REGIMES[regime]
    nonce = FIRST_PAST_SEGMENT_0[regime]
    first = _solutions(nonce, tbs)[0]
    assert first >= SEG_GLOBAL
    bound, chunks_each, chunks_per_step = _chained_pallas_builder(
        _operands(nonce, tbs))(2, b"", DISPATCH // 8 // len(tbs), 8)
    assert chunks_each * len(tbs) == SEG_GLOBAL
    assert chunks_per_step * len(tbs) == DISPATCH
    assert np.asarray(bound(CHUNK0, CHAIN_HALT)).tolist() == [HALT, 0]
    hit = bound(CHUNK0, CHAIN_CLEAR)
    second = bound(CHUNK0 + chunks_per_step, hit)
    third = bound(CHUNK0 + 2 * chunks_per_step, second)
    assert np.asarray(hit).tolist() == [first, first // SEG_GLOBAL + 1]
    assert np.asarray(second).tolist() == np.asarray(third).tolist() \
        == [HALT, 0]


def test_pallas_mesh_chunk_split_returns_the_reference_first():
    """Chunk split, two solutions in one dispatch: the first in segment
    1, a later one in what a whole-dispatch split would make device 3's
    segment 0.  The driver returns the reference's first, halts the one
    dispatch behind it and counts only the work drained."""
    tbs = REGIMES["chunk-split"]
    nonce = FIRST_PAST_SEGMENT_0["chunk-split"]
    sols = _solutions(nonce, tbs)
    span = DISPATCH // N_DEV
    whole_split_seg0 = [f for f in sols if f % span < SEG_LOCAL]
    assert sols[0] >= SEG_GLOBAL and min(whole_split_seg0) > sols[0] \
        and min(whole_split_seg0) // span == N_DEV - 1
    want = puzzle.python_search(nonce, NTZ, tbs)
    assert _position(want, tbs) == 2 + 510 + sols[0] + 1  # widths 0-1 dry
    hashes0 = REGISTRY.get("search.hashes")
    halted0 = REGISTRY.get("search.halted_dispatches")
    got = persistent_search(
        nonce, NTZ, tbs, batch_size=DISPATCH // 8, launch_candidates=DISPATCH,
        step_builder=_chained_pallas_builder)
    assert got.secret == want
    assert REGISTRY.get("search.halted_dispatches") - halted0 == 1
    # the width-0 probe, width 1's 255 chunks, then the hit's dispatch
    # up to the end of its segment; nothing of the dispatch behind it
    assert REGISTRY.get("search.hashes") - hashes0 == \
        2 + 510 + (sols[0] // SEG_GLOBAL + 1) * SEG_GLOBAL


def test_every_device_backend_serves_through_the_persistent_driver(
        monkeypatch):
    """``jax``, ``jax-mesh``, ``pallas`` and ``pallas-mesh`` each answer
    a request with one call of ``persistent_search``, the one search
    driver, and return the reference's first solution."""
    import distpow_tpu.parallel.search as search_mod

    drive = search_mod.persistent_search
    calls = []

    def recorded(*args, **kwargs):
        calls.append(kwargs["model"].name)
        return drive(*args, **kwargs)

    monkeypatch.setattr(search_mod, "persistent_search", recorded)
    nonce, tbs = b"\x61\x62\x63", list(range(256))
    want = puzzle.python_search(nonce, NTZ, tbs)
    for name, kwargs in (("jax", {}), ("jax-mesh", {}),
                         ("pallas", {"interpret": True}),
                         ("pallas-mesh", {"interpret": True})):
        del calls[:]
        backend = get_backend(name, batch_size=BATCH, **kwargs)
        assert backend.search(nonce, NTZ, tbs) == want, name
        assert calls == ["md5"], name
