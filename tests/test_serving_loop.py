"""Persistent serving-loop suite (docs/SERVING.md).

The driver's first-hit parity with the python oracle across widths,
partitions and hash models lives in tests/test_search.py.  Here:

* **chained halt** — a dispatch behind a hit, or behind a halted one,
  exits at its first on-device loop check; results at width
  boundaries match the oracle on one device and on the mesh; halted
  dispatches count no work; cancel latency is bounded; and the polling
  drain never issues a device-array index.
* **backend warmup** — after warmup a served solve compiles nothing.
"""

import threading
import time

import jax
import pytest

from distpow_tpu.models import puzzle
from distpow_tpu.models.registry import get_hash_model
from distpow_tpu.parallel.mesh_search import make_mesh, mesh_persistent_factory
from distpow_tpu.parallel.search import (
    RequestOperands,
    StopFlag,
    persistent_search,
)
from distpow_tpu.runtime.metrics import REGISTRY


# -- chained halt / polling drain --------------------------------------------

def test_chained_halt_contract():
    """A dispatch runs only behind a predecessor that ran and found
    nothing: in a three-dispatch chain whose first hits, the second and
    third run 0 segments and return HALT, and CHAIN_HALT stops one at
    segment 0 as well."""
    import numpy as np

    from distpow_tpu.ops.search_step import (
        CHAIN_CLEAR,
        CHAIN_HALT,
        HALT,
        SENTINEL,
    )

    md5 = get_hash_model("md5")
    step = RequestOperands(b"\x51\x52", 2, 0, 256, md5).persistent_step(
        1, b"", 4, 8)
    flag = StopFlag()
    assert flag.operand() is CHAIN_CLEAR and not flag.is_set()
    results = []
    for c0 in (1, 33, 65):
        res = step(c0, flag.operand())
        flag.chain(res)
        results.append(np.asarray(res).tolist())
    (f, segs), halted2, halted3 = results
    assert f < 1 << 31 and segs >= 1  # the first dispatch hit
    assert halted2 == halted3 == [HALT, 0]
    assert np.asarray(step(1, CHAIN_HALT)).tolist() == [HALT, 0]
    flag.set()
    flag.chain(step(97, flag.operand()))
    assert flag.is_set() and flag.operand() is CHAIN_HALT
    # behind a predecessor that searched all its segments and missed,
    # the successor searches too
    miss = RequestOperands(b"\x51\x52", 8, 0, 256, md5).persistent_step(
        1, b"", 4, 8)
    first = miss(1, CHAIN_CLEAR)
    assert np.asarray(first).tolist() == [SENTINEL, 8]
    assert np.asarray(miss(33, first)).tolist() == [SENTINEL, 8]


# Nonces whose first 4-nibble hit lies in the last dispatch of width 1
# (chunk 254) and in the first dispatch of width 2 (chunk 257) at
# batch_size 1 << 10 and launch_candidates 1 << 12: 16 chunks a
# dispatch, the last width-1 dispatch covering chunks 241-255
BOUNDARY_NONCES = [b"\x90\x00\x00", b"\x90\x0a\x00"]
BOUNDARY = dict(batch_size=1 << 10, launch_candidates=1 << 12)


@pytest.mark.parametrize("nonce", BOUNDARY_NONCES,
                         ids=["last-of-width-1", "first-of-width-2"])
def test_persistent_parity_at_width_boundary(nonce):
    tbs = list(range(256))
    oracle = puzzle.python_search(nonce, 4, tbs)
    assert len(oracle) == (2 if nonce == BOUNDARY_NONCES[0] else 3)
    mesh = persistent_search(
        nonce, 4, tbs, step_builder=lambda operands: mesh_persistent_factory(
            operands, make_mesh(jax.devices())), **BOUNDARY)
    persistent = persistent_search(nonce, 4, tbs, **BOUNDARY)
    assert persistent.secret == mesh.secret == oracle


@pytest.mark.parametrize("depth", [2, 3])
def test_found_solve_counts_drained_work_and_halted_dispatches(depth):
    """The dispatches queued behind the hit are chained to it and halt
    on the device; search.hashes rises by the drained work alone, and
    each halted dispatch counts once under search.halted_dispatches."""
    import numpy as np

    from distpow_tpu.ops.search_step import HALT

    nonce, tbs = BOUNDARY_NONCES[0], list(range(256))
    results = []

    def recording_builder(operands):
        def builder(vw, extra, target_chunks, k):
            step = operands.persistent_step(vw, extra, target_chunks, k)

            def recorded(chunk0, prev):
                results.append(step(chunk0, prev))
                return results[-1]

            return recorded, target_chunks, target_chunks * k

        return builder

    h0 = REGISTRY.get("search.hashes")
    halted0 = REGISTRY.get("search.halted_dispatches")
    got = persistent_search(nonce, 4, tbs, pipeline_depth=depth,
                            step_builder=recording_builder, **BOUNDARY)
    assert got.secret == puzzle.python_search(nonce, 4, tbs)
    assert REGISTRY.get("search.hashes") - h0 == got.hashes_tried
    assert REGISTRY.get("search.halted_dispatches") - halted0 == depth - 1
    behind = [np.asarray(r).tolist() for r in results[-(depth - 1):]]
    assert behind == [[HALT, 0]] * (depth - 1)


def test_drain_issues_no_device_indexing(monkeypatch):
    """Each result is read whole in one host transfer: indexing a
    device array would queue a slice program behind the next
    dispatch."""
    from jax._src.numpy import indexing

    calls = []
    take = indexing.rewriting_take

    def counting_take(*a, **kw):
        calls.append(1)
        return take(*a, **kw)

    nonce, tbs = BOUNDARY_NONCES[1], list(range(256))
    persistent_search(nonce, 4, tbs, **BOUNDARY)  # bind and compile first
    launches = REGISTRY.get("search.launches")
    monkeypatch.setattr(indexing, "rewriting_take", counting_take)
    got = persistent_search(nonce, 4, tbs, **BOUNDARY)
    assert got.secret == puzzle.python_search(nonce, 4, tbs)
    assert REGISTRY.get("search.launches") - launches > 16
    assert calls == []


def test_persistent_cancel_latency_bounded():
    """Cancel mid-search: the driver must return promptly — it stops
    issuing, halts the chain, and never blocks on a result fetch
    while waiting (the poll loop checks the cancel between polls)."""
    ev = threading.Event()
    out = {}

    def run():
        out["res"] = persistent_search(
            b"\xde\xad\xbe", 6, list(range(256)), batch_size=1 << 12,
            launch_candidates=1 << 14, cancel_check=ev.is_set,
        )

    t = threading.Thread(target=run, daemon=True)
    t.start()
    time.sleep(0.5)  # let the pipeline fill
    t0 = time.monotonic()
    ev.set()
    t.join(timeout=30)
    assert not t.is_alive(), "cancel did not stop the persistent search"
    latency = time.monotonic() - t0
    assert out["res"] is None
    # generous CPU bound: the in-flight window is pipeline_depth tiny
    # launches; anything near the full enumeration means the flag or
    # the issue-loop check is broken
    assert latency < 10.0, f"cancel took {latency:.1f}s"


# -- backend warmup ----------------------------------------------------------

def test_jax_backend_persistent_warmup_compiles_and_serves(monkeypatch):
    """After warmup a served solve compiles nothing: the warmed
    programs, dispatched behind CHAIN_HALT, are the ones a solve
    chains through its widths."""
    from distpow_tpu.backends import JaxBackend
    from distpow_tpu.sched import lanes

    # one device, as on a one-chip worker: the mesh lane stays off
    monkeypatch.setattr(lanes, "detect_caps",
                        lambda: lanes.LaneCaps("cpu", 1))
    backend = JaxBackend(batch_size=1 << 12)
    # must not dispatch real segment work; width 3 too, because a solve
    # that ends in width 2 has width 3's first dispatch in flight
    backend.warmup([3], [0, 1, 2, 3])
    compiles = []

    def listen(event, _secs, **_kw):
        if event in ("/jax/core/compile/jaxpr_to_mlir_module_duration",
                     "/jax/core/compile/backend_compile_duration"):
            compiles.append(event)

    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        for nonce in BOUNDARY_NONCES:  # solves across widths 0-2
            got = backend.search(nonce, 4, list(range(256)))
            assert got == puzzle.python_search(nonce, 4, list(range(256)))
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)
    assert compiles == []
