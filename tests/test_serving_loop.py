"""Persistent serving-loop suite (docs/SERVING.md, ISSUE 6).

Three layers:

* **golden parity** — the persistent driver must return the SAME
  first-hit secret (reference enumeration order, byte-identical) as the
  solo serial driver and the python oracle, across chunk widths,
  partitions (full / sub / single-byte / non-power-of-two) and hash
  models.  This is the acceptance bar that lets the persistent loop be
  the serving default.
* **chained halt** — a dispatch behind a hit, or behind a halted one,
  exits at its first on-device loop check; results at width
  boundaries match the oracle; halted dispatches count no work;
  cancel latency is bounded; and the polling drain never issues a
  blocking result conversion (``search.blocking_syncs`` stays flat
  while the serial driver's counter moves) nor a device-array index.
* **backend plumbing** — ``JaxBackend(loop=...)`` selects the driver,
  warmup compiles the persistent programs, and the config default
  serves persistent.
"""

import threading
import time

import pytest

from distpow_tpu.models import puzzle
from distpow_tpu.parallel import partition
from distpow_tpu.parallel.search import (
    StopFlag,
    persistent_search,
    search,
)
from distpow_tpu.runtime.metrics import REGISTRY


NONCES = [b"\x01\x02\x03\x04", b"\x02\x02\x02\x02", b"\xfe\xff"]


# -- golden parity -----------------------------------------------------------

@pytest.mark.parametrize("nonce", NONCES)
@pytest.mark.parametrize("difficulty", [1, 2, 3])
def test_persistent_matches_serial_and_oracle_full_range(nonce, difficulty):
    tbs = list(range(256))
    oracle = puzzle.python_search(nonce, difficulty, tbs)
    serial = search(nonce, difficulty, tbs, batch_size=1 << 14)
    persistent = persistent_search(nonce, difficulty, tbs,
                                   batch_size=1 << 14)
    assert persistent is not None and serial is not None
    assert persistent.secret == serial.secret == oracle


def test_persistent_parity_deep_widths():
    # difficulty 4 pushes into width >= 2 chunks — the multi-segment
    # while_loop must preserve enumeration order across segment
    # boundaries and across the width cursor
    nonce = b"\x11\x22\x33\x44"
    tbs = list(range(256))
    got = persistent_search(nonce, 4, tbs, batch_size=1 << 16)
    assert got is not None
    assert got.secret == puzzle.python_search(nonce, 4, tbs)


@pytest.mark.parametrize("tbs", [
    list(range(64, 128)),            # pow2 sub-partition (sharded worker)
    [7],                             # single thread byte
    [3, 4, 5],                       # contiguous non-pow2 (static regime)
], ids=["pow2-sub", "single", "non-pow2"])
def test_persistent_parity_partitions(tbs):
    nonce = b"\x05\x06\x07\x08"
    oracle = puzzle.python_search(nonce, 2, tbs)
    got = persistent_search(nonce, 2, tbs, batch_size=1 << 13)
    assert got is not None and got.secret == oracle
    assert got.secret[0] in tbs


def test_persistent_parity_worker_shard():
    nonce = b"\x21\x22\x23"
    bits = partition.worker_bits(4)
    tbs = partition.thread_bytes(2, bits)
    oracle = puzzle.python_search(nonce, 2, tbs)
    got = persistent_search(nonce, 2, tbs, batch_size=1 << 13)
    assert got is not None and got.secret == oracle


@pytest.mark.parametrize("model_name", ["sha1", "sha256", "blake2b_256"])
def test_persistent_parity_models(model_name):
    from distpow_tpu.models.registry import get_hash_model

    model = get_hash_model(model_name)
    nonce = b"\x31\x32\x33\x34"
    tbs = list(range(256))
    oracle = puzzle.python_search(nonce, 2, tbs, algo=model_name)
    serial = search(nonce, 2, tbs, model=model, batch_size=1 << 13)
    got = persistent_search(nonce, 2, tbs, model=model,
                            batch_size=1 << 13)
    assert got is not None and serial is not None
    assert got.secret == serial.secret == oracle


def test_persistent_small_launch_budget_matches_oracle():
    # a tiny per-dispatch budget forces MANY multi-segment dispatches
    # through the pipeline — the FIFO drain must still hand back the
    # enumeration-order first hit
    nonce = b"\x41\x42"
    tbs = list(range(256))
    got = persistent_search(nonce, 3, tbs, batch_size=1 << 10,
                            launch_candidates=1 << 12)
    assert got is not None
    assert got.secret == puzzle.python_search(nonce, 3, tbs)
    assert REGISTRY.get("search.persistent_steps") > 0


# -- budget / unsatisfiable gates (contract parity with search()) ------------

def test_persistent_max_hashes_budget():
    got = persistent_search(b"\x01", 30, list(range(256)),
                            batch_size=1 << 12, max_hashes=1 << 14)
    assert got is None


def test_persistent_unsatisfiable_gates():
    assert persistent_search(b"\x01", 33, list(range(256)),
                             cancel_check=lambda: True) is None
    assert persistent_search(b"\x01", 33, list(range(256)),
                             max_hashes=100) is None
    with pytest.raises(ValueError, match="unsatisfiable"):
        persistent_search(b"\x01", 33, list(range(256)))


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
        cached_persistent_step,
    )

    step = cached_persistent_step(b"\x51\x52", 1, 2, 0, 256, 4, "md5",
                                  b"", 8)
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
    miss = cached_persistent_step(b"\x51\x52", 1, 8, 0, 256, 4, "md5",
                                  b"", 8)
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
    serial = search(nonce, 4, tbs, **BOUNDARY)
    persistent = persistent_search(nonce, 4, tbs, **BOUNDARY)
    assert persistent.secret == serial.secret == oracle


@pytest.mark.parametrize("depth", [2, 3])
def test_found_solve_counts_drained_work_and_halted_dispatches(depth):
    """The dispatches queued behind the hit are chained to it and halt
    on the device; search.hashes rises by the drained work alone, and
    each halted dispatch counts once under search.halted_dispatches."""
    import numpy as np

    from distpow_tpu.ops.search_step import HALT, cached_persistent_step

    nonce, tbs = BOUNDARY_NONCES[0], list(range(256))
    results = []

    def recording_builder(vw, extra, target_chunks, k):
        step = cached_persistent_step(nonce, vw, 4, 0, 256, target_chunks,
                                      "md5", extra, k)

        def recorded(chunk0, prev):
            results.append(step(chunk0, prev))
            return results[-1]

        return recorded, target_chunks, target_chunks * k

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


def test_persistent_never_blocks_serial_does():
    nonce, tbs = b"\x61\x62", list(range(256))
    b0 = REGISTRY.get("search.blocking_syncs")
    serial = search(nonce, 3, tbs, batch_size=1 << 10,
                    launch_candidates=1 << 12)
    b1 = REGISTRY.get("search.blocking_syncs")
    persistent = persistent_search(nonce, 3, tbs, batch_size=1 << 10,
                                   launch_candidates=1 << 12)
    b2 = REGISTRY.get("search.blocking_syncs")
    assert serial.secret == persistent.secret
    assert b1 - b0 >= 1, "serial drain stopped counting blocking syncs"
    assert b2 == b1, "persistent drain issued a blocking conversion"


# -- backend plumbing --------------------------------------------------------

def test_jax_backend_loop_selection_and_default():
    from distpow_tpu.backends import JaxBackend, get_backend

    assert JaxBackend().loop == "persistent"  # the serving default
    assert get_backend("jax", loop="serial").loop == "serial"
    with pytest.raises(ValueError, match="unknown search loop"):
        JaxBackend(loop="bogus")
    nonce, tbs = b"\x71\x72", list(range(256))
    per = JaxBackend(batch_size=1 << 13).search(nonce, 2, tbs)
    ser = JaxBackend(batch_size=1 << 13, loop="serial").search(
        nonce, 2, tbs)
    assert per == ser == puzzle.python_search(nonce, 2, tbs)


def test_jax_backend_persistent_warmup_compiles_and_serves(monkeypatch):
    """After warmup a served solve compiles nothing: the warmed
    programs, dispatched behind CHAIN_HALT, are the ones a solve
    chains through its widths."""
    import jax

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


def test_worker_config_search_loop_plumbs_to_backend():
    from distpow_tpu.runtime.config import WorkerConfig

    assert WorkerConfig().SearchLoop == "persistent"
    assert WorkerConfig(SearchLoop="serial").SearchLoop == "serial"
