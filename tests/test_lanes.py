"""Kernel-lane launch planner suite (sched/lanes.py, docs/SERVING.md).

Three layers:

* planner — the capability-driven selection matrix (pallas on TPU-like
  caps, mesh on any multi-device host, xla otherwise), the ``SchedLane``
  override, and sticky compile-failure demotion, all against injected
  :class:`LaneCaps` so the matrix runs anywhere.
* mesh lanes — byte-identical first-hit parity of the mesh slot step
  and the mesh persistent step against their single-device oracles,
  across widths and across non-power-of-two partitions; the conftest
  boots 8 virtual CPU devices, so these exercise real sharded programs.
* engine integration — a forced-mesh scheduler matches the reference
  oracle while ``sched.lane_launches.mesh`` counts the serving, and a
  mixed-hash launch whose groups land on DIFFERENT lanes still returns
  every slot's oracle answer from one launch.
"""

import sys
import threading
from pathlib import Path

import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).parent))

from distpow_tpu.models import puzzle  # noqa: E402
from distpow_tpu.models.registry import get_hash_model  # noqa: E402
from distpow_tpu.ops.difficulty import nibble_masks  # noqa: E402
from distpow_tpu.ops.packing import build_tail_spec  # noqa: E402
from distpow_tpu.ops.search_step import (  # noqa: E402
    CHAIN_CLEAR,
    CHAIN_HALT,
    HALT,
    slot_search_step,
)
from distpow_tpu.parallel.mesh_search import (  # noqa: E402
    AXIS,
    make_mesh,
    mesh_persistent_factory,
    mesh_slot_search_step,
)
from distpow_tpu.parallel.search import (  # noqa: E402
    RequestOperands,
    persistent_search,
)
from distpow_tpu.runtime.metrics import REGISTRY  # noqa: E402
from distpow_tpu.sched.engine import BatchingScheduler  # noqa: E402
from distpow_tpu.sched.lanes import (  # noqa: E402
    LaneCaps,
    LanePlanner,
    build_pallas_group_step,
    persistent_step_builder,
)

GDEF = ("md5", 1, (0, 1, 2), ((1, 2, 3),), 1)


# -- planner selection matrix ------------------------------------------------

def test_rank_selection_matrix():
    cases = [
        (LaneCaps("tpu", 4), "auto", ("pallas", "mesh", "xla")),
        (LaneCaps("tpu", 1), "auto", ("pallas", "xla")),
        (LaneCaps("cpu", 8), "auto", ("mesh", "xla")),
        (LaneCaps("cpu", 1), "auto", ("xla",)),
        # the interpret dev knob admits pallas off-TPU
        (LaneCaps("cpu", 1, interpret=True), "auto", ("pallas", "xla")),
        # overrides pin the head and drop the other specialized lane
        (LaneCaps("tpu", 4), "mesh", ("mesh", "xla")),
        (LaneCaps("cpu", 8), "xla", ("xla",)),
        (LaneCaps("cpu", 8), "pallas", ("xla",)),  # ineligible override
    ]
    for caps, override, want in cases:
        got = LanePlanner(caps=caps, override=override).rank(GDEF, 4096)
        assert got == want, (caps, override, got)


def test_width0_probe_layout_stays_on_xla():
    """The width-0 probe layout (empty chunk_locs) never rides a
    specialized lane: its whole segment is below one batch, so a
    per-layout compile could not pay for itself."""
    probe = ("md5", 1, (0, 1, 2), (), 1)
    for caps in (LaneCaps("tpu", 4), LaneCaps("cpu", 8),
                 LaneCaps("cpu", 1, interpret=True)):
        assert LanePlanner(caps=caps).rank(probe, 4096) == ("xla",)


def test_unknown_override_rejected():
    with pytest.raises(ValueError, match="unknown scheduler lane"):
        LanePlanner(caps=LaneCaps("cpu", 1), override="warp")


def test_demotion_is_sticky_and_falls_to_xla():
    p = LanePlanner(caps=LaneCaps("cpu", 1, interpret=True),
                    override="pallas")
    # md5 IS pallas-eligible under interpret caps, but an unknown model
    # makes the build itself raise — the demotion path
    gdef = ("nosuch", 1, (0, 1, 2), ((1, 2, 3),), 1)
    lane, step = p.resolve(gdef, 4096)
    assert (lane, step) == ("xla", None)
    assert "pallas" in p._demoted[(gdef, 4096)]
    # sticky: re-resolving never retries the demoted lane
    assert p.resolve(gdef, 4096) == ("xla", None)


def test_pallas_build_guards():
    caps = LaneCaps("cpu", 1, interpret=True)
    spec = build_tail_spec(b"\x01\x02", 2, get_hash_model("md5"), b"")
    ok = ("md5", spec.n_blocks, spec.tb_loc, spec.chunk_locs, 1)
    with pytest.raises(ValueError, match="single-block"):
        build_pallas_group_step(("md5", 2) + ok[2:], 4096, caps)
    with pytest.raises(ValueError, match="tile grid"):
        build_pallas_group_step(ok, 4096 + 128, caps)
    with pytest.raises(ValueError, match="TPU hardware"):
        build_pallas_group_step(ok, 4096, LaneCaps("cpu", 1))
    # the eligible shape builds a real (interpret-mode) group step
    step = build_pallas_group_step(ok, 4096, caps)
    assert step.lane == "pallas" and step.coverage == 4096


def test_pallas_interpret_group_step_parity():
    """The pallas group step (interpret mode, so it runs on CPU) agrees
    byte-for-byte with the XLA slot step over the same lane stack."""
    model = get_hash_model("md5")
    caps = LaneCaps("cpu", 1, interpret=True)
    batch = 2048
    spec = build_tail_spec(b"\x31\x32", 2, model, b"")
    gdef = ("md5", spec.n_blocks, spec.tb_loc, spec.chunk_locs, 2)
    step = build_pallas_group_step(gdef, batch, caps)
    oracle = slot_search_step("md5", spec.n_blocks, spec.tb_loc,
                              spec.chunk_locs, batch, 2)
    ops = (
        jnp.stack([jnp.asarray(spec.init_state, jnp.uint32)] * 2),
        jnp.stack([jnp.asarray(spec.base_words, jnp.uint32)] * 2),
        jnp.stack([jnp.asarray(nibble_masks(d, model), jnp.uint32)
                   for d in (1, 2)]),
        jnp.zeros(2, jnp.uint32),
        jnp.full(2, 8, jnp.uint32),
        jnp.asarray([0, 7], jnp.uint32),
    )
    np.testing.assert_array_equal(
        np.asarray(step(ops, None)), np.asarray(oracle(*ops))
    )


# -- mesh lane parity --------------------------------------------------------

def test_mesh_slot_step_parity_across_widths():
    """Per-slot first-hit indices from the sharded slot step are
    byte-identical to the single-device step over the same global span
    — for real hits and for misses, across tail widths."""
    import jax

    model = get_hash_model("md5")
    mesh = make_mesh(jax.devices()[:4])
    batch = 4096  # global; 1024 per device
    for vw, nonce, ntz in ((1, b"\x41\x42", 1), (2, b"\x43", 2),
                           (3, b"\x44\x45\x46", 2)):
        spec = build_tail_spec(nonce, vw, model, b"")
        args = ("md5", spec.n_blocks, spec.tb_loc, spec.chunk_locs)
        dyn = mesh_slot_search_step(mesh, AXIS, *args, batch // 4, 2)
        oracle = slot_search_step(*args, batch, 2)
        masks = jnp.asarray(nibble_masks(ntz, model), jnp.uint32)
        ops = (
            jnp.stack([jnp.asarray(spec.init_state, jnp.uint32)] * 2),
            jnp.stack([jnp.asarray(spec.base_words, jnp.uint32)] * 2),
            jnp.stack([masks] * 2),
            jnp.zeros(2, jnp.uint32),
            jnp.full(2, 8, jnp.uint32),
            jnp.asarray([0, 3], jnp.uint32),
        )
        for c0 in (0, 16, 64):
            cur = ops[:5] + (ops[5] + jnp.uint32(c0),)
            np.testing.assert_array_equal(
                np.asarray(dyn(*cur)), np.asarray(oracle(*cur)),
                err_msg=f"vw={vw} chunk0={c0}",
            )


def test_mesh_persistent_step_parity_nonpow2_partition():
    """The mesh persistent factory's bound step returns the same
    [first-hit, segments] pair as the single-device persistent step —
    including on a non-power-of-two partition (the // % enumeration)."""
    model = get_hash_model("md5")
    import jax

    mesh = make_mesh(jax.devices()[:4])
    for tbc in (256, 96):
        nonce, ntz, vw, chunks, segs = b"\x51\x52", 1, 2, 32, 4
        factory = mesh_persistent_factory(
            RequestOperands(nonce, ntz, 0, tbc, model), mesh, AXIS)
        bound, chunks_each, per_step = factory(vw, b"", chunks, segs)
        assert (chunks_each, per_step) == (chunks, chunks * segs)
        oracle = RequestOperands(nonce, ntz, 0, tbc, model).persistent_step(
            vw, b"", chunks, segs)
        for c0 in (0, 64, 1 << 12):
            got = np.asarray(bound(c0, CHAIN_CLEAR))
            want = np.asarray(oracle(c0, CHAIN_CLEAR))
            np.testing.assert_array_equal(got, want,
                                          err_msg=f"tbc={tbc} c0={c0}")
    # indivisible global batch refuses cleanly (the demotion signal)
    f6 = mesh_persistent_factory(RequestOperands(b"\x51\x52", 1, 0, 6, model),
                                 mesh, AXIS)
    with pytest.raises(ValueError, match="divide"):
        f6(2, b"", 1, 2)


def test_mesh_persistent_chained_halt():
    """On the 8-device mesh, as on one device: behind a dispatch that
    hit, the next two of a chain run 0 segments and return HALT, and
    CHAIN_HALT stops a dispatch at segment 0."""
    import jax

    model = get_hash_model("md5")
    mesh = make_mesh(jax.devices())
    factory = mesh_persistent_factory(
        RequestOperands(b"\x51\x52", 2, 0, 256, model), mesh, AXIS)
    bound, _, _ = factory(1, b"", 8, 4)
    first = bound(1, CHAIN_CLEAR)
    second = bound(33, first)
    third = bound(65, second)
    f, segs = np.asarray(first).tolist()
    assert f < 1 << 31 and segs >= 1
    assert np.asarray(second).tolist() == np.asarray(third).tolist() \
        == [HALT, 0]
    assert np.asarray(bound(1, CHAIN_HALT)).tolist() == [HALT, 0]


@pytest.mark.parametrize("nonce", [b"\x90\x00\x00", b"\x90\x0a\x00"],
                         ids=["last-of-width-1", "first-of-width-2"])
def test_persistent_driver_mesh_builder_across_widths(nonce):
    """persistent_search over the mesh lane, with hits in the last
    dispatch of width 1 and the first of width 2: the oracle's secret,
    one halted dispatch behind the hit, and a second solve of the same
    layout compiles nothing (the chain's constants sit where the mesh
    step's results do)."""
    import jax

    ntz, tbs = 4, list(range(256))
    kw = dict(batch_size=1 << 10, launch_candidates=1 << 12)

    def solve():
        return persistent_search(nonce, ntz, tbs,
                                 step_builder=persistent_step_builder, **kw)

    solve()  # compiles each width's mesh program
    compiles = []

    def listen(event, _secs, **_kw):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles.append(event)

    halted = REGISTRY.get("search.halted_dispatches")
    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        res = solve()
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)
    assert res.secret == puzzle.python_search(nonce, ntz, tbs)
    assert REGISTRY.get("search.halted_dispatches") - halted == 1
    assert compiles == []


def test_persistent_driver_mesh_builder_matches_oracle():
    """End to end: persistent_search driving the mesh lane finds the
    oracle's secret (same enumeration order => same first hit)."""
    nonce, ntz = b"\x61\x62\x63", 2
    builders = []

    def sb(operands):
        builders.append(persistent_step_builder(operands))
        return builders[-1]

    res = persistent_search(nonce, ntz, list(range(256)),
                            batch_size=1 << 12, step_builder=sb)
    assert builders[0] is not None  # 8-device conftest mesh
    assert res is not None
    assert res.secret == puzzle.python_search(nonce, ntz,
                                              list(range(256)))


# -- engine integration ------------------------------------------------------

def test_scheduler_mesh_override_parity_and_counters():
    before = REGISTRY.get("sched.lane_launches.mesh")
    eng = BatchingScheduler(hash_model="md5", batch_size=1 << 12,
                            max_slots=4, lane="mesh")
    try:
        for nonce, ntz in ((b"\x71\x72", 2), (b"\x73", 3)):
            got = eng.search(nonce, ntz, list(range(256)))
            assert got == puzzle.python_search(nonce, ntz,
                                              list(range(256)))
    finally:
        eng.close()
    assert REGISTRY.get("sched.lane_launches.mesh") > before


def test_mixed_hash_launch_across_different_lanes():
    """Groups of one launch landing on DIFFERENT lanes (sha1 demoted to
    xla, md5 on mesh) still each return their oracle's answer."""
    before_mesh = REGISTRY.get("sched.lane_launches.mesh")
    before_xla = REGISTRY.get("sched.lane_launches.xla")
    eng = BatchingScheduler(hash_model="md5", batch_size=1 << 12,
                            max_slots=4, extra_models=("sha1",),
                            start=False)
    orig = eng.planner._eligible

    def no_mesh_for_sha1(lane, gdef, batch):
        if lane == "mesh" and gdef[0] == "sha1":
            return False
        return orig(lane, gdef, batch)

    eng.planner._eligible = no_mesh_for_sha1
    results = {}

    def run(name, nonce, model):
        results[name] = eng.search(nonce, 2, list(range(256)),
                                   hash_model=model)

    threads = [
        threading.Thread(target=run, args=("md5", b"\x81\x82", "md5")),
        threading.Thread(target=run, args=("sha1", b"\x83\x84", "sha1")),
    ]
    for t in threads:
        t.start()
    eng.start()
    try:
        for t in threads:
            t.join(timeout=300)
            assert not t.is_alive()
    finally:
        eng.close()
    assert results["md5"] == puzzle.python_search(b"\x81\x82", 2,
                                                  list(range(256)))
    assert results["sha1"] == puzzle.python_search(
        b"\x83\x84", 2, list(range(256)), algo="sha1")
    assert REGISTRY.get("sched.lane_launches.mesh") > before_mesh
    assert REGISTRY.get("sched.lane_launches.xla") > before_xla
