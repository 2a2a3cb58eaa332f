"""Kernel-lane launch planner — pick the fastest device form per group.

The batching engine (sched/engine.py) packs its slot table into
(model, tail-layout) groups; before this module every group dispatched
through one device form, the vmapped XLA slot step, although the repo's
own Pallas kernels hash the same layouts far faster (docs/KERNELS.md),
and one worker never spanned more than one chip.  The planner closes
both gaps at the launch layer: each group resolves to a ranked **lane**

* ``pallas`` — the hand-written per-model kernel (ops/md5_pallas.py),
  one kernel dispatch per slot lane sharing a single host sync; TPU
  hardware (or the interpret dev knob), pow2 geometry validated through
  ``plan_launch_geometry``.
* ``mesh`` — the vmapped slot step spread over every local device
  (parallel/mesh_search.py ``mesh_slot_search_step``): one launch
  covers ``n_dev x MESH_SPAN x batch`` candidates per slot, the
  VaultxGPU multi-chip throughput lever applied to serving.  The span
  factor widens each device's per-launch slice beyond the configured
  batch so the single host dispatch — the scarce resource in the
  serving loop — is amortized over more of the search segment.
* ``xla`` — the existing single-device vmapped step; always available,
  always last, so no environment regresses.

Resolution happens once per compile key and is CACHED; a lane whose
build or first dispatch fails is **demoted** for that key (the engine
falls back to ``xla`` within the same launch, and
``fallback.lane_demotions`` counts it) and never retried —
compile-failure demotion, the same transparent-fallback contract the
pallas-mesh backend already has per width.  ``SchedLane`` in
WorkerConfig (``override`` here) pins the ranking for operators and
tests.  Every launch counts ``sched.lane_launches.<lane>`` per group
served (runtime/metrics.py registry).

The solo route shares the planner through ``persistent_step_builder``:
a multi-device ``jax`` worker serves each dispatch through the mesh
persistent step (``mesh_persistent_factory``) and so does the fleet
self-calibration that measures through ``backend.search`` — a mesh
worker advertises its real multi-chip rate with zero coordinator
changes (docs/FLEET.md).
"""

from __future__ import annotations

import logging
import math
from dataclasses import dataclass
from typing import Dict, Optional, Set, Tuple

from ..runtime.metrics import REGISTRY as metrics

log = logging.getLogger("distpow.sched.lanes")

#: Ranked lane names, fastest-first; ``xla`` is the always-available tail.
LANES = ("pallas", "mesh", "xla")

#: Default per-device span multiplier for the mesh lane.  Each mesh
#: launch sweeps ``span x batch`` candidates per device: host dispatch
#: cost (python launch assembly + executable invocation + the result
#: sync) is paid once per launch regardless of span, so widening the
#: slice divides that fixed cost across more candidates.  4 keeps the
#: per-launch latency within one engine tick at default batch sizes
#: while recovering most of the amortization headroom.
MESH_SPAN = 4


def mesh_span() -> int:
    """The mesh lane's span multiplier (``DISTPOW_MESH_SPAN`` to tune,
    floor 1)."""
    import os

    try:
        return max(1, int(os.environ.get("DISTPOW_MESH_SPAN", MESH_SPAN)))
    except ValueError:
        return MESH_SPAN


@dataclass(frozen=True)
class LaneCaps:
    """Hardware capabilities the ranking keys on.  Injectable so the
    selection matrix is testable off-TPU (tests/test_lanes.py)."""

    platform: str          # jax.default_backend(): "tpu" | "cpu" | ...
    n_devices: int         # local device count (mesh span)
    interpret: bool = False  # allow interpret-mode pallas off-TPU (dev knob)


def detect_caps() -> LaneCaps:
    import jax

    return LaneCaps(platform=jax.default_backend(),
                    n_devices=len(jax.devices()))


class _MeshGroupStep:
    """Mesh-lane group step: ``mesh_slot_search_step`` plus the
    replicated operand cache.

    Pre-placing the five static operand rows on the mesh
    (``jax.device_put`` with a replicated ``NamedSharding``) keyed on
    the group's slot membership is what makes the lane pay off: fresh
    host arrays would re-lay-out onto every device each launch (~2.5x
    the dispatch cost, measured) while the chunk cursor row — the only
    per-launch change — is a tiny transfer.
    """

    lane = "mesh"

    def __init__(self, dyn, mesh, coverage: int) -> None:
        import jax
        from ..parallel.compat import NamedSharding, PartitionSpec

        self._dyn = dyn
        self._jax = jax
        self._repl = NamedSharding(mesh, PartitionSpec())
        self.coverage = coverage
        self._key: object = None
        self._placed: Optional[tuple] = None

    def __call__(self, ops: tuple, key: object):
        if key != self._key:
            self._placed = tuple(
                self._jax.device_put(o, self._repl) for o in ops[:5]
            )
            self._key = key
        chunk0 = self._jax.device_put(ops[5], self._repl)
        return self._dyn(*self._placed, chunk0)


class _PallasGroupStep:
    """Pallas-lane group step: one layout-keyed kernel dispatch per slot
    lane, stacked on device so the launch keeps the engine's single host
    sync.  Per-lane runtime operands (masks, partition, chunk cursor)
    ride the same slot-op rows the XLA lane builds."""

    lane = "pallas"

    def __init__(self, step, coverage: int) -> None:
        self._step = step
        self.coverage = coverage

    def __call__(self, ops: tuple, key: object):
        return self._step(*ops)


def plan_launch_geometry(target_chunks: int, tbc: int, tile: int,
                         inner: int, launch_steps: int,
                         max_launch: int) -> Tuple[int, int, int]:
    """Pick the kernel launch geometry ``(batch, chunks, k)`` for one
    dispatch — pure math, so the k-selection rules are unit-testable on
    CPU.

    * the batch rounds UP to a whole number of ``tile``-sized grids and
      ``k`` (the launch multiplier) re-clamps to the rounded batch so
      ``batch * k`` stays within ``max_launch``;
    * for non-power-of-two tiles with ``inner > 1``, ``k`` is rounded
      down to a power of two ONLY when that keeps (or makes, after a
      marginal <=2% whole-tile batch growth) the inner loop effective —
      when the growth conditions fail, the ORIGINAL k is kept: rounding
      unconditionally cost non-pow2 tiles with a non-pow2 multiplier up
      to ~2x launch amortization for nothing.
    """
    chunks = max(1, target_chunks)
    batch = chunks * tbc
    # round the batch up to a whole tile grid
    if batch % tile:
        batch = ((batch // tile) + 1) * tile
        chunks = max(1, batch // tbc)
    # re-clamp the launch multiplier to the ROUNDED batch: the driver
    # computed launch_steps for the unrounded one, and rounded_batch * k
    # must stay within the uint32 flat-index bound (_check_launch) and
    # the dispatch budget
    k = max(1, min(launch_steps, max_launch // batch))
    # keep the tuned inner effective for non-power-of-two tiles (the
    # sweep-best sublanes=24 geometries): the kernel shrinks inner until
    # it divides the per-dispatch tile count, and a 24-sublane tile
    # leaves 2^21 candidates at 683 tiles — prime — so with an odd
    # launch multiplier the inner loop collapses all the way to 1, which
    # kept those geometries unshippable.  Two
    # bounded moves fix it: round k down to a power of two (so the
    # dispatch tile count carries pow2 factors), then grow the batch by
    # whole tiles until k*n_tiles divides inner — but ONLY when the
    # growth is marginal (<=2%) and the k clamp is unaffected; otherwise
    # keep the shrink-inner behavior (an uncapped version of this grew
    # small width segments 4x and blew the dispatch budget the k clamp
    # above enforces).  For all-power-of-two geometries
    # every condition already holds: no-op.
    if inner > 1 and (tile & (tile - 1)):
        # the pow2 rounding only commits together with a batch that
        # makes inner effective; when the growth conditions fail, the
        # ORIGINAL k is kept (shrink-inner behavior)
        k2 = 1 << (k.bit_length() - 1)
        need = inner // math.gcd(k2, inner)
        n = batch // tile
        if n % need == 0:
            k = k2
        else:
            cap = batch + max(tile, batch // 50)
            grown = n + (need - n % need)
            while grown * tile <= cap and (grown * tile) % tbc:
                grown += need
            gbatch = grown * tile
            # the k in use must still fit the budget at the grown batch
            # (compare in pow2-rounded space)
            reclamp = max(1, min(launch_steps, max_launch // gbatch))
            if (gbatch <= cap and gbatch % tbc == 0
                    and 1 << (reclamp.bit_length() - 1) >= k2):
                batch = gbatch
                chunks = max(1, batch // tbc)
                k = k2
    return batch, chunks, k


def build_pallas_group_step(gdef: tuple, batch: int,
                            caps: LaneCaps) -> _PallasGroupStep:
    """Build the pallas lane for one launch group, or raise ValueError
    when the kernel cannot express it (no tile for the model,
    multi-block tail, off-TPU without the interpret knob, or a batch
    that does not align to the kernel's pow2 tile grid as judged by
    ``plan_launch_geometry``).  The raise IS the demotion signal."""
    import jax.numpy as jnp

    from ..models.registry import get_hash_model
    from ..ops.md5_pallas import (
        INTERPRET_XLA_FALLBACK,
        LANES as KERNEL_LANES,
        MODEL_GEOMETRY,
        _dyn_pallas_step,
        default_geometry,
    )
    from ..ops.search_step import named_jit

    model_name, n_blocks, tb_loc, chunk_locs, n_pad = gdef
    if model_name not in MODEL_GEOMETRY:
        raise ValueError(f"no pallas kernel for model {model_name}")
    if n_blocks != 1:
        raise ValueError("pallas kernel requires a single-block tail")
    interpret = caps.platform != "tpu"
    if interpret and not caps.interpret:
        raise ValueError(
            f"pallas lane requires TPU hardware (platform is "
            f"{caps.platform!r} and the interpret dev knob is off)"
        )
    if interpret and model_name in INTERPRET_XLA_FALLBACK:
        raise ValueError(
            f"{model_name} pallas tile is TPU-only (interpret-mode "
            f"XLA:CPU compile of the limb-pair graph is pathological)"
        )
    sublanes, inner = default_geometry(model_name, interpret)
    tile = sublanes * KERNEL_LANES
    # pow2-geometry validation through the shared launch planner: with
    # tbc=1 the requested chunk count IS the batch, so any padding or
    # launch split the plan reports means the batch cannot ride the
    # kernel's tile grid as-is — the engine's fixed per-launch coverage
    # cannot absorb either
    planned_batch, _, planned_k = plan_launch_geometry(
        batch, 1, tile, inner, 1, (1 << 31) - 1
    )
    if planned_batch != batch or planned_k != 1:
        raise ValueError(
            f"batch {batch} does not align to the {model_name} kernel "
            f"tile grid (tile={tile}: planned {planned_batch} x "
            f"{planned_k})"
        )
    inner_eff = max(1, inner)
    tiles = batch // tile
    while tiles % inner_eff:
        inner_eff //= 2
    grid = tiles // inner_eff
    model = get_hash_model(model_name)
    _, tb_w, tb_s = tb_loc
    chunk_ws = tuple((w, s) for _, w, s in chunk_locs)
    # mask_words = full digest width: slot rows carry every mask word so
    # per-slot difficulty stays a runtime operand (the slot_search_step
    # discipline), trading the dead-round skip for program sharing
    kernel = _dyn_pallas_step(
        tb_w, tb_s, chunk_ws, grid, sublanes, interpret, inner_eff,
        model.digest_words, model_name,
    )

    def step(init, base, masks, tb_lo, log_tbc, chunk0):
        outs = [
            kernel(
                chunk0[i], init[i], base[i][0], masks[i],
                jnp.stack([tb_lo[i], log_tbc[i]]),
            )
            for i in range(n_pad)
        ]
        return jnp.stack(outs)

    return _PallasGroupStep(named_jit(step, f"{model_name}_pallas_slot_step"),
                            batch)


class LanePlanner:
    """Per-compile-key lane resolution with sticky demotion (module
    docstring).  ``override`` pins the first-ranked lane ("auto" ranks
    by capability); a demoted override falls straight to ``xla`` —
    never silently onto the other specialized lane."""

    def __init__(self, caps: Optional[LaneCaps] = None,
                 override: str = "auto") -> None:
        override = (override or "auto").lower()
        if override not in ("auto",) + LANES:
            raise ValueError(
                f"unknown scheduler lane {override!r}: expected one of "
                f"{('auto',) + LANES}"
            )
        self.override = override
        self._caps = caps
        self._mesh = None
        self._choice: Dict[tuple, str] = {}
        self._demoted: Dict[tuple, Set[str]] = {}
        self._steps: Dict[tuple, object] = {}

    @property
    def caps(self) -> LaneCaps:
        if self._caps is None:
            self._caps = detect_caps()
        return self._caps

    def _get_mesh(self):
        if self._mesh is None:
            import jax

            from ..parallel.mesh_search import make_mesh

            self._mesh = make_mesh(jax.devices()[: self.caps.n_devices])
        return self._mesh

    # -- ranking ------------------------------------------------------------
    def _eligible(self, lane: str, gdef: tuple, batch: int) -> bool:
        """Cheap static screen; build failures demote the rest."""
        if lane == "xla":
            return True
        # the width-0 probe layout (no chunk words): its whole segment
        # is at most one tb row — far below one batch, so a specialized
        # lane's per-layout compile could never pay for itself
        if not gdef[3]:
            return False
        if lane == "mesh":
            return (self.caps.n_devices > 1
                    and batch * mesh_span() * self.caps.n_devices < 1 << 31)
        # pallas: platform screen only — geometry/model checks live in
        # the builder so the demotion log carries the precise reason
        return self.caps.platform == "tpu" or self.caps.interpret

    def rank(self, gdef: tuple, batch: int) -> Tuple[str, ...]:
        """Ranked candidate lanes for a group, override applied and
        ineligible/demoted lanes dropped — always ends in ``xla``."""
        if self.override == "auto":
            ranked = LANES
        elif self.override == "xla":
            ranked = ("xla",)
        else:
            ranked = (self.override, "xla")
        demoted = self._demoted.get((gdef, batch), set())
        out = tuple(
            lane for lane in ranked
            if lane == "xla"
            or (lane not in demoted and self._eligible(lane, gdef, batch))
        )
        return out if out[-1] == "xla" else out + ("xla",)

    # -- resolution ---------------------------------------------------------
    def resolve(self, gdef: tuple, batch: int):
        """(lane, step) for a launch group.  ``step`` is None for the
        ``xla`` lane (the engine owns that dispatch — mixed groups share
        it); otherwise a group-step callable ``step(ops, key)`` with a
        ``coverage`` attribute (candidates per slot per launch).  Build
        failures demote and fall through, so this always returns."""
        key = (gdef, batch)
        while True:
            lane = self._choice.get(key)
            if lane is None:
                lane = self.rank(gdef, batch)[0]
                self._choice[key] = lane
            if lane == "xla":
                return "xla", None
            step = self._steps.get((gdef, batch, lane))
            if step is not None:
                return lane, step
            try:
                step = self._build(lane, gdef, batch)
            except Exception as exc:
                self.demote(gdef, batch, lane, exc)
                continue
            self._steps[(gdef, batch, lane)] = step
            return lane, step

    def demote(self, gdef: tuple, batch: int, lane: str,
               exc: Exception) -> None:
        """Sticky per-key demotion — the compile-failure contract,
        counted under ``fallback.lane_demotions``."""
        metrics.inc("fallback.lane_demotions")
        self._demoted.setdefault((gdef, batch), set()).add(lane)
        self._choice.pop((gdef, batch), None)
        self._steps.pop((gdef, batch, lane), None)
        log.warning(
            "lane %s demoted for group %s (batch %d): %s", lane,
            gdef[0], batch, exc,
        )

    def _build(self, lane: str, gdef: tuple, batch: int):
        if lane == "pallas":
            return build_pallas_group_step(gdef, batch, self.caps)
        assert lane == "mesh", lane
        from ..parallel.mesh_search import AXIS, mesh_slot_search_step

        model_name, n_blocks, tb_loc, chunk_locs, n_pad = gdef
        mesh = self._get_mesh()
        n_dev = int(mesh.devices.size)
        # per-device slice = span x batch: the step enumerates each
        # device's contiguous flat-index range, so widening the local
        # batch IS the span — no program change, just fewer launches
        # per segment (engine cursor advances by the step's coverage)
        local = batch * mesh_span()
        dyn = mesh_slot_search_step(
            mesh, AXIS, model_name, n_blocks, tb_loc, chunk_locs, local,
            n_pad,
        )
        return _MeshGroupStep(dyn, mesh, local * n_dev)


def persistent_step_builder(operands, caps: Optional[LaneCaps] = None,
                            override: str = "auto"):
    """Lane plan for one solo/persistent request's ``operands``
    (``parallel.search.RequestOperands``) — the
    ``parallel.search.persistent_search`` ``step_builder`` hook.

    Returns None when the single-device persistent step IS the plan
    (one device, or the override pins ``xla``); otherwise a builder
    whose per-width result is the mesh persistent step, compile-probed
    at bind time behind ``CHAIN_HALT`` (the warmup trick: the on-device
    loop exits at its first condition check, so probing compiles the
    real program at near-zero device cost) with the operand types the
    driver dispatches, so the probe's program is the served one.  Any
    bind or probe failure demotes the whole request to the
    single-device path — per-lane compile-failure demotion, solo
    edition.
    """
    caps = caps or detect_caps()
    # the persistent route has exactly two lanes, mesh or the default
    # single-device step: only "auto"/"mesh" rankings enable mesh here
    # (a "pallas" override pins the PACKED lanes, not this one)
    if override not in ("auto", "mesh") or caps.n_devices <= 1:
        return None
    import jax

    from ..ops.search_step import CHAIN_HALT
    from ..parallel.mesh_search import AXIS, make_mesh, \
        mesh_persistent_factory

    mesh = make_mesh(jax.devices()[: caps.n_devices])
    factory = mesh_persistent_factory(operands, mesh, AXIS)
    model = operands.model
    demoted = []

    def builder(vw: int, extra: bytes, target_chunks: int, segments: int):
        if demoted:
            return None
        try:
            bound, chunks_each, chunks_per_step = factory(
                vw, bytes(extra), target_chunks, segments
            )
            # halted compile probe: surfaces compile failures here,
            # where demotion is cheap, instead of mid-pipeline.  chunk0
            # is a Python int as the driver passes it, so the probe's
            # program is the served one.  Not awaited: it would queue
            # behind the solve's dispatches in flight and drain them
            bound(0, CHAIN_HALT)
        except Exception as exc:
            metrics.inc("fallback.lane_demotions")
            demoted.append(True)
            log.warning(
                "mesh persistent lane demoted for width %d "
                "(model %s): %s", vw, model.name, exc,
            )
            return None
        return bound, chunks_each, chunks_per_step

    return builder
