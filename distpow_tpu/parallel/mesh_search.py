"""Multi-device search over a ``jax.sharding.Mesh`` — prefix -> core.

This is the TPU-native form of the coordinator's fan-out (SURVEY.md
section 2, strategies 1-2): inside one worker process, the worker's
thread-byte range is sub-partitioned across the devices of a mesh exactly
the way the coordinator partitions it across workers
(coordinator.go:326, worker.go:301-316) — prefix -> core instead of
prefix -> RPC peer.  The "first result wins, everyone stops" protocol
(coordinator.go:202-230) compresses onto ICI: the persistent steps
(``mesh_persistent_step``, ``pallas_mesh_persistent_step``) end every
on-device segment in a ``lax.pmin`` of the per-device first-hit flat
index, so all devices observe a win at the same segment boundary and
leave the loop together — the Found broadcast without any RPC.  The
factories below bind them as ``step_builder``s of
``parallel.search.persistent_search``.

Every step reports hits as *global* flat indices (chunk-major,
thread-byte-minor over the whole worker partition), so the driver's
decode and the reference enumeration-order guarantee are identical to
the single-device path.
"""

from __future__ import annotations

import functools
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from ..models.registry import HashModel, get_hash_model
from ..ops.search_step import (
    SENTINEL,
    _check_launch,
    chain_operand,
    chained_segments,
    eval_dyn_candidates,
    fold_dyn_masks,
    named_jit,
    unpack_operands,
)
from .compat import shard_map as _shard_map

AXIS = "workers"


def make_mesh(devices: Optional[Sequence] = None, axis: str = AXIS) -> Mesh:
    devs = list(devices) if devices is not None else jax.devices()
    from ..runtime.metrics import REGISTRY as metrics

    metrics.gauge("search.mesh_devices", len(devs))
    return Mesh(np.array(devs), (axis,))


def _on_mesh(step, mesh: Mesh):
    """Mark ``step`` as one whose every dispatch spans all the devices
    of ``mesh``: the search driver reads ``mesh_devices`` to count its
    candidates under ``search.mesh_hashes`` as well as
    ``search.hashes``, and names the device count on the width's
    ``search.segment`` span.  The width-0 probe and the single-device
    steps carry no mark, nor do the steps of a one-device mesh (the
    ``pallas`` backend's): their dispatches span one device."""
    if mesh.devices.size > 1:
        step.mesh_devices = int(mesh.devices.size)
    return step


@functools.lru_cache(maxsize=None)
def pallas_mesh_persistent_step(
    mesh: Mesh,
    axis: str,
    model_name: str,
    tb_word: int,
    tb_shift: int,
    chunk_word_shifts,
    grid: int,
    sublanes: int,
    inner: int,
    interpret: bool,
    mask_words: int,
    tb_split: bool,
    log_ndev: int,
    seg_local: int,
    segments: int,
    base_width: int,
    base_off: int,
):
    """The Pallas kernel on every device of the mesh, in the chained
    multi-segment loop of the persistent steps (``chained_segments``).

    Each loop iteration is one segment: ``seg_local`` candidates a
    device, one ``_dyn_pallas_step`` call of ``grid`` steps, then a
    ``lax.pmin`` of the global first-hit index, so every device sees a
    hit at the same segment boundary and leaves the loop with the
    others.  A segment covers one contiguous global span of the
    enumeration, ``[seg * seg_global, (seg + 1) * seg_global)`` flat: in
    the tb-split regime every device scans the segment's chunks on its
    own thread-byte slice; in the chunk-split regime the span is cut
    into ``n_dev`` contiguous chunk spans, one a device.  (Splitting the
    whole dispatch instead would start device ``d`` at ``d / n_dev`` of
    it, and the loop would stop at a hit there before device 0 reached
    an earlier one.)  The first hit of the first segment that has one is
    therefore the dispatch's reference-order first.

    Returned fn: ``(pack, chunk0, prev[2]) -> uint32[2]``, the
    signature of ``mesh_persistent_step``, reading the one-block row at
    ``base_off`` of the request's pack: the first-hit global flat index,
    SENTINEL or HALT, then the segments run.  Behind a ``prev`` that hit
    or halted it runs none.
    """
    from ..ops.md5_pallas import _TILE_FNS, _dyn_pallas_step

    kernel = _dyn_pallas_step(
        tb_word, tb_shift, chunk_word_shifts, grid, sublanes, interpret,
        inner, mask_words, model_name,
    )
    # The kernel's jaxpr is traced here, once, outside shard_map and the
    # loop, and bound in the loop body as it is: traced inside the loop
    # body it took ~3.5 s a program on a v5e host, this way ~0.5 s
    u32 = functools.partial(jax.ShapeDtypeStruct, dtype=jnp.uint32)
    kernel_jaxpr = jax.make_jaxpr(kernel)(
        u32(()), u32((_TILE_FNS[model_name][1],)), u32((base_width,)),
        u32((mask_words,)), u32((2,)))

    def call_kernel(*operands):
        return jax.core.eval_jaxpr(kernel_jaxpr.jaxpr, kernel_jaxpr.consts,
                                   *operands)[0]

    model = get_hash_model(model_name)
    one = jnp.uint32(1)
    seg_global = seg_local << log_ndev
    _check_launch(seg_global, segments)

    def body(pack, chunk0, prev):
        d = jax.lax.axis_index(axis).astype(jnp.uint32)
        tb_lo, log_tbc, _, init, base, masks = unpack_operands(
            pack, model, mask_words, base_off, 1)
        base = base[0]
        part = pack[:2]
        # global chunks one segment spans
        seg_chunks = jnp.uint32(seg_global) >> log_tbc
        if tb_split:
            log_tbl = log_tbc - jnp.uint32(log_ndev)
            part_dev = jnp.stack(
                [tb_lo + (d << log_tbl), log_tbl]).astype(jnp.uint32)

        def search_segment(seg):
            c0 = jnp.uint32(chunk0) + seg * seg_chunks
            if tb_split:
                f_l = call_kernel(c0, init, base, masks, part_dev)
                f_g = ((f_l >> log_tbl) << log_tbc) + (d << log_tbl) \
                    + (f_l & ((one << log_tbl) - one))
            else:
                chunks_dev = jnp.uint32(seg_local) >> log_tbc
                f_l = call_kernel(c0 + d * chunks_dev, init, base, masks,
                                  part)
                f_g = d * jnp.uint32(seg_local) + f_l
            f_g = jnp.where(f_l == jnp.uint32(SENTINEL), jnp.uint32(SENTINEL),
                            seg * jnp.uint32(seg_global) + f_g)
            return jax.lax.pmin(f_g, axis)

        return chained_segments(prev, segments, search_segment)

    # check_vma=False: the pallas_call's output carries no varying-axes
    # annotation; the per-segment pmin is what makes the loop carry
    # replicated
    sharded = _shard_map(
        body, mesh=mesh, in_specs=(P(),) * 3, out_specs=P(), check_vma=False,
    )
    return named_jit(sharded, f"{model_name}_pallas_mesh_persistent_step")


def _pallas_mesh_geometry(model: HashModel, n_dev: int, tbc: int,
                          interpret: bool):
    """``(sublanes, inner)`` of the kernel for a mesh of ``n_dev``
    devices and a partition of ``tbc`` thread bytes, or ValueError where
    the kernel cannot express them: a device count or partition that is
    not a power of two, a model without a kernel, or a TPU-only tile in
    interpret mode."""
    from ..ops.md5_pallas import (
        _TILE_FNS,
        INTERPRET_XLA_FALLBACK,
        default_geometry,
    )

    if n_dev & (n_dev - 1):
        raise ValueError("pallas mesh requires a power-of-two device count")
    if tbc & (tbc - 1):
        raise ValueError("pallas kernel requires power-of-two tb_count")
    if model.name not in _TILE_FNS:
        raise ValueError(f"no pallas kernel for model {model.name}")
    if interpret and model.name in INTERPRET_XLA_FALLBACK:
        # same guard as build_pallas_search_step: interpret mode would
        # hand the unrolled limb-pair tile to XLA:CPU (pathological
        # compile); the backend maps this to its XLA fallback
        raise ValueError(
            f"{model.name} pallas tile is TPU-only (interpret-mode "
            f"XLA:CPU compile of the limb-pair graph is pathological)"
        )
    return default_geometry(model.name, interpret)


@functools.lru_cache(maxsize=None)
def mesh_slot_search_step(
    mesh: Mesh,
    axis: str,
    model_name: str,
    n_blocks: int,
    tb_loc,
    chunk_locs,
    batch_local: int,
    n_slots: int,
):
    """Multi-slot serving step spread over the device mesh — the
    scheduler's ``mesh`` launch lane (sched/lanes.py, docs/SERVING.md).

    Same signature and contract as ``ops.search_step.slot_search_step``
    with ``batch = batch_local * n_dev``: ``(init[n, S],
    base[n, n_blocks, W], masks[n, D], tb_lo[n], log_tbc[n],
    chunk0[n]) -> uint32[n]``.  Device ``d`` evaluates the contiguous
    flat sub-range ``[d * batch_local, (d+1) * batch_local)`` of every
    slot's lane and ``lax.pmin`` folds the per-device minima, so the
    returned per-slot first-hit index is byte-identical to the
    single-device step over the same global span — one launch simply
    covers ``n_dev`` x the candidates (the lane-parity suite,
    tests/test_lanes.py, pins this).
    """
    model = get_hash_model(model_name)
    n_dev = int(mesh.devices.size)
    one = jnp.uint32(1)
    _check_launch(batch_local * n_dev, 1)

    def body(init, base, masks, tb_lo, log_tbc, chunk0):
        d = jax.lax.axis_index(axis).astype(jnp.uint32)
        f0 = d * jnp.uint32(batch_local) + jnp.arange(
            batch_local, dtype=jnp.uint32
        )

        def lane(init1, base1, masks1, tb_lo1, log_tbc1, chunk01):
            chunk = chunk01 + (f0 >> log_tbc1)
            tb = tb_lo1 + (f0 & ((one << log_tbc1) - one))
            state = eval_dyn_candidates(
                model, n_blocks, tb_loc, chunk_locs, init1, base1, tb, chunk
            )
            hit = fold_dyn_masks(model, state, masks1)
            return jnp.min(jnp.where(hit, f0, jnp.uint32(SENTINEL)))

        local = jax.vmap(lane)(init, base, masks, tb_lo, log_tbc, chunk0)
        return jax.lax.pmin(local, axis)

    # check_vma=False for the same reason as the pallas mesh step: the
    # explicit pmin is the collective that makes the result replicated;
    # the vmapped lane's varying-axes typing differs across JAX versions
    sharded = _shard_map(
        body, mesh=mesh, in_specs=(P(),) * 6, out_specs=P(),
        check_vma=False,
    )
    return named_jit(sharded, f"{model_name}_mesh_slot_step")


@functools.lru_cache(maxsize=None)
def mesh_persistent_step(
    mesh: Mesh,
    axis: str,
    model_name: str,
    n_blocks: int,
    tb_loc,
    chunk_locs,
    batch_local: int,
    static_tbc,  # None => power-of-two partition, log2 read from the pack
    segments: int,
    mask_words: int,
    base_off: int,
):
    """Persistent-loop serving step spread over the device mesh — the
    solo/persistent route's ``mesh`` lane (docs/SERVING.md).

    Mirrors ``ops.search_step.persistent_search_step`` with
    ``batch = batch_local * n_dev``: the same multi-segment on-device
    loop (``chained_segments``) with early exit on a hit and the same
    chained halt behind a predecessor that hit or halted, but each
    segment's candidate sub-batch is split across the mesh (device ``d``
    owns flat ``[d * batch_local, (d+1) * batch_local)`` within the
    segment) and a per-segment ``lax.pmin`` folds the device minima into
    the replicated carry — every device therefore observes a hit at the
    same segment boundary and exits together, the module-docstring
    "first result wins, everyone stops" protocol applied inside one
    dispatch.  Returned fn signature matches the single-device step:
    ``(pack, chunk0, prev[2]) -> uint32[2]`` (first-hit global flat
    index, SENTINEL or HALT, then segments executed).
    """
    model = get_hash_model(model_name)
    n_dev = int(mesh.devices.size)
    batch_global = batch_local * n_dev
    _check_launch(batch_global, segments)
    one = jnp.uint32(1)

    def step(pack, chunk0, prev):
        tb_lo, log_tbc, _, init, base, masks = unpack_operands(
            pack, model, mask_words, base_off, n_blocks)
        d = jax.lax.axis_index(axis).astype(jnp.uint32)
        f0 = d * jnp.uint32(batch_local) + jnp.arange(
            batch_local, dtype=jnp.uint32
        )

        def search_segment(seg):
            f = seg * jnp.uint32(batch_global) + f0
            if static_tbc is None:
                chunk = jnp.uint32(chunk0) + (f >> log_tbc)
                tb = tb_lo + (f & ((one << log_tbc) - one))
            else:
                chunk = jnp.uint32(chunk0) + f // jnp.uint32(static_tbc)
                tb = tb_lo + f % jnp.uint32(static_tbc)
            state_w = eval_dyn_candidates(
                model, n_blocks, tb_loc, chunk_locs, init, base, tb,
                chunk,
            )
            hit = fold_dyn_masks(model, state_w, masks, mask_words)
            found = jnp.min(jnp.where(hit, f, jnp.uint32(SENTINEL)))
            return jax.lax.pmin(found, axis)

        return chained_segments(prev, segments, search_segment)

    # check_vma=False: the per-segment pmin inside the while_loop body is
    # what makes the carry replicated; the VMA/replication typing of a
    # collective inside a loop carry differs across JAX versions
    sharded = _shard_map(
        step, mesh=mesh, in_specs=(P(),) * 3, out_specs=P(),
        check_vma=False,
    )
    return named_jit(sharded, f"{model_name}_mesh_persistent_step")


def mesh_persistent_factory(operands, mesh: Mesh, axis: str = AXIS):
    """Persistent step builder over the mesh — the ``step_builder`` hook
    of ``parallel.search.persistent_search`` (the solo/persistent
    route's mesh lane), for one request's ``operands``
    (``parallel.search.RequestOperands``).

    Returns ``builder(vw, extra, target_chunks, segments) ->
    (bound(chunk0, prev), chunks_each, chunks_per_step)`` with the
    driver's exact accounting contract: each dispatch covers up to
    ``segments`` on-device segments of ``target_chunks`` GLOBAL chunks
    each.  Raises ValueError (from the builder) when the global segment
    batch does not divide across the mesh — the caller falls back to
    the single-device persistent step, same per-width contract as the
    pallas mesh factory.

    The request's pack is placed replicated on the mesh here, once, in
    one ``jax.device_put`` from the host; each width's step reads its
    own row of it, and the constant chain operands (``chain_operand``)
    sit where the step's own replicated results do: steady-state
    dispatches move only the chunk cursor, and the first dispatch of a
    solve compiles nothing its successors did not.
    """
    from .compat import NamedSharding

    model = operands.model
    tbc = operands.tbc
    n_dev = int(mesh.devices.size)
    repl = NamedSharding(mesh, P())
    pow2 = tbc & (tbc - 1) == 0
    operands.placed(repl)

    def builder(vw: int, extra: bytes, target_chunks: int, segments: int):
        if vw == 0:
            raise ValueError(
                "width 0 has no persistent form; use the probe"
            )
        batch_global = target_chunks * tbc
        if batch_global % n_dev:
            raise ValueError(
                f"segment batch {batch_global} (chunks={target_chunks}, "
                f"tbc={tbc}) does not divide across {n_dev} devices"
            )
        pack, placed, row = operands.rows(vw, extra, repl)
        spec = pack.specs[row]
        dyn = mesh_persistent_step(
            mesh, axis, model.name, spec.n_blocks, spec.tb_loc,
            spec.chunk_locs, batch_global // n_dev,
            None if pow2 else tbc, segments, pack.mask_words,
            pack.base_offs[row],
        )

        def bound(chunk0, prev):
            return dyn(placed, chunk0, chain_operand(prev, repl))

        return _on_mesh(bound, mesh), target_chunks, target_chunks * segments

    return builder


def pallas_mesh_persistent_factory(
    operands,
    mesh: Mesh,
    axis: str = AXIS,
    interpret: bool = False,
    segment_candidates: Optional[int] = None,
):
    """Persistent step builder over the mesh backed by the Pallas kernel
    (``pallas_mesh_persistent_step``): the ``step_builder`` the
    ``pallas-mesh`` backend hands ``parallel.search.persistent_search``,
    for one request's ``operands``.

    Returns ``builder(vw, extra, target_chunks, segments) ->
    (bound(chunk0, prev), chunks_each, chunks_per_step)``, the contract
    of ``mesh_persistent_factory``.  A dispatch covers what the driver
    planned, ``target_chunks * tbc * segments`` candidates, cut into
    on-device segments of ``segment_candidates`` a device (by default
    ``SEGMENT_GRID_STEPS`` grid steps of the model's kernel geometry,
    ops/md5_pallas.py).  A dispatch smaller than one segment runs one
    segment of its own size, rounded up to the tile grid.  Both sizes
    follow from the driver's batch and budget alone, never from the
    partition, so one program serves every power-of-two partition of a
    sharding regime.

    Raises ValueError where the kernel cannot express the request
    (``_pallas_mesh_geometry``) or, from the builder, a width (a
    multi-block tail); the backend serves those through
    ``mesh_persistent_factory``.  The request's pack is placed
    replicated on the mesh here, as there, and the chain's constants
    sit like the step's own results, so a dispatch behind
    ``CHAIN_HALT`` compiles the program the solve's dispatches run.
    """
    from ..ops.md5_pallas import LANES, SEGMENT_GRID_STEPS
    from .compat import NamedSharding

    model = operands.model
    tbc = operands.tbc
    n_dev = int(mesh.devices.size)
    sublanes, inner = _pallas_mesh_geometry(model, n_dev, tbc, interpret)
    tile = sublanes * LANES
    if segment_candidates is None:
        segment_candidates = SEGMENT_GRID_STEPS * tile * inner
    tb_split = tbc >= n_dev
    log_ndev = n_dev.bit_length() - 1
    repl = NamedSharding(mesh, P())
    operands.placed(repl)

    def builder(vw: int, extra: bytes, target_chunks: int, segments: int):
        spec = operands.spec(vw, extra)
        if spec.n_blocks != 1:
            raise ValueError("pallas kernel requires a single-block tail")
        per_dev = (target_chunks * tbc * segments) >> log_ndev
        seg_local = -(-min(segment_candidates, per_dev) // tile) * tile
        n_seg = -(-per_dev // seg_local)
        tiles = seg_local // tile
        inner_eff = inner
        while tiles % inner_eff:
            inner_eff //= 2
        pack, placed, row = operands.rows(vw, extra, repl)
        _, tb_w, tb_s = spec.tb_loc
        dyn = pallas_mesh_persistent_step(
            mesh, axis, model.name, tb_w, tb_s,
            tuple((w, s) for _, w, s in spec.chunk_locs), tiles // inner_eff,
            sublanes, inner_eff, interpret, pack.mask_words, tb_split,
            log_ndev, seg_local, n_seg, len(spec.base_words[0]),
            pack.base_offs[row],
        )

        def bound(chunk0, prev):
            return dyn(placed, chunk0, chain_operand(prev, repl))

        chunks_each = (seg_local << log_ndev) // tbc
        return _on_mesh(bound, mesh), chunks_each, chunks_each * n_seg

    return builder
