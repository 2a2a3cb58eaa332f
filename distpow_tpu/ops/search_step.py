"""The fused proof-of-work search step — the framework's hot op.

One step evaluates ``chunks_per_step × tb_count`` candidates entirely on
device: flat index -> (chunk, thread byte) -> message words -> hash state
-> difficulty mask -> argmin of hits, returning a single uint32 (the flat
index of the first hit in reference enumeration order, or SENTINEL).

This replaces the reference's per-candidate loop body (worker.go:346-356).
Reference order is preserved exactly: the flat index is chunk-major,
thread-byte-minor, matching the nested loop at worker.go:318-319 where all
thread bytes are tried for each chunk value before the chunk advances.

Two compilation regimes:

* ``build_search_step`` bakes everything but the chunk base into the
  program — maximum constant folding, one compile per (nonce, difficulty,
  partition, batch).  Used where one configuration is re-dispatched many
  times (bench, graft entry).
* the serving steps bind a *layout-keyed* dynamic program to one runtime
  operand, the request's pack (``pack_operands``): the thread-byte
  partition, the absorbed prefix state, the difficulty masks and each
  width's constant tail words, in one ``uint32`` buffer that each program
  slices at offsets static in it.  The compile key is only (model, tail
  layout, batch), where the tail layout depends on the nonce length *mod
  block size* and the chunk width — so a worker that has compiled
  (nonce_len=4, width=2) once serves EVERY 4-byte-nonce request at ANY
  difficulty and ANY partition with zero recompiles.  The constant words
  cost nothing extra at runtime: they are loop-invariant scalars XLA
  hoists out of the batch dimension.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..models.registry import HashModel, get_hash_model
from .difficulty import meets_difficulty, nibble_masks
from .packing import TailSpec, build_tail_spec, make_words

SENTINEL = 0xFFFFFFFF
# Element 0 of a persistent dispatch that did not run because its
# predecessor hit or halted.  Flat indices stay below 2^31
# (``_check_launch``), so neither marker can be a hit.
HALT = 0xFFFFFFFE

# The two constant chain operands of a persistent step, in host form
# (see ``persistent_search_step``): CLEAR reads as a predecessor that
# ran and found nothing, so the dispatch searches; HALT makes it exit
# at its first loop check.  Bound steps take these objects themselves
# and swap in device copies made once (``chain_operands``).
# distpow: ok hot-path-host-sync -- host constants built once at import
# from Python ints; no device value is read
CHAIN_CLEAR = np.array([SENTINEL, 0], np.uint32)
# distpow: ok hot-path-host-sync -- as above
CHAIN_HALT = np.array([HALT, 0], np.uint32)


@functools.lru_cache(maxsize=None)
def chain_operands(sharding=None):
    """Device copies of ``(CHAIN_CLEAR, CHAIN_HALT)``, placed the way a
    persistent step's own results are: uncommitted on the default
    device when ``sharding`` is None, else under ``sharding`` (the mesh
    step's replicated results).  A chain operand placed otherwise than
    the results it stands in for would compile a second program."""
    return tuple(jax.device_put(c, sharding) for c in (CHAIN_CLEAR, CHAIN_HALT))


def chain_operand(prev, sharding=None):
    """The device operand a bound persistent step passes for ``prev``:
    a predecessor's result as it is, a host constant as its device
    copy."""
    if prev is CHAIN_CLEAR:
        return chain_operands(sharding)[0]
    if prev is CHAIN_HALT:
        return chain_operands(sharding)[1]
    return prev


def named_jit(fn: Callable, name: str) -> Callable:
    """``jax.jit(fn)`` under ``name``: the program's XLA module, and the
    profiler's ``XLA Modules`` line, read ``jit_<name>`` instead of the
    ``jit_step`` every search program would otherwise share.  Only the
    name changes."""
    fn.__name__ = fn.__qualname__ = name
    return jax.jit(fn)


# Models whose fused XLA serving step is impractical to COMPILE on the
# TPU backend (>30 min observed for sha512's limb-emulation graph in
# both compress forms, r4c hardware session — docs/KERNELS.md): bench
# and sweep harnesses skip their XLA serving measurements rather than
# spend half an hour compiling, and serving routes through the Pallas kernel
# (ops/md5_pallas.py).  Distinct from INTERPRET_XLA_FALLBACK (an
# interpret-mode/XLA:CPU property): sha3_256 is interpret-fallback but
# its fori_loop serving step compiles fine.
XLA_SERVING_COMPILE_IMPRACTICAL = frozenset({"sha512", "sha384"})


def _eval_candidates(spec: TailSpec, masks, model: HashModel, tb, chunk):
    """Hash a broadcastable batch of candidates and return the hit mask."""
    state = spec.init_state
    for b in range(spec.n_blocks):
        words = make_words(spec, tb, chunk)[b]
        state = model.compress(state, words)
    if model.finalize is not None:  # composed hashes (sha256d)
        state = model.finalize(state)
    return meets_difficulty(state, masks)


def build_search_step(
    nonce: bytes,
    width: int,
    difficulty: int,
    tb_lo: int,
    tb_count: int,
    chunks_per_step: int,
    model: HashModel,
    extra_const_chunk: bytes = b"",
    jit: bool = True,
    launch_steps: int = 1,
) -> Callable:
    """Build ``step(chunk0: uint32) -> uint32`` for one chunk width.

    The thread bytes scanned are ``tb_lo .. tb_lo + tb_count - 1`` (the
    partition algebra always yields contiguous runs; parallel/partition.py).

    One dispatch evaluates ``launch_steps`` consecutive sub-batches of
    ``chunks_per_step × tb_count`` candidates inside a ``fori_loop`` —
    only one sub-batch is ever materialized, so huge launches amortize the
    per-dispatch host<->device round trip without huge buffers.
    """
    spec = build_tail_spec(nonce, width, model, extra_const_chunk)
    masks = nibble_masks(difficulty, model)
    batch = chunks_per_step * tb_count
    _check_launch(batch, launch_steps)

    def sub(chunk0, f):
        chunk = jnp.uint32(chunk0) + f // jnp.uint32(tb_count)
        tb = jnp.uint32(tb_lo) + f % jnp.uint32(tb_count)
        hit = _eval_candidates(spec, masks, model, tb, chunk)
        return jnp.min(jnp.where(hit, f, jnp.uint32(SENTINEL)))

    def step(chunk0):
        f0 = jnp.arange(batch, dtype=jnp.uint32)
        if launch_steps == 1:
            return sub(chunk0, f0)

        def body(i, best):
            f = i.astype(jnp.uint32) * jnp.uint32(batch) + f0
            return jnp.minimum(best, sub(chunk0, f))

        return jax.lax.fori_loop(0, launch_steps, body, jnp.uint32(SENTINEL))

    return named_jit(step, f"{model.name}_static_step") if jit else step


def _check_launch(batch: int, launch_steps: int) -> None:
    if launch_steps < 1:
        raise ValueError(f"launch_steps must be >= 1, got {launch_steps}")
    # strictly below 2^31: at exactly 2^31 the last flat index equals
    # the Pallas kernel's int32 miss marker (0x7FFFFFFF), making a hit
    # at that index indistinguishable from a miss
    if batch * launch_steps >= 1 << 31:
        raise ValueError(
            f"launch covers {batch * launch_steps} candidates; flat "
            f"indices require < 2^31 per dispatch"
        )


def eval_dyn_candidates(model, n_blocks, tb_loc, chunk_locs, init, base, tb, chunk):
    """Hash a batch against runtime-operand nonce words.

    The dynamic-regime twin of ``_eval_candidates``: the tail layout
    (``n_blocks``, ``tb_loc``, ``chunk_locs``) is static, while the
    absorbed prefix state ``init[S]`` and constant words
    ``base[n_blocks,16]`` are device operands.  Shared by the
    single-device and mesh dynamic steps.  Returns the state tuple.
    """
    state = tuple(init[i] for i in range(len(model.init_state)))
    for b in range(n_blocks):
        # row length = words_per_block + model.param_words (blake2's
        # baked per-block parameters ride at the end; packing.py)
        words = [base[b, w] for w in range(base.shape[1])]
        bb, w, s = tb_loc
        if bb == b:
            words[w] = words[w] | (tb << s)
        for j, (cb, cw, cs) in enumerate(chunk_locs):
            if cb == b:
                byte_j = (chunk >> jnp.uint32(8 * j)) & jnp.uint32(0xFF)
                words[cw] = words[cw] | (byte_j << cs)
        state = model.compress(state, words)
    if model.finalize is not None:  # composed hashes (sha256d)
        state = model.finalize(state)
    return state


def mask_words_for(difficulty: int, model) -> int:
    """Digest words the trailing-nibble masks can touch (from the end).

    Trailing nibbles live in the LAST digest words (8 nibbles per uint32
    word; ops/difficulty.py), so a difficulty <= 8 needs exactly one
    significant mask word.  Making this count a COMPILE key (not the
    difficulty itself) lets XLA dead-code-eliminate the rounds and final
    adds that only feed unused digest words, while any difficulty within
    the same bucket still shares one program.
    """
    return max(1, min(model.digest_words, -(-difficulty // 8)))


def fold_dyn_masks(model, state, masks, mask_words: Optional[int] = None):
    """Hit mask against runtime-operand difficulty masks.

    ``masks`` holds the ``mask_words`` significant masks for the LAST
    digest words (``pack_operands`` packs only those); None = all words.
    """
    d = model.digest_words
    k = d if mask_words is None else mask_words
    acc = state[d - k] & masks[0]
    for i in range(1, k):
        acc = acc | (state[d - k + i] & masks[i])
    return acc == 0


# The request pack (docs/SERVING.md "Operand binding").  Words:
#   [0] tb_lo, [1] log2(tbc) (0 where tbc is not a power of two), [2] tbc,
#   then the absorbed prefix state, then the ``mask_words`` significant
#   difficulty masks, then each row's constant tail words (n_blocks rows
#   of words_per_block + param_words).
PACK_HEADER = 3
# The rows of a request's dense pack: widths 0-4, no constant high bytes.
DENSE_ROWS = tuple((vw, b"") for vw in range(5))


@dataclass(frozen=True, eq=False)
class OperandPack:
    """One request's operands in one host ``uint32`` buffer, ``words``.

    Row ``i`` holds the constant tail words of ``specs[i]`` from word
    ``base_offs[i]``.  The buffer's length and every offset follow from
    the layout alone (the nonce length, through each row's tail layout,
    the model and the mask-word bucket), never from the nonce's bytes,
    so the programs that slice it stay layout-keyed."""

    words: np.ndarray
    model_name: str
    tbc: int
    mask_words: int
    specs: Tuple[TailSpec, ...]
    base_offs: Tuple[int, ...]


def pack_operands(nonce: bytes, difficulty: int, tb_lo: int, tbc: int,
                  model: HashModel, rows=DENSE_ROWS) -> OperandPack:
    """Pack ``(nonce, difficulty, tb_lo, tbc)`` for the chunk widths
    ``rows``, a tuple of ``(vw, extra_const_chunk)``.  Numpy only: no
    device is touched until the caller places ``words``."""
    specs = tuple(build_tail_spec(nonce, vw, model, extra)
                  for vw, extra in rows)
    mw = mask_words_for(difficulty, model)
    masks = nibble_masks(difficulty, model)[model.digest_words - mw:]
    pow2 = tbc & (tbc - 1) == 0
    words = [tb_lo, tbc.bit_length() - 1 if pow2 else 0, tbc,
             *specs[0].init_state, *masks]
    offs = []
    for spec in specs:
        offs.append(len(words))
        for row in spec.base_words:
            words.extend(row)
    # distpow: ok hot-path-host-sync -- packs Python ints on the host;
    # no device value is read
    return OperandPack(np.array(words, np.uint32), model.name, tbc, mw,
                       specs, tuple(offs))


def unpack_operands(pack, model: HashModel, mask_words: int, base_off: int,
                    n_blocks: int):
    """``(tb_lo, log_tbc, tbc, init, base[n_blocks, W], masks)``: one
    row's operands, sliced from a placed pack at offsets static in the
    calling program."""
    s = len(model.init_state)
    w = model.words_per_block + model.param_words
    init = pack[PACK_HEADER:PACK_HEADER + s]
    masks = pack[PACK_HEADER + s:PACK_HEADER + s + mask_words]
    base = pack[base_off:base_off + n_blocks * w].reshape(n_blocks, w)
    return pack[0], pack[1], pack[2], init, base, masks


@functools.lru_cache(maxsize=None)
def _dyn_search_step(
    model_name: str,
    n_blocks: int,
    tb_loc,
    chunk_locs,
    batch: int,
    static_tbc,  # None => power-of-two partition, log2 read from the pack
    launch_steps: int,
    mask_words: int,
    base_off: int,
):
    """Layout-keyed jitted step with nonce/difficulty/partition in the
    pack: ``(pack, chunk0) -> uint32``, the row at ``base_off``.

    ``launch_steps`` sub-batches of ``batch`` candidates run inside one
    dispatch via ``fori_loop`` (see ``build_search_step``); the returned
    index spans the full ``launch_steps * batch`` range.
    """
    model = get_hash_model(model_name)
    _check_launch(batch, launch_steps)

    def step(pack, chunk0):
        tb_lo, log_tbc, _, init, base, masks = unpack_operands(
            pack, model, mask_words, base_off, n_blocks)

        def sub(f):
            if static_tbc is None:
                chunk = jnp.uint32(chunk0) + (f >> log_tbc)
                tb = tb_lo + (f & ((jnp.uint32(1) << log_tbc)
                                   - jnp.uint32(1)))
            else:
                chunk = jnp.uint32(chunk0) + f // jnp.uint32(static_tbc)
                tb = tb_lo + f % jnp.uint32(static_tbc)
            state = eval_dyn_candidates(
                model, n_blocks, tb_loc, chunk_locs, init, base, tb, chunk
            )
            hit = fold_dyn_masks(model, state, masks, mask_words)
            return jnp.min(jnp.where(hit, f, jnp.uint32(SENTINEL)))

        f0 = jnp.arange(batch, dtype=jnp.uint32)
        if launch_steps == 1:
            return sub(f0)

        def body(i, best):
            return jnp.minimum(
                best, sub(i.astype(jnp.uint32) * jnp.uint32(batch) + f0))

        return jax.lax.fori_loop(0, launch_steps, body, jnp.uint32(SENTINEL))

    return named_jit(step, f"{model_name}_launch_step")


@functools.lru_cache(maxsize=None)
def _dyn_search_step_w0(model_name: str, n_blocks: int, tb_loc, chunk_locs,
                        mask_words: int, base_off: int):
    """Width-0 probe program: scan ALL 256 thread bytes, mask the ones
    outside the runtime partition.

    Width 0 has exactly ``tb_count`` candidates, so keying the batch on
    the partition would recompile per worker split — defeating warmup.
    Instead one fixed-shape (256) program serves every partition: the
    partition is the pack's (tb_lo, tbc) and out-of-partition hits are
    masked off.  Returns the partition-local flat index (tb - tb_lo) of
    the first hit, or SENTINEL — identical contract to the general step
    at width 0.
    """
    model = get_hash_model(model_name)

    def step(pack, chunk0):
        del chunk0  # width 0: no chunk bytes
        tb_lo, _, tbc, init, base, masks = unpack_operands(
            pack, model, mask_words, base_off, n_blocks)
        tb = jnp.arange(256, dtype=jnp.uint32)
        state = eval_dyn_candidates(
            model, n_blocks, tb_loc, chunk_locs, init, base, tb,
            jnp.uint32(0),
        )
        hit = fold_dyn_masks(model, state, masks, mask_words)
        hit = hit & (tb >= tb_lo) & (tb < tb_lo + tbc)
        return jnp.min(jnp.where(hit, tb - tb_lo, jnp.uint32(SENTINEL)))

    return named_jit(step, f"{model_name}_probe_step")


def _layout_key(pack: OperandPack, row: int):
    spec = pack.specs[row]
    return spec.n_blocks, spec.tb_loc, spec.chunk_locs


def bind_probe(pack: OperandPack, placed):
    """The width-0 probe over ``placed``, a placement of ``pack`` whose
    row 0 is width 0: ``bound(chunk0) -> uint32``."""
    w0 = _dyn_search_step_w0(pack.model_name, *_layout_key(pack, 0),
                             pack.mask_words, pack.base_offs[0])

    def bound0(chunk0):
        return w0(placed, chunk0)

    return bound0


def _static_tbc(tbc: int):
    return None if tbc & (tbc - 1) == 0 else tbc


def cached_search_step(
    nonce: bytes,
    width: int,
    difficulty: int,
    tb_lo: int,
    tb_count: int,
    chunks_per_step: int,
    model_name: str,
    extra_const_chunk: bytes = b"",
    launch_steps: int = 1,
):
    """A layout-keyed program (cached by layout, see module docstring)
    bound to one pack of this request's operands, placed on the default
    device.  Same contract as ``build_search_step``: one dispatch covers
    ``launch_steps * chunks_per_step * tb_count`` candidates."""
    model = get_hash_model(model_name)
    pack = pack_operands(bytes(nonce), difficulty, tb_lo, tb_count, model,
                         ((width, extra_const_chunk),))
    placed = jax.device_put(pack.words)
    if width == 0:
        return bind_probe(pack, placed)
    dyn = _dyn_search_step(
        model_name, *_layout_key(pack, 0), chunks_per_step * tb_count,
        _static_tbc(tb_count), launch_steps, pack.mask_words,
        pack.base_offs[0],
    )

    def bound(chunk0):
        return dyn(placed, chunk0)

    return bound


def flat_to_candidate(
    f: int, chunk0: int, tb_lo: int, tb_count: int
) -> Tuple[int, int]:
    """Host-side inverse of the step's index map: flat -> (chunk, tb)."""
    return chunk0 + f // tb_count, tb_lo + f % tb_count


@functools.lru_cache(maxsize=None)
def persistent_search_step(
    model_name: str,
    n_blocks: int,
    tb_loc,
    chunk_locs,
    batch: int,
    static_tbc,  # None => power-of-two partition, log2 read from the pack
    segments: int,
    mask_words: int,
    base_off: int,
):
    """Persistent-loop serving step: a multi-segment on-device search
    (docs/SERVING.md).

    Where the ``fori_loop`` steps above run every sub-batch
    unconditionally, this step carries a device-resident found
    flag/result buffer through a ``while_loop``: each iteration
    evaluates one ``batch``-candidate sub-batch, folds its first hit
    into the carried best index, and the loop EXITS as soon as the
    carry holds a hit.  One dispatch therefore covers up to
    ``segments`` sub-batches of device work with zero host round trips
    between them, and a hit surfaces without paying for the launch's
    remaining segments.

    Chained halt: the last operand ``prev`` is the previous dispatch's
    own ``uint32[2]`` result (or ``CHAIN_CLEAR`` / ``CHAIN_HALT``).
    The loop runs only while ``prev[0] == SENTINEL``, i.e. the
    predecessor ran and found nothing; otherwise the dispatch exits at
    its first loop check and returns ``[HALT, 0]``, which halts its own
    successor in turn.  So once one dispatch of a solve hits, every
    dispatch queued behind it on the device costs one loop check,
    without the host having to learn of the hit first.

    Signature of the returned jitted fn (all uint32): ``(pack, chunk0,
    prev[2]) -> uint32[2]``, reading the row at ``base_off`` of the
    request's pack — ``[0]`` is the first-hit flat index over the full
    ``segments * batch`` span (reference enumeration order; segments
    scan in order and each segment folds its own minimum), SENTINEL, or
    HALT; ``[1]`` is the number of segments actually executed (the
    driver's evaluated-work accounting, and the
    ``search.persistent_steps`` instrument).
    """
    model = get_hash_model(model_name)
    _check_launch(batch, segments)

    def step(pack, chunk0, prev):
        # sliced outside the loop: loop-invariant operands of its body
        tb_lo, log_tbc, _, init, base, masks = unpack_operands(
            pack, model, mask_words, base_off, n_blocks)
        f0 = jnp.arange(batch, dtype=jnp.uint32)

        def search_segment(seg):
            f = seg * jnp.uint32(batch) + f0
            if static_tbc is None:
                chunk = jnp.uint32(chunk0) + (f >> log_tbc)
                tb = tb_lo + (f & ((jnp.uint32(1) << log_tbc)
                                   - jnp.uint32(1)))
            else:
                chunk = jnp.uint32(chunk0) + f // jnp.uint32(static_tbc)
                tb = tb_lo + f % jnp.uint32(static_tbc)
            state = eval_dyn_candidates(
                model, n_blocks, tb_loc, chunk_locs, init, base, tb, chunk
            )
            hit = fold_dyn_masks(model, state, masks, mask_words)
            return jnp.min(jnp.where(hit, f, jnp.uint32(SENTINEL)))

        return chained_segments(prev, segments, search_segment)

    return named_jit(step, f"{model_name}_persistent_step")


def chained_segments(prev, segments: int, search_segment):
    """The loop of the persistent steps, single-device and mesh alike.

    Runs ``search_segment(seg) -> first-hit flat index or SENTINEL``
    for ``seg = 0, 1, ...`` until one hits or ``segments`` have run,
    provided ``prev[0] == SENTINEL`` (see ``persistent_search_step``).
    Returns ``uint32[2]``: the first hit, SENTINEL or HALT, then the
    segments run."""
    go = prev[0] == jnp.uint32(SENTINEL)

    def cond(state):
        seg, best = state
        return go & (seg < jnp.uint32(segments)) & \
            (best == jnp.uint32(SENTINEL))

    def body(state):
        seg, best = state
        return seg + jnp.uint32(1), jnp.minimum(best, search_segment(seg))

    seg, best = jax.lax.while_loop(
        cond, body, (jnp.uint32(0), jnp.uint32(SENTINEL))
    )
    return jnp.stack([jnp.where(go, best, jnp.uint32(HALT)), seg])


def bind_persistent_step(pack: OperandPack, placed, row: int,
                         chunks_per_step: int, segments: int):
    """The single-device persistent step for ``pack``'s row ``row``
    (a width above 0) over ``placed``, a single-device placement of the
    pack: ``bound(chunk0, prev) -> uint32[2]`` covering up to ``segments
    * chunks_per_step * tbc`` candidates a dispatch (early exit on a
    hit; no work behind a ``prev`` that hit or halted, or behind
    ``CHAIN_HALT``).  Width 0 has a single 256-candidate probe and no
    chunk axis: ``bind_probe`` serves it."""
    if pack.specs[row].width == 0:
        raise ValueError("width 0 has no persistent form; use bind_probe")
    dyn = persistent_search_step(
        pack.model_name, *_layout_key(pack, row), chunks_per_step * pack.tbc,
        _static_tbc(pack.tbc), segments, pack.mask_words,
        pack.base_offs[row],
    )

    def bound(chunk0, prev):
        return dyn(placed, chunk0, chain_operand(prev))

    return bound


def _slot_lane(model: HashModel, n_blocks: int, tb_loc, chunk_locs,
               batch: int, launch_steps: int):
    """One slot's un-vmapped search lane — the shared core of the
    single-model ``slot_search_step`` and the mixed-hash
    ``mixed_slot_search_step`` (each vmaps it over its own slot axis)."""

    def one(init, base, masks, tb_lo, log_tbc, chunk0):
        def sub(f):
            chunk = jnp.uint32(chunk0) + (f >> log_tbc)
            tb = tb_lo + (f & ((jnp.uint32(1) << log_tbc) - jnp.uint32(1)))
            state = eval_dyn_candidates(
                model, n_blocks, tb_loc, chunk_locs, init, base, tb, chunk
            )
            hit = fold_dyn_masks(model, state, masks)
            return jnp.min(jnp.where(hit, f, jnp.uint32(SENTINEL)))

        f0 = jnp.arange(batch, dtype=jnp.uint32)
        if launch_steps == 1:
            return sub(f0)

        def body(i, best):
            return jnp.minimum(
                best, sub(i.astype(jnp.uint32) * jnp.uint32(batch) + f0)
            )

        return jax.lax.fori_loop(0, launch_steps, body, jnp.uint32(SENTINEL))

    return one


@functools.lru_cache(maxsize=None)
def slot_search_step(
    model_name: str,
    n_blocks: int,
    tb_loc,
    chunk_locs,
    batch: int,
    n_slots: int,
    launch_steps: int = 1,
):
    """Multi-slot serving step: ``n_slots`` independent searches in ONE
    dispatch (the continuous-batching scheduler's hot op, sched/engine.py).

    The single-slot dynamic regime (``_dyn_search_step``) already makes
    every per-request quantity a runtime operand; this step vmaps that
    lane over a leading slot axis, so one compiled program evaluates a
    whole *batch of searches* — each slot with its own nonce operands,
    its own difficulty masks, and its own partition — and returns the
    per-slot first-hit flat index (or SENTINEL) as a ``uint32[n_slots]``
    vector fetched in a single host<->device round trip.

    Signature of the returned jitted fn (all uint32):
    ``(init[n, S], base[n, n_blocks, W], masks[n, D], tb_lo[n],
    log_tbc[n], chunk0[n]) -> uint32[n]``.

    Differences from the single-slot step, both deliberate:

    * masks carry ALL digest words (``mask_words`` is not a compile
      key): per-slot difficulty is then purely an operand, so slots at
      different difficulties share one program — the whole point of
      packing them.
    * the partition rides ``log_tbc`` per slot (power-of-two partitions
      only; the scheduler falls back to solo search otherwise), so one
      lane's flat range ``[0, batch)`` spans ``batch >> log_tbc`` chunk
      values — lanes with narrower partitions simply cover more chunks
      per launch.
    """
    model = get_hash_model(model_name)
    _check_launch(batch, launch_steps)
    return named_jit(jax.vmap(
        _slot_lane(model, n_blocks, tb_loc, chunk_locs, batch, launch_steps)
    ), f"{model_name}_slot_step")


@functools.lru_cache(maxsize=None)
def mixed_slot_search_step(
    groups: tuple,
    batch: int,
    launch_steps: int = 1,
):
    """Mixed-hash multi-slot step: slots of DIFFERENT hash models share
    one device dispatch (docs/SERVING.md).

    ``groups`` is an ordered tuple of per-model sub-batch descriptors
    ``(model_name, n_blocks, tb_loc, chunk_locs, n_slots)`` — the
    compile key is therefore the full MODEL SET of the launch (plus
    each group's padded lane count), extending the single-model step's
    layout key exactly the way the scheduler's launch planner groups
    its slot table.  Inside the one compiled program each group runs
    its own vmapped lane stack (per-model compress functions cannot
    share lanes — different round structures — but they CAN share a
    launch, which is what restores batching to mixed-hash traffic that
    previously forfeited it to the solo fallback).

    The returned jitted fn takes a tuple of per-group operand tuples
    ``((init[n_i, S_i], base[n_i, b_i, W_i], masks[n_i, D_i],
    tb_lo[n_i], log_tbc[n_i], chunk0[n_i]), ...)`` and returns a tuple
    of per-group ``uint32[n_i]`` first-hit vectors, all fetched in one
    host<->device round trip.
    """
    lanes = tuple(
        _slot_lane(get_hash_model(m), nb, tl, cl, batch, launch_steps)
        for (m, nb, tl, cl, _n) in groups
    )
    _check_launch(batch, launch_steps)

    def step(group_ops):
        return tuple(
            jax.vmap(lane)(*ops) for lane, ops in zip(lanes, group_ops)
        )

    return named_jit(step, "mixed_slot_step")
