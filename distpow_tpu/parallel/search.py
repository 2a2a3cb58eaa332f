"""The search driver: chained persistent dispatches over one solve.

Replaces the reference worker's ``miner`` goroutine hot loop
(worker.go:258-401).  Differences dictated by the accelerator model
(SURVEY.md section 7 "hard parts"):

* The reference enumerates one candidate at a time and polls its cancel
  channel every iteration (worker.go:320-345).  A TPU kernel is
  uninterruptible, so the driver dispatches fixed-size batches and checks
  ``cancel_check`` between dispatches — cancellation latency is bounded by
  one batch.
* The chunk counter grows by appending carry bytes (worker.go:234-244),
  changing the message length.  The driver therefore runs one fused-step
  specialization per chunk *width* (0, 1, 2, ... bytes); within a width the
  space is a dense integer range and the kernel maps flat indices to
  candidates arithmetically.  Widths above 4 bytes (beyond uint32 lanes)
  fix the high chunk bytes per launch segment.
* Dispatches are pipelined (depth 2 by default) so the host prepares launch
  N+1 while the device crunches launch N; results are drained FIFO, which
  preserves reference enumeration order for the returned first match.
* Every device backend serves through :func:`persistent_search`; a
  backend's ``step_builder`` picks the device form of each width's
  dispatches (the mesh steps, the Pallas kernel) under one contract.

Batch-boundary note: a width-``w`` launch whose chunk range overruns
``256**w`` hashes candidates whose ``w``-byte little-endian chunk encoding
has a zero top byte.  Those are not in the reference's canonical
enumeration (its encodings are minimal) but they are perfectly valid
secrets under the puzzle contract — any solving secret is acceptable
(coordinator.go:202 takes whichever result arrives first) — so the driver
accepts them rather than paying a tail recompile per width.  The launch
multiplier widens the possible overrun to up to one full launch
(``launch_steps * chunks_per_step`` chunks past the segment end), but a
wrapped candidate can only win when no canonical candidate in the same
launch solves (canonical flat indices sort first), and every result is
re-verified host-side with hashlib before being returned.
"""

from __future__ import annotations

import time
from collections import deque
from dataclasses import dataclass
from typing import Callable, Optional, Sequence, Tuple

import jax
import numpy as np

from ..models import puzzle
from ..models.registry import HashModel, get_hash_model
from ..ops.packing import build_tail_spec
from ..ops.search_step import (
    CHAIN_CLEAR,
    CHAIN_HALT,
    DENSE_ROWS,
    SENTINEL,
    OperandPack,
    bind_persistent_step,
    bind_probe,
    pack_operands,
)
from ..runtime.metrics import REGISTRY as metrics
from ..runtime.spans import SPANS
from ..runtime.watchdog import FIRST_COMPILE_GRACE_S, WATCHDOG

DEFAULT_BATCH = 1 << 20
DEFAULT_PIPELINE_DEPTH = 2
# Candidates one dispatch should cover.  Every launch costs one
# host<->device round trip to fetch its first-hit index, so a dispatch
# must carry enough work to amortize it; steps run `launch_steps`
# sub-batches in an on-device fori_loop, keeping materialized buffers at
# the (much smaller) batch size.  2^30 is ~0.1 s of md5 device work;
# chip_smoke.py prints the measured local round trip this could be
# re-sized against.
DEFAULT_LAUNCH_CANDIDATES = 1 << 30


def scaled_launch_candidates(cost_ops: int, reference_ops: int = 584) -> int:
    """Per-dispatch candidate budget scaled by measured model cost.

    ``DEFAULT_LAUNCH_CANDIDATES`` (2^30) is tuned for md5: ~0.1-0.2 s
    of device work per launch at the measured ~10 GH/s, which bounds
    both cancellation latency (cancel_check runs between launches) and
    solve-time granularity (a hit surfaces when its launch drains).
    The slower hashes at the same budget stretch one launch to 2-4 s
    (measured: sha512/sha384/sha3 e2e solves quantized to ~2 s steps,
    an earlier chip reading) — scaling by
    ``HashModel.cost_ops`` keeps launch wall-clock roughly
    model-independent.  The 2^24 floor preserves dispatch
    amortization; an explicitly configured ``MaxLaunchCandidates``
    bypasses this entirely.
    """
    return max(1 << 24,
               (DEFAULT_LAUNCH_CANDIDATES * reference_ops)
               // max(cost_ops, reference_ops))


def launch_steps_for(
    vw: int,
    sub_chunks: int,
    tbc: int,
    max_launch: int = DEFAULT_LAUNCH_CANDIDATES,
) -> int:
    """Launch multiplier for one width segment.

    Pure function of (width, sub-batch candidate count, budget) — boot
    warmup (backends._warm_layouts) and serving both call it, which is
    what keeps the warmed compile keys identical to the served ones.
    Everything is computed from ``sub_chunks * tbc`` (== effective_batch
    for every power-of-two partition) and the width's CANONICAL 256-
    thread-byte candidate volume, never from the partition's own chunk
    count — the resulting k is identical across partitions, so it is safe
    inside compile keys.  The segment cap bounds overscan on small widths
    (a sub-256 partition may overscan its segment by at most 256/tbc)."""
    if vw == 0 or sub_chunks < 1:
        return 1
    sub_cand = sub_chunks * tbc
    seg_chunks = (1 << 32) if vw >= 4 else 256 ** vw - 256 ** (vw - 1)
    k_seg = -(-(seg_chunks * 256) // sub_cand)
    k_rtt = max_launch // sub_cand
    return max(1, min(k_rtt, k_seg))


def effective_batch(batch_size: int) -> int:
    """Requested batch size normalized to a partition-independent value.

    The serving batch must be a pure function of the configured size —
    NOT of the request's thread-byte count — so that the layout-keyed
    programs warmed at boot (tbc=256) are byte-for-byte the programs
    every power-of-two partition dispatches.  Rounding down to a
    multiple of 256 makes ``chunks * tbc == effective_batch`` exact for
    every pow2 tbc <= 256."""
    return max(256, batch_size - batch_size % 256)

@dataclass
class SearchResult:
    secret: bytes
    thread_byte: int
    chunk: bytes
    hashes_tried: int


class _RateMeter:
    """Process-wide live-throughput meter behind ``search.hashes_per_s``.

    One shared meter, not per-search state: concurrent searches all
    drain the same device, so the meaningful rate is candidates drained
    per wall-clock interval ACROSS searches (per-search EMAs writing one
    gauge would interleave garbage).  EMA over drain-to-drain windows
    smooths dispatch jitter; when the last active search exits the gauge
    drops to 0 — a stale full-throughput reading on an idle worker is
    the stuck-gauge class this plane polices elsewhere (review PR 3).
    """

    def __init__(self) -> None:
        import threading

        self._lock = threading.Lock()
        self._active = 0
        self._last_t: Optional[float] = None
        self._ema: Optional[float] = None

    def enter(self) -> None:
        with self._lock:
            self._active += 1

    def exit(self) -> None:
        with self._lock:
            self._active -= 1
            if self._active <= 0:
                self._last_t = self._ema = None
                metrics.gauge("search.hashes_per_s", 0)

    def note(self, n_cand: int) -> None:
        now = time.monotonic()
        with self._lock:
            prev, self._last_t = self._last_t, now
            if prev is None or now <= prev:
                return
            inst = n_cand / (now - prev)
            self._ema = inst if self._ema is None else \
                0.7 * self._ema + 0.3 * inst
            metrics.gauge("search.hashes_per_s", round(self._ema, 3))


_RATE_METER = _RateMeter()


def _register_part_counters() -> None:
    """Create ``search.mesh_hashes`` and ``search.finalize_hashes`` at
    each solve's start, so a worker whose solves all ran on one device,
    or under a hash with no ``finalize`` stage, reads 0 there, where a
    program that does not count that part has no such counter at all."""
    metrics.inc("search.mesh_hashes", 0)
    metrics.inc("search.finalize_hashes", 0)


# canonical home is the jax-free partition module (advisor r3: the
# native backend validates runs without importing the JAX compute path);
# re-exported here because the driver and the device backends import it
# from this module.
from .partition import contiguous_bounds  # noqa: E402,F401


def assemble_secret(
    chunk0: int, f: int, vw: int, extra: bytes, tb_lo: int, tbc: int
) -> Tuple[bytes, int]:
    """Host-side inverse of a launch's flat index: ``(secret, tb)``.

    One home for the candidate reconstruction two drains share — the
    solo FIFO drain below and the continuous-batching scheduler's
    per-slot drain (sched/engine.py).  The width mask reproduces the
    launch-overrun aliasing documented in the module docstring: an
    overshot chunk int wraps into a zero-top-byte encoding, which is a
    valid (verified) secret even though it is off the canonical
    enumeration.
    """
    chunk_int = (chunk0 + f // tbc) & 0xFFFFFFFF
    tb = tb_lo + f % tbc
    chunk_bytes = (
        (chunk_int & (256 ** vw - 1)).to_bytes(vw, "little") if vw else b""
    ) + extra
    return bytes([tb]) + chunk_bytes, tb


def width_segments(width: int):
    """Yield (variable_width, chunk_lo, chunk_hi, extra_const_chunk) for one
    chunk width.  For width <= 4 the whole width is one dense uint32 range;
    beyond that the high bytes are fixed per segment."""
    if width == 0:
        yield 0, 0, 1, b""
        return
    if width <= 4:
        yield width, 256 ** (width - 1), 256 ** width, b""
        return
    hi_w = width - 4
    for hi in range(256 ** (hi_w - 1), 256 ** hi_w):
        yield 4, 0, 1 << 32, hi.to_bytes(hi_w, "little")


def _unsatisfiable_wait(model: HashModel, difficulty: int, cancel_check,
                        max_hashes) -> None:
    """The driver's unsatisfiable-difficulty gate.

    Unsatisfiable: the digest only has max_difficulty nibbles.  The
    reference would brute-force forever (worker.go:246-256 never
    reaches the threshold); we busy-wait on the cancel/budget gates
    instead of burning the device.  With NEITHER gate supplied the
    wait could never end — a trap for bare library callers (the
    worker always passes a cancel_check), so that combination
    raises instead (VERDICT r3 weak #4 / item 7).
    """
    if cancel_check is None and max_hashes is None:
        raise ValueError(
            f"difficulty {difficulty} exceeds {model.name}'s "
            f"{model.max_difficulty} digest nibbles (unsatisfiable) "
            f"and no cancel_check/max_hashes gate was supplied; the "
            f"search could never return"
        )
    # (no watchdog involvement: this loop never touches the device,
    # and beating here could mask a genuinely hung concurrent search
    # on the shared staleness clock)
    while True:
        if cancel_check is not None and cancel_check():
            return None
        if max_hashes is not None:
            return None
        time.sleep(0.01)


class RequestOperands:
    """One request's operands, bound once (docs/SERVING.md "Operand
    binding").

    Widths 0-4 read one host pack (``ops/search_step.py
    pack_operands``), built at the request's first placement and placed
    at most once per device form: ``placed(sharding)`` under a mesh
    step's replicated sharding, ``placed()`` on one device.  The
    one-device programs (the width-0 probe, the default persistent
    step) read a placement already made through its first shard, so a
    request that the mesh steps serve places its pack once.  A width
    past 4 fixes its high chunk bytes per 2^32-candidate segment, so
    each such segment packs and places a one-row pack of its own, behind
    the previous segment's device work.  Each placement runs in a
    ``search.bind`` span and counts once under
    ``search.operand_binds``.
    """

    def __init__(self, nonce: bytes, difficulty: int, tb_lo: int, tbc: int,
                 model: HashModel):
        self.nonce = bytes(nonce)
        self.difficulty = difficulty
        self.tb_lo = tb_lo
        self.tbc = tbc
        self.model = model
        self._dense: Optional[OperandPack] = None
        self._placed: dict = {}

    def _pack(self, rows) -> OperandPack:
        return pack_operands(self.nonce, self.difficulty, self.tb_lo,
                             self.tbc, self.model, rows)

    def _dense_pack(self) -> OperandPack:
        if self._dense is None:
            self._dense = self._pack(DENSE_ROWS)
        return self._dense

    def _place(self, make_pack, sharding):
        with SPANS.span("search.bind", hash=self.model.name):
            pack = make_pack()
            placed = jax.device_put(pack.words, sharding)
        metrics.inc("search.operand_binds")
        return pack, placed

    def placed(self, sharding=None):
        """The dense pack under ``sharding``; None: on one device."""
        arr = self._placed.get(sharding)
        if arr is None:
            if sharding is None and self._placed:
                arr = next(iter(self._placed.values())).addressable_data(0)
            else:
                arr = self._place(self._dense_pack, sharding)[1]
            self._placed[sharding] = arr
        return arr

    def spec(self, vw: int, extra: bytes = b""):
        """Width ``vw``'s tail layout, with no placement."""
        if extra:
            return build_tail_spec(self.nonce, vw, self.model, extra)
        return self._dense_pack().specs[vw]

    def rows(self, vw: int, extra: bytes = b"", sharding=None):
        """``(pack, placed, row)``: the placed pack that holds width
        ``vw`` with constant high chunk bytes ``extra``, and its row."""
        if extra:
            pack, placed = self._place(lambda: self._pack(((vw, extra),)),
                                       sharding)
            return pack, placed, 0
        placed = self.placed(sharding)
        return self._dense, placed, vw

    def probe(self):
        """The width-0 probe, ``bound(chunk0) -> uint32``."""
        return bind_probe(*self.rows(0)[:2])

    def persistent_step(self, vw: int, extra: bytes, chunks_per_step: int,
                        segments: int):
        """The default one-device persistent step of width ``vw``
        (``ops/search_step.py bind_persistent_step``)."""
        return bind_persistent_step(*self.rows(vw, extra), chunks_per_step,
                                    segments)


# Host-side poll cadence while a launch result is not yet ready.  Short
# enough that drain latency adds negligibly to a launch's wall-clock
# (launches are 0.1-0.25 s of device work by budget), long enough that
# polling is not a busy spin.
DEFAULT_POLL_INTERVAL_S = 0.001


class StopFlag:
    """The chain operand of one solve's persistent dispatches
    (docs/SERVING.md chained halt).

    Every persistent dispatch takes, as its last operand, the result of
    the persistent dispatch issued before it, and its on-device loop
    runs only behind a predecessor that ran and found nothing
    (``ops/search_step.py persistent_search_step``).  So the device,
    not the host, ends a solve: a dispatch queued behind a hit exits at
    its first loop check and passes the halt on, and the host can keep
    dispatches in flight across width boundaries without searching past
    the answer.  ``operand()`` is what the next dispatch binds:
    ``CHAIN_CLEAR`` before the solve's first persistent dispatch, then
    the last one's result (``chain``), or ``CHAIN_HALT`` for good once
    ``set()``.  The solo driver sets it on a cancel and issues nothing
    after, so there it is the invariant guard; dispatches already
    queued run out in the background, each still stopping at its own
    hit.  Warmup and the mesh lane's compile probe dispatch behind
    ``CHAIN_HALT`` directly, so compiling costs near-zero device work.
    """

    __slots__ = ("_operand",)

    def __init__(self) -> None:
        self._operand = CHAIN_CLEAR

    def chain(self, res) -> None:
        """Make ``res``, the dispatch just issued, the next one's
        predecessor."""
        if self._operand is not CHAIN_HALT:
            self._operand = res

    def set(self) -> None:
        self._operand = CHAIN_HALT

    def is_set(self) -> bool:
        return self._operand is CHAIN_HALT

    def operand(self):
        return self._operand


def persistent_search(
    nonce: bytes,
    difficulty: int,
    thread_bytes: Sequence[int],
    *,
    model: Optional[HashModel] = None,
    batch_size: int = DEFAULT_BATCH,
    pipeline_depth: int = DEFAULT_PIPELINE_DEPTH,
    cancel_check: Optional[Callable[[], bool]] = None,
    max_hashes: Optional[int] = None,
    max_width: int = 8,
    launch_candidates: Optional[int] = None,
    poll_interval_s: float = DEFAULT_POLL_INTERVAL_S,
    step_builder: Optional[Callable] = None,
) -> Optional[SearchResult]:
    """Find the first (reference-enumeration-order) solving secret.

    Returns None if cancelled or ``max_hashes`` exhausted.
    ``launch_candidates`` defaults to the model's cost-scaled budget
    (``scaled_launch_candidates``), so a direct library caller gets the
    launch granularity a backend would.  Four properties of the loop
    (docs/SERVING.md):

    * each dispatch is a multi-segment on-device loop
      (``ops/search_step.py persistent_search_step``) that early-exits
      on the first hit,
      so the per-dispatch candidate budget no longer trades hit
      latency against round-trip amortization;
    * the dispatches of a solve form one chain (:class:`StopFlag`):
      each takes its predecessor's result, and one behind a hit does
      no work.  One FIFO therefore runs through the whole solve, with
      no drain at width boundaries: the next width's first dispatch
      chains from the last one issued, and FIFO order still returns
      the reference-order first hit, because nothing behind a hit
      searches;
    * the drain POLLS the in-flight head's readiness
      (``jax.Array.is_ready`` — a cheap flag query, not a result
      fetch) and only then reads it, whole, in one host transfer, so
      the host never blocks inside a result fetch and never queues an
      indexing program behind the next dispatch (the waiting time is
      observable as ``search.poll`` spans);
    * cancellation stops issuing dispatches the moment it is observed:
      the host returns immediately, and the abandoned device work is
      bounded at the in-flight window (≤ ``pipeline_depth`` dispatches
      running out their segment budget in the background) without
      shrinking launches.

    The width-0 probe (256 candidates, ``bind_probe``) stays outside
    the chain: its result is a scalar.  With the default depth of 2 it
    is drained before a second persistent dispatch is issued, so at
    most one width-1 dispatch runs behind a probe hit.

    The request's operands are bound once, before the probe
    (:class:`RequestOperands`): one host pack for widths 0-4, placed
    once per device form, which each width's step slices.

    ``step_builder`` is the device-form hook (the backends, and
    sched/lanes.py ``persistent_step_builder``): called once, with the
    request's :class:`RequestOperands`, it returns None (the default
    single-device persistent step for every width) or a builder, called
    per width segment as ``builder(vw, extra, target_chunks, k)``,
    which may return ``(step, chunks_each, chunks_per_step)`` — a
    drop-in for the default single-device persistent step with the
    identical ``(chunk0, prev) -> uint32[2]`` contract and first-hit
    order over the same global candidate span — or None to keep the
    default for that width.  The mesh steps serve every dispatch across
    the local devices this way, and the Pallas mesh step runs the
    kernel; enumeration order (and so results) stays byte-identical.
    """
    model = model or get_hash_model("md5")
    if launch_candidates is None:
        launch_candidates = scaled_launch_candidates(model.cost_ops)
    nonce = bytes(nonce)
    tb_lo, tbc = contiguous_bounds(thread_bytes)
    if difficulty > model.max_difficulty:
        return _unsatisfiable_wait(model, difficulty, cancel_check,
                                   max_hashes)
    target_chunks = max(1, effective_batch(batch_size) // tbc)
    chain = StopFlag()
    operands = RequestOperands(nonce, difficulty, tb_lo, tbc, model)
    builder = None

    hashes = 0
    # FIFO of in-flight dispatches over the whole solve:
    # (res, chunk0, vw, extra, seg_chunks, chunks_each, on_mesh)
    # where seg_chunks is the dispatch's IN-SEGMENT chunk span: a
    # dispatch's compiled span can overshoot the segment end, and the
    # overshot chunk ints alias back into covered candidates through the
    # width mask, so they are not searched work and are not counted
    # (else hashes_tried and max_hashes inflate on small partitions);
    # chunks_each the chunk count of one on-device segment and on_mesh
    # whether the step spans the worker's mesh.  vw == 0 is the width-0
    # probe, outside the chain.
    inflight: deque = deque()

    def count(n_cand: int, on_mesh: bool) -> None:
        nonlocal hashes
        hashes += n_cand
        metrics.inc("search.hashes", n_cand)
        if on_mesh:
            metrics.inc("search.mesh_hashes", n_cand)
        if model.finalize is not None:
            metrics.inc("search.finalize_hashes", n_cand)

    def drain_one() -> Tuple[Optional[SearchResult], bool]:
        """Poll the head to readiness, then convert.  Returns
        ``(found, cancelled)`` — polling honors cancel_check, so a
        cancel arriving mid-wait stops the search without blocking on
        the device."""
        res, chunk0, vw, extra, seg_chunks, chunks_each, on_mesh = \
            inflight.popleft()
        # deliberately NO WATCHDOG.beat() inside the poll wait: a hung
        # device leaves is_ready() false forever, and beating here
        # would mask exactly the staleness the hang watchdog exists to
        # convert into a visible death — the poll wait accumulates
        # staleness like a blocking fetch would
        if not res.is_ready():
            # the wait for a dispatch's result, under the miner thread's
            # trace id; a wait the cancel ends is not recorded
            with SPANS.span("search.poll") as wait:
                while not res.is_ready():
                    if cancel_check is not None and cancel_check():
                        wait.discard()
                        # account the popped head like the rest of the
                        # flush: dispatched work the device completes
                        # either way
                        count(seg_chunks * tbc, on_mesh)
                        return None, True
                    time.sleep(poll_interval_s)
        if vw:
            # the whole ready pair in one host transfer: indexing the
            # device array (res[0]) would run a slice program, queued
            # behind the dispatch issued after this one
            # distpow: ok hot-path-host-sync -- the drain's sanctioned
            # conversion, entered only with res.is_ready() confirmed
            f, segs = np.asarray(res).tolist()
            metrics.inc("search.persistent_steps", segs)
            n_cand = min(segs * chunks_each, seg_chunks) * tbc
        else:
            # width-0 probe: single 256-candidate launch, polled to
            # readiness above like every other dispatch — the
            # conversion cannot block
            f = int(res)
            n_cand = seg_chunks * tbc
        count(n_cand, on_mesh)
        _RATE_METER.note(n_cand)
        if f == SENTINEL:
            return None, False
        secret, tb = assemble_secret(chunk0, f, vw, extra, tb_lo, tbc)
        if not puzzle.check_secret(nonce, secret, difficulty, model.name):
            raise RuntimeError(
                f"kernel returned non-solving candidate tb={tb} "
                f"chunk={secret[1:].hex()} (kernel/oracle divergence)"
            )
        return SearchResult(
            secret=secret, thread_byte=tb, chunk=secret[1:],
            hashes_tried=hashes,
        ), False

    def flush_inflight_counts(behind_hit: bool = False) -> None:
        """Account the dispatches still in flight at an early exit
        without fetching them.  Behind a hit of a chained dispatch each
        ran zero segments by construction: it counts nothing to
        ``search.hashes`` and one to ``search.halted_dispatches``.  On
        every other exit (cancel, budget, a probe hit) the device runs
        them out: they count their dispatched span, an upper bound
        (docs/SERVING.md)."""
        while inflight:
            *_, seg_chunks, _ce, on_mesh = inflight.popleft()
            if behind_hit:
                metrics.inc("search.halted_dispatches")
            else:
                count(seg_chunks * tbc, on_mesh)

    def drain_all() -> Tuple[Optional[SearchResult], bool]:
        while inflight:
            found, cancelled = drain_one()
            if found is not None or cancelled:
                return found, cancelled
        return None, False

    def finish(found: Optional[SearchResult], cancelled: bool):
        if cancelled:
            chain.set()
            flush_inflight_counts()
            metrics.inc("search.cancelled")
            return None
        # a hit at width 0 (empty chunk) came from the probe, which is
        # outside the chain: what follows it was not halted
        flush_inflight_counts(behind_hit=found is not None
                              and bool(found.chunk))
        if found is not None:
            metrics.inc("search.found")
        return found

    _register_part_counters()
    _RATE_METER.enter()
    try:
        with WATCHDOG.active():
            for width in range(0, max_width + 1):
                for vw, lo, hi, extra in width_segments(width):
                    # one span per width segment: a device gap inside a
                    # solve then names the segment whose dispatch or
                    # drain left it
                    dispatches = 0
                    with SPANS.span("search.segment", width=width,
                                    dispatches=0, hash=model.name) as seg:
                        WATCHDOG.beat()  # step build may compile below
                        k = launch_steps_for(vw, target_chunks, tbc,
                                             launch_candidates)
                        if vw == 0:
                            # the request's operands are bound here, once:
                            # the step builder places them where its
                            # steps read them, and the probe reads that
                            # placement too
                            builder = (step_builder(operands)
                                       if step_builder is not None else None)
                            step0 = operands.probe()
                            step, chunks_per_step, chunks_each = \
                                None, 1, 1
                        else:
                            plan = (builder(vw, extra, target_chunks, k)
                                    if builder is not None else None)
                            if plan is not None:
                                step, chunks_each, chunks_per_step = plan
                            else:
                                step = operands.persistent_step(
                                    vw, extra, target_chunks, k)
                                chunks_each = target_chunks
                                chunks_per_step = target_chunks * k
                        # the mesh steps carry the mark (mesh_search.py
                        # _on_mesh); the probe and the single-device
                        # steps do not
                        devices = getattr(step, "mesh_devices", None)
                        seg.annotate(devices=devices or 1)
                        chunk0 = lo
                        first_launch = True
                        while chunk0 < hi:
                            seg_chunks = min(chunks_per_step, hi - chunk0)
                            WATCHDOG.beat()
                            if cancel_check is not None and cancel_check():
                                return finish(None, True)
                            if max_hashes is not None and hashes >= max_hashes:
                                return finish(*drain_all())
                            c = chunk0 & 0xFFFFFFFF
                            if first_launch:
                                first_launch = False
                                # first dispatch of a segment may compile
                                # when the layout cache is cold: one
                                # uninterruptible gap that can far exceed
                                # the hang timeout for the biggest graphs,
                                # so the watchdog widens its window for it
                                with WATCHDOG.grace(FIRST_COMPILE_GRACE_S):
                                    res = step0(c) if vw == 0 else \
                                        step(c, chain.operand())
                            else:
                                res = step0(c) if vw == 0 else \
                                    step(c, chain.operand())
                            if vw:
                                chain.chain(res)
                            metrics.inc("search.launches")
                            dispatches += 1
                            seg.annotate(dispatches=dispatches)
                            inflight.append((res, chunk0, vw, extra,
                                             seg_chunks, chunks_each,
                                             devices is not None))
                            chunk0 += chunks_per_step
                            if len(inflight) >= pipeline_depth:
                                found, cancelled = drain_one()
                                if found is not None or cancelled:
                                    return finish(found, cancelled)
            return finish(*drain_all())
    finally:
        _RATE_METER.exit()
