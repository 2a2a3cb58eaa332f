"""Continuous-batching engine — many searches, one device loop.

Before this subsystem, each worker Mine owned the device: K concurrent
requests piled up K miner threads that serialized through
``parallel/search.py`` (the PR-3 contention stress test recorded the
pile-up in ``worker.active_searches``), wasting the batch dimension the
device sweeps anyway.  This engine inverts the ownership: ONE device
loop thread holds the accelerator, and requests become *slots* in a
table the loop packs into shared batched launches via
``ops/search_step.py slot_search_step`` — the same continuous-batching
insight that powers modern inference servers, applied to puzzle search.

Slot lifecycle (docs/SCHEDULER.md):

* **join** — ``submit()`` appends a slot to the run queue; the loop
  admits it at the next launch boundary.  A new Mine never waits for
  another request's search to *finish* — only for the in-flight launch
  (the same one-launch granularity solo cancellation already had).
* **run** — each iteration the loop picks the most-starved slot
  (minimum virtual time; deterministic ``(vtime, seq)`` order), packs
  every compatible active slot into one vmapped dispatch, and fetches
  the per-slot first-hit vector in a single host sync.  Per-slot
  difficulty masks and partitions are runtime operands, so slots at
  different difficulties share one compiled program.
* **leave** — a hit (host-verified), a cancel (polled per boundary), or
  an exhausted enumeration finishes the slot and wakes its waiter.

Weighted-fair allocation: a slot's virtual time advances by
``candidates / weight`` per launch, and both launch selection and
oversubscription preemption order by ``(vtime, seq)`` — a hard
(high-ntz) puzzle therefore gets exactly its fair share of launches and
can never starve cheap ones, while cheap ones finish within a bounded
number of quanta.  When the slot table is full, the loop preempts the
most-served active slot back to the run queue once it is a full quantum
ahead of the queue head (flight-recorder event ``sched.slot_preempt``).

Searches the packed step cannot express — non-power-of-two partitions,
unsatisfiable difficulties — fall back to the wrapped solo backend, so
the engine is always a drop-in for ``backend.search``.
"""

from __future__ import annotations

import logging
import threading
import time
from typing import Callable, List, Optional, Sequence, Tuple

from ..models import puzzle
from ..models.registry import get_hash_model
from ..ops.difficulty import nibble_masks
from ..ops.packing import build_tail_spec
from ..ops.search_step import (
    SENTINEL,
    XLA_SERVING_COMPILE_IMPRACTICAL,
    mixed_slot_search_step,
    slot_search_step,
)
from ..parallel.partition import contiguous_bounds
from ..parallel.search import assemble_secret, effective_batch, width_segments
from ..runtime.metrics import REGISTRY as metrics
from .lanes import LanePlanner
from ..runtime.spans import SPANS
from ..runtime.telemetry import RECORDER
from ..runtime.watchdog import FIRST_COMPILE_GRACE_S, WATCHDOG

log = logging.getLogger("distpow.sched")

# Idle/reap poll period: how often queued-slot cancels are honored when
# the device is otherwise quiet (active slots are reaped every launch
# boundary, which is far more frequent under load).
_IDLE_TICK_S = 0.02


class Slot:
    """One active search's scheduler state.  ``done`` fires exactly once
    with either ``secret`` set (hit), ``secret=None`` (cancelled or
    enumeration exhausted), or ``error`` set (engine failure).
    ``model`` is the slot's hash model — slots of different models can
    share a mixed-hash launch (docs/SERVING.md)."""

    __slots__ = (
        "seq", "nonce", "ntz", "tb_lo", "tbc", "log_tbc", "weight",
        "cancel_check", "masks", "done", "secret", "error", "vtime",
        "launches", "submitted_t", "first_launch_t", "exhausted",
        "_segments", "vw", "seg_hi", "extra", "spec", "chunk0",
        "_cancelled", "model", "span", "preemptions",
    )

    def __init__(self, seq: int, nonce: bytes, ntz: int, tb_lo: int,
                 tbc: int, cancel_check: Optional[Callable[[], bool]],
                 weight: float, masks: object, segments: object,
                 model: object) -> None:
        self.model = model
        self.seq = seq
        self.nonce = nonce
        self.ntz = ntz
        self.tb_lo = tb_lo
        self.tbc = tbc
        self.log_tbc = tbc.bit_length() - 1
        self.weight = weight
        self.cancel_check = cancel_check
        self.masks = masks
        self.done = threading.Event()
        self.secret: Optional[bytes] = None
        self.error: Optional[str] = None
        self.vtime = 0.0
        self.launches = 0
        self.submitted_t = time.monotonic()
        self.first_launch_t: Optional[float] = None
        self.exhausted = False
        self._segments = segments
        self._cancelled = False
        self.vw = 0
        self.seg_hi = 0
        self.extra = b""
        self.spec = None
        self.chunk0 = 0
        self.span = None  # sched.slot forensics span (docs/FORENSICS.md)
        self.preemptions = 0

    def cancel(self) -> None:
        """Request cancellation; honored at the next launch boundary."""
        # distpow: ok unguarded-shared-write -- monotonic False->True
        # flag set from caller threads; the device loop re-reads it at
        # every launch boundary (cancel_requested), so the worst a
        # bare store costs is one extra launch, never a missed cancel
        self._cancelled = True

    def cancel_requested(self) -> bool:
        if self._cancelled:
            return True
        if self.cancel_check is not None and self.cancel_check():
            self._cancelled = True
        return self._cancelled

    def result(self, timeout: Optional[float] = None) -> Optional[bytes]:
        """Block for the slot's outcome; raises on engine failure."""
        if not self.done.wait(timeout):
            raise TimeoutError(f"slot {self.seq} not done in {timeout}s")
        if self.error is not None:
            raise RuntimeError(self.error)
        return self.secret


class BatchingScheduler:
    """Drop-in for ``backend.search`` that multiplexes concurrent
    searches onto shared batched launches (module docstring).

    ``fallback`` is the wrapped solo backend for shapes the packed step
    cannot express.  ``start=False`` defers the device loop (tests
    submit a deterministic slot set first, then :meth:`start`).

    ``lane`` pins the launch-lane ranking (``WorkerConfig.SchedLane``):
    "auto" lets the planner rank by hardware capability, "pallas" /
    "mesh" / "xla" forces that lane first (sched/lanes.py).
    """

    def __init__(self, hash_model: str = "md5", batch_size: int = 1 << 20,
                 max_slots: int = 8, max_width: int = 8,
                 fallback: object = None,
                 start: bool = True,
                 extra_models: Sequence[str] = (),
                 lane: str = "auto") -> None:
        self.model = get_hash_model(hash_model)
        # models the packed step serves: the default plus any configured
        # extras (WorkerConfig.SchedHashModels).  Slots of different
        # models share one mixed-hash launch; models whose fused XLA
        # serving step is impractical to compile stay on the solo route
        # regardless (XLA_SERVING_COMPILE_IMPRACTICAL — on TPU those are
        # served by the Pallas kernels through a solo backend).
        self.models = {self.model.name: self.model}
        for name in extra_models:
            m = get_hash_model(name)
            if m.name not in XLA_SERVING_COMPILE_IMPRACTICAL:
                self.models[m.name] = m
        self.batch = effective_batch(batch_size)
        self.max_slots = max(1, int(max_slots))
        self.max_width = max_width
        self.fallback = fallback
        self.lane = lane
        self.planner = LanePlanner(override=lane)
        self._cond = threading.Condition()
        self._pending: List[Slot] = []
        self._active: List[Slot] = []
        self._seq = 0
        self._stop = threading.Event()
        self._dead = False
        self._compiled: set = set()
        self._thread: Optional[threading.Thread] = None
        if start:
            self.start()

    # -- lifecycle ----------------------------------------------------------
    def start(self) -> None:
        if self._thread is not None and self._thread.is_alive():
            return
        self._stop.clear()
        self._thread = threading.Thread(
            target=self._loop, name="sched-batching-loop", daemon=True
        )
        self._thread.start()

    def close(self) -> None:
        """Stop the loop; unfinished slots complete with ``None`` (the
        cancelled shape — callers see a clean no-result, not a hang)."""
        self._stop.set()
        with self._cond:
            # reject submissions racing with shutdown BEFORE draining:
            # a slot appended after the drain would have no loop left
            # to ever finish it (search() routes the refusal to the
            # fallback backend)
            self._dead = True
            self._cond.notify_all()
        t = self._thread
        if t is not None:
            t.join(timeout=10.0)
        with self._cond:
            leftovers = self._pending + self._active
            self._pending = []
            self._active = []
            self._publish_gauges_locked()
        for s in leftovers:
            self._finish(s, None)

    # -- submission ---------------------------------------------------------
    def supports(self, difficulty: int, thread_bytes: Sequence[int],
                 hash_model: Optional[str] = None) -> bool:
        """True when the packed step can serve this shape: an admitted
        hash model, a contiguous power-of-two partition and a
        satisfiable difficulty."""
        model = self.models.get(hash_model or self.model.name)
        if model is None:
            return False
        try:
            _, tbc = contiguous_bounds(thread_bytes)
        except ValueError:
            return False
        return (0 < tbc <= 256 and tbc & (tbc - 1) == 0
                and difficulty <= model.max_difficulty)

    def submit(self, nonce: bytes, difficulty: int,
               thread_bytes: Sequence[int],
               cancel_check: Optional[Callable[[], bool]] = None,
               weight: float = 1.0,
               hash_model: Optional[str] = None) -> Slot:
        model = self.models[hash_model or self.model.name]
        nonce = bytes(nonce)
        tb_lo, tbc = contiguous_bounds(thread_bytes)
        masks = nibble_masks(difficulty, model)
        segments = self._segment_stream()
        with self._cond:
            if self._dead:
                raise RuntimeError(
                    "batching scheduler is closed or its device loop died"
                )
            self._seq += 1
            slot = Slot(self._seq, nonce, difficulty, tb_lo, tbc,
                        cancel_check, weight, masks, segments, model)
            # slot-residency forensics span (docs/FORENSICS.md): the
            # submitting miner thread carries the request's trace id
            # (SPANS.bind in nodes/worker.py), so the slot's whole
            # scheduler life — queue wait, launches, preemptions —
            # lands on that request's timeline.
            # distpow: ok unclosed-span -- slot spans cross the
            # submit(miner)->device-loop thread boundary by design;
            # _finish() is the single exit point for every slot (hit,
            # cancel, exhaustion, loop death, close) and finishes the
            # handle exactly once
            slot.span = SPANS.begin("sched.slot", seq=slot.seq,
                                    model=model.name)
            # virtual-clock floor: a joining slot starts at the
            # currently most-starved slot's vtime, not 0 — otherwise a
            # stream of fresh arrivals (each sorting first at vtime 0)
            # would outrank a long-running slot forever and starve it,
            # the exact failure the fair clock exists to prevent
            slot.vtime = min(
                (s.vtime for s in self._active + self._pending),
                default=0.0,
            )
            if not self._advance_segment(slot):
                raise RuntimeError("empty enumeration")  # unreachable
            self._pending.append(slot)
            self._publish_gauges_locked()
            self._cond.notify_all()
        return slot

    def _solo(self, nonce: bytes, difficulty: int, thread_bytes: bytes,
              cancel_check: Optional[Callable[[], bool]],
              hash_model: Optional[str]) -> Optional[bytes]:
        """Route one search outside the packed step.

        Default-model shapes go to the wrapped fallback backend (it was
        built for that model).  Off-default models the packed step
        cannot serve — impractical-to-compile, not configured, or an
        unsupported shape — run through the solo XLA driver with the
        requested model instead: the fallback backend's model would be
        WRONG for them (docs/SERVING.md; on TPU, serve those models
        from a worker whose configured backend is their Pallas kernel).
        """
        if hash_model is None or hash_model == self.model.name:
            if self.fallback is None:
                raise ValueError(
                    f"unsupported search shape for the batching scheduler "
                    f"(difficulty={difficulty}) and no fallback backend"
                )
            metrics.inc("sched.fallback_searches")
            return self.fallback.search(
                nonce, difficulty, thread_bytes, cancel_check=cancel_check
            )
        model = get_hash_model(hash_model)
        if model.name in XLA_SERVING_COMPILE_IMPRACTICAL:
            # never run these through the solo XLA driver either: the
            # fused serving step is the thing that is impractical to
            # compile (>30 min observed on the TPU backend, r4c), and
            # a "fallback" that wedges the miner thread and device in
            # that compile is worse than an honest refusal
            raise ValueError(
                f"hash model {model.name!r} is never admitted to the XLA "
                f"serving path (XLA_SERVING_COMPILE_IMPRACTICAL): serve "
                f"it from a worker whose configured backend is its "
                f"Pallas kernel"
            )
        metrics.inc("sched.fallback_searches")
        from ..parallel.search import persistent_search
        from .lanes import persistent_step_builder

        res = persistent_search(
            nonce, difficulty, thread_bytes,
            model=model, batch_size=self.batch,
            cancel_check=cancel_check,
            step_builder=lambda operands: persistent_step_builder(
                operands, override=self.lane),
        )
        return None if res is None else res.secret

    def search(self, nonce: bytes, difficulty: int, thread_bytes: bytes,
               cancel_check: Optional[Callable[[], bool]] = None,
               hash_model: Optional[str] = None) -> Optional[bytes]:
        """Backend-compatible facade: first solving secret or None."""
        if self._dead or not self.supports(difficulty, thread_bytes,
                                           hash_model):
            return self._solo(nonce, difficulty, thread_bytes,
                              cancel_check, hash_model)
        try:
            slot = self.submit(nonce, difficulty, thread_bytes,
                               cancel_check=cancel_check,
                               hash_model=hash_model)
        except RuntimeError:
            # closed/died between the liveness check and the append —
            # the slot was never queued, so serve solo rather than
            # hang or leak the race to the miner thread
            if self.fallback is None and (hash_model is None
                                          or hash_model == self.model.name):
                raise
            return self._solo(nonce, difficulty, thread_bytes,
                              cancel_check, hash_model)
        return slot.result()

    # -- cursor -------------------------------------------------------------
    def _segment_stream(self):
        for width in range(0, self.max_width + 1):
            yield from width_segments(width)

    def _advance_segment(self, slot: Slot) -> bool:
        """Move the slot to its next width segment; False = exhausted."""
        for vw, lo, hi, extra in slot._segments:
            slot.vw = vw
            slot.seg_hi = hi
            slot.extra = extra
            slot.chunk0 = lo
            slot.spec = build_tail_spec(slot.nonce, vw, slot.model, extra)
            return True
        return False

    @staticmethod
    def _group_key(slot: Slot) -> tuple:
        # slots sharing (model, tail layout) can share one vmapped lane
        # stack; DIFFERENT groups still share the LAUNCH through the
        # mixed step, whose compile key is the ordered group-key set
        # (ops/search_step.py mixed_slot_search_step)
        spec = slot.spec
        return (slot.model.name, spec.n_blocks, spec.tb_loc,
                spec.chunk_locs)

    # -- the device loop ----------------------------------------------------
    def _loop(self) -> None:
        try:
            while not self._stop.is_set():
                with self._cond:
                    self._reap_locked(self._active)
                    self._reap_locked(self._pending)
                    self._admit_locked()
                    group = self._pick_locked()
                    if group is None:
                        self._cond.wait(timeout=_IDLE_TICK_S)
                        continue
                self._launch(group)
        except Exception as exc:  # the loop must never die silently
            log.exception("batching scheduler device loop died: %s", exc)
            metrics.inc("sched.loop_failures")
            RECORDER.record("sched.loop_failure", error=str(exc))
            with self._cond:
                self._dead = True
                slots = self._pending + self._active
                self._pending = []
                self._active = []
                self._publish_gauges_locked()
            for s in slots:
                self._finish(s, None, error=f"scheduler loop died: {exc}")

    def _publish_gauges_locked(self) -> None:
        metrics.gauge("sched.active_slots", len(self._active))
        metrics.gauge("sched.run_queue_depth", len(self._pending))

    def _reap_locked(self, slots: List[Slot]) -> None:
        for s in list(slots):
            if s.cancel_requested():
                slots.remove(s)
                metrics.inc("search.cancelled")
                self._finish(s, None)
        self._publish_gauges_locked()

    def _admit_locked(self) -> None:
        self._pending.sort(key=lambda s: (s.vtime, s.seq))
        while self._pending and len(self._active) < self.max_slots:
            self._active.append(self._pending.pop(0))
        if self._pending and self._active:
            # oversubscribed: preempt the most-served active slot once
            # it is a full quantum ahead of the queue head — bounded
            # round-robin between the overflow set, at most one swap
            # per boundary so the table never thrashes
            head = self._pending[0]
            victim = max(self._active, key=lambda s: (s.vtime, s.seq))
            if victim.vtime >= head.vtime + self.batch / victim.weight:
                self._active.remove(victim)
                self._pending.append(victim)
                self._active.append(self._pending.pop(0))
                victim.preemptions += 1
                metrics.inc("sched.slots_preempted")
                RECORDER.record(
                    "sched.slot_preempt", slot=victim.seq,
                    for_slot=head.seq, vtime=round(victim.vtime, 1),
                )
        self._publish_gauges_locked()

    def _pick_locked(self) -> Optional[List[Slot]]:
        if not self._active:
            return None
        # most-starved first across ALL groups: slots of different
        # models share a mixed-hash launch, so fairness ordering no
        # longer forfeits batching at model boundaries
        cohort = sorted(self._active, key=lambda s: (s.vtime, s.seq))
        cohort = cohort[: self.max_slots]
        # serve at most ONE layout group per model: batching across
        # models is the occupancy win (solo fallback served exactly 1),
        # but batching across LAYOUTS buys nothing the fair clock
        # doesn't already deliver by rotating groups — and every layout
        # SUBSET the join/leave churn produced would be a fresh
        # mixed-step compile key (the power-of-two lane pad bounds
        # pads, not subsets).  This caps a launch's group count at the
        # admitted-model count, so compile keys stay bounded by
        # model-subsets x per-model (layout, pad).
        keep = {}
        for s in cohort:  # cohort is (vtime, seq)-ordered: first slot
            keep.setdefault(s.model.name, self._group_key(s))  # leads
        cohort = [s for s in cohort
                  if self._group_key(s) == keep[s.model.name]]
        return cohort

    @staticmethod
    def _lane_ops(lanes: List[Slot]) -> tuple:
        import jax.numpy as jnp

        return (
            jnp.asarray([s.spec.init_state for s in lanes], jnp.uint32),
            jnp.asarray([s.spec.base_words for s in lanes], jnp.uint32),
            jnp.asarray([s.masks for s in lanes], jnp.uint32),
            jnp.asarray([s.tb_lo for s in lanes], jnp.uint32),
            jnp.asarray([s.log_tbc for s in lanes], jnp.uint32),
            jnp.asarray([s.chunk0 & 0xFFFFFFFF for s in lanes],
                        jnp.uint32),
        )

    def _launch(self, group: List[Slot]) -> None:
        import jax

        # group the cohort by (model, layout): each group is one vmapped
        # lane stack; all groups share the single dispatch.  Per-group
        # lane counts pad to a power of two so the compile-key space
        # stays bounded the way the single-group n_pad already was.
        by_key: dict = {}
        for s in group:
            by_key.setdefault(self._group_key(s), []).append(s)
        ordered = sorted(by_key.items(), key=lambda kv: kv[0])
        gdefs, gops, gslots, gkeys = [], [], [], []
        for key, slots in ordered:
            model_name, n_blocks, tb_loc, chunk_locs = key
            n_pad = 1 << (len(slots) - 1).bit_length()
            lanes = slots + [slots[-1]] * (n_pad - len(slots))
            gdefs.append((model_name, n_blocks, tb_loc, chunk_locs, n_pad))
            gops.append(self._lane_ops(lanes))
            gslots.append(slots)
            # slot-membership key for the mesh lane's replicated operand
            # cache: static rows only change when the lane stack does
            gkeys.append(tuple(
                (s.seq, s.vw, s.ntz, s.extra) for s in lanes
            ))
        # resolve each group's launch lane (sched/lanes.py): pallas /
        # mesh groups dispatch their own steps; every xla group shares
        # the classic slot/mixed dispatch.  Resolution is cached, so the
        # per-launch planner cost is a dict hit per group.
        resolved = [self.planner.resolve(gd, self.batch)
                    for gd in gdefs]
        lanes_used = [lane for lane, _ in resolved]
        compile_key = (tuple(gdefs), tuple(lanes_used), self.batch)
        first_compile = compile_key not in self._compiled

        def run():
            pending: List[Tuple[int, object]] = []
            xla_idx = [i for i, lane in enumerate(lanes_used)
                       if lane == "xla"]
            for i, (lane, gstep) in enumerate(resolved):
                if lane == "xla":
                    continue
                try:
                    pending.append((i, gstep(gops[i], gkeys[i])))
                except Exception as exc:
                    # dispatch/compile failure: demote this lane for the
                    # key and serve the group through xla in THIS launch
                    # — no request ever observes the demotion
                    self.planner.demote(gdefs[i], self.batch, lane, exc)
                    lanes_used[i] = "xla"
                    xla_idx.append(i)
            if xla_idx:
                xla_idx.sort()
                if len(xla_idx) == 1:
                    i = xla_idx[0]
                    m, nb, tl, cl, n_pad = gdefs[i]
                    s = slot_search_step(m, nb, tl, cl, self.batch, n_pad)
                    pending.append((i, s(*gops[i])))
                else:
                    s = mixed_slot_search_step(
                        tuple(gdefs[i] for i in xla_idx), self.batch
                    )
                    pending.extend(
                        zip(xla_idx, s(tuple(gops[i] for i in xla_idx)))
                    )
            # one host sync for the whole launch regardless of how many
            # lanes served it — the engine's single-sync discipline
            fetched = jax.device_get([r for _, r in pending])
            out: List[object] = [None] * len(gdefs)
            for (i, _), v in zip(pending, fetched):
                out[i] = v
            return out

        now = time.monotonic()
        with WATCHDOG.active():
            WATCHDOG.beat()
            if first_compile:
                self._compiled.add(compile_key)
                with WATCHDOG.grace(FIRST_COMPILE_GRACE_S):
                    res_groups = run()
            else:
                res_groups = run()

        # per-group launch coverage: specialized lanes may sweep more
        # than self.batch candidates per slot per launch (the mesh lane
        # covers n_dev x batch) — every cursor/fairness/throughput
        # account below uses the group's own coverage
        coverages = [
            self.batch if lanes_used[i] == "xla"
            else resolved[i][1].coverage
            for i in range(len(gdefs))
        ]
        metrics.observe("sched.batch_occupancy", len(group))
        metrics.inc("sched.launches")
        for lane in lanes_used:
            metrics.inc(f"sched.lane_launches.{lane}")
        if len({d[0] for d in gdefs}) > 1:
            metrics.inc("sched.mixed_hash_launches")
        metrics.inc("search.hashes",
                    sum(len(sl) * c for sl, c in zip(gslots, coverages)))
        finished: List[Tuple[Slot, Optional[bytes]]] = []
        for slots, res, cov in zip(gslots, res_groups, coverages):
            for i, s in enumerate(slots):
                s.launches += 1
                s.vtime += cov / s.weight
                if s.first_launch_t is None:
                    s.first_launch_t = now
                    metrics.observe("sched.slot_wait_s",
                                    now - s.submitted_t)
                # distpow: ok relaunch-loop-sync -- res is a fetched host array (the single device_get above is this launch's one sanctioned sync); converting lanes here cannot block on the device
                f = int(res[i])
                if f != SENTINEL:
                    secret, _ = assemble_secret(
                        s.chunk0, f, s.vw, s.extra, s.tb_lo, s.tbc
                    )
                    if not puzzle.check_secret(s.nonce, secret, s.ntz,
                                               s.model.name):
                        # kernel/oracle divergence: fail THIS slot
                        # loudly, keep the loop serving the others (the
                        # solo driver kills its whole miner thread here)
                        finished.append((s, None))
                        s.error = (
                            f"packed step returned non-solving candidate "
                            f"{secret.hex()} (kernel/oracle divergence)"
                        )
                        continue
                    metrics.inc("search.found")
                    finished.append((s, secret))
                    continue
                s.chunk0 += cov >> s.log_tbc
                if s.chunk0 >= s.seg_hi and not self._advance_segment(s):
                    s.exhausted = True
                    finished.append((s, None))
        with self._cond:
            for s, _ in finished:
                if s in self._active:
                    self._active.remove(s)
            self._publish_gauges_locked()
        for s, secret in finished:
            self._finish(s, secret, error=s.error)

    def _finish(self, slot: Slot, secret: Optional[bytes],
                error: Optional[str] = None) -> None:
        slot.secret = secret
        slot.error = error
        if slot.span is not None:
            slot.span.finish(
                launches=slot.launches, preemptions=slot.preemptions,
                outcome=("found" if secret is not None
                         else "error" if error else "no-result"),
            )
        slot.done.set()
