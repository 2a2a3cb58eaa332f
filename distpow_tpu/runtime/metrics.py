"""Process-wide metrics registry (no reference equivalent — the
reference's only observability is its tracing subsystem; SURVEY.md
section 5 "Metrics: no counters").

A deliberately tiny, dependency-free counter/gauge/histogram registry.
Every node process has one ``REGISTRY``; hot paths increment named
counters, set gauges, and observe latency/size samples into
log-bucketed histograms; the node's ``Stats`` RPC ships a snapshot (see
nodes/coordinator.py and nodes/worker.py; ``python -m
distpow_tpu.cli.stats`` prints it, ``--prom`` renders Prometheus text
exposition — docs/METRICS.md is the catalog).

Counter names in use (machine-checked: ``KNOWN_COUNTERS`` below is the
declaration distpow-lint's ``metrics-registry`` rule verifies every
``metrics.inc("…")`` call site against — docs/LINT.md):

* ``search.hashes``        — candidates evaluated (all backends)
* ``search.mesh_hashes``   — the part of ``search.hashes`` whose
  dispatches spanned every device of the worker's mesh (the steps the
  mesh factories mark, parallel/mesh_search.py ``_on_mesh``); the
  width-0 probe, single-device steps and one-device meshes add
  nothing here
* ``search.finalize_hashes`` — the part of ``search.hashes`` whose
  candidates ran a model's ``finalize`` stage (models/registry.py:
  sha256d's second compression), counted by the search driver at the
  same points and clipped the same way; 0 for a hash with no such stage
* ``search.launches``      — device dispatches
* ``search.cancelled``     — searches stopped by a cancel check
* ``search.found``         — searches that returned a secret
* ``search.persistent_steps`` — on-device sub-batches (segments)
  executed inside persistent-loop dispatches (early-exit means this
  can be far below the dispatched segment budget)
* ``search.halted_dispatches`` — persistent dispatches still in flight
  behind a found answer, which the chained halt stopped on the device
  before any segment ran (about one per found solve at the default
  pipeline depth; docs/SERVING.md)
* ``search.operand_binds`` — placements of a request's operand pack
  (parallel/search.py ``RequestOperands``): one a request, on one
  device or a mesh (the one-device programs read a mesh placement's
  first shard), and one more per 2^32-candidate segment of a width
  past 4; each inside a ``search.bind`` span
* ``worker.mine_rpcs`` / ``worker.found_rpcs`` / ``worker.cancel_rpcs``
* ``worker.results_sent``  — messages queued to the forwarder
* ``worker.forward_retries`` — result deliveries retried after a
  coordinator outage (nodes/worker.py start_forwarder)
* ``coord.mine_rpcs`` / ``coord.fanouts`` / ``coord.late_results``
* ``coord.worker_failures`` / ``coord.reassigned_shards``
* ``coord.stale_results_dropped`` — zombie-round results dropped by the
  Result handler's round tag (nodes/coordinator.py module docstring)
* ``cache.hit`` / ``cache.miss`` / ``cache.add`` / ``cache.evict``
* ``powlib.retries`` / ``powlib.reconnects`` / ``powlib.degraded``
  — client-side coordinator-outage recovery (nodes/powlib.py)
* ``powlib.retry_after`` — server-paced RETRY_AFTER backpressure
  retries (non-counting: they never burn the transport retry budget)
* ``sched.launches`` — batched device dispatches issued by the
  continuous-batching engine (sched/engine.py)
* ``sched.mixed_hash_launches`` — batched launches whose slot set
  spans more than one hash model (per-model sub-batches inside one
  compiled program — sched/engine.py, docs/SERVING.md)
* ``sched.lane_launches.<lane>`` — launch groups served per launch
  lane (``pallas`` / ``mesh`` / ``xla`` — the sched/lanes.py planner;
  a demoted group counts under the lane that actually served it)
* ``sched.admission_rejected`` — Mine requests shed by the
  coordinator's bounded run queue (sched/admission.py)
* ``sched.coalesced_requests`` — duplicate in-flight Mines attached as
  waiters to an existing fan-out round (sched/coalesce.py)
* ``sched.slots_preempted`` — active slots rotated back to the run
  queue by the weighted-fair allocator under oversubscription
* ``sched.fallback_searches`` — searches the packed step could not
  express, served through the wrapped solo backend
* ``sched.loop_failures`` — scheduler device-loop deaths (slots fail
  over to errors, never hangs)
* ``rpc.handler_errors`` — handler exceptions returned to callers in
  the response frame (runtime/rpc.py _dispatch)
* ``rpc.codec.negotiated_v2`` / ``rpc.codec.fallback_v1`` — per-
  connection wire-codec negotiation outcomes (runtime/rpc.py
  ``rpc.hello``; docs/RPC.md): binary v2 agreed vs transparent JSON
  fallback against a v1-only peer
* ``coord.abandoned_resyncs`` — background best-effort Found re-syncs
  to workers abandoned during a round (nodes/coordinator.py
  ``_resync_abandoned`` — off the Mine success path; per-outcome
  detail rides the ``coord.abandoned_resync`` flight-recorder event)
* ``compile_cache.errors`` (+ ``.read_errors`` / ``.write_errors`` /
  ``.keygen_errors``) — persistent XLA cache failures
  (runtime/compile_cache.py)
* ``fallback.pallas_mesh_step`` — requests or width segments the
  ``pallas`` and ``pallas-mesh`` backends served through the XLA mesh
  steps instead of the kernel (backends/__init__.py)
* ``fallback.lane_demotions`` — launch lanes demoted to a slower lane
  after a build or dispatch failure (sched/lanes.py), and widths the
  mesh backends served through the single-device persistent step
  because the segment batch does not divide across the mesh
  (backends/__init__.py)
* ``fallback.warmup_failures`` — boot warmups that raised
  (nodes/worker.py); chip_smoke.py fails when any ``fallback.*``
  counter is above 0
* ``faults.injected.<kind>`` — fault-injection plane activity
  (runtime/faults.py; kind in refuse/delay/truncate/duplicate/drop)
* ``telemetry.dropped_events`` / ``telemetry.dumps`` — flight-recorder
  ring overwrites and dump-on-fault snapshots (runtime/telemetry.py)
* ``obs.scrapes`` / ``obs.scrape_failures`` — fleet-scraper sweeps
  issued and per-node polls that failed or missed the shared sweep
  deadline (distpow_tpu/obs/scrape.py, docs/SLO.md)
* ``slo.evaluations`` / ``slo.breaches`` — SLO-engine verdict runs and
  verdicts that breached (distpow_tpu/obs/slo.py; every breach also
  records an ``slo.breach`` flight-recorder event)
* ``fleet.joins`` — elastic workers admitted via ``Fleet.Register``
  (re-registrations after a lost lease included;
  distpow_tpu/fleet/membership.py, docs/FLEET.md)
* ``fleet.lease_expiries`` — heartbeat leases retired after missing
  their TTL (the vanished-worker path into orphan reassignment)
* ``fleet.drains`` — leases released through the graceful
  ``Fleet.Drain`` RPC (in-flight rounds completed first)
* ``fleet.hedged_shards`` — straggler shards duplicated onto the
  least-loaded live worker while a round waited on a silent owner
  (nodes/coordinator.py ``_maybe_hedge``)
* ``spans.dropped`` — span-ring overwrites: per-trace forensics
  fetches lose their oldest spans (runtime/spans.py, docs/FORENSICS.md)
* ``forensics.slow_captures`` — Mine rounds auto-captured into the
  flight recorder by the slow-request trigger (threshold or rolling-p99
  exceedance — nodes/coordinator.py, runtime/spans.py)
* ``forensics.fetches`` / ``forensics.fetch_failures`` — fleet-wide
  span sweeps issued and per-node Spans polls that failed or missed
  the shared deadline (distpow_tpu/obs/forensics.py)
* ``cluster.not_owner_redirects`` — misrouted Mines a pooled
  coordinator answered with the typed NOT_OWNER redirect + ring
  snapshot (distpow_tpu/cluster/, docs/CLUSTER.md)
* ``cluster.foreign_mines`` — Mines a pooled coordinator served for a
  key it does NOT own (``no_redirect`` hedged/failover sends — the
  shared worker fleet makes them correct, only cache locality pays)
* ``cluster.ring_serves`` — ``Cluster.Ring`` snapshot requests served
* ``cluster.reroutes`` — powlib mines re-routed to a different shard
  after adopting a NOT_OWNER redirect's ring snapshot
* ``cluster.failovers`` — powlib mines failed over to a ring sibling
  after the owner shard's transport died and its re-dial failed
* ``cluster.sibling_hedges`` — RETRY_AFTER rejections hedged to the
  next ring sibling instead of waiting out the owner's hint
  (non-counting, like every server-paced retry)
* ``repl.pushes`` — dominance-cache entries write-behind-pushed to a
  ring successor (one per entry-destination pair;
  distpow_tpu/cluster/replication.py, docs/CLUSTER.md "Replication")
* ``repl.push_failures`` — entries dropped from the bounded push queue
  or lost to a failed ``Cluster.CacheSync`` (anti-entropy heals both)
* ``repl.installs`` — replica-side installs accepted through the
  dominance order (CacheSync pushes, handoff chunks, anti-entropy
  heals alike)
* ``repl.stale_drops`` — replica-side pushes REJECTED by the dominance
  order (a stale lower-ntz push after a higher-ntz install — proof the
  order held, never a regression)
* ``repl.handoff_keys`` — entries pushed to their new owner during a
  warm shard handoff (``Cluster.Handoff``, before the ring change is
  acked)
* ``repl.antientropy_rounds`` — anti-entropy digest-exchange sweeps
  completed against the ring successors
* ``health.leak_suspects`` — resource gauges the trend detector judged
  monotone-climbing past the noise floor (runtime/health.py; each also
  records a ``health.leak_suspect`` flight-recorder event —
  docs/SOAK.md "Sentinels")
* ``soak.sweeps`` — fleet sweeps the soak harness ingested into the
  time-series store (distpow_tpu/load/soak.py, docs/SOAK.md)
* ``soak.phase_breaches`` — shape phases whose windowed SLO judgment
  breached during a soak (one per failing phase, not per objective)
* ``obs.spool_rotations`` — time-series JSONL spool segments rotated
  out by the size cap (distpow_tpu/obs/timeseries.py; same rotation
  machinery as the flight-recorder journal)

Histogram names in use (same machine check, ``KNOWN_HISTOGRAMS`` /
``KNOWN_HISTOGRAM_PREFIXES`` vs ``observe()``/``time()`` call sites):

* ``coord.mine_s.hit`` / ``coord.mine_s.miss`` — Mine RPC end-to-end
  latency split by dominance-cache outcome (nodes/coordinator.py)
* ``coord.first_result_s``       — fan-out to first worker result
* ``coord.cancel_propagation_s`` — fan-out to last cancellation ACK
* ``worker.solve_s``          — backend search latency for found secrets
* ``worker.solve_s.<model>``  — the same distribution split per hash
  model (family; the per-model SLO objectives and the cluster
  aggregation's per-model breakdown read these — docs/SLO.md)
* ``obs.sweep_s``      — fleet-scraper merge time per sweep
  (distpow_tpu/obs/scrape.py)
* ``fleet.heartbeat_rtt_s`` — worker-observed lease-heartbeat round
  trip (distpow_tpu/fleet/agent.py; the cadence side lives in the
  registry's per-lease EMA and drives the hedge threshold)
* ``cluster.failover_s`` — first owner-shard transport failure to the
  successful reply from another shard: the client-observed cost of
  riding out a coordinator death (nodes/powlib.py, docs/CLUSTER.md)
* ``repl.push_lag_s`` — round completion (queue admit) to the entry
  landing on its last ring successor: the replication window a member
  death can lose (distpow_tpu/cluster/replication.py)
* ``repl.handoff_s`` — wall time of one warm shard handoff (all
  targets, chunked sends, deadline-bounded)
* ``worker.time_to_cancel_s`` — Mine receipt to honored cancellation
* ``powlib.mine_s``    — client-observed mine round-trip incl. retries
* ``sched.batch_occupancy`` — real (non-padding) slots per batched
  launch: the continuous-batching win is this distribution's mean
* ``sched.slot_wait_s`` — submit-to-first-dispatch queueing latency of
  a scheduler slot (admission + run-queue wait)
* ``rpc.frame.sent_bytes`` / ``rpc.frame.recv_bytes`` — wire frame sizes
* ``rpc.client.call_s.<Service.Method>``     — per-method round-trip
* ``rpc.server.dispatch_s.<Service.Method>`` — per-method handler time
* ``load.lag_s`` — open-loop generator lag: how far behind its seeded
  Poisson schedule each arrival fired (distpow_tpu/load/loadgen.py; a
  lagging generator silently converts open-loop into closed-loop, so
  the soak verdict judges this distribution — docs/SOAK.md)

Gauge names in use (``KNOWN_GAUGES`` below; lint-gated since the soak
plane made gauges load-bearing — a typo'd sentinel gauge would hide a
leak from the trend detector exactly when it matters):

* ``worker.active_searches`` / ``worker.mine_queue_depth`` /
  ``worker.forward_queue_depth`` — worker occupancy and bounded-queue
  depths (nodes/worker.py)
* ``search.hashes_per_s``  — rolling backend throughput
* ``sched.active_slots`` / ``sched.run_queue_depth`` — continuous-
  batching occupancy and bounded run-queue depth (sched/engine.py)
* ``search.mesh_devices`` — device count of the most recently built
  search mesh (parallel/mesh_search.py make_mesh — the mesh serving
  lanes and backends all pass through it)
* ``fleet.live_workers``   — coordinator-side count of non-draining
  members, static and elastic alike (distpow_tpu/fleet/membership.py)
* ``proc.rss_bytes`` / ``proc.open_fds`` / ``proc.threads`` — per-node
  self-telemetry sampled on every Stats snapshot (runtime/health.py;
  the soak plane's leak sentinels watch these — docs/SOAK.md)
* ``ring.spans_depth`` / ``ring.flightrec_depth`` /
  ``ring.repl_queue_depth`` — occupancy of the bounded rings the repo
  owns (span ring, flight-recorder ring, replication push queue);
  forwarder backlog and sched run queue already ship as the
  ``*_queue_depth`` gauges above
"""

from __future__ import annotations

import math
import threading
import time
from typing import Dict, List, Optional, Tuple, Union

Number = Union[int, float]

# The declared counter registry.  distpow-lint's ``metrics-registry``
# rule parses these literals (AST, no import) and flags any
# ``metrics.inc``/``REGISTRY.inc`` call site whose literal name is not
# declared here — a typo'd counter otherwise splits silently into a
# real-but-frozen counter and a ghost twin nobody reads.  Keep the
# docstring list above and this set in sync (test_lint.py asserts it).
KNOWN_COUNTERS = frozenset({
    "search.hashes", "search.launches", "search.cancelled", "search.found",
    "search.persistent_steps",
    "search.halted_dispatches", "search.mesh_hashes",
    "search.finalize_hashes", "search.operand_binds",
    "worker.mine_rpcs", "worker.found_rpcs", "worker.cancel_rpcs",
    "worker.results_sent", "worker.forward_retries",
    "coord.mine_rpcs", "coord.fanouts", "coord.late_results",
    "coord.worker_failures", "coord.reassigned_shards",
    "coord.stale_results_dropped",
    "cache.hit", "cache.miss", "cache.add", "cache.evict",
    "powlib.retries", "powlib.reconnects", "powlib.degraded",
    "powlib.retry_after",
    "sched.launches", "sched.admission_rejected",
    "sched.coalesced_requests", "sched.slots_preempted",
    "sched.fallback_searches", "sched.loop_failures",
    "sched.mixed_hash_launches",
    "rpc.handler_errors",
    "rpc.codec.negotiated_v2", "rpc.codec.fallback_v1",
    "coord.abandoned_resyncs",
    "compile_cache.errors", "compile_cache.read_errors",
    "compile_cache.write_errors", "compile_cache.keygen_errors",
    "fallback.pallas_mesh_step",
    "fallback.lane_demotions", "fallback.warmup_failures",
    "telemetry.dropped_events", "telemetry.dumps",
    "obs.scrapes", "obs.scrape_failures",
    "slo.evaluations", "slo.breaches",
    "fleet.joins", "fleet.lease_expiries", "fleet.drains",
    "fleet.hedged_shards",
    "spans.dropped",
    "forensics.slow_captures",
    "forensics.fetches", "forensics.fetch_failures",
    "cluster.not_owner_redirects", "cluster.foreign_mines",
    "cluster.ring_serves",
    "cluster.reroutes", "cluster.failovers", "cluster.sibling_hedges",
    "repl.pushes", "repl.push_failures", "repl.installs",
    "repl.stale_drops", "repl.handoff_keys", "repl.antientropy_rounds",
    "health.leak_suspects",
    "soak.sweeps", "soak.phase_breaches",
    "obs.spool_rotations",
})

# Families minted from runtime values (f-string call sites): the
# literal prefix must match one of these.
KNOWN_COUNTER_PREFIXES = frozenset({
    "faults.injected.",
    "search.",  # backends/__init__.py count_exit: search.{cancelled,found}
    "sched.lane_launches.",  # sched/engine.py per-lane launch counters
})

# The declared histogram registry — the same rule checks every
# ``metrics.observe``/``metrics.time`` call site against these.
KNOWN_HISTOGRAMS = frozenset({
    "coord.mine_s.hit", "coord.mine_s.miss",
    "coord.first_result_s", "coord.cancel_propagation_s",
    "worker.solve_s", "worker.time_to_cancel_s",
    "powlib.mine_s",
    "sched.batch_occupancy", "sched.slot_wait_s",
    "rpc.frame.sent_bytes", "rpc.frame.recv_bytes",
    "obs.sweep_s",
    "fleet.heartbeat_rtt_s",
    "cluster.failover_s",
    "repl.push_lag_s", "repl.handoff_s",
    "load.lag_s",
})

# Per-method families (runtime/rpc.py mints one histogram per
# "Service.Method" seen on the wire).
KNOWN_HISTOGRAM_PREFIXES = frozenset({
    "rpc.client.call_s.",
    "rpc.server.dispatch_s.",
    "worker.solve_s.",  # per-hash-model solve latency (nodes/worker.py)
})

# The declared gauge registry — lint-gated like counters since the
# leak sentinels (runtime/health.py) made gauge NAMES load-bearing: a
# typo'd ``metrics.gauge("…")`` would split a climbing resource gauge
# away from the trend detector watching the declared name.
KNOWN_GAUGES = frozenset({
    "worker.active_searches", "worker.mine_queue_depth",
    "worker.forward_queue_depth",
    "search.hashes_per_s", "search.mesh_devices",
    "sched.active_slots", "sched.run_queue_depth",
    "fleet.live_workers",
    "proc.rss_bytes", "proc.open_fds", "proc.threads",
    "ring.spans_depth", "ring.flightrec_depth", "ring.repl_queue_depth",
})

# No gauge families are minted from runtime values today; the empty
# declaration keeps the lint context explicit (and greppable) anyway.
KNOWN_GAUGE_PREFIXES = frozenset()

# Log-bucket geometry: 4 buckets per octave (bounds grow by 2^0.25, so a
# bucket is at most ~19% wide) — fine enough for honest p95/p99
# estimates across the nine decades this registry spans (µs RPC
# dispatches to multi-minute compiles; byte to multi-MB frames) at a
# bounded, value-independent memory cost.
_BUCKETS_PER_OCTAVE = 4
_LOG_GROWTH = math.log(2.0) / _BUCKETS_PER_OCTAVE


class Histogram:
    """Log-bucketed distribution: count/sum/min/max plus percentile
    ESTIMATES (each reported percentile is the upper bound of its
    bucket, so estimates err high by at most one bucket width, ~19%).

    Exemplars (docs/FORENSICS.md): each bucket retains the LAST
    ``(trace_id, value, ts)`` observed with a trace id — the pointer
    from "p99 moved" to the one request that landed there, at a
    bounded (one tuple per touched bucket) memory cost.  Merged
    bucket-wise across nodes (obs/merge.py, freshest wins) and emitted
    as OpenMetrics exemplars by ``stats --prom --openmetrics``.

    Lock discipline: instances carry no lock of their own — the owning
    :class:`Metrics` registry serializes ``observe`` under its single
    registry lock, the same (cheap) critical section a counter
    increment pays.
    """

    __slots__ = ("count", "sum", "min", "max", "_buckets", "_zeros",
                 "_exemplars")

    def __init__(self) -> None:
        self.count = 0
        self.sum = 0.0
        self.min: Optional[float] = None
        self.max: Optional[float] = None
        self._buckets: Dict[int, int] = {}  # log-bucket index -> count
        self._zeros = 0  # non-positive samples (zero-latency clock ticks)
        # log-bucket index (None = zero bucket) -> (trace_id, value, ts)
        self._exemplars: Dict[Optional[int], Tuple[int, float, float]] = {}

    def observe(self, value: Number,
                trace_id: Optional[int] = None) -> None:
        v = float(value)
        self.count += 1
        self.sum += v
        if self.min is None or v < self.min:
            self.min = v
        if self.max is None or v > self.max:
            self.max = v
        if v > 0.0:
            idx = math.floor(math.log(v) / _LOG_GROWTH)
            self._buckets[idx] = self._buckets.get(idx, 0) + 1
        else:
            idx = None
            self._zeros += 1
        if trace_id:
            self._exemplars[idx] = (int(trace_id), v,
                                    round(time.time(), 6))

    @staticmethod
    def bound(idx: int) -> float:
        """Upper bound of log-bucket ``idx``."""
        return math.exp((idx + 1) * _LOG_GROWTH)

    def percentile(self, q: float) -> Optional[float]:
        """Estimated q-quantile (q in [0, 1]); None when empty."""
        if self.count == 0:
            return None
        rank = q * self.count
        cum = self._zeros
        if cum >= rank and self._zeros:
            return 0.0
        for idx in sorted(self._buckets):
            cum += self._buckets[idx]
            if cum >= rank:
                est = self.bound(idx)
                # the true sample lies inside the bucket; clamp the
                # bucket-bound estimate to the observed extremes
                return min(max(est, self.min or est), self.max or est)
        return self.max

    def to_dict(self) -> dict:
        """JSON-able snapshot; ``buckets`` is ``[[upper_bound, count],
        ...]`` in ascending bound order (non-cumulative — the Prometheus
        renderer in cli/stats.py accumulates).  ``exemplars`` rides only
        when some bucket holds one: ``[[upper_bound, trace_id, value,
        ts], ...]`` keyed by the same rounded bounds, so merge and
        rendering pair them with their buckets exactly."""
        buckets: List[Tuple[float, int]] = []
        if self._zeros:
            buckets.append((0.0, self._zeros))
        buckets.extend(
            (round(self.bound(i), 9), self._buckets[i])
            for i in sorted(self._buckets)
        )
        out = {
            "count": self.count,
            "sum": round(self.sum, 9),
            "min": self.min,
            "max": self.max,
            "p50": self.percentile(0.50),
            "p95": self.percentile(0.95),
            "p99": self.percentile(0.99),
            "buckets": [[b, c] for b, c in buckets],
        }
        if self._exemplars:
            out["exemplars"] = [
                [0.0 if i is None else round(self.bound(i), 9),
                 tid, v, ts]
                for i, (tid, v, ts) in sorted(
                    self._exemplars.items(),
                    key=lambda kv: (float("-inf") if kv[0] is None
                                    else kv[0]))
            ]
        return out


class _Timer:
    """Context manager returned by :meth:`Metrics.time` — observes the
    block's wall-clock duration (seconds) into the named histogram."""

    __slots__ = ("_metrics", "_name", "_t0")

    def __init__(self, metrics: "Metrics", name: str):
        self._metrics = metrics
        self._name = name
        self._t0 = 0.0

    def __enter__(self) -> "_Timer":
        self._t0 = time.monotonic()
        return self

    def __exit__(self, *exc) -> None:
        self._metrics.observe(self._name, time.monotonic() - self._t0)


class Metrics:
    def __init__(self) -> None:
        self._counters: Dict[str, Number] = {}
        self._gauges: Dict[str, Number] = {}
        self._hists: Dict[str, Histogram] = {}
        self._lock = threading.Lock()
        self._start = time.monotonic()
        # exemplar capture switch (docs/FORENSICS.md): call sites pass
        # trace ids unconditionally; flipping this off drops them at
        # the registry so bench.py --forensics-overhead can measure
        # exemplars-on vs -off without touching the instrumented seams
        self.exemplars_enabled = True

    def inc(self, name: str, n: Number = 1) -> None:
        with self._lock:
            self._counters[name] = self._counters.get(name, 0) + n

    def gauge(self, name: str, value: Number) -> None:
        with self._lock:
            self._gauges[name] = value

    def observe(self, name: str, value: Number,
                trace_id: Optional[int] = None) -> None:
        """Add one sample to the named histogram (created on first
        touch, like counters — distpow-lint polices the names).
        ``trace_id`` (when the call site has a request in scope)
        retains the sample as its bucket's exemplar."""
        with self._lock:
            h = self._hists.get(name)
            if h is None:
                h = self._hists[name] = Histogram()
            h.observe(value,
                      trace_id if self.exemplars_enabled else None)

    def time(self, name: str) -> _Timer:
        """``with metrics.time("x.y"): ...`` observes the block's
        duration in seconds into histogram ``x.y``."""
        return _Timer(self, name)

    def get(self, name: str) -> Number:
        with self._lock:
            return self._counters.get(name, self._gauges.get(name, 0))

    def get_histogram(self, name: str) -> Optional[dict]:
        with self._lock:
            h = self._hists.get(name)
            return h.to_dict() if h is not None else None

    def snapshot(self) -> dict:
        with self._lock:
            return {
                "uptime_secs": round(time.monotonic() - self._start, 3),
                "counters": dict(self._counters),
                "gauges": dict(self._gauges),
                "histograms": {
                    name: h.to_dict() for name, h in self._hists.items()
                },
            }

    def reset(self) -> None:
        """Testing hook."""
        with self._lock:
            self._counters.clear()
            self._gauges.clear()
            self._hists.clear()
            self._start = time.monotonic()


REGISTRY = Metrics()
