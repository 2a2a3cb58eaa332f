"""One run of one cell: boot the deployment, warm it, drive the window
through powlib's ``Client.mine``, check every answer against the plain
reference, and print the result line.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Everything runs in this process (a chip belongs to one process).  The
window's requests are paired with their answers by the trace id powlib
gives each request, so a repeat of a key is never credited with its
sibling's answer, and each request is timed from when it was due.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import queue
import shutil
import sys
import tempfile
import threading
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional

from . import manifest, profile_reduce, reference, traffic

# how long past the window's close the answers of its requests are
# awaited before a request counts as missing
DRAIN_S = 60.0
WARM_TIMEOUT_S = 900.0
# the compared numbers: an exact comparison, limit 0 each
LIMITS = {"wrong": 0, "missing": 0, "stray": 0}


def device_check(chips: int) -> dict:
    """Pin JAX to the TPU; raises when it finds none or fewer than
    ``chips`` chips.  Tests replace this function."""
    import jax

    jax.config.update("jax_platforms", "tpu")
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < chips:
        raise RuntimeError(f"need {chips} TPU chip(s), JAX has {devs}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak_bytes() -> int:
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.devices()]
    return int(max(peaks, default=0))


# -- requests and their answers ----------------------------------------------

@dataclass
class Request:
    nonce: bytes
    ntz: int          # the puzzle the answer is judged by
    sent_ntz: int     # what was asked (the control asks one less)
    due: float        # monotonic seconds
    first: Optional[int] = None   # the pool's recorded first solution
    trace_id: Optional[int] = None
    answered: Optional[float] = None
    secret: Optional[bytes] = None
    error: Optional[str] = None
    done: threading.Event = field(default_factory=threading.Event)


class _TraceIds:
    """Stands in for the client's tracer: binds the trace powlib
    creates for each Mine to the request being sent."""

    def __init__(self, tracer, ledger: "Ledger"):
        self._tracer = tracer
        self._ledger = ledger

    def create_trace(self):
        trace = self._tracer.create_trace()
        self._ledger._bind(trace.trace_id)
        return trace

    def __getattr__(self, name):
        return getattr(self._tracer, name)


class Ledger:
    """Every request sent and the answer paired with it."""

    def __init__(self, client):
        self._client = client
        self._lock = threading.Lock()
        self._sending: Optional[Request] = None
        self._by_tid: Dict[int, Request] = {}
        self.requests: List[Request] = []
        self.stray: List[object] = []
        client.tracer = _TraceIds(client.tracer, self)
        self._stop = threading.Event()
        self._drainer = threading.Thread(target=self._drain, daemon=True,
                                         name="bench-drain")
        self._drainer.start()

    def _bind(self, trace_id: int) -> None:
        with self._lock:
            if self._sending is not None:
                self._sending.trace_id = trace_id
                self._by_tid[trace_id] = self._sending

    def send(self, nonce: bytes, ntz: int, sent_ntz: int,
             due: float, first: Optional[int] = None) -> Request:
        req = Request(nonce=bytes(nonce), ntz=ntz, sent_ntz=sent_ntz,
                      due=due, first=first)
        with self._lock:
            self._sending = req
            self.requests.append(req)
        try:
            self._client.mine(req.nonce, sent_ntz)
        finally:
            with self._lock:
                self._sending = None
        return req

    def _pair(self, res) -> Optional[Request]:
        if res.token:
            return self._by_tid.get(json.loads(res.token)["trace_id"])
        # a failure carries no token: the oldest open request of its key
        for req in self.requests:
            if (req.answered is None and req.nonce == bytes(res.nonce)
                    and req.sent_ntz == res.num_trailing_zeros):
                return req
        return None

    def _drain(self) -> None:
        q = self._client.notify_queue
        while not self._stop.is_set():
            try:
                res = q.get(timeout=0.05)
            except queue.Empty:
                continue
            now = time.monotonic()
            with self._lock:
                req = self._pair(res)
                if req is None or req.answered is not None:
                    self.stray.append(res)
                    continue
                req.answered = now
                req.secret = None if res.secret is None else bytes(res.secret)
                req.error = res.error
            req.done.set()

    def wait_all(self, deadline: float) -> None:
        for req in list(self.requests):
            left = deadline - time.monotonic()
            if left <= 0 or not req.done.wait(left):
                return

    def close(self) -> None:
        self._stop.set()
        self._drainer.join(timeout=5)


# -- the deployment ----------------------------------------------------------

class Deployment:
    """The configuration's cluster in this process: coordinator, workers
    and one powlib client over localhost RPC, with the tracing server
    when the configuration runs one."""

    def __init__(self, config: dict, mix: dict, workdir: str):
        from distpow_tpu.load.harness import InProcCluster
        from distpow_tpu.runtime.config import TracingServerConfig
        from distpow_tpu.runtime.trace_server import TracingServer

        worker = {**config["worker"],
                  "WarmupNonceLens": [mix["nonce_len"]],
                  "WarmupWidths": list(mix["warm_widths"])}
        backend = worker.pop("Backend")
        tracer, self.server = {}, None
        if config.get("tracing_server"):
            self.server = TracingServer(TracingServerConfig(
                ServerBind="127.0.0.1:0",
                OutputFile=os.path.join(workdir, "trace_output.log"),
                ShivizOutputFile=os.path.join(workdir, "shiviz_output.log")))
            tracer = {"TracerServerAddr": self.server.open()}
            self.server.accept_in_background()
        try:
            self.cluster = InProcCluster(
                n_workers=int(config["workers"]), backend=backend,
                coord_extra={**config.get("coordinator", {}), **tracer},
                worker_extra={**worker, **tracer}, client_extra=tracer)
        except BaseException:
            if self.server is not None:
                self.server.close()
            raise
        self.ledger = Ledger(self.cluster.client)

    def wait_warm(self) -> None:
        for w in self.cluster.workers:
            if not w.warmup_done.wait(WARM_TIMEOUT_S):
                raise RuntimeError(f"worker warm-up took over "
                                   f"{WARM_TIMEOUT_S} s")

    def backends(self) -> List[str]:
        return [w.handler.backend.name for w in self.cluster.workers]

    def close(self) -> None:
        try:
            self.ledger.close()
            self.cluster.close()
        finally:
            if self.server is not None:
                self.server.close()


# -- warm-up and window ------------------------------------------------------

def warm_traffic(dep: Deployment, mix: dict, seed: int) -> None:
    """The mix's own warm-up (``traffic.py``): its ``warm_pool``, or
    nonces of a domain the window never draws from; its requests leave
    the ledger after.  Raises when an answer does not come."""
    warm, ledger, key = mix.get("warmup", {}), dep.ledger, 0

    def send(ntz):
        nonlocal key
        nonce = traffic.key_nonce(seed, key, mix["nonce_len"], "warmup")
        key += 1
        return ledger.send(nonce, ntz, ntz, time.monotonic())

    def await_all(reqs):
        for req in reqs:
            if not req.done.wait(WARM_TIMEOUT_S):
                raise RuntimeError("a warm-up request was not answered")

    for nonce, _first in mix.get("warm_pool", []):
        ntz = int(mix["pool_ntz"])
        await_all([ledger.send(bytes.fromhex(nonce), ntz, ntz,
                               time.monotonic())])
    for ntz, _weight in mix["difficulties"]:
        for _ in range(int(warm.get("requests", 0))):
            await_all([send(ntz)])
        for k in warm.get("bursts", []):
            await_all([send(ntz) for _ in range(int(k))])
    with ledger._lock:
        ledger.requests = []


def _annotate(name: str):
    from jax.profiler import TraceAnnotation

    return TraceAnnotation(name)


def drive_window(dep: Deployment, mix: dict, seed: int, seconds: float,
                 control: bool) -> tuple:
    """Send the window's requests; returns (t0, t_close, lags_s, spent):
    ``spent`` when a closed loop's pool ran out before the close.
    ``control`` asks each puzzle one zero short of what it is judged
    by: the guarantee the control breaks."""
    ledger = dep.ledger
    less = 1 if control else 0
    t0 = time.monotonic()
    t_close = t0 + seconds
    lags: List[float] = []
    spent = False
    if mix["loop"] == "closed":
        spent = True
        for arr in traffic.closed_arrivals(mix, seed):
            now = time.monotonic()
            if now >= t_close:
                spent = False
                break
            with _annotate("bench.submit"):
                req = ledger.send(arr.nonce, arr.ntz, arr.ntz - less, now,
                                  arr.first)
            with _annotate("bench.await_answer"):
                if not req.done.wait(max(0.0, t_close + DRAIN_S
                                         - time.monotonic())):
                    spent = False
                    break
    else:
        def submit(arr, due):
            with _annotate("bench.submit"):
                ledger.send(arr.nonce, arr.ntz, arr.ntz - less, due)

        runner = traffic.OpenLoopRunner(submit, span=_annotate)
        runner.run(traffic.build_schedule(mix, seed, seconds), t0)
        lags = runner.lags_s
        with _annotate("bench.drain"):
            ledger.wait_all(t_close + DRAIN_S)
    return t0, t_close, lags, spent


# -- numbers -----------------------------------------------------------------

def percentile(values: List[float], q: float) -> float:
    """Nearest rank over all values (inf for a missing answer)."""
    s = sorted(values)
    return s[max(0, math.ceil(q * len(s)) - 1)]


@dataclass
class Readings:
    """What a per-layer reader may read (``benchmark/layers/``)."""

    cell: manifest.Cell
    device: dict
    requests: List[Request]
    valid: List[Request]
    credited_hashes: int
    counters: Dict[str, float]
    histograms: Dict[str, dict]
    lags_s: List[float]
    trace: Optional[profile_reduce.Reduced]


def judge(cell: manifest.Cell, ledger: Ledger) -> tuple:
    """(valid answers, compared numbers) by the plain reference."""
    model = cell.config["hash_model"]
    valid, wrong, missing = [], 0, 0
    for req in ledger.requests:
        if req.answered is None:
            missing += 1
        elif (req.error is None and req.secret is not None
              and reference.solves(model, req.nonce, req.secret, req.ntz)):
            valid.append(req)
        else:
            wrong += 1
    return valid, {"wrong": wrong, "missing": missing,
                   "stray": len(ledger.stray)}


def end_to_end(name: str, reqs: List[Request], valid: List[Request],
               t0: float, credited: int) -> Optional[float]:
    if name == "search_rate":
        last = max((r.answered for r in valid), default=None)
        return None if last is None else credited / (last - t0) / 1e9
    ok = {id(r) for r in valid}
    lat = [r.answered - r.due if id(r) in ok else math.inf for r in reqs]
    if name == "solve_p95_s":
        return percentile(lat, 0.95)
    if name == "solve_p50_s":
        return percentile(lat, 0.50)
    raise KeyError(f"no end-to-end metric {name!r} in the harness")


def _finite(x):
    """``x`` with every non-finite float replaced by None."""
    if isinstance(x, float) and not math.isfinite(x):
        return None
    if isinstance(x, dict):
        return {k: _finite(v) for k, v in x.items()}
    if isinstance(x, (list, tuple)):
        return [_finite(v) for v in x]
    return x


def _p95_by_half(reqs, valid, t0, t_close) -> list:
    """p95 of the requests due in each half of the window: a backlog
    that grows through the window shows as a second half far above the
    first (the rate sweep's test)."""
    mid, out = (t0 + t_close) / 2, []
    for half in ([r for r in reqs if r.due < mid],
                 [r for r in reqs if r.due >= mid]):
        out.append(end_to_end("solve_p95_s", half, valid, t0, 0)
                   if half else None)
    return out


class _Compiles:
    """Counts backend compiles while ``on`` (jax.monitoring)."""

    def __init__(self):
        self.on = False
        self.n = 0
        self.registered = False

    def __call__(self, event: str, _seconds: float, **_kw) -> None:
        if self.on and event == "/jax/core/compile/backend_compile_duration":
            self.n += 1


COMPILES = _Compiles()


def _count_compiles() -> None:
    """Registers COMPILES with jax.monitoring, once per process."""
    import jax

    if not COMPILES.registered:
        jax.monitoring.register_event_duration_secs_listener(COMPILES)
        COMPILES.registered = True


def _xplane(trace_dir: str) -> str:
    for dirpath, _dirs, files in os.walk(trace_dir):
        for f in files:
            if f.endswith(".xplane.pb"):
                return os.path.join(dirpath, f)
    raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")


def _parse(argv) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    # not for the driver: the control of PERF.md
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def _log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def run(argv=None, t_start: Optional[float] = None) -> int:
    """One run; returns the exit code.  Prints the result line last on
    stdout only when the run got as far as judging its answers."""
    t_start = time.monotonic() if t_start is None else t_start
    args = _parse(argv)
    cell = manifest.load_cell(args.workload)
    try:
        dev = device_check(cell.chips)
    except Exception as exc:
        _log(f"no device for {cell.name}: {exc}")
        return 2
    import jax

    from distpow_tpu.runtime import compile_cache
    from distpow_tpu.runtime.metrics import REGISTRY

    compile_cache.enable()
    _count_compiles()
    COMPILES.n = 0
    workdir = tempfile.mkdtemp(prefix="bench-")
    reduced = None
    try:
        dep = Deployment(cell.config, cell.mix, workdir)
        try:
            dep.wait_warm()
            warm_traffic(dep, cell.mix, args.seed)
            setup_s = time.monotonic() - t_start
            _log(f"{cell.name}: set-up {setup_s:.3f} s, backends "
                 f"{dep.backends()}")
            before = REGISTRY.snapshot()
            if args.trace:
                # device ops and the bench.* annotations; no Python
                # function tracer (it slowed the host several-fold)
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0
                jax.profiler.start_trace(os.path.join(workdir, "trace"),
                                         profiler_options=opts)
            COMPILES.on = True
            # a program built inside the window is a warm-up gap: name it
            jax.config.update("jax_log_compiles", True)
            with _annotate("bench.window"):
                t0, t_close, lags, spent = drive_window(
                    dep, cell.mix, args.seed, args.seconds,
                    bool(args.control))
            COMPILES.on = False
            jax.config.update("jax_log_compiles", False)
            if args.trace:
                jax.profiler.stop_trace()
            after = REGISTRY.snapshot()
            dev["memory_peak_bytes"] = memory_peak_bytes()
            backends = dep.backends()
        finally:
            dep.close()
        if args.trace:
            reduced = profile_reduce.reduce_trace(
                profile_reduce.load_xplane(_xplane(workdir), dev["platform"]),
                cell.chips)
    finally:
        shutil.rmtree(workdir, ignore_errors=True)

    ledger = dep.ledger
    reqs = ledger.requests
    valid, checks = judge(cell, ledger)
    credited = sum(reference.credit(r.secret, r.first) for r in valid)
    past_first = sum(r.first is not None
                     and reference.enum_position(r.secret) > r.first
                     for r in valid)
    counters = {k: v - before["counters"].get(k, 0)
                for k, v in after["counters"].items()}
    hists = {}
    for k, h in after["histograms"].items():
        b = before["histograms"].get(k, {})
        hists[k] = {"count": h["count"] - b.get("count", 0),
                    "sum": h["sum"] - b.get("sum", 0.0)}
    values = {"setup_s": setup_s}
    for m in cell.end_to_end:
        if m["name"] != "setup_s":
            values[m["name"]] = end_to_end(m["name"], reqs, valid, t0,
                                           credited)
    if args.trace:
        dev["busy_s"] = reduced.mean_busy_s
        dev["window_s"] = reduced.window_s
        readings = Readings(cell=cell, device=dev, requests=reqs,
                            valid=valid, credited_hashes=credited,
                            counters=counters, histograms=hists,
                            lags_s=lags, trace=reduced)
        entries = cell.per_layer
        for m in entries:
            values[m["name"]] = manifest.reader(m["name"])(readings)
    else:
        entries = cell.end_to_end
    # a tail over a missing answer is infinite: such a run is not
    # correct, and the metric stays out of the line (JSON has no inf)
    metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
               for m in entries if values.get(m["name"]) is not None
               and math.isfinite(values[m["name"]])}
    correct = bool(reqs) and all(checks[k] <= LIMITS[k] for k in LIMITS)
    result = {"correct": correct, "attempted": len(reqs),
              "failed": checks["wrong"] + checks["missing"],
              "metrics": metrics, "device": dev}
    if reduced is not None:
        result["breakdown"] = {
            "device_ops": [list(x) for x in reduced.device_ops],
            "idle_gaps": [list(x) for x in reduced.idle_gaps]}
    result["diag"] = {
        "backends": backends, "compiles_in_window": COMPILES.n,
        "answered": sum(r.answered is not None for r in reqs),
        "window_s": t_close - t0, "credited_hashes": credited,
        "past_first": past_first, "pool_spent": spent,
        "search_hashes": counters.get("search.hashes", 0),
        "gen_lag_max_s": max(lags, default=0.0),
        "e2e": {k: v for k, v in values.items()
                if isinstance(v, (int, float))},
        "p95_by_half_s": _p95_by_half(reqs, valid, t0, t_close),
    }
    result["checks"] = {k: {"value": checks[k], "limit": LIMITS[k]}
                        for k in LIMITS}
    for k in LIMITS:
        print(f"check {k}: {checks[k]} (limit {LIMITS[k]})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(_finite(result), allow_nan=False), flush=True)
    return 0
