#!/usr/bin/env python
"""Chip smoke: drive the served md5 path once on a TPU, through the
entry points a user calls, and check every answer.

    python chip_smoke.py             # one chip: phases A and B
    python chip_smoke.py --chips 4   # four chips: the pallas-mesh path
                                     # next to its one-chip twin

Everything runs in THIS process: a chip belongs to one process at a
time.  JAX is pinned to the TPU before anything touches a device, so it
cannot fall back to the CPU; with no TPU the script exits 2 and prints
no result.  Each phase boots a real in-process cluster
(``load/harness.py`` InProcCluster: coordinator, one worker and a powlib
client over localhost RPC) with an in-process tracing server, and
``trace_check`` must find no violation in its logs.

* A — the reference deployment: the default WorkerConfig (``jax``
  backend, persistent loop, 2^20 batch, default boot warmup, waited
  for), serving the demo's pattern (three nonces at difficulty 5, one
  repeated at 7: the dominance-cache supersede path) and one fresh
  nonce at difficulty 8 (32 bits, BASELINE config 4).
* B — the scheduler: ``Backend="auto"`` (must resolve to ``pallas``)
  with ``Scheduler="batching"`` and 8 concurrent requests at difficulty
  6, which the ``pallas`` lane must serve.
* ``--chips 4`` — phase B's requests on a worker whose ``auto`` resolves
  to ``pallas-mesh`` over the 4 chips and on its one-chip twin
  (``Backend="pallas"``), scheduler off on both so the backend serves:
  byte-identical secrets, a 4-device mesh, and at least 2x the one-chip
  rate over a fixed 2^34-candidate span at difficulty 32.

Every secret is checked with hashlib.  Any ``fallback.*`` counter above
0 fails the run.  Stdout carries one JSON object per line; the last is
``{"ok": true, "device": {...}}`` and appears only when every check
passed.  Traces and logs go to ``chiprun_out/chip_smoke/``.
"""

from __future__ import annotations

import argparse
import hashlib
import json
import logging
import os
import statistics
import sys
import time
import traceback

REPO = os.path.dirname(os.path.abspath(__file__))
OUT_DIR = os.path.join(REPO, "chiprun_out", "chip_smoke")

# (nonce, difficulty in hex nibbles), served one after another
PHASE_A_REQUESTS = [
    (bytes([1, 2, 3, 4]), 5),
    (bytes([5, 6, 7, 8]), 5),
    (bytes([2, 2, 2, 2]), 5),
    (bytes([2, 2, 2, 2]), 7),   # supersedes its difficulty-5 cache entry
    (bytes([0x4D, 0x44, 0x35, 0x08]), 8),
]
# served concurrently
PHASE_B_NONCES = [bytes([0xB0 + i, 0x5C, 0x4E, 0xD0]) for i in range(8)]
PHASE_B_NTZ = 6
SCHED_MAX_INFLIGHT = 8
# the fixed span of the four-chip scale check: unsatisfiable in
# practice (all 32 md5 nibbles zero), so both backends search all of it
SPAN_CANDIDATES = 1 << 34
SPAN_NTZ = 32
SPAN_NONCE = bytes([0x5B, 0xA4, 0x00, 0x01])
MIN_MESH_SPEEDUP = 2.0

# What a TPU host must resolve and serve.  Tests rehearse the phases on
# the CPU by replacing ``device_check`` and these names.
BACKEND_B = "pallas"
LANE_B = "pallas"
TWIN_BACKEND = "pallas"
MESH_BACKEND = "pallas-mesh"
# extra WorkerConfig fields for every worker (tests shrink the shapes)
WORKER_OVERRIDES: dict = {}

REQUEST_TIMEOUT_S = 300.0
WARMUP_TIMEOUT_S = 600.0


def device_check() -> dict:
    """Pin JAX to the TPU and describe its devices; raises when JAX
    finds no TPU."""
    import jax

    jax.config.update("jax_platforms", "tpu")
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def emit(record: dict) -> None:
    print(json.dumps(record), flush=True)


def solves(nonce: bytes, secret: bytes, ntz: int) -> bool:
    digest = hashlib.md5(bytes(nonce) + bytes(secret)).hexdigest()
    return ntz == 0 or digest.endswith("0" * ntz)


def enum_hashes(secret: bytes) -> int:
    """Candidates up to and including ``secret`` in the reference
    enumeration (thread byte fastest, then the little-endian chunk)."""
    return int.from_bytes(secret[1:], "little") * 256 + secret[0] + 1


def counters() -> dict:
    from distpow_tpu.runtime.metrics import REGISTRY

    return dict(REGISTRY.snapshot()["counters"])


def delta(after: dict, before: dict, prefixes: tuple) -> dict:
    return {k: v - before.get(k, 0) for k, v in sorted(after.items())
            if k.startswith(prefixes) and v - before.get(k, 0)}


def worker_config(**fields) -> dict:
    """The default WorkerConfig's serving shape (the harness would
    otherwise turn the boot warmup off), plus ``fields``."""
    from distpow_tpu.runtime.config import WorkerConfig

    default = WorkerConfig()
    return {"Backend": default.Backend,
            "WarmupNonceLens": list(default.WarmupNonceLens),
            "WarmupWidths": list(default.WarmupWidths),
            **fields, **WORKER_OVERRIDES}


class TracedCluster:
    """InProcCluster with every node tracing to an in-process tracing
    server; ``close()`` shuts the cluster down and returns the
    trace_check violations of its logs."""

    def __init__(self, name: str, worker: dict, coord: dict | None = None):
        from distpow_tpu.load.harness import InProcCluster
        from distpow_tpu.runtime.config import TracingServerConfig
        from distpow_tpu.runtime.trace_server import TracingServer

        self.trace_path = os.path.join(OUT_DIR, f"{name}_trace.log")
        self.shiviz_path = os.path.join(OUT_DIR, f"{name}_shiviz.log")
        for p in (self.trace_path, self.shiviz_path):
            if os.path.exists(p):
                os.remove(p)
        self.server = TracingServer(TracingServerConfig(
            ServerBind="127.0.0.1:0", OutputFile=self.trace_path,
            ShivizOutputFile=self.shiviz_path))
        addr = self.server.open()
        self.server.accept_in_background()
        tracer = {"TracerServerAddr": addr}
        try:
            self.cluster = InProcCluster(
                n_workers=1, backend=worker.pop("Backend"),
                coord_extra={**(coord or {}), **tracer},
                worker_extra={**worker, **tracer},
                client_extra=tracer)
        except BaseException:
            self.server.close()
            raise
        self.worker = self.cluster.workers[0]

    def wait_warm(self) -> float | None:
        if not self.worker.warmup_done.wait(WARMUP_TIMEOUT_S):
            raise RuntimeError(f"warmup did not finish in "
                               f"{WARMUP_TIMEOUT_S}s")
        return self.worker.handler.warmup_s

    def mine(self, requests: list) -> dict:
        """Send every (nonce, ntz) at once; returns {(nonce, ntz):
        (seconds, MineResult)} once all have answered."""
        t0 = time.monotonic()
        for nonce, ntz in requests:
            self.cluster.client.mine(nonce, ntz)
        out = {}
        while len(out) < len(requests):
            res = self.cluster.client.notify_queue.get(
                timeout=REQUEST_TIMEOUT_S)
            out[(bytes(res.nonce), res.num_trailing_zeros)] = (
                time.monotonic() - t0, res)
        return out

    def close(self) -> list:
        from distpow_tpu.runtime.trace_check import (
            check_shiviz_log,
            check_trace_log,
        )

        try:
            self.cluster.close()
            # the nodes' last events are in flight to the server: wait
            # until the log stops growing
            size = -1
            while size != os.path.getsize(self.trace_path):
                size = os.path.getsize(self.trace_path)
                time.sleep(0.5)
        finally:
            self.server.close()
        return (check_trace_log(self.trace_path)
                + check_shiviz_log(self.shiviz_path))


def check_results(phase: str, results: dict, failures: list,
                  **extra) -> dict:
    """Emit and hashlib-check every answer; returns {nonce: secret}."""
    secrets = {}
    for (nonce, ntz), (seconds, res) in results.items():
        ok = (res.error is None and res.secret is not None
              and solves(nonce, res.secret, ntz))
        emit({"phase": phase, "nonce": nonce.hex(), "ntz": ntz,
              "seconds": seconds,
              "enum_hashes": enum_hashes(res.secret) if res.secret else None,
              "secret": res.secret.hex() if res.secret else None,
              "error": res.error, "hashlib_ok": ok, **extra})
        if not ok:
            failures.append(f"{phase}: nonce {nonce.hex()} ntz {ntz}: "
                            f"secret {res.secret!r} error {res.error}")
        secrets[nonce] = res.secret
    return secrets


def round_trip(failures: list, n: int = 101) -> None:
    """Median host<->device round trip of one tiny launch ending in
    ``int(out)`` (what sizes parallel/search.py's launch budget)."""
    import jax
    import jax.numpy as jnp

    step = jax.jit(lambda x: x + jnp.uint32(1))
    x = jnp.uint32(7)
    int(step(x))  # compile
    samples = []
    for _ in range(n):
        t0 = time.perf_counter()
        int(step(x))
        samples.append(time.perf_counter() - t0)
    emit({"phase": "rtt", "median_s": statistics.median(samples),
          "min_s": min(samples), "max_s": max(samples), "n": n})


def stats_of(tc: TracedCluster) -> dict:
    st = tc.worker.handler.Stats({})
    return {k: st.get(k) for k in ("backend", "scheduler", "platform",
                                   "device_kind", "device_count",
                                   "warmup_s")}


def phase_a(failures: list) -> None:
    tc = TracedCluster("phase_a", worker_config())
    try:
        emit({"phase": "A", "warmup_s": tc.wait_warm(),
              "stats": stats_of(tc)})
        for nonce, ntz in PHASE_A_REQUESTS:
            h0 = counters().get("search.hashes", 0)
            results = tc.mine([(nonce, ntz)])
            check_results("A", results, failures, hashes_dispatched=(
                counters().get("search.hashes", 0) - h0))
    finally:
        failures.extend(f"A trace: {v}" for v in tc.close())


def phase_b(failures: list) -> None:
    tc = TracedCluster(
        "phase_b", worker_config(Backend="auto", Scheduler="batching"),
        coord={"SchedMaxInflight": SCHED_MAX_INFLIGHT})
    try:
        warm = tc.wait_warm()
        resolved = tc.worker.handler.backend.name
        emit({"phase": "B", "warmup_s": warm, "auto": resolved,
              "stats": stats_of(tc)})
        if resolved != BACKEND_B:
            failures.append(f"B: auto resolved to {resolved}, "
                            f"not {BACKEND_B}")
        before = counters()
        results = tc.mine([(n, PHASE_B_NTZ) for n in PHASE_B_NONCES])
        check_results("B", results, failures)
        moved = delta(counters(), before, ("sched.", "search."))
        emit({"phase": "B", "counters": moved})
        if not moved.get(f"sched.lane_launches.{LANE_B}"):
            failures.append(f"B: no launch served by the {LANE_B} lane: "
                            f"{moved}")
        if moved.get("sched.fallback_searches"):
            failures.append(f"B: {moved['sched.fallback_searches']} "
                            f"searches bypassed the scheduler")
    finally:
        failures.extend(f"B trace: {v}" for v in tc.close())


def timed_span(backend) -> float:
    """Candidates/s over SPAN_CANDIDATES at SPAN_NTZ through the
    backend's own step builder (the second of two runs: the first
    compiles every width)."""
    from distpow_tpu.parallel.search import persistent_search
    from distpow_tpu.runtime.metrics import REGISTRY

    threads = list(range(256))

    def run():
        return persistent_search(
            SPAN_NONCE, SPAN_NTZ, threads, model=backend.model,
            batch_size=backend.batch_size,
            launch_candidates=backend.max_launch,
            max_hashes=SPAN_CANDIDATES,
            step_builder=backend._step_builder)

    for timed in (False, True):
        h0 = REGISTRY.get("search.hashes")
        t0 = time.monotonic()
        found = run()
        seconds = time.monotonic() - t0
        hashes = REGISTRY.get("search.hashes") - h0
        if found is not None:
            raise RuntimeError(f"span search found {found.secret.hex()}")
    emit({"phase": "span", "backend": backend.name, "hashes": hashes,
          "seconds": seconds, "rate_hps": hashes / seconds})
    return hashes / seconds


def phase_mesh(failures: list) -> None:
    from distpow_tpu.runtime.metrics import REGISTRY

    requests = [(n, PHASE_B_NTZ) for n in PHASE_B_NONCES]
    secrets, backends = {}, {}
    for name, backend in (("twin", TWIN_BACKEND), ("mesh", "auto")):
        tc = TracedCluster(name, worker_config(Backend=backend))
        try:
            warm = tc.wait_warm()
            backends[name] = tc.worker.handler.backend
            emit({"phase": name, "warmup_s": warm,
                  "backend": backends[name].name, "stats": stats_of(tc)})
            secrets[name] = check_results(name, tc.mine(requests), failures)
        finally:
            failures.extend(f"{name} trace: {v}" for v in tc.close())
    if backends["mesh"].name != MESH_BACKEND:
        failures.append(f"mesh: auto resolved to {backends['mesh'].name}, "
                        f"not {MESH_BACKEND}")
    if secrets["twin"] != secrets["mesh"]:
        failures.append(f"mesh secrets differ from the twin's: "
                        f"{secrets['mesh']} vs {secrets['twin']}")
    n_mesh = REGISTRY.get("search.mesh_devices")
    if n_mesh != 4:
        failures.append(f"mesh spans {n_mesh} devices, not 4")
    rate_1 = timed_span(backends["twin"])
    rate_4 = timed_span(backends["mesh"])
    emit({"phase": "span", "speedup": rate_4 / rate_1,
          "mesh_devices": n_mesh})
    if rate_4 < MIN_MESH_SPEEDUP * rate_1:
        failures.append(f"mesh rate {rate_4:.4g}/s is under "
                        f"{MIN_MESH_SPEEDUP}x the one-chip {rate_1:.4g}/s")


def run(chips: int) -> list:
    """Every phase of the ``chips`` form; returns the failures."""
    failures: list = []
    phases = [phase_mesh] if chips == 4 else [round_trip, phase_a, phase_b]
    for phase in phases:
        try:
            phase(failures)
        except Exception as exc:
            traceback.print_exc()
            failures.append(f"{phase.__name__} raised: {exc!r}")
    moved = counters()
    emit({"phase": "end", "counters": delta(
        moved, {}, ("fallback.", "sched.lane_launches.", "sched.launches",
                    "search.launches", "search.hashes"))})
    failures.extend(f"fallback counter {k} = {v}"
                    for k, v in delta(moved, {}, ("fallback.",)).items())
    return failures


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)
    try:
        dev = device_check()
    except Exception as exc:
        print(f"[chip_smoke] no TPU: {exc}", file=sys.stderr)
        return 2
    if dev["platform"] != "tpu" or dev["count"] != args.chips:
        print(f"[chip_smoke] need {args.chips} TPU chip(s), JAX reports "
              f"{dev}", file=sys.stderr)
        return 2
    os.makedirs(OUT_DIR, exist_ok=True)
    logging.basicConfig(
        level=logging.INFO, filename=os.path.join(OUT_DIR, "chip_smoke.log"),
        format="%(asctime)s %(name)s %(levelname)s %(message)s")

    from distpow_tpu.runtime import compile_cache

    compile_cache.enable()
    import jax

    emit({"phase": "device", **dev,
          "compile_cache_dir": jax.config.jax_compilation_cache_dir})
    failures = run(args.chips)
    for f in failures:
        print(f"[chip_smoke] FAIL {f}", file=sys.stderr)
    if failures:
        return 1
    emit({"ok": True, "device": dev})
    return 0


if __name__ == "__main__":
    sys.exit(main())
