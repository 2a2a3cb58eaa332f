"""Shared set-up of the benchmark's CPU tests: a copy of the checkout's
benchmark at a size the CPU finishes in seconds, and the look for a
chip replaced, as tests/test_chip_smoke.py does for chip_smoke.py."""

from __future__ import annotations

import json
import os
import shutil
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

CPU_KIND = "cpu-rehearsal"
# entries of the cells not in BENCHMARK.json yet (PERF.md section 7)
CANDIDATES = os.path.join(REPO, "tests", "bench", "data", "candidates.json")
# each open mix at a difficulty the CPU solves in milliseconds; a
# pooled mix gets a pool of its own at SMALL_POOL_NTZ (make_pool.make)
SMALL = {"small-zipf": [[2, 0.5], [3, 0.5]], "concurrent-d7": [[3, 1.0]]}
SMALL_POOL_NTZ = 3


def with_candidates(man: dict) -> dict:
    """``man`` with the entries of ``CANDIDATES`` it lacks; a metric it
    has gains the candidates' cells."""
    with open(CANDIDATES) as f:
        extra = json.load(f)
    for key in ("configs", "workloads", "end_to_end", "per_layer"):
        have = {e["name"]: e for e in man[key]}
        for e in extra[key]:
            if e["name"] not in have:
                man[key].append(e)
            elif "workloads" in have[e["name"]]:
                have[e["name"]]["workloads"] += [
                    w for w in e["workloads"]
                    if w not in have[e["name"]]["workloads"]]
    return man


def cells() -> dict:
    """Every cell of the rehearsal: name -> its manifest entry."""
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return {w["name"]: w for w in with_candidates(json.load(f))[
            "workloads"]}


def small_pool(count: int = 32, strata: int = 4) -> dict:
    """A pool and warm pool at SMALL_POOL_NTZ, made on the CPU by the
    benchmark's own search."""
    from benchmark import make_pool, md5_search

    search = md5_search.FirstSolution(1 << 14)
    made = make_pool.make(search, SMALL_POOL_NTZ, count, 60.0, strata)
    return {"pool": made["pool"], "warm_pool": made["warm_pool"]}


_SMALL_POOL = {}


def small_root(tmp_path) -> str:
    """A copy of BENCHMARK.json, with every candidate cell, and of
    benchmark/ with small shapes."""
    root = str(tmp_path / "checkout")
    os.makedirs(root)
    if not _SMALL_POOL:
        _SMALL_POOL.update(small_pool())
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        man = with_candidates(json.load(f))
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(man, f)
    shutil.copytree(os.path.join(REPO, "benchmark"),
                    os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    for name, diffs in SMALL.items():
        _edit(root, "traffic", name, difficulties=diffs,
              warm_widths=[0, 1, 2])
    _edit(root, "traffic", "search-d8", difficulties=[[SMALL_POOL_NTZ, 1.0]],
          pool_ntz=SMALL_POOL_NTZ, strata=4, warm_widths=[0, 1, 2],
          **_SMALL_POOL)
    for name in ("md5-a2-1w", "md5-mesh4", "md5-batching"):
        path = os.path.join(root, "benchmark", "configs", name + ".json")
        with open(path) as f:
            cfg = json.load(f)
        cfg["worker"].update(BatchSize=1 << 13, PallasInterpret=True)
        if cfg["chips"] == 4:
            cfg["worker"]["MeshDevices"] = 4
        with open(path, "w") as f:
            json.dump(cfg, f)
    return root


def _edit(root: str, kind: str, name: str, **fields) -> None:
    path = os.path.join(root, "benchmark", kind, name + ".json")
    with open(path) as f:
        data = json.load(f)
    data.update(fields)
    with open(path, "w") as f:
        json.dump(data, f)


def on_cpu(monkeypatch, root: str) -> None:
    """Point the harness at ``root`` and let it run on the CPU: the
    chip check, ``auto`` and the lane planner see what a TPU host
    would resolve, with the kernels in interpret mode."""
    import distpow_tpu.backends as backends
    from benchmark import harness, manifest, roofline
    from distpow_tpu.runtime.metrics import REGISTRY
    from distpow_tpu.sched import lanes

    chips = {}

    def device_check(n):
        chips["n"] = n
        return {"platform": "cpu", "kind": CPU_KIND, "count": n}

    monkeypatch.setattr(manifest, "ROOT", root)
    monkeypatch.setattr(harness, "device_check", device_check)
    monkeypatch.setitem(roofline.PEAKS, CPU_KIND, {
        "vpu_int32_ops_per_s": 1e12, "derivation": "CPU rehearsal"})
    monkeypatch.setattr(
        backends, "resolve_auto",
        lambda: "pallas-mesh" if chips.get("n") == 4 else "pallas")
    monkeypatch.setattr(
        lanes, "detect_caps",
        lambda: lanes.LaneCaps(platform="cpu", n_devices=1, interpret=True))
    REGISTRY.reset()


def run_cell(capsys, cell: str, *extra: str, seconds: str = "1.5",
             seed: str = "4294967311"):
    """One in-process run; returns (exit code, result line or None)."""
    from benchmark import harness

    rc = harness.run(["--workload", cell, "--seed", seed,
                      "--seconds", seconds, *extra])
    lines = capsys.readouterr().out.strip().splitlines()
    return rc, (json.loads(lines[-1]) if lines else None)
