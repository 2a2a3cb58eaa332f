"""CPU rehearsal of every cell's code path at a tiny size (python-free:
the cells' own backends, the kernels in interpret mode), the result
line's shape, and a new configuration, mix and per-layer metric found
from files and manifest entries alone."""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys

import bench_helpers as bh
import pytest

CELLS = list(bh.cells())


def _manifest():
    with open(os.path.join(bh.REPO, "BENCHMARK.json")) as f:
        return bh.with_candidates(json.load(f))


def _expected(cell: str, key: str) -> set:
    man = _manifest()
    e2e = {m["name"] for m in man["end_to_end"]
           if cell in m.get("workloads", [cell])}
    if key == "end_to_end":
        return e2e
    return {m["name"] for m in man["per_layer"]
            if m["moves"] in e2e and cell in m.get("workloads", [cell])}


@pytest.fixture
def cpu(tmp_path, monkeypatch):
    root = bh.small_root(tmp_path)
    bh.on_cpu(monkeypatch, root)
    return root


@pytest.mark.parametrize("cell", CELLS)
def test_every_cell_runs_and_is_correct(cpu, capsys, cell):
    chips = next(w["chips"] for w in _manifest()["workloads"]
                 if w["name"] == cell)
    rc, res = bh.run_cell(capsys, cell)
    assert rc == 0 and res["correct"] is True, res
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["metrics"]) == _expected(cell, "end_to_end")
    assert all(m["value"] > 0 for m in res["metrics"].values())
    assert res["device"]["count"] == chips
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(
        res["device"])
    assert list(res)[-1] == "checks"
    if not cell.startswith("md5-batching."):
        # the batching cell's warm-up bursts miss a lane shape now and
        # then, on the chip too (PERF.md section 7)
        assert res["diag"]["compiles_in_window"] == 0


@pytest.mark.parametrize("cell", ["md5-a2-1w.search-d8",
                                  "md5-batching.concurrent-d7"])
def test_traced_run_reports_the_cells_per_layer_metrics(cpu, capsys, cell):
    rc, res = bh.run_cell(capsys, cell, "--trace", "1")
    assert rc == 0 and res["correct"] is True, res
    assert set(res["metrics"]) == _expected(cell, "per_layer")
    assert 0 < res["device"]["busy_s"] <= res["device"]["window_s"]
    assert res["breakdown"]["device_ops"] and res["breakdown"]["idle_gaps"]
    assert len(res["breakdown"]["device_ops"]) <= 10


def test_an_answer_past_the_pools_first_is_credited_the_first(cpu, capsys):
    """A search that skipped candidates (here: a pool that records
    every first solution at position 1) is credited no more."""
    path = os.path.join(cpu, "benchmark", "traffic", "search-d8.json")
    with open(path) as f:
        mix = json.load(f)
    mix["pool"] = [[nonce, 1] for nonce, _ in mix["pool"]]
    with open(path, "w") as f:
        json.dump(mix, f)
    rc, res = bh.run_cell(capsys, "md5-a2-1w.search-d8")
    assert rc == 0 and res["correct"] is True
    assert res["diag"]["credited_hashes"] == res["diag"]["answered"]
    assert res["diag"]["past_first"] == res["diag"]["answered"]


def test_a_spent_pool_ends_the_window(cpu, capsys):
    rc, res = bh.run_cell(capsys, "md5-a2-1w.search-d8", seconds="30")
    assert rc == 0 and res["correct"] is True
    assert res["diag"]["pool_spent"] is True
    assert res["attempted"] == len(bh._SMALL_POOL["pool"])
    assert res["diag"]["past_first"] == 0


def test_a_new_config_mix_and_layer_need_only_new_files(cpu, capsys):
    root = cpu
    bench = os.path.join(root, "benchmark")
    with open(os.path.join(bench, "configs", "md5-py.json"), "w") as f:
        json.dump({"name": "md5-py", "workers": 2, "chips": 1,
                   "hash_model": "md5", "tracing_server": False,
                   "worker": {"Backend": "jax", "BatchSize": 4096},
                   "coordinator": {},
                   "source": "test", "reduced": []}, f)
    with open(os.path.join(bench, "traffic", "burst-d2.json"), "w") as f:
        json.dump({"loop": "open", "rate_hz": 40.0, "n_keys": 8,
                   "zipf_s": 0.5, "nonce_len": 3,
                   "difficulties": [[1, 1.0], [2, 1.0]],
                   "warm_widths": [], "warmup": {"requests": 1}}, f)
    with open(os.path.join(bench, "layers", "answered_share.py"), "w") as f:
        f.write("def read(r):\n"
                "    return 100.0 * len(r.valid) / len(r.requests)\n")
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        man = json.load(f)
    man["configs"].append({"name": "md5-py", "source": "test",
                           "file": "benchmark/configs/md5-py.json",
                           "reduced": [], "why": "test"})
    man["workloads"].append({"name": "md5-py.burst-d2", "config": "md5-py",
                             "traffic": "burst-d2", "chips": 1,
                             "why": "test"})
    for m in man["end_to_end"]:
        if m["name"] in ("solve_p95_s", "solve_p50_s"):
            m["workloads"].append("md5-py.burst-d2")
    assert {"solve_p95_s", "solve_p50_s"} <= {
        m["name"] for m in man["end_to_end"]}
    man["per_layer"].append({"name": "answered_share", "unit": "%",
                             "better": "higher", "source": "host_clock",
                             "layer": "client", "moves": "solve_p95_s",
                             "workloads": ["md5-py.burst-d2"]})
    with open(path, "w") as f:
        json.dump(man, f)
    rc, res = bh.run_cell(capsys, "md5-py.burst-d2")
    assert rc == 0 and res["correct"] is True, res
    assert set(res["metrics"]) == {"setup_s", "solve_p95_s", "solve_p50_s"}
    rc, res = bh.run_cell(capsys, "md5-py.burst-d2", "--trace", "1")
    assert rc == 0 and res["correct"] is True, res
    # the layers whose cell lists leave the new cell out stay out
    assert set(res["metrics"]) == {"answered_share"}
    assert res["metrics"]["answered_share"]["value"] == 100.0


def test_a_second_hash_model_needs_only_new_files(cpu, capsys):
    """A sha256d configuration, a pool mix solved under sha256d and a
    layer, added as files and manifest entries alone, run correct and
    are judged by the sha256d reference.  The jax backend, not python:
    a traced run needs device operations to read."""
    from benchmark import harness, make_pool, manifest, md5_search, reference

    root = cpu
    bench = os.path.join(root, "benchmark")
    with open(os.path.join(bench, "configs", "sha256d-py.json"), "w") as f:
        json.dump({"name": "sha256d-py", "workers": 1, "chips": 1,
                   "hash_model": "sha256d", "tracing_server": False,
                   "worker": {"Backend": "jax", "HashModel": "sha256d",
                              "BatchSize": 4096},
                   "coordinator": {}, "source": "test", "reduced": []}, f)
    made = make_pool.make(
        lambda nonce, ntz: make_pool.brute_first(nonce, ntz, "sha256d"),
        2, 8, 60.0, 4, hash_model="sha256d")
    with open(os.path.join(bench, "traffic", "pool-d2.json"), "w") as f:
        json.dump({"loop": "closed", "nonce_len": 4,
                   "difficulties": [[2, 1.0]], "warm_widths": [0, 1, 2],
                   "pool_ntz": 2, "pool_hash": "sha256d", "strata": 4,
                   "pool": made["pool"], "warm_pool": made["warm_pool"]}, f)
    with open(os.path.join(bench, "layers", "at_first_share.py"), "w") as f:
        f.write("from benchmark import reference\n\n\n"
                "def read(r):\n"
                "    pos = sum(reference.enum_position(q.secret)"
                " for q in r.valid)\n"
                "    return 100.0 * r.credited_hashes / pos\n")
    cell = "sha256d-py.pool-d2"
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        man = json.load(f)
    man["configs"].append({"name": "sha256d-py", "source": "test",
                           "file": "benchmark/configs/sha256d-py.json",
                           "reduced": [], "why": "test"})
    man["workloads"].append({"name": cell, "config": "sha256d-py",
                             "traffic": "pool-d2", "chips": 1,
                             "why": "test"})
    next(m for m in man["end_to_end"]
         if m["name"] == "search_rate")["workloads"].append(cell)
    man["per_layer"].append({"name": "at_first_share", "unit": "%",
                             "better": "higher", "source": "host_clock",
                             "layer": "search driver",
                             "moves": "search_rate", "workloads": [cell]})
    with open(path, "w") as f:
        json.dump(man, f)
    rc, res = bh.run_cell(capsys, cell, seconds="30")
    assert rc == 0 and res["correct"] is True, res
    assert res["diag"]["pool_spent"] is True and res["attempted"] == 8
    assert res["diag"]["past_first"] == 0
    assert set(res["metrics"]) == {"setup_s", "search_rate"}
    assert res["diag"]["compiles_in_window"] == 0
    rc, res = bh.run_cell(capsys, cell, "--trace", "1")
    assert rc == 0 and res["correct"] is True, res
    assert res["metrics"]["at_first_share"]["value"] == 100.0

    # judged by sha256d: a secret that solves md5 alone is wrong
    nonce, pos = bytes.fromhex(made["pool"][0][0]), 1
    while not (reference.solves("md5", nonce, md5_search.secret_at(pos), 2)
               and not reference.solves("sha256d", nonce,
                                        md5_search.secret_at(pos), 2)):
        pos += 1
    secret = md5_search.secret_at(pos)
    req = harness.Request(nonce=nonce, ntz=2, sent_ntz=2, due=0.0,
                          answered=1.0, secret=secret)

    class Answered:
        requests, stray = [req], []

    valid, checks = harness.judge(manifest.load_cell(cell), Answered)
    assert valid == [] and checks["wrong"] == 1


def _run_command(cwd: str, env: dict) -> subprocess.CompletedProcess:
    return subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload",
         "md5-a2-1w.search-d8", "--seed", "3", "--seconds", "1",
         "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=120)


def test_without_a_tpu_it_exits_nonzero_and_prints_no_result():
    out = _run_command(bh.REPO, dict(os.environ, JAX_PLATFORMS="cpu"))
    assert out.returncode != 0
    assert out.stdout.strip() == ""


def test_the_benchmark_alone_exits_nonzero_and_prints_no_result(tmp_path):
    with open(os.path.join(bh.REPO, "BENCHMARK.json")) as f:
        paths = json.load(f)["paths"]
    shutil.copy(os.path.join(bh.REPO, "BENCHMARK.json"), tmp_path)
    for p in paths:
        shutil.copytree(os.path.join(bh.REPO, p), tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    out = _run_command(str(tmp_path), dict(os.environ, JAX_PLATFORMS="cpu"))
    assert out.returncode != 0
    assert out.stdout.strip() == ""
