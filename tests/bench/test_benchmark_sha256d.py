"""CPU rehearsal of the sha256d cell, ``sha256d-a2-1w.search-d7-sha256d``,
at a tiny size: the manifest's own configuration and mix, with a small
pool solved under sha256d by hashlib and a 2^13 batch (``small_sha256d.py``),
served by one ``jax`` worker whose step runs sha256d's ``finalize``
stage."""

from __future__ import annotations

import json
import os

import bench_helpers as bh
import pytest
from small_sha256d import sha256d_pool

CELL = "sha256d-a2-1w.search-d7-sha256d"


def _per_layer() -> set:
    with open(os.path.join(bh.REPO, "BENCHMARK.json")) as f:
        man = json.load(f)
    e2e = {m["name"] for m in man["end_to_end"]
           if CELL in m.get("workloads", [CELL])}
    return {m["name"] for m in man["per_layer"]
            if m["moves"] in e2e and CELL in m.get("workloads", [CELL])}


@pytest.fixture
def cpu(tmp_path, monkeypatch):
    root = bh.small_root(tmp_path)
    bh.on_cpu(monkeypatch, root)
    return root


def test_untraced_run_is_correct_at_the_first_solutions(cpu, capsys):
    rc, res = bh.run_cell(capsys, CELL, seconds="30")
    assert rc == 0 and res["correct"] is True, res
    assert res["diag"]["pool_spent"] is True and res["attempted"] == 8
    # every answer is hashlib's reference-order first
    assert res["diag"]["past_first"] == 0
    assert res["diag"]["credited_hashes"] == sum(
        p for _, p in sha256d_pool()["pool"])
    assert set(res["metrics"]) == {"setup_s", "search_rate"}
    assert res["diag"]["compiles_in_window"] == 0
    assert res["diag"]["backends"] == ["jax"]


def test_traced_run_reports_the_cells_per_layer_metrics(cpu, capsys):
    rc, res = bh.run_cell(capsys, CELL, "--trace", "1", seconds="30")
    assert rc == 0 and res["correct"] is True, res
    assert {"sha256d_search_roofline", "finalize_hash_share",
            "overscan_ratio", "device_idle_share.search"} == _per_layer()
    assert set(res["metrics"]) == _per_layer()
    # the composed stage served every candidate
    assert res["metrics"]["finalize_hash_share"]["value"] == 100.0
    assert 0 < res["metrics"]["sha256d_search_roofline"]["value"] < 100
    assert res["diag"]["backends"] == ["jax"]
