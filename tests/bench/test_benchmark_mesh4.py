"""CPU rehearsal of the four-chip cell, ``md5-mesh4.search-d8``, at a
tiny size: one worker whose ``auto`` backend resolves to pallas-mesh
over four virtual devices, the kernel in interpret mode."""

from __future__ import annotations

import json
import os

import bench_helpers as bh
import pytest

CELL = "md5-mesh4.search-d8"


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


def test_traced_run_reports_the_cells_per_layer_metrics(cpu, capsys):
    rc, res = bh.run_cell(capsys, CELL, "--trace", "1")
    assert rc == 0 and res["correct"] is True, res
    assert res["device"]["count"] == 4
    assert "mesh_hash_share" in _per_layer()
    assert set(res["metrics"]) == _per_layer()
    # every width but the one-device probe ran across the mesh
    assert 0 < res["metrics"]["mesh_hash_share"]["value"] < 100
    assert res["diag"]["backends"] == ["pallas-mesh"]


def test_untraced_run_compiles_nothing_in_the_window(cpu, capsys):
    rc, res = bh.run_cell(capsys, CELL)
    assert rc == 0 and res["correct"] is True, res
    assert set(res["metrics"]) == {"setup_s", "search_rate"}
    assert res["diag"]["compiles_in_window"] == 0
