"""The comparison that decides ``correct`` has to fail: the control
(each puzzle asked one zero short, the guarantee it breaks) and the
faults a cell can have, planted under the timed path, each turn
``correct`` false.  On the CPU at a tiny size; the control's chip
readings are in PERF.md."""

from __future__ import annotations

import bench_helpers as bh
import pytest

from benchmark import harness


@pytest.fixture
def cpu(tmp_path, monkeypatch):
    root = bh.small_root(tmp_path)
    bh.on_cpu(monkeypatch, root)
    return monkeypatch


@pytest.mark.parametrize("cell", ["md5-a2-1w.search-d8",
                                  "md5-a2-1w.small-zipf",
                                  "md5-batching.concurrent-d7"])
def test_the_control_is_not_correct(cpu, capsys, cell):
    rc, res = bh.run_cell(capsys, cell, "--control", "1")
    assert rc == 0 and res["correct"] is False
    assert res["checks"]["wrong"]["value"] > 0


@pytest.mark.parametrize("cell", ["md5-a2-1w.search-d8",
                                  "md5-batching.concurrent-d7"])
def test_an_answer_altered_where_it_is_produced(cpu, capsys, cell):
    from distpow_tpu.nodes.worker import WorkerRPCHandler

    finish = WorkerRPCHandler._finish_found

    def altered(self, key, secret, *a, **kw):
        return finish(self, key, bytes([secret[0] ^ 0x5A]) + secret[1:],
                      *a, **kw)

    cpu.setattr(WorkerRPCHandler, "_finish_found", altered)
    rc, res = bh.run_cell(capsys, cell)
    assert res is not None and res["correct"] is False


def test_a_step_that_never_finds(cpu, capsys):
    """The search returns its state unchanged: no secret ever comes
    (the worker reports a bare cancellation ACK, which the coordinator
    turns into an error answer)."""
    import importlib

    search = importlib.import_module("distpow_tpu.parallel.search")
    cpu.setattr(search, "persistent_search", lambda *a, **kw: None)
    cpu.setattr(harness, "DRAIN_S", 1.0)
    rc, res = bh.run_cell(capsys, "md5-a2-1w.search-d8", seconds="0.5")
    assert res is not None and res["correct"] is False
    assert res["failed"] > 0
