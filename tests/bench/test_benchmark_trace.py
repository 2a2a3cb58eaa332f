"""Trace reduction (benchmark/profile_reduce.py): busy time as the
union of device operations in the window, idle share, top operations
and idle gaps labelled by the benchmark's annotations."""

from __future__ import annotations

import gzip
import os

import bench_helpers  # noqa: F401
import pytest

from benchmark import profile_reduce as pr

HERE = os.path.dirname(os.path.abspath(__file__))
# a 0.4 s traced window of md5-a2-1w.small-zipf on the v5e (my chip
# run, PR 22), with the numbers that run printed from it
RECORDED = os.path.join(HERE, "data", "small_zipf_tpu.xplane.pb.gz")
MS = 1_000_000


def _trace():
    t = pr.Trace()
    # window [0, 100) ms; device 0 busy 10-30 (two overlapping ops) and
    # 50-60 and 95-120 (clipped to 95-100); device 1 busy 10-20
    t.device_ops["/device:TPU:0"] = [
        ("search", 10 * MS, 15 * MS), ("fetch", 20 * MS, 10 * MS),
        ("search", 50 * MS, 10 * MS), ("search", 95 * MS, 25 * MS)]
    t.device_ops["/device:TPU:1"] = [("search", 10 * MS, 10 * MS)]
    t.annotations = [("bench.window", 0, 100 * MS),
                     ("bench.submit", 0, 5 * MS),
                     ("bench.await_answer", 5 * MS, 90 * MS),
                     ("bench.submit", 62 * MS, 2 * MS)]
    return t


def test_busy_is_the_union_clipped_to_the_window():
    r = pr.reduce_trace(_trace(), n_devices=2)
    assert r.window_s == pytest.approx(0.1)
    assert r.busy_s["/device:TPU:0"] == pytest.approx(0.035)
    assert r.busy_s["/device:TPU:1"] == pytest.approx(0.010)
    assert r.mean_busy_s == pytest.approx(0.0225)
    assert r.idle_share == pytest.approx(0.775)


def test_a_device_that_ran_nothing_counts_as_idle():
    r = pr.reduce_trace(_trace(), n_devices=4)
    assert r.mean_busy_s == pytest.approx(0.045 / 4)


def test_top_ops_and_labelled_gaps():
    r = pr.reduce_trace(_trace(), n_devices=2)
    assert r.device_ops[0][0] == "search"
    assert dict(r.device_ops)["search"] == pytest.approx(0.040 / 2)
    # gaps with no device busy: 0-10, 30-50, 60-95; longest first
    assert [round(s, 6) for _, s in r.idle_gaps] == [0.035, 0.02, 0.01]
    # 0-10 is half submit, half await: the shorter span wins the tie
    assert [name for name, _ in r.idle_gaps] == [
        "await_answer", "await_answer", "submit"]
    # a gap that a submit covers as much as the await: the inner wins
    t = _trace()
    t.annotations.append(("bench.submit", 30 * MS, 20 * MS))
    assert pr.reduce_trace(t, 2).idle_gaps[1][0] == "submit"


def test_no_window_or_no_device_op_is_an_error():
    t = _trace()
    t.annotations = t.annotations[1:]
    with pytest.raises(ValueError):
        pr.reduce_trace(t, 2)
    with pytest.raises(ValueError):
        pr.reduce_trace(pr.Trace(annotations=_trace().annotations), 1)


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    path = tmp_path_factory.mktemp("xplane") / "t.xplane.pb"
    with gzip.open(RECORDED, "rb") as src:
        path.write_bytes(src.read())
    return pr.load_xplane(str(path))


def test_recorded_tpu_trace_reduces_as_the_chip_run_printed(recorded):
    assert list(recorded.device_ops) == ["/device:TPU:0"]
    r = pr.reduce_trace(recorded, n_devices=1)
    assert r.window_s == pytest.approx(0.698479158, abs=1e-9)
    assert r.mean_busy_s == pytest.approx(0.03486875, abs=1e-9)
    assert 100 * r.idle_share == pytest.approx(95.00790401536935)
    assert r.device_ops[0] == ("%while.3", pytest.approx(0.034550261))
    assert all(" = " not in name for name, _ in r.device_ops)
    assert r.idle_gaps[0] == ("drain", pytest.approx(0.113551374))
    assert {n for n, _ in r.idle_gaps} <= {"drain", "until_due", "submit"}
