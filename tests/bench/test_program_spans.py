"""The device's idle time split by the program's spans
(benchmark/program_spans.py): inside ``worker.solve`` (the search
driver's) and outside it (the host path's), and idle gaps named after
the innermost ``bench.*`` or ``distpow.*`` span."""

from __future__ import annotations

import glob

import bench_helpers  # noqa: F401
import pytest

from benchmark import profile_reduce as pr
from benchmark import program_spans as ps

MS = 1_000_000


def _trace():
    """The window [0, 100) ms; device 0 busy 10-30, 50-60 and 95-100;
    device 1 busy 10-20 (as in test_benchmark_trace.py)."""
    t = pr.Trace()
    t.device_ops["/device:TPU:0"] = [
        ("search", 10 * MS, 15 * MS), ("fetch", 20 * MS, 10 * MS),
        ("search", 50 * MS, 10 * MS), ("search", 95 * MS, 25 * MS)]
    t.device_ops["/device:TPU:1"] = [("search", 10 * MS, 10 * MS)]
    t.annotations = [("bench.window", 0, 100 * MS),
                     ("bench.submit", 0, 5 * MS),
                     ("bench.await_answer", 5 * MS, 90 * MS),
                     ("bench.submit", 62 * MS, 2 * MS)]
    return t


def _spans():
    """A request (6-94 ms) whose solve runs 8-52 ms in two segments,
    then forwards its result; a second worker's solve overlaps it."""
    return [("distpow.powlib.mine", 6 * MS, 88 * MS),
            ("distpow.worker.solve", 8 * MS, 44 * MS),
            ("distpow.search.segment", 8 * MS, 2 * MS),
            ("distpow.search.segment", 10 * MS, 42 * MS),
            ("distpow.worker.result_forward", 52 * MS, 3 * MS),
            ("distpow.worker.solve", 48 * MS, 14 * MS)]


@pytest.mark.parametrize("n_devices", [1, 2])
def test_the_two_shares_add_up_to_the_idle_share(n_devices):
    t = _trace()
    if n_devices == 1:
        del t.device_ops["/device:TPU:1"]
    r = pr.reduce_trace(t, n_devices)
    sp = ps.split(t, _spans(), n_devices)
    assert sp.idle_share == pytest.approx(r.idle_share, abs=1e-12)
    assert sp.search_driver_share + sp.host_path_share == \
        pytest.approx(sp.idle_share, abs=1e-12)
    # the solves cover 8-62 ms (54 ms); device 0 is busy 30 ms of it
    # (10-30, 50-60), device 1 10 ms (10-20)
    driver = {1: (54 - 30) / 100, 2: ((54 - 30) + (54 - 10)) / 200}
    assert sp.search_driver_share == pytest.approx(driver[n_devices])
    assert sp.host_path_share == pytest.approx(
        r.idle_share - driver[n_devices])


def test_a_device_that_ran_nothing_is_idle_inside_every_solve():
    t = _trace()
    sp = ps.split(t, _spans(), n_devices=4)
    assert sp.idle_share == pytest.approx(pr.reduce_trace(t, 4).idle_share)
    assert sp.search_driver_share == pytest.approx(
        ((54 - 30) + (54 - 10) + 2 * 54) / 400)


def test_without_program_spans_all_idle_time_is_the_host_paths():
    t = _trace()
    sp = ps.split(t, [], n_devices=2)
    assert sp.search_driver_share == 0
    assert sp.host_path_share == pytest.approx(pr.reduce_trace(t, 2)
                                               .idle_share)


def test_idle_time_goes_to_the_innermost_open_span():
    t = _trace()
    by = ps.idle_by_span(t, _spans(), n_devices=2)
    assert sum(by.values()) == pytest.approx(pr.reduce_trace(t, 2)
                                             .idle_share)
    # device 0 idle 0-10, 30-50, 60-95; device 1 idle 0-10, 20-100
    assert by == pytest.approx({
        "none": (7 + 12) / 200, "powlib.mine": (34 + 34) / 200,
        "search.segment": (20 + 30) / 200, "worker.solve": (4 + 11) / 200,
        "worker.result_forward": 3 / 200})


def test_gaps_are_named_after_the_innermost_program_span():
    gaps = ps.idle_gaps(_trace(), _spans())
    # gaps: 60-95 (35 ms), 30-50 (20), 0-10 (10); longest first
    assert [round(s, 6) for _, s, _ in gaps] == [0.035, 0.02, 0.01]
    names = [name for name, _, _ in gaps]
    # 60-95: the await covers it all, powlib.mine 34 ms of it; 30-50:
    # the await, powlib.mine, the solve and its second segment cover it
    # all, and the segment is the shortest; 0-10: submit and await
    # cover 5 ms each, and submit is the shorter
    assert names == ["await_answer", "search.segment", "submit"]
    assert ps.label(_spans(), 52 * MS, 55 * MS) == "worker.result_forward"
    assert ps.label([], 0, MS) == "none"
    assert [round(st, 6) for _, _, st in gaps] == [0.06, 0.03, 0.0]
    assert [n for n, _, _ in ps.idle_gaps(_trace(), _spans(),
                                          min_s=0.015)] == names[:2]


def test_load_reads_the_program_spans_of_a_recorded_trace(tmp_path):
    import jax
    from jax.profiler import TraceAnnotation

    from distpow_tpu.runtime.spans import SpanRecorder

    rec = SpanRecorder(capacity=4)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with TraceAnnotation("bench.window"):
            with rec.span("worker.solve", trace_id=1):
                jax.numpy.ones(8).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    trace, spans = ps.load(path, platform="cpu")
    assert [name for name, _, _ in spans] == ["distpow.worker.solve"]
    assert [a[0] for a in trace.annotations] == ["bench.window"]
    (_, s, d), = spans
    lo, hi = pr.window_of(trace)
    assert lo <= s and s + d <= hi

