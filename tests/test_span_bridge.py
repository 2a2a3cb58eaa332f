"""The span layer's profiler bridge (runtime/spans.py) and the served
path's handle spans: a span opened while a ``jax.profiler`` session
collects is also a ``distpow.<name>`` host event on the device trace's
clock, and the converted record sites still write the ring records they
wrote when they were timed by hand."""

from __future__ import annotations

import glob
import importlib
import json
import os
import sys
import time

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from test_nodes import Stack, mine_and_wait  # noqa: E402

from distpow_tpu.models import puzzle  # noqa: E402
from distpow_tpu.runtime.spans import SPANS, SpanRecorder  # noqa: E402

MS = 1_000_000


def _profiled(tmp_path, body):
    """Run ``body`` under a profiler session; returns the trace's
    ``distpow.*`` host events as {name: [(start ns, dur ns, stats)]}."""
    import jax
    from jax.profiler import ProfileData

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        body()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    out = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("distpow."):
                        out.setdefault(ev.name, []).append(
                            (int(ev.start_ns), int(ev.duration_ns),
                             dict(ev.stats)))
    return out


def test_spans_become_nested_trace_events_as_long_as_their_records(tmp_path):
    rec = SpanRecorder(capacity=16)

    def body():
        with rec.span("worker.solve", trace_id=5, node="w", shard=0):
            time.sleep(0.01)
            seg = rec.span("search.segment", width=4, dispatches=0)
            time.sleep(0.02)
            seg.finish(dispatches=3)
            time.sleep(0.005)

    events = _profiled(tmp_path, body)
    ((s_solve, d_solve, _),) = events["distpow.worker.solve"]
    ((s_seg, d_seg, stats),) = events["distpow.search.segment"]
    assert s_solve < s_seg and s_seg + d_seg < s_solve + d_solve
    assert stats["width"] == 4 and stats["dispatches"] == 3
    ring = {s["name"]: s for s in rec.recent()}
    assert abs(d_solve - ring["worker.solve"]["dur_s"] * 1e9) < MS
    assert abs(d_seg - ring["search.segment"]["dur_s"] * 1e9) < MS
    assert ring["search.segment"]["attrs"] == {"width": 4, "dispatches": 3}


def test_no_event_when_spans_are_off_or_cross_threads(tmp_path):
    off = SpanRecorder(capacity=16)
    off.configure(enabled=False)
    on = SpanRecorder(capacity=16)

    def body():
        with off.span("worker.solve", trace_id=1):
            time.sleep(0.002)
        # begin() spans finish on another thread: ring only
        on.begin("sched.slot", trace_id=2).finish()
        on.record("search.launch", time.time(), 0.001, trace_id=2)

    events = _profiled(tmp_path, body)
    assert events == {}
    assert off.recent() == []
    assert [s["name"] for s in on.recent()] == ["sched.slot", "search.launch"]


def test_no_session_no_mark_and_the_ring_as_before():
    rec = SpanRecorder(capacity=16)
    with rec.span("worker.solve", trace_id=3, node="w") as sp:
        assert sp._mark is None
    h = rec.span("search.poll", trace_id=3)
    h.discard()
    h.finish()  # a discarded span stays unrecorded
    (s,) = rec.recent()
    assert s["name"] == "worker.solve" and s["attrs"] == {}


def test_an_error_inside_a_span_closes_its_event(tmp_path):
    rec = SpanRecorder(capacity=16)

    def body():
        with pytest.raises(ValueError):
            with rec.span("worker.solve", trace_id=9):
                raise ValueError("boom")

    events = _profiled(tmp_path, body)
    ((_, _, stats),) = events["distpow.worker.solve"]
    assert stats["outcome"] == "error:ValueError"
    assert rec.recent()[0]["attrs"] == {"outcome": "error:ValueError"}


# -- the served path's converted sites ----------------------------------------

def _trace_id(res) -> int:
    return int(json.loads(bytes(res.token).decode())["trace_id"])


def test_served_path_records_the_same_spans_and_attrs():
    SPANS.reset()
    s = Stack(1)
    try:
        client = s.new_client("client1")
        res = mine_and_wait(client, b"\x70\x02", 2)
        assert res.error is None
        tid = _trace_id(res)
        deadline = time.monotonic() + 5.0
        while not any(x["name"] == "worker.result_forward"
                      for x in SPANS.spans_for(tid)):
            assert time.monotonic() < deadline
            time.sleep(0.01)
        by = {}
        for x in SPANS.spans_for(tid):
            by.setdefault(x["name"], []).append(x)
        (pm,) = by["powlib.mine"]
        assert pm["attrs"] == {"ntz": 2, "outcome": "ok"}
        assert pm["node"] == "client1"
        (cm,) = by["coord.mine"]
        assert cm["attrs"] == {"path": "miss", "ntz": 2}
        (fan,) = by["coord.fanout"]
        (race,) = by["coord.first_result"]
        rid = fan["attrs"]["round"]
        assert fan["attrs"] == {"round": rid, "nonce": "7002", "ntz": 2,
                                "shards": 1}
        assert race["attrs"] == {"round": rid, "nonce": "7002", "ntz": 2,
                                 "winner_byte": 0}
        # the race holds the fan-out, and the storm starts where it ends
        assert race["ts"] <= fan["ts"] and fan["dur_s"] <= race["dur_s"]
        (storm,) = by["coord.cancel_storm"]
        assert storm["attrs"] == {"round": rid, "nonce": "7002", "ntz": 2,
                                  "late_results": 0}
        assert storm["ts"] == pytest.approx(race["ts"] + race["dur_s"],
                                            abs=0.05)
        fwd = by["worker.result_forward"][0]
        assert fwd["attrs"] == {"worker_byte": 0, "attempts": 1,
                                "kind": "result"}
        assert fwd["node"] == "worker1"
        # the umbrella spans hold the coordinator's
        assert pm["ts"] <= cm["ts"] and cm["dur_s"] <= pm["dur_s"]
    finally:
        s.close()


def test_failed_mine_records_error_outcomes_and_no_round_spans():
    """A Mine that no worker can take: the client and coordinator spans
    carry the error outcome; the round spans, which describe a round
    that got past its fan-out, are not recorded."""
    SPANS.reset()
    s = Stack(1, failure_policy="reassign")
    try:
        s.workers[0].shutdown()
        client = s.new_client("client1", MineRetries=0)
        res = mine_and_wait(client, b"\x71\x01", 1)
        assert res.error is not None
        spans = SPANS.recent()
        (pm,) = [x for x in spans if x["name"] == "powlib.mine"]
        assert pm["attrs"] == {"ntz": 1, "outcome": "error"}
        (cm,) = [x for x in spans if x["name"] == "coord.mine"]
        assert cm["attrs"]["outcome"].startswith("error:")
        assert cm["trace_id"] == pm["trace_id"] != 0
        names = {x["name"] for x in spans}
        assert not names & {"coord.fanout", "coord.first_result",
                            "coord.cancel_storm", "worker.result_forward"}
    finally:
        s.close()


@pytest.mark.parametrize("driver", ["persistent_search", "backend"])
def test_search_drivers_record_segments_and_launch_waits(driver,
                                                         monkeypatch):
    """The search driver records one ``search.segment`` span per width,
    with its dispatch count and the devices its step spans, and
    ``search.poll`` waits with no attributes — called directly and
    through the backend a worker serves with."""
    from distpow_tpu.backends import JaxBackend
    from distpow_tpu.sched import lanes

    # one device, as on a one-chip worker: the mesh lane stays off
    monkeypatch.setattr(lanes, "detect_caps",
                        lambda: lanes.LaneCaps("cpu", 1))
    search_mod = importlib.import_module("distpow_tpu.parallel.search")
    rec = SPANS
    rec.reset()
    nonce = b"\x72\x01\x02\x03"
    kw = dict(batch_size=1 << 10, launch_candidates=1 << 12)
    with rec.bind(777, "w-test"):
        if driver == "backend":
            secret = JaxBackend(batch_size=kw["batch_size"],
                                max_launch=kw["launch_candidates"]).search(
                nonce, 3, list(range(256)))
        else:
            secret = search_mod.persistent_search(
                nonce, 3, list(range(256)), **kw).secret
    assert puzzle.check_secret(nonce, secret, 3)
    spans = rec.spans_for(777)
    segs = [x for x in spans if x["name"] == "search.segment"]
    assert [x["attrs"]["width"] for x in segs] == list(range(len(secret)))
    assert all(x["node"] == "w-test" and x["attrs"]["dispatches"] >= 1
               for x in segs)
    # one device serves every width here (tests/test_mesh_reference.py
    # covers the mesh steps)
    assert all(x["attrs"]["devices"] == 1 for x in segs)
    assert all(x["attrs"] == {} for x in spans if x["name"] == "search.poll")
