"""The kernels layer's sha256d share of the int32 peak
(``benchmark/layers/sha256d_search_roofline.py``) on synthetic
readings."""

from __future__ import annotations

import bench_helpers  # noqa: F401
import pytest

from benchmark import harness, manifest, md5_search, roofline
from benchmark.profile_reduce import Reduced

KIND = "TPU v5 lite"
PEAK = roofline.vpu_int32_peak(KIND)
NONCE = b"\x01\x02\x03\x04"
# (reference position, ntz) of each synthetic answer: widths 1-3, and
# 7 and 8 nibbles, whose op counts differ by one (the mask test)
ANSWERS = [(4000, 7), (300000, 7), (123456789, 8)]


def _readings(hash_model: str, busy_s: dict) -> harness.Readings:
    cell = manifest.Cell(name="c", chips=len(busy_s),
                         config={"hash_model": hash_model}, mix={},
                         end_to_end=[], per_layer=[])
    valid = [harness.Request(nonce=NONCE, ntz=ntz, sent_ntz=ntz, due=0.0,
                             secret=md5_search.secret_at(pos))
             for pos, ntz in ANSWERS]
    trace = Reduced(window_s=60.0, n_devices=len(busy_s), busy_s=busy_s,
                    device_ops=[], idle_gaps=[])
    return harness.Readings(cell=cell, device={"kind": KIND},
                            requests=valid, valid=valid,
                            credited_hashes=sum(p for p, _ in ANSWERS),
                            counters={}, histograms={}, lags_s=[],
                            trace=trace)


def _ops() -> int:
    return sum(pos * roofline.sha256d_search_ops(len(NONCE), ntz)
               for pos, ntz in ANSWERS)


@pytest.mark.parametrize("busy_s", [{"/device:TPU:0": 2.5},
                                    {"/device:TPU:0": 1.0,
                                     "/device:TPU:1": 0.5}])
def test_reads_the_formula(busy_s):
    read = manifest.reader("sha256d_search_roofline")
    got = read(_readings("sha256d", busy_s))
    want = 100.0 * _ops() / (PEAK * sum(busy_s.values()))
    assert got == pytest.approx(want, rel=1e-12)


@pytest.mark.parametrize("hash_model", ["md5", "sha256"])
def test_reads_none_off_sha256d(hash_model):
    read = manifest.reader("sha256d_search_roofline")
    assert read(_readings(hash_model, {"/device:TPU:0": 1.0})) is None


def test_reads_none_without_a_trace_or_busy_time():
    read = manifest.reader("sha256d_search_roofline")
    r = _readings("sha256d", {"/device:TPU:0": 1.0})
    r.trace = None
    assert read(r) is None
    assert read(_readings("sha256d", {"/device:TPU:0": 0.0})) is None


def test_cannot_pass_100_at_the_peak():
    read = manifest.reader("sha256d_search_roofline")
    at_peak = _ops() / PEAK  # the busy time of a search at the peak
    assert read(_readings("sha256d", {"/device:TPU:0": at_peak})) \
        == pytest.approx(100.0)
    assert read(_readings("sha256d", {"/device:TPU:0": 1.01 * at_peak})) \
        < 100.0
