"""The benchmark's traffic, reference and pairing, on the CPU."""

from __future__ import annotations

import hashlib
import itertools
import json
import os
import queue
import shutil
import threading
import time

import bench_helpers  # puts the checkout on sys.path
import pytest

from benchmark import harness, reference, traffic

ZIPF = {"loop": "open", "rate_hz": 200.0, "n_keys": 4096, "zipf_s": 0.99,
        "nonce_len": 4, "difficulties": [[4, 0.5], [5, 0.3], [6, 0.2]],
        "warm_widths": [0, 1, 2, 3]}


def _solve(nonce: bytes, ntz: int) -> bytes:
    for n in itertools.count():
        secret = bytes([n & 255]) + (n >> 8).to_bytes(3, "little")
        if hashlib.md5(nonce + secret).hexdigest().endswith("0" * ntz):
            return secret


def test_same_seed_same_schedule_other_seed_disjoint_nonces():
    a = traffic.build_schedule(ZIPF, 2 ** 40 + 7, 5.0)
    b = traffic.build_schedule(ZIPF, 2 ** 40 + 7, 5.0)
    c = traffic.build_schedule(ZIPF, 2 ** 40 + 8, 5.0)
    assert a == b and len(a) > 500
    assert {x.nonce for x in a}.isdisjoint({x.nonce for x in c})
    # Zipf: repeats of hot keys, difficulties from the blend only
    assert len({x.key for x in a}) < len(a)
    assert {x.ntz for x in a} == {4, 5, 6}
    assert all(0 <= x.t < 5.0 for x in a)
    assert [x.t for x in a] == sorted(x.t for x in a)


def test_warmup_domain_never_meets_the_window():
    window = traffic.build_schedule(ZIPF, 11, 5.0)
    warm = {traffic.key_nonce(11, k, 4, "warmup") for k in range(4096)}
    assert warm.isdisjoint({x.nonce for x in window})


def test_closed_loop_draws_a_fresh_key_per_request():
    mix = {"loop": "closed", "nonce_len": 4, "difficulties": [[8, 1.0]]}
    first = list(itertools.islice(traffic.closed_arrivals(mix, 5), 200))
    again = list(itertools.islice(traffic.closed_arrivals(mix, 5), 200))
    assert first == again
    assert len({a.nonce for a in first}) == 200
    assert {a.ntz for a in first} == {8}


def test_reference_accepts_a_true_secret_and_rejects_a_wrong_one():
    nonce = bytes([1, 2, 3, 4])
    secret = _solve(nonce, 3)
    assert reference.solves("md5", nonce, secret, 3)
    assert not reference.solves("md5", nonce, secret[:1] + bytes(
        [secret[1] ^ 1]) + secret[2:], 3)
    assert not reference.solves("md5", nonce, secret, 9)


@pytest.mark.parametrize("secret,position", [
    (bytes([0]), 1),
    (bytes([255]), 256),
    (bytes([3, 1]), 256 + 3 + 1),
    (bytes([0, 0, 0, 1]), 2 ** 16 * 256 + 1),
    # a wrapped chunk (zero top byte): credited past its whole width
    (bytes([7, 5, 0]), (5 + 2 ** 16) * 256 + 8),
])
def test_enum_position(secret, position):
    assert reference.enum_position(secret) == position


def test_open_loop_runner_fires_late_never_skips():
    now = [0.0]
    fired = []

    def submit(arr, due):
        fired.append((arr.key, due))
        now[0] += 0.5  # a submit that stalls the generator

    def sleep(d):
        now[0] += d

    runner = traffic.OpenLoopRunner(submit, clock=lambda: now[0],
                                    sleep=sleep)
    sched = [traffic.Arrival(t=t, key=i, nonce=b"n", ntz=1)
             for i, t in enumerate([0.1, 0.2, 1.0])]
    runner.run(sched, t0=10.0)
    assert [k for k, _ in fired] == [0, 1, 2]
    assert [d for _, d in fired] == [10.1, 10.2, 11.0]
    assert runner.lags_s == pytest.approx([0.0, 0.4, 0.1])


class _FakeTrace:
    def __init__(self, tid):
        self.trace_id = tid


class _FakeTracer:
    def __init__(self):
        self.n = 0

    def create_trace(self):
        self.n += 1
        return _FakeTrace(self.n)


class _Result:
    def __init__(self, nonce, ntz, secret, tid, error=None):
        self.nonce, self.num_trailing_zeros = nonce, ntz
        self.secret, self.error = secret, error
        self.token = None if tid is None else json.dumps(
            {"trace_id": tid, "vc": {}}).encode()


class _FakeClient:
    """powlib's shape: mine() creates a trace, answers come on a queue."""

    def __init__(self):
        self.tracer = _FakeTracer()
        self.notify_queue = queue.Queue()
        self.sent = []

    def mine(self, nonce, ntz):
        trace = self.tracer.create_trace()
        self.sent.append((nonce, ntz, trace.trace_id))


def test_each_answer_pairs_with_its_own_request():
    client = _FakeClient()
    ledger = harness.Ledger(client)
    try:
        nonce = bytes([9, 9, 9, 9])
        first = ledger.send(nonce, 2, 2, due=100.0)
        second = ledger.send(nonce, 2, 2, due=105.0)
        assert [s[2] for s in client.sent] == [first.trace_id,
                                               second.trace_id]
        # the repeat is answered first: it must not take the first's time
        client.notify_queue.put(_Result(nonce, 2, b"\x01", second.trace_id))
        assert second.done.wait(5) and not first.done.is_set()
        t_second = second.answered
        time.sleep(0.01)
        client.notify_queue.put(_Result(nonce, 2, b"\x02", first.trace_id))
        assert first.done.wait(5)
        assert (first.secret, second.secret) == (b"\x02", b"\x01")
        assert first.answered > t_second
        # an answer for no request is a stray, never credited
        client.notify_queue.put(_Result(nonce, 2, b"\x03", 999))
        deadline = time.monotonic() + 5
        while not ledger.stray and time.monotonic() < deadline:
            time.sleep(0.01)
        assert len(ledger.stray) == 1
    finally:
        ledger.close()


def test_latency_is_timed_from_the_due_time_and_missing_is_infinite():
    reqs = [harness.Request(nonce=b"a", ntz=1, sent_ntz=1, due=10.0),
            harness.Request(nonce=b"b", ntz=1, sent_ntz=1, due=10.0),
            harness.Request(nonce=b"c", ntz=1, sent_ntz=1, due=11.0)]
    reqs[0].answered, reqs[2].answered = 10.5, 11.25
    valid = [reqs[0], reqs[2]]
    assert harness.end_to_end("solve_p50_s", reqs, valid, 9.0, 0) == 0.5
    assert harness.end_to_end("solve_p95_s", reqs, valid, 9.0, 0) == float(
        "inf")
    assert harness.end_to_end("search_rate", reqs, valid, 9.0,
                              int(4.5e9)) == pytest.approx(2.0)


def test_percentile_is_nearest_rank():
    vals = list(range(1, 101))
    assert harness.percentile(vals, 0.95) == 95
    assert harness.percentile(vals, 0.5) == 50
    assert harness.percentile([3.0], 0.99) == 3.0


def test_ledger_drain_thread_stops():
    client = _FakeClient()
    ledger = harness.Ledger(client)
    ledger.close()
    assert not ledger._drainer.is_alive()
    assert threading.active_count() >= 1


POOL = [[f"{k:08x}", 1000 - k * 7] for k in range(48)]


def test_a_pool_is_sent_stratified_by_size_and_then_ends():
    mix = {"loop": "closed", "nonce_len": 4, "difficulties": [[8, 1.0]],
           "pool_ntz": 8, "strata": 4, "pool": POOL}
    a = list(traffic.closed_arrivals(mix, 2 ** 40 + 3))
    b = list(traffic.closed_arrivals(mix, 2 ** 40 + 3))
    c = list(traffic.closed_arrivals(mix, 2 ** 40 + 4))
    assert a == b and a != c
    assert sorted(x.nonce.hex() for x in a) == sorted(n for n, _ in POOL)
    assert {x.ntz for x in a} == {8}
    ranked = sorted(p for _, p in POOL)
    stratum = {p: ranked.index(p) // 12 for p in ranked}
    for j in range(12):
        assert sorted(stratum[x.first] for x in a[4 * j:4 * j + 4]) == [
            0, 1, 2, 3]


def test_a_pool_must_fill_its_strata_at_its_own_difficulty(tmp_path):
    path = tmp_path / "m.json"
    good = {"loop": "closed", "nonce_len": 4, "difficulties": [[8, 1.0]],
            "pool_ntz": 8, "pool_hash": "md5", "strata": 4, "pool": POOL}
    path.write_text(json.dumps(good))
    assert traffic.load_mix(str(path)) == good
    for bad in ({"pool": POOL[:47]}, {"difficulties": [[7, 1.0]]}):
        path.write_text(json.dumps({**good, **bad}))
        with pytest.raises(ValueError):
            traffic.load_mix(str(path))


def test_a_pool_must_name_its_hash(tmp_path):
    path = tmp_path / "m.json"
    mix = {"loop": "closed", "nonce_len": 4, "difficulties": [[8, 1.0]],
           "pool_ntz": 8, "strata": 4, "pool": POOL}
    path.write_text(json.dumps(mix))
    with pytest.raises(ValueError, match="pool_hash"):
        traffic.load_mix(str(path))


def _root_with_cell(tmp_path, hash_model: str, traffic_name: str,
                    runs: str = "") -> str:
    """A checkout with one cell: a configuration of ``hash_model``, whose
    worker runs ``runs`` (default the same), on the repository's mix
    ``traffic_name``."""
    root = tmp_path / "checkout"
    (root / "benchmark" / "configs").mkdir(parents=True)
    (root / "benchmark" / "traffic").mkdir()
    src = os.path.join(bench_helpers.REPO, "benchmark", "traffic",
                       traffic_name + ".json")
    shutil.copy(src, root / "benchmark" / "traffic")
    (root / "benchmark" / "configs" / "c.json").write_text(json.dumps(
        {"name": "c", "workers": 1, "chips": 1, "hash_model": hash_model,
         "worker": {"Backend": "python",
                    "HashModel": runs or hash_model}}))
    (root / "BENCHMARK.json").write_text(json.dumps({
        "configs": [{"name": "c", "file": "benchmark/configs/c.json"}],
        "workloads": [{"name": "c." + traffic_name, "config": "c",
                       "traffic": traffic_name, "chips": 1}],
        "end_to_end": [], "per_layer": []}))
    return str(root)


@pytest.mark.parametrize("hash_model,mix", [("sha256d", "search-d8"),
                                            ("md5", "search-d7-sha256d")])
def test_a_cell_whose_pool_was_solved_under_another_hash_is_refused(
        tmp_path, monkeypatch, hash_model, mix):
    from benchmark import manifest

    root = _root_with_cell(tmp_path, hash_model, mix)
    monkeypatch.setattr(manifest, "ROOT", root)
    touched = []
    monkeypatch.setattr(harness, "device_check", touched.append)
    pool_hash = traffic.load_mix(os.path.join(
        root, "benchmark", "traffic", mix + ".json"))["pool_hash"]
    with pytest.raises(ValueError) as err:
        harness.run(["--workload", "c." + mix, "--seed", "1",
                     "--seconds", "1"])
    assert hash_model in str(err.value) and pool_hash in str(err.value)
    assert pool_hash != hash_model and touched == []


@pytest.mark.parametrize("hash_model,runs,mix", [
    ("sha256d", "md5", "small-zipf"), ("md5", "sha256d", "search-d8")])
def test_a_config_whose_worker_runs_another_hash_is_refused(
        tmp_path, monkeypatch, hash_model, runs, mix):
    from benchmark import manifest

    monkeypatch.setattr(manifest, "ROOT",
                        _root_with_cell(tmp_path, hash_model, mix, runs))
    touched = []
    monkeypatch.setattr(harness, "device_check", touched.append)
    with pytest.raises(ValueError) as err:
        harness.run(["--workload", "c." + mix, "--seed", "1",
                     "--seconds", "1"])
    assert "HashModel" in str(err.value) and runs in str(err.value)
    assert touched == []


def test_an_open_loop_mix_takes_any_hash(tmp_path, monkeypatch):
    from benchmark import manifest

    monkeypatch.setattr(manifest, "ROOT",
                        _root_with_cell(tmp_path, "sha256d", "small-zipf"))
    assert manifest.load_cell("c.small-zipf").config["hash_model"] == (
        "sha256d")


@pytest.mark.parametrize("secret,first,credit", [
    (bytes([3, 1]), None, 260),
    (bytes([3, 1]), 1000, 260),
    # past the recorded first: credited with the first alone
    (bytes([3, 1]), 100, 100),
])
def test_credit_never_passes_the_recorded_first(secret, first, credit):
    assert reference.credit(secret, first) == credit
