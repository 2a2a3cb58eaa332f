"""End-to-end protocol tests: client -> coordinator -> workers over real RPC.

In-process analogue of the reference's multi-node-on-localhost validation
(SURVEY.md section 4): every node runs with its own MemorySink tracer so
the causal action sequences — the reference's correctness oracle — can be
asserted directly.  Boot order mirrors cmd/* (coordinator, then workers,
then clients; SURVEY.md section 3.5).
"""

import contextlib
import queue
import threading
import time

import pytest

from distpow_tpu.models import puzzle
from distpow_tpu.nodes import Client, Coordinator, Worker
from distpow_tpu.runtime.config import ClientConfig, CoordinatorConfig, WorkerConfig
from distpow_tpu.runtime.tracing import MemorySink


class Stack:
    """coordinator + N workers + client(s), each with a MemorySink.

    Everything binds on ':0' and real addresses are wired afterwards
    (Coordinator.set_worker_addrs) — no probe-then-rebind port races.
    """

    def __init__(self, n_workers: int, backend: str = "python", difficulty_model="md5",
                 coord_cache_file: str = "", failure_policy: str = "error",
                 failure_probe_secs: float = 0.2, sink_factory=None,
                 worker_extra: dict = None, coord_extra: dict = None):
        sink_factory = sink_factory or (lambda name: MemorySink())
        self._sink_factory = sink_factory
        self.sinks = {"coordinator": sink_factory("coordinator")}
        self.coordinator = Coordinator(
            CoordinatorConfig(
                ClientAPIListenAddr="127.0.0.1:0",
                WorkerAPIListenAddr="127.0.0.1:0",
                Workers=["pending:0"] * n_workers,
                CacheFile=coord_cache_file,
                FailurePolicy=failure_policy,
                FailureProbeSecs=failure_probe_secs,
                **(coord_extra or {}),
            ),
            sink=self.sinks["coordinator"],
        )
        client_addr, worker_api_addr = self.coordinator.initialize_rpcs()

        self.workers = []
        worker_addrs = []
        for i in range(n_workers):
            wid = f"worker{i + 1}"
            self.sinks[wid] = self._sink_factory(wid)
            w = Worker(
                WorkerConfig(
                    WorkerID=wid,
                    ListenAddr="127.0.0.1:0",
                    CoordAddr=worker_api_addr,
                    Backend=backend,
                    HashModel=difficulty_model,
                    **(worker_extra or {}),
                ),
                sink=self.sinks[wid],
            )
            worker_addrs.append(w.initialize_rpcs())
            w.start_forwarder()
            self.workers.append(w)
        self.coordinator.set_worker_addrs(worker_addrs)

        self.coord_client_addr = client_addr
        self.clients = []

    def new_client(self, cid: str, **cfg_extra) -> Client:
        """``cfg_extra``: extra ClientConfig fields (e.g. the powlib
        retry knobs the fault-injection tests tune)."""
        self.sinks[cid] = self._sink_factory(cid)
        c = Client(
            ClientConfig(ClientID=cid, CoordAddr=self.coord_client_addr,
                         **cfg_extra),
            sink=self.sinks[cid],
        )
        c.initialize()
        self.clients.append(c)
        return c

    def close(self):
        for c in self.clients:
            c.close()
        for w in self.workers:
            w.shutdown()
        self.coordinator.shutdown()

    def action_names(self, node: str):
        return [a[1] for a in self.sinks[node].actions()]


@pytest.fixture
def stack1():
    s = Stack(1)
    yield s
    s.close()


@pytest.fixture
def stack4():
    s = Stack(4)
    yield s
    s.close()


def mine_and_wait(client: Client, nonce: bytes, ntz: int, timeout=30):
    client.mine(nonce, ntz)
    return client.notify_queue.get(timeout=timeout)


def test_single_worker_end_to_end(stack1):
    client = stack1.new_client("client1")
    res = mine_and_wait(client, b"\x01\x02\x03\x04", 2)
    assert res.nonce == b"\x01\x02\x03\x04"
    assert res.num_trailing_zeros == 2
    assert puzzle.check_secret(res.nonce, res.secret, 2)
    # the result equals the reference-order first match for the full range
    oracle = puzzle.python_search(b"\x01\x02\x03\x04", 2, list(range(256)))
    assert res.secret == oracle

    # client trace ordering (powlib.go:106-176)
    assert stack1.action_names("client1") == [
        "PowlibMiningBegin", "PowlibMine", "PowlibSuccess", "PowlibMiningComplete",
    ]
    # coordinator protocol spine (coordinator.go:139-298)
    coord = stack1.action_names("coordinator")
    assert coord[0] == "CoordinatorMine"
    assert coord[1] == "CacheMiss"
    assert "CoordinatorWorkerMine" in coord
    assert "CoordinatorWorkerResult" in coord
    assert "CoordinatorWorkerCancel" in coord
    assert coord[-1] == "CoordinatorSuccess"
    # CacheAdd happens when the worker result arrives
    assert "CacheAdd" in coord
    # worker: Mine -> (CacheMiss) -> Result -> Cancel last (worker.go:375-387)
    wk = stack1.action_names("worker1")
    assert wk[0] == "WorkerMine"
    assert "WorkerResult" in wk
    assert wk[-1] == "WorkerCancel"
    assert wk.index("WorkerResult") < wk.index("WorkerCancel")


def test_four_workers_partition_and_ledger(stack4):
    client = stack4.new_client("client1")
    res = mine_and_wait(client, b"\x05\x06\x07\x08", 2)
    assert puzzle.check_secret(res.nonce, res.secret, 2)

    coord = stack4.action_names("coordinator")
    # fan-out recorded one CoordinatorWorkerMine per worker
    assert coord.count("CoordinatorWorkerMine") == 4
    # cancel broadcast >= one per worker (more if late results re-broadcast)
    assert coord.count("CoordinatorWorkerCancel") % 4 == 0
    assert coord.count("CoordinatorWorkerCancel") >= 4
    # every worker saw the Mine and recorded a Cancel; a WorkerResult (if
    # any) precedes the first WorkerCancel after it.  (The strict
    # "WorkerCancel last" only holds without late-result re-broadcasts,
    # whose no-task path appends WorkerCancel + CacheAdd, worker.go:215-221.)
    for i in range(4):
        wk = stack4.action_names(f"worker{i + 1}")
        assert wk[0] == "WorkerMine"
        assert "WorkerCancel" in wk
        if "WorkerResult" in wk:
            r = wk.index("WorkerResult")
            assert "WorkerCancel" in wk[r:]
    # the Mine RPC returned (ledger complete) and the system is idle enough
    # for a second request to run cleanly
    res2 = mine_and_wait(client, b"\x09\x0a", 2)
    assert puzzle.check_secret(res2.nonce, res2.secret, 2)


def test_winning_secret_lands_in_all_caches(stack4):
    client = stack4.new_client("client1")
    res = mine_and_wait(client, b"\x11\x12", 2)
    time.sleep(0.3)  # Found broadcast completes before Mine returns; margin
    for i in range(4):
        entry = stack4.workers[i].handler.result_cache.peek(b"\x11\x12")
        assert entry is not None
        # every worker cache converged to a secret >= the winner in the
        # dominance order (late results may dominate the first winner)
        assert entry.num_trailing_zeros >= 2
    coord_entry = stack4.coordinator.handler.result_cache.peek(b"\x11\x12")
    assert coord_entry is not None


def test_cache_hit_skips_fanout(stack1):
    client = stack1.new_client("client1")
    mine_and_wait(client, b"\x21\x22", 2)
    coord_before = stack1.action_names("coordinator")
    n_mines = coord_before.count("CoordinatorWorkerMine")

    res2 = mine_and_wait(client, b"\x21\x22", 2)
    assert puzzle.check_secret(res2.nonce, res2.secret, 2)
    coord_after = stack1.action_names("coordinator")
    # no new fan-out; the hit path records CacheHit then CoordinatorSuccess
    assert coord_after.count("CoordinatorWorkerMine") == n_mines
    assert coord_after[-2:] == ["CacheHit", "CoordinatorSuccess"]


def test_reassign_dead_worker_at_fanout():
    """FailurePolicy="reassign": a worker that is down when the request
    arrives has its shard reassigned to a live worker, and the request
    still completes (the reference would fail the Mine RPC,
    coordinator.go:196-198; divergence documented in config.py)."""
    s = Stack(2, failure_policy="reassign")
    try:
        s.workers[1].shutdown()  # worker2 is gone before the first request
        client = s.new_client("client1")
        res = mine_and_wait(client, b"\x61\x62", 2, timeout=30)
        assert puzzle.check_secret(res.nonce, res.secret, 2)
        coord = s.action_names("coordinator")
        # 2 fan-out attempts + 1 reassignment of the dead worker's shard
        assert coord.count("CoordinatorWorkerMine") == 3
        mines = [a[2]["WorkerByte"] for a in s.sinks["coordinator"].actions()
                 if a[1] == "CoordinatorWorkerMine"]
        assert sorted(mines) == [0, 1, 1]  # shard 1 re-issued
    finally:
        s.close()


def test_reassign_worker_dies_mid_protocol():
    """A worker that dies while mining stops acking; the ledger drops its
    expectations after a failed Found/probe and the Mine still returns."""
    s = Stack(2, failure_policy="reassign")
    try:
        client = s.new_client("client1")
        client.mine(b"\x63\x64", 4)  # ~65K python hashes: slow enough
        time.sleep(0.15)
        s.workers[1].server.shutdown()  # inbound RPCs (Found/Ping) now fail
        res = client.notify_queue.get(timeout=60)
        assert puzzle.check_secret(res.nonce, res.secret, 4)
    finally:
        s.close()


def test_reassign_hung_worker_detected():
    """A hung-but-connected worker (Mine RPC never returns) must be
    detected via the bounded ack timeout and its shard reassigned —
    WITHOUT its timeout ever sitting on the round's critical path: the
    parallel fan-out (ISSUE 5) harvests the hung ack off-path while the
    live worker is already mining (nonce 676927's first difficulty-5
    secret in the live worker's own half is candidate ~7.3M, seconds
    of python hashing, so the round outlives the 1 s ack deadline and
    the reassignment is observable)."""
    s = Stack(2, failure_policy="reassign", failure_probe_secs=0.2)
    s.coordinator.handler._call_timeout = 1.0
    try:
        # worker2's Mine handler hangs forever (process alive, TCP open)
        s.workers[1].handler.Mine = lambda params: time.sleep(3600)
        client = s.new_client("client1")
        res = mine_and_wait(client, b"\x67\x69\x27", 5, timeout=120)
        assert puzzle.check_secret(res.nonce, res.secret, 5)
        mines = [a[2]["WorkerByte"] for a in s.sinks["coordinator"].actions()
                 if a[1] == "CoordinatorWorkerMine"]
        assert sorted(mines) == [0, 1, 1]
    finally:
        s.close()


def test_hung_worker_does_not_block_round_start():
    """Head-of-line proof (ISSUE 5 acceptance): with one fully hung
    worker in the fan-out set, the live workers' round must start and
    complete WITHOUT paying the hung worker's ack timeout — the serial
    fan-out used to block `_call_timeout` before the round even began."""
    s = Stack(3, failure_policy="reassign", failure_probe_secs=0.2)
    try:
        hang = lambda params: time.sleep(3600)  # noqa: E731
        s.workers[2].handler.Mine = hang
        s.workers[2].handler.Found = hang
        s.workers[2].handler.Ping = hang
        client = s.new_client("client1")
        t0 = time.monotonic()
        res = mine_and_wait(client, b"\x6c\x6d", 2, timeout=30)
        elapsed = time.monotonic() - t0
        assert puzzle.check_secret(res.nonce, res.secret, 2)
        # default reassign _call_timeout is 10 s; the serial baseline
        # would spend >= one full timeout inside _assign_shards alone.
        # The parallel path's only hung-worker cost is the SHARED Found
        # deadline during the cancel storm, bounded by one timeout — but
        # fanout->first-result must stay flat
        assert elapsed < s.coordinator.handler._call_timeout + 5.0
        evs = [e for e in __import__(
            "distpow_tpu.runtime.telemetry", fromlist=["RECORDER"]
        ).RECORDER.recent() if e["kind"] == "coord.first_result"]
        assert evs, "no first-result event recorded"
        assert evs[-1]["latency_s"] < 2.0, (
            f"fanout->first-result head-of-line blocked: {evs[-1]}"
        )
    finally:
        s.close()


def test_failed_mine_does_not_leak_task_entry():
    """Every exit path out of the miss protocol must release the task
    queue — a flaky cluster must not grow the coordinator task table."""
    s = Stack(1, failure_policy="reassign", failure_probe_secs=0.1)
    try:
        s.workers[0].shutdown()
        client = s.new_client("client1")
        client.mine(b"\x69\x6a", 2)  # all workers dead -> Mine errors
        # the failure surfaces as an error result (VERDICT r1 item 6),
        # not a silent drop that would leave the client blocked forever
        r = client.notify_queue.get(timeout=10.0)
        assert r.secret is None and r.error is not None
        deadline = time.time() + 5
        while s.coordinator.handler._tasks and time.time() < deadline:
            time.sleep(0.05)
        assert s.coordinator.handler._tasks == {}
    finally:
        s.close()


def test_pallas_mesh_worker_serves_through_protocol():
    """A worker with Backend=pallas-mesh (interpret mode off-TPU, the
    PallasInterpret dev knob) serves a full Mine through the real RPC
    protocol — the kernel mesh path integrated at the node layer."""
    s = Stack(1, backend="pallas-mesh",
              worker_extra={"BatchSize": 1 << 13,
                            "PallasInterpret": True,
                            "WarmupNonceLens": [], "WarmupWidths": []})
    try:
        client = s.new_client("client1")
        res = mine_and_wait(client, b"\x6a\x6b", 2, timeout=240)
        assert res.error is None
        assert puzzle.check_secret(res.nonce, res.secret, 2)
    finally:
        s.close()


def test_worker_compilation_cache_dir(tmp_path):
    """CompilationCacheDir persists XLA compiles across boots: after a
    jax-backend solve, the cache directory holds compiled programs."""
    import jax

    cache_dir = str(tmp_path / "xla_cache")
    s = Stack(1, backend="jax",
              worker_extra={"CompilationCacheDir": cache_dir,
                            "BatchSize": 1 << 12,
                            "WarmupNonceLens": [], "WarmupWidths": []})
    try:
        assert jax.config.jax_compilation_cache_dir == cache_dir
        # CPU-mesh compiles are faster than the production 0.5s
        # persistence threshold; persist everything for the assertion
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        # in-suite, earlier tests may have already compiled (and
        # lru-cached) every program this solve needs — the persistent
        # cache only writes on a FRESH compile, so force one
        jax.clear_caches()
        client = s.new_client("client1")
        res = mine_and_wait(client, b"\x5a\x5b", 2)
        assert puzzle.check_secret(res.nonce, res.secret, 2)
        import os
        assert os.path.isdir(cache_dir) and len(os.listdir(cache_dir)) > 0
    finally:
        # the knob is process-global jax config: restore for later tests
        jax.config.update("jax_compilation_cache_dir", None)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)
        s.close()


def test_orphaned_miner_self_cancels_on_cache_install():
    """A miner whose coordinator abandoned it stops as soon as a
    satisfying secret lands in the worker cache, delivering that secret
    as its result instead of burning the backend forever."""
    import queue as q

    from distpow_tpu.backends import PythonBackend
    from distpow_tpu.nodes.worker import WorkerRPCHandler
    from distpow_tpu.runtime.tracing import MemorySink, Tracer

    tracer = Tracer("workerX", MemorySink())
    rq: "q.Queue" = q.Queue()
    h = WorkerRPCHandler(tracer, rq, PythonBackend())
    trace = tracer.create_trace()
    token = trace.generate_token()
    from distpow_tpu.runtime.tracing import encode_token

    # the first difficulty-6 secret of nonce 090910 is candidate
    # ~67M of the enumeration (tens of seconds of python hashing): the
    # miner must exit via the cache install, not by finding a secret
    h.Mine({"nonce": [9, 9, 0x10], "num_trailing_zeros": 6,
            "worker_byte": 0, "worker_bits": 0,
            "token": encode_token(token)})
    time.sleep(0.2)
    secret = b"\x12\x34"  # any value; dominance only needs ntz >= 6
    h.result_cache.add(b"\x09\x09\x10", 6, secret, None)
    res = rq.get(timeout=15)  # result delivered from the cache
    assert bytes(res["secret"]) == secret
    # the finisher is now blocked awaiting Found; deliver it
    h.Found({"nonce": [9, 9, 0x10], "num_trailing_zeros": 6,
             "worker_byte": 0, "secret": list(secret),
             "token": encode_token(token)})
    ack = rq.get(timeout=5)
    assert ack["secret"] is None


def test_error_policy_is_reference_parity():
    """Default FailurePolicy="error": worker failure fails the Mine."""
    s = Stack(2)  # default error policy
    try:
        s.workers[1].shutdown()
        client = s.new_client("client1")
        client.mine(b"\x65\x66", 2)
        # powlib surfaces the coordinator-side RPC error; with the
        # busy-retry dial (coordinator.go:169-172) the request never
        # completes — assert no result arrives within a short window
        with pytest.raises(queue.Empty):
            client.notify_queue.get(timeout=1.0)
    finally:
        s.close()


def test_coordinator_cache_resume_across_restart(tmp_path):
    """Checkpoint/resume at the node level: a restarted coordinator
    serves a previously-solved nonce from its journal without re-mining
    (the reference restarts cold, coordinator.go:105-108)."""
    cache_file = str(tmp_path / "coord_cache.jsonl")
    s1 = Stack(1, coord_cache_file=cache_file)
    c1 = s1.new_client("client1")
    r1 = mine_and_wait(c1, b"\x42\x43", 2)
    s1.close()

    s2 = Stack(1, coord_cache_file=cache_file)
    c2 = s2.new_client("client1")
    r2 = mine_and_wait(c2, b"\x42\x43", 2)
    assert r2.secret == r1.secret
    coord = s2.action_names("coordinator")
    # pure cache hit: no fan-out after restart
    assert coord.count("CoordinatorWorkerMine") == 0
    assert "CacheHit" in coord
    s2.close()


def test_dominance_supersede_demo_scenario(stack1):
    # the reference demo's interesting pair: same nonce at difficulty 2
    # then 3 (cmd/client/main.go:46-51 uses 5 then 7) — a cached 2-zeros
    # secret must NOT satisfy the 3-zeros request, whose result then
    # replaces it (coordinator.go:403,436)
    client = stack1.new_client("client1")
    nonce = b"\x02\x02\x02\x02"
    r1 = mine_and_wait(client, nonce, 2)
    r2 = mine_and_wait(client, nonce, 3)
    assert puzzle.check_secret(nonce, r2.secret, 3)
    coord = stack1.action_names("coordinator")
    # second request missed (2 < 3) and re-mined
    assert coord.count("CoordinatorWorkerMine") == 2
    entry = stack1.coordinator.handler.result_cache.peek(nonce)
    assert entry.num_trailing_zeros >= 3
    # the lower-difficulty entry was removed in favor of the higher one
    assert "CacheRemove" in coord


def test_two_clients_concurrent_demo(stack4):
    # the reference's built-in smoke scenario: two clients, four requests,
    # including a repeated nonce at increasing difficulty
    # (cmd/client/main.go:40-60)
    c1 = stack4.new_client("client1")
    c2 = stack4.new_client("client2")
    c1.mine(b"\x01\x02\x03\x04", 3)
    c1.mine(b"\x05\x06\x07\x08", 2)
    c2.mine(b"\x02\x02\x02\x02", 2)
    c2.mine(b"\x02\x02\x02\x02", 3)

    results = []
    for _ in range(4):
        got = None
        for c in (c1, c2):
            try:
                got = c.notify_queue.get(timeout=0.05)
                break
            except queue.Empty:
                continue
        if got is None:
            time.sleep(0.1)
            continue
        results.append(got)
    deadline = time.time() + 60
    while len(results) < 4 and time.time() < deadline:
        for c in (c1, c2):
            try:
                results.append(c.notify_queue.get(timeout=0.2))
            except queue.Empty:
                pass
    assert len(results) == 4
    for r in results:
        assert puzzle.check_secret(r.nonce, r.secret, r.num_trailing_zeros)


def test_late_result_rebroadcast_via_warm_caches(stack4):
    # Warm every worker cache, then issue the same puzzle again: all four
    # workers answer from cache immediately -> one winner + three late
    # results -> the coordinator re-broadcasts Found per late result and
    # drains N acks each (coordinator.go:237-280)
    client = stack4.new_client("client1")
    nonce = b"\x31\x32"
    mine_and_wait(client, nonce, 2)
    time.sleep(0.3)

    # clear the coordinator cache so the request fans out again, but keep
    # worker caches warm
    stack4.coordinator.handler.result_cache._entries.clear()
    res = mine_and_wait(client, nonce, 2)
    assert puzzle.check_secret(nonce, res.secret, 2)
    coord = stack4.action_names("coordinator")
    # at least one late CoordinatorWorkerResult beyond the winner
    assert coord.count("CoordinatorWorkerResult") >= 2
    # re-broadcast rounds: cancels are a multiple of 4 and > 4
    assert coord.count("CoordinatorWorkerCancel") % 4 == 0
    assert coord.count("CoordinatorWorkerCancel") > 4
    # ledger completed: follow-up request still works
    res3 = mine_and_wait(client, b"\x41\x42", 2)
    assert puzzle.check_secret(b"\x41\x42", res3.secret, 2)


def test_duplicate_concurrent_mine_same_key(stack1):
    # documented fix for coordinator.go:376-381: two concurrent Mine
    # requests for the same (nonce, zeros) must both complete
    client = stack1.new_client("client1")
    nonce = b"\x51\x52"
    client.mine(nonce, 3)
    client.mine(nonce, 3)
    r1 = client.notify_queue.get(timeout=60)
    r2 = client.notify_queue.get(timeout=60)
    for r in (r1, r2):
        assert puzzle.check_secret(nonce, r.secret, 3)


def test_worker_cache_hit_path_trace(stack1):
    client = stack1.new_client("client1")
    nonce = b"\x61\x62"
    mine_and_wait(client, nonce, 2)
    time.sleep(0.2)
    # clear coordinator cache; worker cache stays warm -> miner cache-hit
    # path (worker.go:260-299): CacheHit then WorkerResult then WorkerCancel
    stack1.coordinator.handler.result_cache._entries.clear()
    mine_and_wait(client, nonce, 2)
    wk = stack1.action_names("worker1")
    hit = wk.index("CacheHit")
    assert "WorkerResult" in wk[hit:]
    assert wk[-1] == "WorkerCancel"


def test_trace_tokens_cross_all_nodes(stack1):
    # one request's trace id must appear at client, coordinator, and worker
    client = stack1.new_client("client1")
    mine_and_wait(client, b"\x71\x72", 2)
    tid = {e["trace_id"] for e in stack1.sinks["client1"].events
           if e["type"] == "action"}
    assert len(tid) == 1
    tid = tid.pop()
    coord_tids = {e["trace_id"] for e in stack1.sinks["coordinator"].events
                  if e["type"] == "action"}
    worker_tids = {e["trace_id"] for e in stack1.sinks["worker1"].events
                   if e["type"] == "action"}
    assert tid in coord_tids and tid in worker_tids


def test_superseded_miner_exits_silently():
    """A repeat Mine for a key whose previous round is still running must
    cancel the zombie miner WITHOUT it emitting nil ACKs — those would be
    routed into the new round's coordinator queue (keyed (nonce, ntz)) and
    either trip the first-message protocol check or drain its ack ledger
    early (ADVICE r1: worker task-table overwrite)."""
    import queue as q

    from distpow_tpu.backends import PythonBackend
    from distpow_tpu.nodes.worker import WorkerRPCHandler
    from distpow_tpu.runtime.tracing import MemorySink, Tracer, encode_token

    tracer = Tracer("workerY", MemorySink())
    rq: "q.Queue" = q.Queue()
    h = WorkerRPCHandler(tracer, rq, PythonBackend())
    token = encode_token(tracer.create_trace().generate_token())

    # round 1: difficulty 10 on the python backend never finishes on its own
    h.Mine({"nonce": [7, 7], "num_trailing_zeros": 10, "worker_byte": 0,
            "worker_bits": 0, "token": token})
    time.sleep(0.2)
    # round 2: same key replaces round 1; its zombie must exit silently
    h.Mine({"nonce": [7, 7], "num_trailing_zeros": 10, "worker_byte": 0,
            "worker_bits": 0, "token": token})
    time.sleep(0.5)
    assert rq.empty(), "superseded miner leaked a message into the queue"

    # the NEW round still works: a cache install stops it and it delivers
    secret = b"\x12\x34"
    h.result_cache.add(b"\x07\x07", 10, secret, None)
    res = rq.get(timeout=15)
    assert bytes(res["secret"]) == secret
    h.Found({"nonce": [7, 7], "num_trailing_zeros": 10, "worker_byte": 0,
             "secret": list(secret), "token": token})
    ack = rq.get(timeout=5)
    assert ack["secret"] is None
    # and nothing further arrives from either round
    time.sleep(0.3)
    assert rq.empty()


def test_round_ids_survive_backward_clock_restart(tmp_path, monkeypatch):
    """A coordinator restart under a spoofed BACKWARD clock step (larger
    than the downtime) must still order new round ids after old ones —
    the persisted restart epoch, not the wall clock, carries the ordering
    (VERDICT r2 weak #6) — and the worker's zombie-vs-live resolution
    (worker.py _task_take) must therefore pop the zombie, not the live
    round."""
    from distpow_tpu.nodes import coordinator as coord_mod
    from distpow_tpu.nodes.worker import TaskRound, WorkerRPCHandler
    from distpow_tpu.runtime.tracing import Tracer

    epoch_path = str(tmp_path / "cache.jsonl.epoch")

    # boot 1, normal clock: a round goes out and its cancel is lost
    e1 = coord_mod.load_restart_epoch(epoch_path)
    rid_zombie = coord_mod.new_round_id(e1)

    # boot 2: the clock has stepped WAY back (before boot) and the fresh
    # process has no in-memory monotonic floor; the persisted epoch must
    # still strictly increase
    monkeypatch.setattr(coord_mod.time, "time", lambda: 1.0)
    monkeypatch.setattr(coord_mod.time, "time_ns", lambda: 1_000)
    monkeypatch.setattr(coord_mod, "_last_round_ns", [0])
    e2 = coord_mod.load_restart_epoch(epoch_path)
    assert e2 > e1
    rid_live = coord_mod.new_round_id(e2)
    assert rid_live > rid_zombie  # epoch dominates the backward clock

    # worker side: a Found tagged with the NEW round id against a zombie
    # entry from the old round pops + supersedes the zombie...
    handler = WorkerRPCHandler(
        Tracer("worker1", MemorySink()), queue.Queue(), backend=None
    )
    key = (b"\x01", 2)
    zombie = TaskRound(rid_zombie)
    handler._task_set(key, zombie)
    assert handler._task_take(key, rid_live) is None
    assert zombie.superseded and zombie.ev.is_set()
    # ...while a stale Found tagged with the OLD id must not disturb the
    # live round
    live = TaskRound(rid_live)
    handler._task_set(key, live)
    assert handler._task_take(key, rid_zombie) is None
    assert not live.superseded
    assert handler._task_get(key) is live

    # mixed-format window: a pre-epoch 16-char id (bare time_ns hex)
    # held by a long-lived worker must order BELOW any epoch-prefixed id
    # (worker.py _rid_order pads it as epoch 0)
    old_format = f"{123_456_789_000:016x}"
    legacy = TaskRound(old_format)
    handler._task_set(key, legacy)
    assert handler._task_take(key, rid_live) is None
    assert legacy.superseded


@pytest.mark.slow
def test_coordinator_restart_mid_mine(tmp_path):
    """Fault injection (VERDICT r1 items 5+6): the coordinator dies while
    a worker is mining and comes back on the same ports.  The client must
    OBSERVE the failure (error result, not a silent hang), the worker's
    forwarder must re-dial and deliver its result to the restarted
    coordinator (journal-backed cache), and a client retry must complete."""
    from distpow_tpu.nodes import Coordinator
    from distpow_tpu.runtime.config import CoordinatorConfig

    cache_file = str(tmp_path / "coord_cache.jsonl")
    s = Stack(1, coord_cache_file=cache_file)
    try:
        client = s.new_client("client1")
        nonce = b"\x77\x78"
        # difficulty 5 ~= 1M candidates on the python backend: seconds of
        # mining, plenty of window to kill the coordinator mid-search
        client.mine(nonce, 5)
        time.sleep(0.6)  # fan-out done, worker mining

        old_client_addr = s.coordinator.client_addr
        old_worker_addr = s.coordinator.worker_addr
        worker_addrs = [w.bound_addr for w in s.workers]
        s.coordinator.shutdown()

        # the in-flight Mine must surface as an error result
        r = client.notify_queue.get(timeout=30)
        assert r.error is not None and r.secret is None

        # restart on the same ports (create_server sets SO_REUSEADDR);
        # retry briefly — the worker's re-dial loop targeting this very
        # port can transiently occupy it via a Linux self-connect
        for attempt in range(40):
            try:
                s.coordinator = Coordinator(
                    CoordinatorConfig(
                        ClientAPIListenAddr=old_client_addr,
                        WorkerAPIListenAddr=old_worker_addr,
                        Workers=worker_addrs,
                        CacheFile=cache_file,
                    ),
                    sink=s.sinks["coordinator"],
                )
                s.coordinator.initialize_rpcs()
                break
            except OSError:
                # a half-bound server (first listener ok, second raced)
                # must release its port before the retry
                with contextlib.suppress(Exception):
                    s.coordinator.shutdown()
                if attempt == 39:
                    raise
                time.sleep(0.25)

        # the worker finishes its (never-cancelled) search and the
        # forwarder re-delivers to the restarted coordinator, landing the
        # secret in its journal-backed cache; the retried request then
        # completes (usually as a pure cache hit)
        client2 = s.new_client("client1-retry")
        res = mine_and_wait(client2, nonce, 5, timeout=120)
        assert res.error is None
        assert puzzle.check_secret(nonce, res.secret, 5)
    finally:
        s.close()


def test_round_ids_survive_corrupt_epoch_file(tmp_path, monkeypatch):
    """Epoch durability (VERDICT r3 item 9): a corrupt PRIMARY epoch
    file — torn write, bit rot, truncation to a parseable-but-tiny int —
    must be detected (checksum) and recovered from the .bak replica,
    under a spoofed backward clock so any silent wall-clock fallback
    would order wrong; the worker's zombie-vs-live resolution must
    still pop the zombie."""
    from distpow_tpu.nodes import coordinator as coord_mod
    from distpow_tpu.nodes.worker import TaskRound, WorkerRPCHandler
    from distpow_tpu.runtime.tracing import Tracer

    epoch_path = str(tmp_path / "cache.jsonl.epoch")

    e1 = coord_mod.load_restart_epoch(epoch_path)
    rid_zombie = coord_mod.new_round_id(e1)

    # legacy (pre-checksum) bare-int files must still be accepted
    assert coord_mod._read_epoch_file(epoch_path) == e1
    with open(epoch_path, "w") as fh:
        fh.write(str(e1))
    assert coord_mod._read_epoch_file(epoch_path) == e1

    # corrupt the primary four ways; each must be REJECTED, not parsed
    for garbage in ("17 deadbeef",            # checksum mismatch
                    "not-a-number",           # unparseable
                    str(e1)[:2],              # truncated past the separator:
                                              # bare "17" parses as int but
                                              # sits below the wall-clock
                                              # floor every legacy write had
                    str(e1)[:2] + " bogus"):  # truncated value + junk crc
        with open(epoch_path, "w") as fh:
            fh.write(garbage)
        assert coord_mod._read_epoch_file(epoch_path) is None

    # restart under a backward-stepped clock: recovery must come from
    # the .bak replica, not the clock
    monkeypatch.setattr(coord_mod.time, "time", lambda: 1.0)
    monkeypatch.setattr(coord_mod.time, "time_ns", lambda: 1_000)
    monkeypatch.setattr(coord_mod, "_last_round_ns", [0])
    e2 = coord_mod.load_restart_epoch(epoch_path)
    assert e2 > e1
    rid_live = coord_mod.new_round_id(e2)
    assert rid_live > rid_zombie

    # both replicas corrupt -> loud wall-clock fallback, still functional
    for p in (epoch_path, epoch_path + ".bak"):
        with open(p, "w") as fh:
            fh.write("zz zz")
    e3 = coord_mod.load_restart_epoch(epoch_path)
    assert isinstance(e3, int)
    # and the rewrite healed both replicas (checksummed)
    assert coord_mod._read_epoch_file(epoch_path) == e3
    assert coord_mod._read_epoch_file(epoch_path + ".bak") == e3

    # zombie-vs-live at the worker with the recovered ordering
    handler = WorkerRPCHandler(
        Tracer("worker1", MemorySink()), queue.Queue(), backend=None
    )
    key = (b"\x01", 2)
    zombie = TaskRound(rid_zombie)
    handler._task_set(key, zombie)
    assert handler._task_take(key, rid_live) is None
    assert zombie.superseded and zombie.ev.is_set()


def test_worker_restart_rejoins_service():
    """The full worker recovery cycle (round 4): a dead worker's shard
    is reassigned (requests keep completing), and a REPLACEMENT worker
    booted on the same configured address rejoins fan-out with no
    coordinator change — the reference's static worker list + lazy
    redial contract (coordinator.go:169-172,356-368), which reassign
    must not break."""
    import contextlib

    from distpow_tpu.nodes.worker import Worker
    from distpow_tpu.runtime.config import WorkerConfig

    s = Stack(2, failure_policy="reassign", failure_probe_secs=0.2)
    try:
        dead_addr = s.workers[1].bound_addr
        coord_worker_addr = s.workers[1].config.CoordAddr
        s.workers[1].shutdown()

        client = s.new_client("client1")
        res = mine_and_wait(client, b"\x71\x72", 2, timeout=30)
        assert puzzle.check_secret(res.nonce, res.secret, 2)

        # replacement on the SAME address (retry: the coordinator's
        # redial loop can transiently self-connect the freed port)
        s.sinks["worker2b"] = MemorySink()
        for attempt in range(40):
            try:
                w2b = Worker(
                    WorkerConfig(
                        WorkerID="worker2b",
                        ListenAddr=dead_addr,
                        CoordAddr=coord_worker_addr,
                        Backend="python",
                    ),
                    sink=s.sinks["worker2b"],
                )
                w2b.initialize_rpcs()
                break
            except OSError:
                with contextlib.suppress(Exception):
                    w2b.shutdown()
                time.sleep(0.25)
        else:
            raise AssertionError("could not rebind the dead worker's port")
        w2b.start_forwarder()
        s.workers.append(w2b)  # Stack.close() tears it down

        # a FRESH nonce fans out to the replacement and completes
        res2 = mine_and_wait(client, b"\x73\x74", 2, timeout=30)
        assert puzzle.check_secret(res2.nonce, res2.secret, 2)
        deadline = time.time() + 10
        while time.time() < deadline and not any(
            a[1] == "WorkerMine" for a in s.sinks["worker2b"].actions()
        ):
            time.sleep(0.05)
        assert any(a[1] == "WorkerMine"
                   for a in s.sinks["worker2b"].actions()), \
            "replacement worker never participated in fan-out"
    finally:
        s.close()


def test_hung_worker_with_long_round_completes_after_reap():
    """Review PR 5 regression (reproduced pre-fix): with one fully hung
    worker and a round outliving the ~2 s ping timeout, _reap_dead
    kills the worker (closing its client — which fails the pending
    parallel Mine-ack future) and reassigns its shard; when
    _harvest_inflight later resolves that failed future it must NOT
    re-orphan the shard — the duplicate (worker, shard) task entry owed
    the 2N-ack ledger acks the worker could never send, spinning the
    drain loop forever and hanging the client's Mine RPC."""
    from distpow_tpu.models import puzzle as pz

    class SlowFinder:
        """Holds the round open past the reap window, honoring cancel."""

        def search(self, nonce, difficulty, thread_bytes, cancel_check=None):
            deadline = time.monotonic() + 6.0
            while time.monotonic() < deadline:
                if cancel_check and cancel_check():
                    return None
                time.sleep(0.1)
            return pz.python_search(nonce, difficulty, thread_bytes)

    s = Stack(3, failure_policy="reassign", failure_probe_secs=0.2)
    # ack deadline AFTER the ping-based reap (~2.2 s): the reap must win
    # the race so the harvest sees an already-reassigned shard
    s.coordinator.handler._call_timeout = 4.0
    try:
        hang = lambda params: time.sleep(3600)  # noqa: E731
        s.workers[2].handler.Mine = hang
        s.workers[2].handler.Found = hang
        s.workers[2].handler.Ping = hang
        for w in s.workers[:2]:
            w.handler.backend = SlowFinder()
        client = s.new_client("client1")
        res = mine_and_wait(client, b"\x6e\x6f", 2, timeout=60)
        assert puzzle.check_secret(res.nonce, res.secret, 2)
        mines = [a[2]["WorkerByte"] for a in s.sinks["coordinator"].actions()
                 if a[1] == "CoordinatorWorkerMine"]
        # shard 2: the initial issue + exactly ONE reassignment — a
        # second (harvest-driven) reissue is the ledger-corrupting bug
        assert mines.count(2) == 2, mines
    finally:
        s.close()


def test_backend_auto_resolves_from_hardware():
    """``Backend: "auto"`` resolves to the measured-best backend for
    the hardware at boot (backends/get_backend): on this CPU test mesh
    (8 virtual devices, conftest) that is the jax-mesh backend — on a
    TPU it would be the pallas kernels — and the resolved backend must
    actually serve."""
    import jax

    from distpow_tpu.backends import (
        JaxBackend,
        JaxMeshBackend,
        get_backend,
    )

    backend = get_backend("auto", hash_model="md5", batch_size=1 << 13)
    expected = JaxMeshBackend if len(jax.devices()) > 1 else JaxBackend
    assert isinstance(backend, expected), type(backend)
    secret = backend.search(b"\x61\x62", 2, list(range(256)))
    assert secret == puzzle.python_search(b"\x61\x62", 2, list(range(256)))


def test_backend_auto_raises_when_cpu_was_not_chosen():
    """JAX lands on the CPU when it cannot open the chip (another
    process holding it): ``auto`` must then refuse instead of serving
    from the CPU; the XLA backends need the platform CHOSEN as cpu."""
    import jax

    from distpow_tpu.backends import get_backend

    jax.config.update("jax_platforms", None)
    try:
        with pytest.raises(RuntimeError, match="not chosen as cpu"):
            get_backend("auto", hash_model="md5")
    finally:
        jax.config.update("jax_platforms", "cpu")
    assert get_backend("auto", hash_model="md5").name in ("jax", "jax-mesh")


def _raise_value_error(*args, **kwargs):
    raise ValueError("forced refusal")


def test_pallas_step_fallback_is_counted(monkeypatch):
    """The one-chip ``pallas`` backend counts a width the kernel refuses
    under the same counter as ``pallas-mesh``: it is the same backend
    over a one-device mesh."""
    import distpow_tpu.parallel.mesh_search as mesh_search
    from distpow_tpu.backends import get_backend
    from distpow_tpu.runtime.metrics import REGISTRY

    monkeypatch.setattr(mesh_search, "pallas_mesh_persistent_factory",
                        lambda *a, **k: _raise_value_error)
    before = REGISTRY.get("fallback.pallas_mesh_step")
    b = get_backend("pallas", hash_model="md5", batch_size=1 << 12,
                    interpret=True)
    secret = b.search(b"\x61\x62", 2, list(range(256)))
    assert secret == puzzle.python_search(b"\x61\x62", 2, list(range(256)))
    assert REGISTRY.get("fallback.pallas_mesh_step") > before


@pytest.mark.parametrize("refuse", ["request", "width"])
def test_pallas_mesh_fallback_is_counted(monkeypatch, refuse):
    import distpow_tpu.parallel.mesh_search as mesh_search
    from distpow_tpu.backends import PallasMeshBackend
    from distpow_tpu.runtime.metrics import REGISTRY

    if refuse == "request":
        monkeypatch.setattr(mesh_search, "pallas_mesh_persistent_factory",
                            _raise_value_error)
    else:
        monkeypatch.setattr(mesh_search, "pallas_mesh_persistent_factory",
                            lambda *a, **k: _raise_value_error)
    before = REGISTRY.get("fallback.pallas_mesh_step")
    b = PallasMeshBackend(hash_model="md5", batch_size=1 << 12,
                          mesh_devices=4, interpret=True)
    secret = b.search(b"\x63\x64", 2, list(range(256)))
    assert secret == puzzle.python_search(b"\x63\x64", 2, list(range(256)))
    assert REGISTRY.get("fallback.pallas_mesh_step") > before


def test_lane_demotion_is_counted(monkeypatch):
    from distpow_tpu.models.registry import get_hash_model
    from distpow_tpu.ops.packing import build_tail_spec
    from distpow_tpu.runtime.metrics import REGISTRY
    from distpow_tpu.sched import lanes

    monkeypatch.setattr(lanes, "build_pallas_group_step", _raise_value_error)
    planner = lanes.LanePlanner(
        caps=lanes.LaneCaps(platform="cpu", n_devices=1, interpret=True))
    spec = build_tail_spec(bytes(4), 2, get_hash_model("md5"), b"")
    gdef = ("md5", spec.n_blocks, spec.tb_loc, spec.chunk_locs, 2)
    before = REGISTRY.get("fallback.lane_demotions")
    assert planner.resolve(gdef, 1 << 13) == ("xla", None)
    assert REGISTRY.get("fallback.lane_demotions") == before + 1


def test_warmup_failure_is_counted_and_releases_waiters(monkeypatch):
    from distpow_tpu.backends import JaxBackend
    from distpow_tpu.runtime.metrics import REGISTRY

    monkeypatch.setattr(JaxBackend, "warmup", _raise_value_error)
    before = REGISTRY.get("fallback.warmup_failures")
    s = Stack(1, backend="jax", worker_extra={
        "BatchSize": 1 << 12, "WarmupNonceLens": [4], "WarmupWidths": [1]})
    try:
        assert s.workers[0].warmup_done.wait(30)
        assert REGISTRY.get("fallback.warmup_failures") == before + 1
        assert s.workers[0].handler.warmup_s is None
    finally:
        s.close()


@pytest.mark.parametrize("backend", ["jax", "python"])
def test_stats_report_the_device(backend):
    """Stats (and the boot log) say what the backend serves on."""
    import jax

    s = Stack(1, backend=backend, worker_extra={
        "BatchSize": 1 << 12, "WarmupNonceLens": [], "WarmupWidths": []})
    try:
        assert s.workers[0].warmup_done.wait(5)
        snap = s.workers[0].handler.Stats({})
        if backend == "python":
            assert snap["platform"] is None and snap["device_count"] == 0
        else:
            assert snap["platform"] == "cpu"
            assert snap["device_kind"] == jax.devices()[0].device_kind
            assert snap["device_count"] == len(jax.devices())
    finally:
        s.close()
