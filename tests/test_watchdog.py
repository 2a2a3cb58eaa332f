"""Device-hang watchdog tests (runtime/watchdog.py).

The watchdog exists for a failure mode the Go reference cannot have: an
accelerator dispatch that never returns leaves a worker answering
liveness probes while its Mine task never completes.
These tests cover the monitor itself, the search-driver
instrumentation, and the WorkerConfig plumbing.
"""

import threading
import time

import pytest

from distpow_tpu.runtime.watchdog import EXIT_CODE, WATCHDOG, DeviceWatchdog


@pytest.fixture
def dog():
    d = DeviceWatchdog()
    yield d
    d.stop()


def test_fires_on_stale_active_section(dog):
    fired = []
    dog.start(0.2, on_hang=fired.append)
    with dog.active():
        assert dog.fired.wait(2.0), "no fire despite stale active section"
    assert fired and fired[0] >= 0.2


def test_beats_keep_active_section_alive(dog):
    dog.start(0.3, on_hang=lambda s: None)
    with dog.active():
        for _ in range(6):
            time.sleep(0.1)
            dog.beat()
    assert not dog.fired.is_set()


def test_idle_never_fires(dog):
    # active == 0: nothing drives the device, staleness is meaningless
    dog.start(0.2, on_hang=lambda s: None)
    time.sleep(0.7)
    assert not dog.fired.is_set()


def test_noop_when_not_started(dog):
    dog.beat()
    with dog.active():
        pass
    assert not dog.running


def test_rejects_bad_timeout(dog):
    with pytest.raises(ValueError):
        dog.start(0)


def test_grace_widens_window_for_one_operation(dog):
    # a single long op (a first XLA compile cannot beat) inside grace()
    # must not fire even though it exceeds the base timeout...
    dog.start(0.2, on_hang=lambda s: None)
    with dog.active():
        with dog.grace(5.0):
            time.sleep(0.6)  # 3x the base timeout, under the grace
        assert not dog.fired.is_set()
        # ...and leaving the block restores the normal window
        assert dog.fired.wait(2.0), "base timeout not restored after grace"


def test_grace_still_fires_when_exceeded(dog):
    fired = []
    dog.start(0.1, on_hang=fired.append)
    with dog.active():
        with dog.grace(0.3):
            assert dog.fired.wait(2.0), "hang under grace never detected"
    assert fired and fired[0] >= 0.3


def test_nested_grace_widest_wins(dog):
    dog.start(0.1, on_hang=lambda s: None)
    with dog.active():
        with dog.grace(5.0):
            with dog.grace(0.2):
                # inner narrower grace must not shrink the outer window
                time.sleep(0.5)
            assert not dog.fired.is_set()


def test_inner_grace_does_not_leak_into_outer_block(dog):
    # review r4: a depth-counter implementation kept the inner 900s
    # window active for the rest of the outer block, delaying genuine
    # hang detection 15x
    dog.start(0.1, on_hang=lambda s: None)
    with dog.active():
        with dog.grace(0.3):
            with dog.grace(30.0):
                pass  # wide inner grace exits immediately
            # hang here must be caught by the outer 0.3s grace, not 30s
            assert dog.fired.wait(2.0), "inner grace leaked into outer block"


def test_search_driver_hang_detected():
    """A dispatch whose result never becomes ready must trip the
    watchdog through parallel.search's own instrumentation: the drain's
    poll wait deliberately does not beat."""
    from distpow_tpu.parallel.search import persistent_search

    unblock = threading.Event()

    class HungResult:
        def is_ready(self):  # a dispatch that never completes
            return False

    def builder(vw, extra, target_chunks, segments):
        return (lambda chunk0, prev: HungResult()), target_chunks, \
            target_chunks * segments

    WATCHDOG.start(0.3, on_hang=lambda s: None)
    try:
        t = threading.Thread(
            target=lambda: persistent_search(
                b"\x01", 2, list(range(256)), step_builder=lambda _: builder,
                pipeline_depth=1, batch_size=1 << 10,
                cancel_check=unblock.is_set,
            ),
            daemon=True,
        )
        t.start()
        assert WATCHDOG.fired.wait(3.0), \
            "watchdog did not detect the hung drain"
    finally:
        unblock.set()  # the poll wait honors the cancel and returns
        t.join(timeout=5.0)
        WATCHDOG.stop()


def test_slow_first_launch_compile_not_killed(monkeypatch):
    """A cold layout's FIRST dispatch pays the XLA compile — one gap
    that can far exceed the hang timeout (sha512 unrolled: >22 min
    observed).  The driver wraps that dispatch in a grace window, so an
    armed watchdog must ride out a slow first compile and still serve
    the result."""
    import importlib

    import jax.numpy as jnp

    from distpow_tpu.ops.search_step import SENTINEL

    search_mod = importlib.import_module("distpow_tpu.parallel.search")

    # shrink the grace so the test can also prove it expires (below)
    monkeypatch.setattr(search_mod, "FIRST_COMPILE_GRACE_S", 5.0)

    calls = {"n": 0}

    def builder(vw, extra, target_chunks, segments):
        def step(chunk0, prev):
            calls["n"] += 1
            if calls["n"] == 1:
                time.sleep(0.8)  # "compile": 4x the base timeout
            # nonce 570400 at 3 nibbles: no width-0 solution, and
            # (tb 0, chunk 16) solves
            return jnp.asarray([0 if chunk0 == 16 else SENTINEL, 1],
                               jnp.uint32)

        return step, 1, 1

    WATCHDOG.start(0.2, on_hang=lambda s: None)
    try:
        res = search_mod.persistent_search(
            bytes.fromhex("570400"), 3, list(range(256)),
            step_builder=lambda _: builder, pipeline_depth=1,
            batch_size=1 << 10)
        assert res is not None and res.secret == bytes([0, 16])
        assert not WATCHDOG.fired.is_set(), \
            "watchdog killed a healthy slow first compile"
    finally:
        WATCHDOG.stop()


def test_acquire_release_refcount(dog):
    dog.acquire(5.0)
    dog.acquire(9.0)  # shared; first timeout wins
    assert dog.running and dog._timeout == 5.0
    dog.release()
    assert dog.running, "watchdog stopped while a co-owner remains"
    dog.release()
    assert not dog.running


def test_stop_with_hung_section_does_not_blind_rearm(dog):
    """A section still stuck inside active() across a stop/start cycle
    must not skew the counter and disable a re-armed watchdog."""
    entered, unblock = threading.Event(), threading.Event()

    def hung_section():
        with dog.active():
            entered.set()
            unblock.wait()

    dog.start(5.0, on_hang=lambda s: None)
    t = threading.Thread(target=hung_section, daemon=True)
    t.start()
    assert entered.wait(2.0)
    dog.stop()          # section still inside active()
    unblock.set()       # now it unwinds (paired decrement)
    t.join(timeout=5.0)
    dog.start(0.2, on_hang=lambda s: None)
    with dog.active():
        assert dog.fired.wait(2.0), "re-armed watchdog is blind"


def test_worker_config_arms_watchdog():
    """DeviceHangTimeoutS > 0 on WorkerConfig starts the process
    watchdog at worker boot, and the owning worker's shutdown stops it;
    0 (the default) leaves it off."""
    from tests.test_nodes import Stack

    assert not WATCHDOG.running
    stack = Stack(2, worker_extra={"DeviceHangTimeoutS": 300.0})
    try:
        assert WATCHDOG.running
        assert WATCHDOG._timeout == 300.0
        assert stack.workers[0].handler.Stats({})["watchdog_armed"] is True
        # one armed worker down, the other keeps its protection
        stack.workers[0].shutdown()
        assert WATCHDOG.running
    finally:
        stack.close()
    assert not WATCHDOG.running, "last armed worker's shutdown must disarm"
    # default config: off (reference parity)
    stack = Stack(1)
    try:
        assert not WATCHDOG.running
    finally:
        stack.close()


@pytest.mark.slow
def test_hung_worker_process_dies_and_request_completes(tmp_path):
    """The full recovery chain at the process level: a worker whose
    backend wedges (tests/hang_worker_child.py — the stand-in for a TPU
    dispatch that never returns) still answers Ping, so ONLY the
    watchdog can unblock the protocol: it kills the worker with
    EXIT_CODE, the coordinator's FailurePolicy="reassign" prunes it,
    and the healthy worker completes every client request."""
    from tests.proc_harness import ProcStack

    stack = ProcStack(
        tmp_path, workers=2, seed=777,
        coord_overrides={"FailurePolicy": "reassign",
                         "FailureProbeSecs": 0.5},
    )
    try:
        stack.boot_core()
        hang_child = stack.spawn(
            "tests/hang_worker_child.py", stack.coord_cfg["Workers"][0],
            stack.coord_cfg["WorkerAPIListenAddr"],
        )
        stack.boot_worker(1)  # blocks on its "serving ... RPCs" line
        stack.wait_for_line(hang_child, "HANG_WORKER_READY")

        client = stack.spawn(
            "-m", "distpow_tpu.cli.client",
            "--config", stack.config("client_config.json"),
            "--config2", stack.config("client2_config.json"),
            "--difficulty", "2",
        )
        out, _ = client.communicate(timeout=120)
        assert client.returncode == 0, out
        assert out.count("MineResult") == 4, out

        # the zombie died by watchdog (exit 43), not by our teardown
        rc = hang_child.wait(timeout=30)
        assert rc == EXIT_CODE, (rc, hang_child.stdout.read())
    finally:
        stack.close()


def test_section_in_flight_before_arming_is_covered(dog):
    """A device section entered while the watchdog is STOPPED must still
    count once a later start()/acquire() arms the monitor (advisor r3:
    the old early-return in active() left such sections permanently
    invisible — e.g. a search already dispatching when a worker boots
    and arms, or when bench/sweep call start())."""
    entered = threading.Event()
    release = threading.Event()

    def hung_section():
        with dog.active():          # watchdog not running yet
            entered.set()
            release.wait(5.0)       # simulates a dispatch that never beats

    t = threading.Thread(target=hung_section, daemon=True)
    t.start()
    assert entered.wait(2.0)
    assert dog._active == 1         # counted even while stopped
    dog.start(0.2, on_hang=lambda s: None)
    assert dog.fired.wait(2.0), \
        "pre-armed in-flight section never detected as hung"
    release.set()
    t.join(2.0)
