"""CLI / multi-process tests: config-gen consistency and the full
reference demo scenario as real OS processes on localhost — the closest
analogue of actually deploying the reference's five binaries
(SURVEY.md section 3.5 startup sequence)."""

import os
import subprocess
import sys
import time
from pathlib import Path

import pytest

from distpow_tpu.cli import config_gen
from distpow_tpu.runtime.config import (
    ClientConfig,
    CoordinatorConfig,
    TracingServerConfig,
    WorkerConfig,
    read_json_config,
)

REPO = Path(__file__).resolve().parent.parent


def test_config_gen_consistency(tmp_path):
    config_gen.main(["--config-dir", str(tmp_path), "--workers", "3", "--seed", "7"])
    ts = read_json_config(tmp_path / "tracing_server_config.json", TracingServerConfig)
    coord = read_json_config(tmp_path / "coordinator_config.json", CoordinatorConfig)
    c1 = read_json_config(tmp_path / "client_config.json", ClientConfig)
    c2 = read_json_config(tmp_path / "client2_config.json", ClientConfig)
    w = read_json_config(tmp_path / "worker_config.json", WorkerConfig)

    assert coord.TracerServerAddr == ts.ServerBind
    assert c1.CoordAddr == coord.ClientAPIListenAddr
    assert c2.CoordAddr == coord.ClientAPIListenAddr
    assert c2.ClientID != c1.ClientID
    assert w.CoordAddr == coord.WorkerAPIListenAddr
    assert w.ListenAddr == "PASS VIA COMMAND-LINE"
    assert len(coord.Workers) == 3
    assert len({ts.ServerBind, coord.ClientAPIListenAddr,
                coord.WorkerAPIListenAddr, *coord.Workers}) == 6
    for addr in coord.Workers:
        port = int(addr.rsplit(":", 1)[1])
        assert 1024 <= port < 35535


def test_stock_configs_load():
    assert len(read_json_config(REPO / "config/coordinator_config.json",
                                CoordinatorConfig).Workers) == 4
    assert read_json_config(REPO / "config/worker_config.json",
                            WorkerConfig).Backend == "jax"
    assert read_json_config(REPO / "config/client_config.json",
                            ClientConfig).ClientID == "client1"


def test_difficulty_bits_translation():
    """--difficulty-bits N == --difficulty N/4 (SURVEY.md section 7's
    unit mapping: BASELINE configs speak bits, the protocol's
    numTrailingZeros counts nibbles, worker.go:246-256)."""
    from distpow_tpu.cli.client import difficulty_nibbles

    assert difficulty_nibbles(None, 32) == 8  # --difficulty-bits 32
    assert difficulty_nibbles(8, None) == 8   # == --difficulty 8
    assert difficulty_nibbles(None, None) == 5  # default
    assert difficulty_nibbles(None, 4) == 1
    with pytest.raises(ValueError):
        difficulty_nibbles(None, 30)  # not a whole number of nibbles


@pytest.mark.slow
def test_multiprocess_demo_scenario(tmp_path):
    """Boot tracing server + coordinator + 2 workers + demo client as
    subprocesses, difficulty 2/4 nibbles, python backend (no JAX warmup
    in the workers keeps this fast)."""
    from tests.proc_harness import ProcStack

    stack = ProcStack(tmp_path, workers=2, seed=123)
    try:
        stack.boot_core()
        for i in range(len(stack.coord_cfg["Workers"])):
            stack.boot_worker(i)
        time.sleep(0.5)

        client = stack.spawn(
            "-m", "distpow_tpu.cli.client",
            "--config", stack.config("client_config.json"),
            "--config2", stack.config("client2_config.json"),
            # bits unit: 8 bits = 2 nibbles (exercises the
            # SURVEY §7 difficulty-unit translation end-to-end)
            "--difficulty-bits", "8")
        out, _ = client.communicate(timeout=120)
        assert client.returncode == 0, out
        assert out.count("MineResult") == 4, out

        time.sleep(0.5)
        trace_log = (tmp_path / "trace_output.log").read_text()
        for marker in ("PowlibMiningBegin", "CoordinatorMine", "WorkerMine",
                       "WorkerResult", "CoordinatorSuccess",
                       "PowlibMiningComplete", "[client1]", "[client2]",
                       "[coordinator]", "[worker1]", "[worker2]"):
            assert marker in trace_log, f"missing {marker}"
        shiviz = (tmp_path / "shiviz_output.log").read_text()
        assert shiviz.startswith("(?<host>")
        assert "coordinator {" in shiviz
    finally:
        stack.close()


def test_worker_multihost_bootstrap_subprocess():
    """The --jax-* flags join a jax.distributed cluster before backend
    construction; a 1-process cluster over the virtual CPU mesh proves
    the bootstrap + mesh-search path (multi-host DCN uses the identical
    code with N processes).  Run in a subprocess: jax.distributed state
    is process-global."""
    import socket

    probe = socket.socket()
    probe.bind(("127.0.0.1", 0))
    port = probe.getsockname()[1]
    probe.close()
    code = (
        "import jax; jax.config.update('jax_platforms', 'cpu')\n"
        "from distpow_tpu.cli.worker import maybe_init_distributed\n"
        f"maybe_init_distributed('127.0.0.1:{port}', 1, 0)\n"
        "assert jax.process_count() == 1\n"
        "from distpow_tpu.backends import JaxMeshBackend\n"
        "from distpow_tpu.models import puzzle\n"
        "s = JaxMeshBackend(batch_size=1<<13).search(\n"
        "    b'\\x01\\x02', 2, list(range(256)))\n"
        "assert puzzle.check_secret(b'\\x01\\x02', s, 2)\n"
        "jax.distributed.shutdown()\n"
        "print('MULTIHOST_BOOTSTRAP_OK')\n"
    )
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = str(REPO)
    out = subprocess.run(
        [sys.executable, "-c", code], env=env, cwd=str(REPO),
        capture_output=True, text=True, timeout=240,
    )
    assert "MULTIHOST_BOOTSTRAP_OK" in out.stdout, out.stderr[-2000:]
