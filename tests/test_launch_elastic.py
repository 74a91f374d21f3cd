"""Launcher + elastic manager tests (reference: test_fleet_launch_*.sh,
test_fleet_launch_elastic.sh — localhost multi-process cluster)."""
import os
import subprocess
import sys
import textwrap
import time

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ENV = dict(os.environ, JAX_PLATFORMS="cpu",
           PYTHONPATH=REPO)


def _run_launch(tmp_path, script_body, extra_args, timeout=240):
    script = tmp_path / "trainer.py"
    script.write_text(textwrap.dedent(script_body))
    cmd = [sys.executable, "-m", "paddle_tpu.distributed.launch",
           *extra_args, str(script)]
    return subprocess.run(cmd, env=ENV, cwd=REPO, capture_output=True,
                          text=True, timeout=timeout)


def test_launch_sets_env_contract(tmp_path):
    log_dir = tmp_path / "logs"
    r = _run_launch(tmp_path, """
        import os
        rank = int(os.environ["PADDLE_TRAINER_ID"])
        n = int(os.environ["PADDLE_TRAINERS_NUM"])
        eps = os.environ["PADDLE_TRAINER_ENDPOINTS"].split(",")
        assert len(eps) == n == 2
        assert os.environ["PADDLE_CURRENT_ENDPOINT"] == eps[rank]
        print(f"rank {rank} of {n} OK", flush=True)
        """,
        ["--nproc", "2", "--log_dir", str(log_dir)])
    assert r.returncode == 0, r.stderr
    logs = sorted(os.listdir(log_dir))
    assert logs == ["workerlog.0", "workerlog.1"]
    assert "rank 0 of 2 OK" in (log_dir / "workerlog.0").read_text()


def test_launch_virtual_mesh_devices(tmp_path):
    r = _run_launch(tmp_path, """
        import jax
        assert jax.device_count() == 4, jax.devices()
        print("mesh ok", flush=True)
        """,
        ["--nproc", "1", "--devices_per_proc", "4"])
    assert r.returncode == 0, r.stderr


def test_launch_propagates_failure(tmp_path):
    r = _run_launch(tmp_path, """
        import os, sys
        sys.exit(7 if os.environ["PADDLE_TRAINER_ID"] == "1" else 0)
        """,
        ["--nproc", "2"])
    assert r.returncode == 7


def test_launch_elastic_relaunch(tmp_path):
    marker = tmp_path / "attempts"
    r = _run_launch(tmp_path, f"""
        import os, sys
        marker = {str(marker)!r}
        with open(marker, "a") as f:
            f.write("x")
        attempts = len(open(marker).read())
        sys.exit(101 if attempts < 3 else 0)
        """,
        ["--nproc", "1", "--elastic", "--max_restarts", "5"])
    assert r.returncode == 0, r.stderr
    assert marker.read_text() == "xxx"


def test_elastic_manager_membership(tmp_path):
    from paddle_tpu.distributed.fleet.elastic import (
        ElasticManager, ElasticStatus, FileStore, MemoryStore)
    store = FileStore(str(tmp_path / "store"))
    m1 = ElasticManager("2:3", store, host="a", heartbeat_interval=0.1,
                        ttl=1.0)
    m2 = ElasticManager("2:3", store, host="b", heartbeat_interval=0.1,
                        ttl=1.0)
    m1.register(); m2.register()
    assert m1.wait(timeout=5)
    assert m1.hosts() == ["a", "b"]
    assert m1.watch() == ElasticStatus.HOLD  # steady state

    # scale-out: membership change -> RESTART
    m3 = ElasticManager("2:3", store, host="c", heartbeat_interval=0.1,
                        ttl=1.0)
    m3.register()
    time.sleep(0.3)
    assert m1.watch() == ElasticStatus.RESTART
    assert m1.watch() == ElasticStatus.HOLD  # re-observed, stable again

    # node death: heartbeat stops -> TTL expiry -> below np_min -> HOLD
    m2.deregister(); m3.deregister()
    time.sleep(1.5)
    assert m1.hosts() == ["a"]
    assert m1.watch() == ElasticStatus.HOLD
    m1.exit(completed=True)
    assert m1.hosts() == []


def test_elastic_np_parse():
    from paddle_tpu.distributed.fleet.elastic.manager import _parse_np
    assert _parse_np(2) == (2, 2)
    assert _parse_np("4") == (4, 4)
    assert _parse_np("2:8") == (2, 8)
