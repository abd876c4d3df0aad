"""Launch CLI + multi-process bootstrap tests (VERDICT r1 item 6).

The real-process test spawns `python -m paddle_tpu.distributed.launch
--backend cpu --nproc_per_node 2 --devices-per-proc 4` — two OS
processes, each with 4 virtual CPU devices, forming one 8-device
jax.distributed job (the reference's test_dist_base subprocess
pattern)."""
import os
import re
import subprocess
import sys

import pytest


REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PAYLOAD = os.path.join(REPO, "tests", "launch_payload.py")


def _scrubbed_env():
    from paddle_tpu.distributed.launch.main import scrub_backend_env
    env = scrub_backend_env(dict(os.environ))
    # the LAUNCHER process itself must not try for a TPU backend
    # (libtpu is installed, and plain jax tries it first)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    return env


class TestLaunchCLI:
    def test_two_process_train_step(self, tmp_path):
        log_dir = str(tmp_path / "logs")
        proc = subprocess.run(
            [sys.executable, "-m", "paddle_tpu.distributed.launch",
             "--backend", "cpu", "--nproc_per_node", "2",
             "--devices-per-proc", "4", "--log_dir", log_dir, PAYLOAD],
            env=_scrubbed_env(), cwd=REPO, timeout=600,
            capture_output=True, text=True)
        logs = ""
        for rank in (0, 1):
            with open(os.path.join(log_dir, f"workerlog.{rank}")) as f:
                logs += f.read()
        assert proc.returncode == 0, (proc.stdout, proc.stderr, logs)
        losses = re.findall(r"LAUNCH_OK rank=(\d) world=2 "
                            r"loss=([0-9.]+)", logs)
        assert sorted(r for r, _ in losses) == ["0", "1"], logs
        # SPMD: both processes computed the same global loss
        assert losses[0][1] == losses[1][1], logs

    def test_failure_propagates(self, tmp_path):
        bad = tmp_path / "bad.py"
        bad.write_text("import sys; sys.exit(3)\n")
        proc = subprocess.run(
            [sys.executable, "-m", "paddle_tpu.distributed.launch",
             "--backend", "cpu", "--nproc_per_node", "2",
             "--devices-per-proc", "2", str(bad)],
            env=_scrubbed_env(), cwd=REPO, timeout=120,
            capture_output=True, text=True)
        assert proc.returncode == 3

    def test_multinode_requires_master(self):
        proc = subprocess.run(
            [sys.executable, "-m", "paddle_tpu.distributed.launch",
             "--nnodes", "2", PAYLOAD],
            env=_scrubbed_env(), cwd=REPO, timeout=60,
            capture_output=True, text=True)
        assert proc.returncode == 2
        assert "--master" in proc.stderr


class TestBootstrapEnv:
    def test_single_process_noop(self):
        import paddle_tpu.distributed as dist
        g = dist.init_parallel_env()
        assert g is not None
        assert dist.get_rank() == 0

    def test_env_parsing_guard(self, monkeypatch):
        from paddle_tpu.distributed import parallel
        monkeypatch.delenv("PADDLE_MASTER", raising=False)
        monkeypatch.setenv("PADDLE_TRAINERS_NUM", "1")
        assert parallel._maybe_init_jax_distributed() is False


class TestMultiNodeElastic:
    """Coordinated whole-job restart across nodes (VERDICT r2 missing #5;
    ref: fleet/elastic/manager.py:126 ElasticManager). Two node-launchers
    share one elastic rendezvous on localhost; killing one node's worker
    must restart BOTH nodes' workers at epoch 1."""

    def test_two_node_coordinated_restart(self, tmp_path):
        import socket
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            port = s.getsockname()[1]
        master = f"127.0.0.1:{port}"
        payload = os.path.join(REPO, "tests", "elastic_payload.py")
        env = _scrubbed_env()

        def node(rank, log_dir):
            return subprocess.Popen(
                [sys.executable, "-m", "paddle_tpu.distributed.launch",
                 "--backend", "cpu", "--nnodes", "2",
                 "--node_rank", str(rank), "--nproc_per_node", "1",
                 "--master", master, "--max_restarts", "1",
                 "--log_dir", log_dir, payload],
                env=env, cwd=REPO, stdout=subprocess.PIPE,
                stderr=subprocess.STDOUT, text=True)

        d0, d1 = str(tmp_path / "n0"), str(tmp_path / "n1")
        p0 = node(0, d0)
        p1 = node(1, d1)
        out0, _ = p0.communicate(timeout=180)
        out1, _ = p1.communicate(timeout=180)
        logs = ""
        for d, rank in ((d0, 0), (d1, 1)):
            with open(os.path.join(d, f"workerlog.{rank}")) as f:
                logs += f.read()
        assert p0.returncode == 0, (out0, out1, logs)
        assert p1.returncode == 0, (out0, out1, logs)
        # epoch 0: both ranks started, rank 1 crashed
        assert "ELASTIC_START rank=0 epoch=0" in logs
        assert "ELASTIC_CRASH rank=1 epoch=0" in logs
        # the COORDINATED restart: rank 0's healthy 300s sleeper was
        # killed and BOTH ranks completed epoch 1
        assert "ELASTIC_OK rank=0 epoch=1" in logs
        assert "ELASTIC_OK rank=1 epoch=1" in logs
        # launcher announced the coordinated restart
        assert "coordinated restart" in out0 + out1
