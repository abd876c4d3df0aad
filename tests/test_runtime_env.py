"""What a process is told from outside: where its compiled programs are
kept, which device it may open, and what its children may open
(`paddle_tpu.utils.runtime_env`, `core.device`, `launch.scrub_backend_env`,
`benchmarks/run.py`'s platform check)."""
import os
import subprocess
import sys

import jax
import pytest

import paddle_tpu as pt
from paddle_tpu.utils import runtime_env

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class TestCompileCache:
    @pytest.fixture
    def cache_config(self):
        before = jax.config.jax_compilation_cache_dir
        yield
        jax.config.update("jax_compilation_cache_dir", before)

    def test_env_dir_is_left_to_jax(self, monkeypatch, cache_config):
        jax.config.update("jax_compilation_cache_dir", "/untouched")
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
        assert runtime_env.use_compile_cache() == "/some/dir"
        # nothing was set in code: jax read the variable itself at import
        assert jax.config.jax_compilation_cache_dir == "/untouched"

    def test_default_is_a_fixed_dir_in_the_checkout(self, monkeypatch,
                                                    cache_config):
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        want = os.path.join(REPO, ".jax_cache")
        assert runtime_env.use_compile_cache() == want
        assert jax.config.jax_compilation_cache_dir == want
        assert runtime_env.default_compile_cache_dir() == want

    def test_a_process_started_with_the_variable_caches_there(self, tmp_path):
        """End to end in a fresh interpreter, as the chip tool would set
        it: the program's compile lands in the directory the variable
        names and nowhere in the checkout."""
        env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=str(tmp_path),
                   JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
        code = (
            "import jax, jax.numpy as jnp\n"
            "from paddle_tpu.utils.runtime_env import use_compile_cache\n"
            "print(use_compile_cache())\n"
            "jax.config.update("
            "'jax_persistent_cache_min_compile_time_secs', 0)\n"
            "jax.config.update("
            "'jax_persistent_cache_min_entry_size_bytes', 0)\n"
            "jax.jit(lambda x: jnp.sin(x) @ x.T)(jnp.ones((64, 64)))"
            ".block_until_ready()\n")
        out = subprocess.run([sys.executable, "-c", code], env=env,
                             capture_output=True, text=True, timeout=300)
        assert out.returncode == 0, out.stderr
        assert out.stdout.strip() == str(tmp_path)
        assert any(tmp_path.iterdir()), "nothing was cached there"

    def test_no_other_code_sets_the_cache_dir(self):
        """One function owns the setting: a second
        `jax.config.update("jax_compilation_cache_dir", ...)` anywhere
        would override a directory placed from outside."""
        hits = []
        for top in ("paddle_tpu", "tools", "tests", "examples"):
            for root, _dirs, files in os.walk(os.path.join(REPO, top)):
                hits += [os.path.join(root, f) for f in files
                         if f.endswith(".py")]
        hits += [os.path.join(REPO, f)
                 for f in ("chip_smoke.py", "__graft_entry__.py")]
        needle = '"jax_compilation' + '_cache_dir"'
        setters = set()
        for path in hits:
            with open(path, encoding="utf-8") as f:
                text = f.read()
            if "update(" + needle in text.replace("\n", "").replace(" ", ""):
                setters.add(os.path.relpath(path, REPO))
        # tests restore the value they found; only runtime_env chooses one
        assert setters <= {"paddle_tpu/utils/runtime_env.py",
                           "tests/test_runtime_env.py"}, setters


class TestChildEnv:
    def test_children_inherit_cpu_only_inside_the_block(self, monkeypatch):
        monkeypatch.setenv("JAX_PLATFORMS", "tpu")
        probe = [sys.executable, "-c",
                 "import os; print(os.environ.get('JAX_PLATFORMS'))"]
        with runtime_env.cpu_only_child_env():
            inside = subprocess.run(probe, capture_output=True, text=True)
        assert inside.stdout.strip() == "cpu"
        assert os.environ["JAX_PLATFORMS"] == "tpu"     # restored
        monkeypatch.delenv("JAX_PLATFORMS")
        with runtime_env.cpu_only_child_env():
            assert os.environ["JAX_PLATFORMS"] == "cpu"
        assert "JAX_PLATFORMS" not in os.environ

    def test_scrub_backend_env_keeps_the_cache_dir(self):
        from paddle_tpu.distributed.launch.main import scrub_backend_env
        env = scrub_backend_env({
            "JAX_COMPILATION_CACHE_DIR": "/some/dir",
            "JAX_PLATFORMS": "tpu", "XLA_FLAGS": "--x", "TPU_NAME": "t",
            "LIBTPU_INIT_ARGS": "a", "PJRT_DEVICE": "TPU", "HOME": "/h"})
        assert env == {"JAX_COMPILATION_CACHE_DIR": "/some/dir",
                       "HOME": "/h"}

    def test_replica_process_refused_by_a_parent_that_holds_a_chip(
            self, monkeypatch):
        """A chip belongs to one process. A parent whose jax runs on an
        accelerator cannot have replica processes on it, and quietly
        giving them the CPU would hide that."""
        from paddle_tpu.inference import replica_proc
        assert jax.devices()            # this process has touched jax
        with replica_proc._worker_device_env():     # cpu parent: cpu child
            assert os.environ["JAX_PLATFORMS"] == "cpu"
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        with pytest.raises(RuntimeError, match="holds the tpu backend"):
            replica_proc._worker_device_env()


class TestDeviceChoice:
    @pytest.fixture(autouse=True)
    def _restore_place(self):
        from paddle_tpu.core import device
        before = device._current_place
        yield
        device._current_place = before

    def test_default_place_is_the_backend_jax_has(self):
        assert pt.get_device() == "cpu:0"
        assert not pt.is_compiled_with_tpu()

    @pytest.mark.parametrize("request_,error", [
        ("tpu", RuntimeError),      # a platform this process has not
        ("tpu:0", RuntimeError),
        ("gpu", RuntimeError),
        ("cpu:99", ValueError),     # an index the platform has not
        ("xpu", ValueError),        # not a platform jax knows
        ("axelerator:1", ValueError),
    ])
    def test_absent_device_is_an_error_not_the_cpu(self, request_, error):
        with pytest.raises(error):
            pt.set_device(request_)
        assert pt.get_device() == "cpu:0"       # and nothing changed

    def test_present_device_is_taken(self):
        assert pt.set_device("cpu:3").jax_device() == jax.devices("cpu")[3]
        assert pt.get_device() == "cpu:3"
        t = pt.to_tensor([1.0, 2.0]).to("cpu:2")
        assert t._data.devices() == {jax.devices("cpu")[2]}
        with pytest.raises(RuntimeError):
            pt.to_tensor([1.0]).to("tpu")


class TestBenchmarkPlatformCheck:
    """`benchmarks/run.py` measures on the chip or not at all (PERF.md
    section 1: "No TPU, no result line")."""

    def test_fails_without_a_chip_and_prints_no_result_line(self):
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        out = subprocess.run(
            [sys.executable, os.path.join(REPO, "benchmarks", "run.py"),
             "--workload", "gpt2-small.train-1k", "--seed", "1",
             "--seconds", "1", "--trace", "0"],
            env=env, capture_output=True, text=True, timeout=600, cwd=REPO)
        assert out.returncode != 0
        assert "needs 1 TPU chip" in out.stderr
        assert out.stdout.strip() == ""         # and no result line

    @pytest.mark.parametrize("device_kind", [
        "TPU v5",           # exact match: not answered by "TPU v5 lite"
        "TPU v6 lite",      # a chip the benchmark's table does not hold
    ])
    def test_a_device_kind_outside_the_table_has_no_peaks(self, device_kind):
        """A share of a peak is read against the device's own peaks or
        not at all: never against the v5e's by default."""
        import importlib.util
        spec = importlib.util.spec_from_file_location(
            "bench_peaks",
            os.path.join(REPO, "benchmarks", "harness", "peaks.py"))
        peaks = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(peaks)
        assert peaks.peaks(device_kind) is None
        assert peaks.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
