"""Pallas block-size autotune cache:
pick/persist/reload logic, kill-switch, and reentrancy — the machinery
is exercised with mocked timings (the real kernel measurement needs the
TPU; chip_smoke.py runs the sweeps on the chip)."""
import numpy as np
import pytest

from paddle_tpu.kernels.pallas import autotune


@pytest.fixture(autouse=True)
def _isolated_cache(tmp_path, monkeypatch):
    monkeypatch.setenv("PADDLE_TPU_CACHE_DIR", str(tmp_path))
    autotune.clear()
    autotune.drain_sweeps()
    yield
    autotune.clear()
    autotune.drain_sweeps()


def test_picks_fastest_and_persists(tmp_path):
    times = {(128, 512): 0.03, (256, 1024): 0.01, (512, 512): 0.02}
    calls = []

    def run(c):
        calls.append(c)
        return times[c]

    key = ("fwd", 4, 256, 256, 8, 8, 64, 1, 0)
    win = autotune.tune(key, list(times), run)
    assert win == (256, 1024)
    # every candidate measured at least once
    assert set(calls) == set(times)
    # memoized: no more measurement
    calls.clear()
    assert autotune.tune(key, list(times), run) == (256, 1024)
    assert calls == []
    # survives a fresh in-process state (disk reload)
    autotune.clear()
    assert autotune.lookup(key) == (256, 1024)
    assert autotune.tune(key, list(times), run) == (256, 1024)
    assert calls == []


def test_failed_candidates_are_skipped():
    def run(c):
        if c == (512, 512):
            raise RuntimeError("vmem oom")
        return {(128, 512): 0.02, (256, 1024): 0.05}[c]

    win = autotune.tune(("bwd", 1, 128, 128, 2, 2, 64, 0, 0),
                        [(512, 512), (128, 512), (256, 1024)], run)
    assert win == (128, 512)


def test_all_failed_falls_back_to_first():
    def run(c):
        raise RuntimeError("nope")

    key = ("fwd", 1, 128, 128, 2, 2, 64, 0, 1)
    win = autotune.tune(key, [(256, 1024), (128, 512)], run)
    assert win == (256, 1024)
    # a transient all-fail must NOT freeze into the cache
    assert autotune.lookup(key) is None


def test_kill_switch(monkeypatch):
    monkeypatch.setenv("PADDLE_TPU_PALLAS_AUTOTUNE", "0")
    assert not autotune.enabled()
    monkeypatch.setenv("PADDLE_TPU_PALLAS_AUTOTUNE", "1")
    assert autotune.enabled()


def test_reentrancy_guard():
    """A measurement that re-enters tune() (the kernel under test calls
    the autotuned entrypoint) must not recurse into another search."""
    inner_calls = []

    def run_outer(c):
        w = autotune.tune(("fwd", 9, 9, 9, 9, 9, 9, 9, 9),
                          [(1, 1), (2, 2)],
                          lambda c2: inner_calls.append(c2) or 0.01)
        assert w == (1, 1)          # first candidate, no search
        return {(128, 512): 0.02, (256, 1024): 0.01}[c]

    win = autotune.tune(("fwd", 2, 256, 256, 4, 4, 64, 1, 0),
                        [(128, 512), (256, 1024)], run_outer)
    assert win == (256, 1024)
    assert inner_calls == []        # inner search never measured


def test_distinct_keys_distinct_entries():
    k1 = ("fwd", 4, 256, 256, 8, 8, 64, 1, 0)
    k2 = ("fwd", 4, 512, 512, 8, 8, 64, 1, 0)
    autotune.tune(k1, [(1, 1), (2, 2)], lambda c: {(1, 1): 0.1,
                                                   (2, 2): 0.2}[c])
    autotune.tune(k2, [(1, 1), (2, 2)], lambda c: {(1, 1): 0.2,
                                                   (2, 2): 0.1}[c])
    assert autotune.lookup(k1) == (1, 1)
    assert autotune.lookup(k2) == (2, 2)


def test_sweep_runs_kernels_while_the_caller_is_being_traced():
    """The kernels call tune() from inside the jit trace of the program
    that uses them. The sweep has to compile and RUN candidates there:
    arrays it makes must be real, a Pallas kernel it launches must
    execute. On jax 0.9.0 both used to be staged into the caller's
    trace, every candidate raised on the first value it read, the
    failure was swallowed and the defaults were 'tuned'."""
    import importlib
    import jax
    import jax.numpy as jnp
    import numpy as np
    fa = importlib.import_module("paddle_tpu.kernels.pallas.flash_attention")
    key = ("fwd", "in_trace", 128, 128, 2, 2, 64, 1, 0)
    seen = {}

    def run_candidate(c):
        x = jnp.asarray(np.ones((1, 128, 128), np.float32))
        out, _lse = jax.jit(lambda x: fa._flash_fwd_fused(
            x, x, x, 2, True, block_q=c[0], block_k=c[1],
            interpret=True, autotune_ok=False))(x)
        seen[c] = float(np.asarray(out[0, 0, 0]))   # a real value
        return {(128, 128): 0.02, (64, 128): 0.01}[c]

    @jax.jit
    def program(x):
        win = autotune.tune(key, [(128, 128), (64, 128)], run_candidate)
        return x * win[0]

    assert float(program(jnp.float32(1.0))) == 64.0
    assert autotune.lookup(key) == (64, 128)        # persisted
    (sweep,) = autotune.drain_sweeps()
    assert sweep["persisted"] and sweep["errors"] == {}
    assert sweep["seconds"] > 0
    assert set(seen) == {(128, 128), (64, 128)}


def test_failed_candidates_are_recorded_with_their_error():
    key = ("fwd", "errs", 1, 1, 1, 1, 1, 1, 0)

    def run_candidate(c):
        if c == (2, 2):
            raise ValueError("does not fit")
        return 0.01

    assert autotune.tune(key, [(1, 1), (2, 2)], run_candidate) == (1, 1)
    (sweep,) = autotune.drain_sweeps()
    assert sweep["errors"] == {"(2, 2)": "ValueError: does not fit"}
    assert sweep["candidates"]["(2, 2)"] is None
