"""Chaos suite for the resilience layer (paddle_tpu.resilience).

Every fault here is injected deterministically through the named fault
points in paddle_tpu.resilience.faults — no sleeping-and-hoping. The
contracts under test:

  * LLMEngine: a poisoned/OOMing/deadline-expired request fails ALONE;
    every other admitted request finishes with oracle-exact tokens and
    its pages return to the pool.
  * DataLoader: a worker SIGKILL'd (hard-exited) mid-epoch is detected
    and respawned; the epoch completes identically to serial, and no
    /dev/shm segment outlives the loader on ANY exit path.
  * Checkpoints: a crash at any point between shard writes and the
    final rename leaves the previous checkpoint untouched;
    resume_latest() restores the newest COMPLETE checkpoint, skipping
    torn/corrupted ones.
"""
import os
import warnings

# what a spawned DataLoader worker's interpreter was started with
# (EnvGuardDs reads it in the worker)
_ENV_AT_IMPORT = os.environ.get("JAX_PLATFORMS")

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu.io import DataLoader, Dataset
from paddle_tpu.resilience import faults


@pytest.fixture(autouse=True)
def _clean_faults():
    faults.clear_all()
    yield
    faults.clear_all()


# ---------------------------------------------------------------------------
# the harness itself
# ---------------------------------------------------------------------------
class TestFaultHarness:
    def test_disarmed_is_noop(self):
        faults.fault_point("nothing.armed", x=1)   # must not raise

    def test_context_scoping_and_fired(self):
        with faults.inject("chaos.a", exc=ValueError("boom")):
            with pytest.raises(ValueError, match="boom"):
                faults.fault_point("chaos.a")
        faults.fault_point("chaos.a")              # cleared on exit
        assert faults.fired("chaos.a") == 1

    def test_times_budget(self):
        faults.inject("chaos.b", exc=RuntimeError, times=2)
        for _ in range(2):
            with pytest.raises(RuntimeError):
                faults.fault_point("chaos.b")
        faults.fault_point("chaos.b")              # budget exhausted
        assert faults.fired("chaos.b") == 2

    def test_match_and_when(self):
        with faults.inject("chaos.c", exc=KeyError, match={"rid": "bad"}):
            faults.fault_point("chaos.c", rid="good")
            with pytest.raises(KeyError):
                faults.fault_point("chaos.c", rid="bad")
        with faults.inject("chaos.d", exc=KeyError,
                           when=lambda ctx: ctx.get("i", 0) > 3):
            faults.fault_point("chaos.d", i=1)
            with pytest.raises(KeyError):
                faults.fault_point("chaos.d", i=7)

    def test_delay(self):
        import time
        with faults.inject("chaos.e", delay=0.05):
            t0 = time.monotonic()
            faults.fault_point("chaos.e")
            assert time.monotonic() - t0 >= 0.05

    def test_when_may_call_back_into_faults(self):
        # sequencing predicate: fire B only after A has fired
        faults.inject("chaos.seq.a", exc=ValueError, times=1)
        faults.inject("chaos.seq.b", exc=RuntimeError,
                      when=lambda ctx: faults.fired("chaos.seq.a") > 0)
        faults.fault_point("chaos.seq.b")          # A not fired yet
        with pytest.raises(ValueError):
            faults.fault_point("chaos.seq.a")
        with pytest.raises(RuntimeError):
            faults.fault_point("chaos.seq.b")

    def test_snapshot_drops_when(self):
        faults.inject("chaos.f", exc=ValueError, match={"bi": 1})
        faults.inject("chaos.g", exc=ValueError, when=lambda c: True)
        names = {s.name for s in faults.snapshot()}
        assert names == {"chaos.f"}    # `when` callables don't pickle


# ---------------------------------------------------------------------------
# engine hardening
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def tiny_gpt():
    from paddle_tpu.models import GPTForCausalLM
    from paddle_tpu.models.gpt import gpt_tiny
    pt.seed(0)
    return GPTForCausalLM(gpt_tiny())


def _engine(model, **kw):
    from paddle_tpu.inference import LLMEngine
    args = dict(max_batch=2, block_size=16, decode_chunk=4,
                prompt_quantum=16, max_model_len=64)
    args.update(kw)
    return LLMEngine(model, **args)


def _oracle(model, prompt, n_new):
    from paddle_tpu.models.generation import generate
    out = generate(model, pt.to_tensor(np.asarray(prompt, np.int32)[None]),
                   max_new_tokens=n_new).numpy()[0]
    return out[len(prompt):]


def _drain(eng):
    done = {}
    while eng.has_unfinished:
        for r in eng.step():
            done[r.request_id] = r
    return done


class TestEngineResilience:
    def test_tight_pool_no_decode_oom(self, tiny_gpt):
        """Regression (ADVICE r5 medium): decode leases are capped at
        the sequence's remaining token budget, so a pool sized exactly
        to add_request's feasibility check (need + trash page) serves
        the request instead of raising MemoryError mid-serving."""
        rng = np.random.default_rng(2)
        prompt = rng.integers(0, 1024, (17,)).astype(np.int32)
        # total 37 tokens -> need ceil(37/8)=5 blocks; pool = 5 + trash
        eng = _engine(tiny_gpt, max_batch=1, block_size=8, num_blocks=6,
                      decode_chunk=4)
        (r,) = eng.generate([prompt], max_new_tokens=20)
        assert r.ok and len(r.output_ids) == 20
        np.testing.assert_array_equal(r.output_ids,
                                      _oracle(tiny_gpt, prompt, 20))
        assert eng.cache.available_blocks == 5

    def test_poisoned_decode_isolated(self, tiny_gpt):
        """Injected OOM at decode: the poisoned request is failed and
        evicted, every other admitted request finishes exactly."""
        rng = np.random.default_rng(5)
        prompts = {k: rng.integers(0, 1024, (9,)).astype(np.int32)
                   for k in ("good1", "bad", "good2")}
        eng = _engine(tiny_gpt)
        for k, p in prompts.items():
            eng.add_request(k, p, max_new_tokens=8)
        with faults.inject("engine.decode.seq",
                           exc=MemoryError("chaos decode OOM"),
                           match={"rid": "bad"}):
            done = _drain(eng)
        assert done["bad"].finish_reason == "error"
        assert "chaos decode OOM" in done["bad"].error
        for k in ("good1", "good2"):
            assert done[k].ok
            np.testing.assert_array_equal(
                done[k].output_ids, _oracle(tiny_gpt, prompts[k], 8))
        # the failed request's pages went back to the pool
        assert eng.cache.available_blocks == \
            eng.cache.allocator.num_blocks - 1
        assert eng.stats["failed_requests"] == 1

    def test_poisoned_prefill_isolated(self, tiny_gpt):
        rng = np.random.default_rng(6)
        pg = rng.integers(0, 1024, (9,)).astype(np.int32)
        pb = rng.integers(0, 1024, (11,)).astype(np.int32)
        eng = _engine(tiny_gpt)
        eng.add_request("good", pg, max_new_tokens=6)
        eng.add_request("bad", pb, max_new_tokens=6)
        with faults.inject("engine.prefill.seq",
                           exc=RuntimeError("chaos prefill"),
                           match={"rid": "bad"}):
            done = _drain(eng)
        assert done["bad"].finish_reason == "error"
        assert done["good"].ok
        np.testing.assert_array_equal(done["good"].output_ids,
                                      _oracle(tiny_gpt, pg, 6))
        assert eng.cache.available_blocks == \
            eng.cache.allocator.num_blocks - 1

    def test_deadline_evicted_while_neighbor_finishes(self, tiny_gpt):
        rng = np.random.default_rng(7)
        pv = rng.integers(0, 1024, (9,)).astype(np.int32)
        pn = rng.integers(0, 1024, (12,)).astype(np.int32)
        eng = _engine(tiny_gpt)
        clock = {"now": 0.0}
        eng._now = lambda: clock["now"]     # deterministic TTL clock
        eng.add_request("victim", pv, max_new_tokens=30, deadline_s=5.0)
        eng.add_request("neighbor", pn, max_new_tokens=8)
        eng.step()                          # both admitted, decoding
        assert any(s is not None and s.rid == "victim"
                   for s in eng.slots)
        clock["now"] = 10.0                 # victim's TTL elapses
        done = _drain(eng)
        assert done["victim"].finish_reason == "deadline"
        assert not done["victim"].ok
        assert done["neighbor"].ok
        np.testing.assert_array_equal(done["neighbor"].output_ids,
                                      _oracle(tiny_gpt, pn, 8))
        assert eng.cache.available_blocks == \
            eng.cache.allocator.num_blocks - 1
        assert eng.stats["deadline_expired"] == 1

    def test_load_shedding_rejects_with_reason(self, tiny_gpt):
        eng = _engine(tiny_gpt, max_batch=1, block_size=8, num_blocks=5,
                      shed_load=True, max_waiting=1)
        eng.add_request("big", np.zeros(20, np.int32), max_new_tokens=20)
        eng.add_request("long", np.zeros(60, np.int32), max_new_tokens=10)
        eng.add_request("ok1", np.zeros(4, np.int32), max_new_tokens=2)
        eng.add_request("spill", np.zeros(4, np.int32), max_new_tokens=2)
        done = _drain(eng)
        assert done["big"].finish_reason == "rejected"
        assert "cache blocks" in done["big"].error
        assert done["long"].finish_reason == "rejected"
        assert "max_model_len" in done["long"].error
        assert done["spill"].finish_reason == "rejected"
        assert "queue is full" in done["spill"].error
        assert done["ok1"].ok
        assert eng.stats["rejected_requests"] == 3

    def test_legacy_raise_admission_preserved(self, tiny_gpt):
        eng = _engine(tiny_gpt, max_batch=1, block_size=8, num_blocks=5)
        with pytest.raises(MemoryError):
            eng.add_request("big", np.zeros(20, np.int32),
                            max_new_tokens=20)
        with pytest.raises(ValueError):
            eng.add_request("long", np.zeros(60, np.int32),
                            max_new_tokens=10)


# ---------------------------------------------------------------------------
# crash-safe checkpoints
# ---------------------------------------------------------------------------
class TestCrashSafeCheckpoint:
    def _save(self, path, arr):
        from paddle_tpu import distributed as dist
        dist.checkpoint.save_state_dict(
            {"w": pt.to_tensor(arr)}, str(path))

    def test_crash_between_tmp_and_rename(self, tmp_path):
        from paddle_tpu import distributed as dist
        a = np.arange(12, dtype=np.float32).reshape(3, 4)
        self._save(tmp_path / "step_10", a)
        with pytest.raises(KeyboardInterrupt):
            with faults.inject("checkpoint.before_rename",
                               exc=KeyboardInterrupt("crash")):
                self._save(tmp_path / "step_20", a * 2)
        # the destination never appeared; only hidden staging litter
        assert not (tmp_path / "step_20").exists()
        with pytest.raises(KeyboardInterrupt):
            with faults.inject("checkpoint.before_meta",
                               exc=KeyboardInterrupt("crash")):
                self._save(tmp_path / "step_30", a * 3)
        assert not (tmp_path / "step_30").exists()
        dst = {"w": pt.to_tensor(np.zeros_like(a))}
        got = dist.checkpoint.resume_latest(dst, str(tmp_path),
                                            cleanup=True)
        assert got and got.endswith("step_10")
        np.testing.assert_array_equal(dst["w"].numpy(), a)
        # cleanup reaped the staging dirs
        assert not [f for f in os.listdir(tmp_path) if ".tmp-" in f]

    def test_resume_skips_torn_checkpoint(self, tmp_path):
        from paddle_tpu import distributed as dist
        a = np.arange(8, dtype=np.float32)
        self._save(tmp_path / "step_1", a)
        self._save(tmp_path / "step_2", a * 2)
        # corrupt the newest checkpoint's shard payload
        step2 = tmp_path / "step_2"
        shard = next(f for f in os.listdir(step2) if f.endswith(".npy"))
        (step2 / shard).write_bytes(b"garbage")
        assert dist.checkpoint.verify_checkpoint(str(step2))
        assert dist.checkpoint.is_complete(str(tmp_path / "step_1"))
        dst = {"w": pt.to_tensor(np.zeros_like(a))}
        with pytest.warns(UserWarning, match="torn checkpoint"):
            got = dist.checkpoint.resume_latest(dst, str(tmp_path))
        assert got.endswith("step_1")
        np.testing.assert_array_equal(dst["w"].numpy(), a)

    def test_soft_failure_between_overwrite_renames_rolls_back(
            self, tmp_path):
        """Overwriting save raises after the previous checkpoint moved
        aside but before the new one landed: the previous checkpoint is
        rolled back in place — plain load_state_dict(path) keeps
        working, no resume needed."""
        from paddle_tpu import distributed as dist
        a = np.arange(8, dtype=np.float32)
        self._save(tmp_path / "latest", a)
        with pytest.raises(KeyboardInterrupt):
            with faults.inject("checkpoint.between_renames",
                               exc=KeyboardInterrupt("crash")):
                self._save(tmp_path / "latest", a * 2)
        dst = {"w": pt.to_tensor(np.zeros_like(a))}
        dist.checkpoint.load_state_dict(dst, str(tmp_path / "latest"))
        np.testing.assert_array_equal(dst["w"].numpy(), a)  # v1, not v2

    def test_hard_crash_between_overwrite_renames_repaired(
            self, tmp_path):
        """HARD crash (no rollback ran) in the same window: the
        previous COMPLETE checkpoint is stranded as a hidden .old dir
        with the destination absent — resume_latest restores it."""
        from paddle_tpu import distributed as dist
        a = np.arange(8, dtype=np.float32)
        self._save(tmp_path / "latest", a)
        # simulate the post-SIGKILL state the rollback can't reach
        os.replace(tmp_path / "latest", tmp_path / ".latest.old-999")
        dst = {"w": pt.to_tensor(np.zeros_like(a))}
        got = dist.checkpoint.resume_latest(dst, str(tmp_path),
                                            cleanup=True)
        assert got and got.endswith("latest")
        np.testing.assert_array_equal(dst["w"].numpy(), a)
        assert not [f for f in os.listdir(tmp_path)
                    if ".tmp-" in f or ".old-" in f]

    def test_resume_latest_empty_root(self, tmp_path):
        from paddle_tpu import distributed as dist
        assert dist.checkpoint.resume_latest({}, str(tmp_path)) is None
        assert dist.checkpoint.resume_latest(
            {}, str(tmp_path / "missing")) is None

    def test_resume_ignores_non_checkpoint_dirs(self, tmp_path):
        """Sibling dirs without a metadata.json (logs/, tensorboard/)
        are not checkpoints: never warned about, never quarantined —
        even with cleanup=True."""
        from paddle_tpu import distributed as dist
        a = np.arange(4, dtype=np.float32)
        self._save(tmp_path / "step_3", a)
        (tmp_path / "logs").mkdir()
        (tmp_path / "logs" / "events.txt").write_text("hi")
        dst = {"w": pt.to_tensor(np.zeros_like(a))}
        with warnings.catch_warnings():
            warnings.simplefilter("error")      # any warning fails
            got = dist.checkpoint.resume_latest(dst, str(tmp_path),
                                                cleanup=True)
        assert got.endswith("step_3")
        assert (tmp_path / "logs" / "events.txt").read_text() == "hi"

    def test_manifest_written_and_filtered(self, tmp_path):
        from paddle_tpu import distributed as dist
        self._save(tmp_path / "c", np.ones(4, np.float32))
        files = dist.checkpoint.get_checkpoint_files(str(tmp_path / "c"))
        assert files == ["w"]
        assert dist.checkpoint.verify_checkpoint(
            str(tmp_path / "c")) == []

    def test_framework_io_atomic_save(self, tmp_path):
        fp = str(tmp_path / "model.pdparams")
        a = np.arange(6, dtype=np.float32)
        pt.save({"a": pt.to_tensor(a)}, fp)
        with pytest.raises(KeyboardInterrupt):
            with faults.inject("framework_io.before_rename",
                               exc=KeyboardInterrupt("crash")):
                pt.save({"a": pt.to_tensor(a * 9)}, fp)
        # crash mid-save: the previous pickle is intact, not torn
        np.testing.assert_array_equal(pt.load(fp)["a"].numpy(), a)


# ---------------------------------------------------------------------------
# self-healing DataLoader
# ---------------------------------------------------------------------------
class ShmDs(Dataset):
    """Module-level (spawn-picklable); big samples force the
    SharedMemory transport path."""

    def __init__(self, n=24):
        self.n = n

    def __getitem__(self, i):
        rng = np.random.RandomState(i)
        return rng.standard_normal(64 * 1024).astype(np.float32), \
            np.int64(i)

    def __len__(self):
        return self.n


class EnvGuardDs(ShmDs):
    """Asserts the spawn-env contract: JAX_PLATFORMS=cpu is in the
    worker's environment from the interpreter's start — before this
    test module is imported there (_ENV_AT_IMPORT), so also when the
    dataset is UNPICKLED, not just when __getitem__ runs."""

    def __setstate__(self, state):
        assert _ENV_AT_IMPORT == "cpu", \
            "worker interpreter started without JAX_PLATFORMS=cpu"
        assert os.environ.get("JAX_PLATFORMS") == "cpu"
        self.__dict__.update(state)


def tensor_collate(batch):
    """Module-level (itself spawn-picklable) collate returning framework
    Tensors — Tensor.__reduce__ (numpy roundtrip) makes the OUTPUT
    spawn-picklable, so the loader keeps the process tier."""
    xs, ys = zip(*batch)
    return (pt.to_tensor(np.stack(xs)), pt.to_tensor(np.asarray(ys)))


def _shm_segments(loader):
    """The /dev/shm names that are this loader's own: its workers name
    every segment with `loader._shm_prefix`, so the accounting is blind
    to what other processes of the box create and unlink meanwhile."""
    try:
        return {f for f in os.listdir("/dev/shm")
                if f.startswith(loader._shm_prefix)}
    except FileNotFoundError:       # macOS etc. — skip the accounting
        return None


def _collect(loader):
    return [(np.asarray(x.numpy()), np.asarray(y.numpy()))
            for x, y in loader]


class TestSelfHealingDataLoader:
    def test_worker_killed_mid_epoch_heals(self):
        # the FATAL healing contract: hard-exit (SIGKILL-equivalent:
        # no error report, no cleanup) worker 0 the first time it
        # reaches batch 2. The respawn batch NUMBER is load-dependent
        # — the hard exit can kill the queue's feeder thread before
        # batch 0's pickle ever reaches the pipe, in which case the
        # parent (correctly) respawns at batch 0 — so only the respawn
        # itself is asserted; the real contract is the batch-exact
        # healed epoch checked below. The /dev/shm accounting lives in
        # its own test so THIS correctness contract can
        # never ride out a timing race un-asserted.
        ds = ShmDs(n=24)
        serial = _collect(DataLoader(ds, batch_size=4, num_workers=0))
        with faults.inject("io.worker.batch", exit_code=1, times=1,
                           match={"bi": 2, "attempt": 0}):
            with pytest.warns(UserWarning,
                              match="respawning at batch"):
                healed = _collect(DataLoader(ds, batch_size=4,
                                             num_workers=2))
        assert len(healed) == len(serial) == 6
        for (sx, sy), (px, py) in zip(serial, healed):
            np.testing.assert_array_equal(sx, px)
            np.testing.assert_array_equal(sy, py)

    def test_worker_kill_shm_leak_accounting(self):
        # the shm-leak accounting for the same kill scenario. A hard
        # kill can land between segment creation in _pack and the
        # payload reaching the parent's queue (the worker's queue
        # feeder thread races the exit), and that batch's payload dies
        # with the worker; its segments do not outlive the epoch,
        # because the parent unlinks by name what is left of its stem.
        # Only the loader's own names are counted.
        loader = DataLoader(ShmDs(n=24), batch_size=4, num_workers=2)
        with faults.inject("io.worker.batch", exit_code=1, times=1,
                           match={"bi": 2, "attempt": 0}):
            with pytest.warns(UserWarning, match="respawning at batch"):
                healed = _collect(loader)
        assert len(healed) == 6
        assert not _shm_segments(loader), "leaked /dev/shm segments"

    def test_undelivered_segments_are_unlinked_by_their_stem(self):
        # what the two accountings here rest on: a worker names each
        # segment by the stem it was spawned with, leaf by leaf, and
        # the parent can unlink by that stem what never reached it
        from paddle_tpu.io import _process_worker as PW
        loader = DataLoader(ShmDs(n=8), batch_size=4, num_workers=2)
        if _shm_segments(loader) is None:
            pytest.skip("/dev/shm cannot be listed here")
        stem = loader._shm_prefix + "e1"
        big = np.zeros(PW._SHM_THRESHOLD, np.uint8)
        segments = []
        try:
            PW._pack([big, big], segments, stem + "w0a0b0")
            assert [seg.name.lstrip("/") for seg in segments] == \
                [stem + "w0a0b0n0", stem + "w0a0b0n1"]
            assert len(_shm_segments(loader)) == 2
        finally:
            for seg in segments:
                seg.close()
            PW.unlink_stem(stem)    # the payload itself was "lost"
        assert not _shm_segments(loader)

    def test_restart_budget_exhausts(self):
        ds = ShmDs(n=24)
        # kill EVERY incarnation at batch 2 -> bounded restarts, then a
        # clear error (not a hang)
        with faults.inject("io.worker.batch", exit_code=1,
                           match={"bi": 2}):
            with pytest.raises(RuntimeError, match="exhausted"), \
                    warnings.catch_warnings():
                warnings.simplefilter("ignore")
                _collect(DataLoader(ds, batch_size=4, num_workers=2,
                                    max_worker_restarts=1))

    def test_early_exit_unlinks_all_segments(self):
        ds = ShmDs(n=64)
        loader = DataLoader(ds, batch_size=4, num_workers=2,
                            prefetch_factor=2)
        it = iter(loader)
        next(it)
        next(it)
        it.close()      # generator finally: stop -> join -> drain
        assert not _shm_segments(loader), \
            "early consumer exit leaked /dev/shm segments"
        # the loader is reusable afterwards
        assert len(_collect(loader)) == 16

    def test_env_guard_precedes_unpickle(self, monkeypatch):
        # parent without JAX_PLATFORMS: the child can only pass
        # EnvGuardDs.__setstate__ if it was SPAWNED with the variable
        monkeypatch.delenv("JAX_PLATFORMS", raising=False)
        out = _collect(DataLoader(EnvGuardDs(n=8), batch_size=4,
                                  num_workers=2))
        assert len(out) == 2
        # and the parent's own environment is as it was
        assert "JAX_PLATFORMS" not in os.environ

    def test_tensor_collate_stays_on_process_tier(self):
        """Tensor-returning collate_fns used to demote to the thread
        tier (Tensors had no pickle protocol); Tensor.__reduce__ lifted
        that — the probe must accept them, spawn real workers, and the
        batches must round-trip the worker->parent queue exactly."""
        ds = ShmDs(n=8)
        loader = DataLoader(ds, batch_size=4, num_workers=2,
                            collate_fn=tensor_collate)
        with warnings.catch_warnings(record=True) as caught:
            warnings.simplefilter("always")
            out = _collect(loader)
        assert not [w for w in caught
                    if "falling back" in str(w.message)], \
            "Tensor collate demoted to the thread tier"
        assert loader._spawn_picklable_result is True
        assert len(out) == 2
        serial = _collect(DataLoader(ds, batch_size=4, num_workers=0,
                                     collate_fn=tensor_collate))
        for (sx, sy), (px, py) in zip(serial, out):
            np.testing.assert_array_equal(sx, px)
            np.testing.assert_array_equal(sy, py)


# ---------------------------------------------------------------------------
# fused optimizer: instance-hyper mutation honored (satellite)
# ---------------------------------------------------------------------------
def test_fused_step_honors_hyper_mutation():
    from paddle_tpu.optimizer import Adam

    def run(fused):
        os.environ["PADDLE_TPU_FUSED_OPT"] = "1" if fused else "0"
        try:
            pt.seed(0)
            lin = pt.nn.Linear(8, 8)
            x = pt.to_tensor(np.random.default_rng(0).standard_normal(
                (4, 8)).astype(np.float32))
            opt = Adam(learning_rate=0.01, parameters=lin.parameters())
            for i in range(6):
                if i == 3:      # mid-training mutation
                    opt.beta1 = 0.5
                    opt.epsilon = 1e-3
                loss = (lin(x) ** 2).mean()
                loss.backward()
                opt.step()
                opt.clear_grad()
            return [np.asarray(p._data) for p in lin.parameters()], opt
        finally:
            os.environ.pop("PADDLE_TPU_FUSED_OPT", None)

    fused, opt = run(True)
    eager, _ = run(False)
    for a, b in zip(fused, eager):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=5e-6)
    # the mutation recompiled (2 signatures) instead of being ignored
    assert len(opt.__dict__["_fused_step_cache"]) == 2
