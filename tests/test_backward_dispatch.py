"""Batched + whole-graph backward dispatch (ISSUE 10/13):
bit-identical-gradients suite (whole_graph vs batched vs per_node
across hooks, retain_graph, create_graph, multi-consumer fan-in, dead
output slots, the fused-optimizer end-to-end path), mode controls,
fused-segment degradation, the whole-graph trace cache
(hit/miss/bypass telemetry, invalidation), the backward compile-family
budget, and the bandwidth-window-validated autotune sweep."""
import os

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import observability as obs
from paddle_tpu.autograd import dispatch_queue as dq
from paddle_tpu.kernels.pallas import autotune


@pytest.fixture(autouse=True)
def _clean():
    obs.disable()
    obs.reset()
    yield
    obs.disable()
    obs.reset()
    dq.set_dispatch_mode("whole_graph")


def _params(seed=0, n=16):
    rng = np.random.default_rng(seed)
    w1 = pt.to_tensor(rng.standard_normal((n, n)).astype(np.float32),
                      stop_gradient=False)
    w2 = pt.to_tensor(rng.standard_normal((n, n)).astype(np.float32),
                      stop_gradient=False)
    x = pt.to_tensor(rng.standard_normal((4, n)).astype(np.float32))
    return w1, w2, x


def _bit_identical(a, b):
    a, b = np.asarray(a), np.asarray(b)
    return a.dtype == b.dtype and np.array_equal(a, b)


# ---------------------------------------------------------------------------
# bit-identical gradients: batched vs per_node
# ---------------------------------------------------------------------------
class TestBitIdenticalGradients:
    def _both_modes(self, fn):
        """Run `fn` under every dispatch mode; gradients must be
        bit-identical to the per_node reference in all of them."""
        with dq.backward_dispatch_mode("per_node"):
            a = fn()
        for mode in ("batched", "whole_graph"):
            with dq.backward_dispatch_mode(mode):
                b = fn()
            assert len(a) == len(b)
            for ga, gb in zip(a, b):
                assert _bit_identical(ga, gb), mode
        return a

    def test_linear_chain(self):
        def run():
            w1, w2, x = _params()
            loss = (pt.matmul(pt.ops.tanh(pt.matmul(x, w1)), w2)
                    ** 2).mean()
            loss.backward()
            return [w1.grad.numpy(), w2.grad.numpy()]
        self._both_modes(run)

    def test_hooks_fire_identically(self):
        fired = {"per_node": 0, "batched": 0, "whole_graph": 0}

        def run():
            mode = dq.dispatch_mode()
            w1, w2, x = _params()
            h = pt.ops.tanh(pt.matmul(x, w1))

            def hook(g):
                fired[mode] += 1
                return g * 2
            h.register_hook(hook)
            loss = (pt.matmul(h, w2) ** 2).mean()
            loss.backward()
            return [w1.grad.numpy(), w2.grad.numpy()]
        self._both_modes(run)
        assert fired["per_node"] == fired["batched"] \
            == fired["whole_graph"] == 1

    def test_leaf_hook_identical(self):
        def run():
            w1, w2, x = _params()
            w1.register_hook(lambda g: g * 3)
            loss = (pt.matmul(pt.ops.tanh(pt.matmul(x, w1)), w2)
                    ** 2).mean()
            loss.backward()
            return [w1.grad.numpy(), w2.grad.numpy()]
        self._both_modes(run)

    def test_retain_graph_double_backward(self):
        def run():
            w1, w2, x = _params()
            loss = (pt.matmul(pt.ops.tanh(pt.matmul(x, w1)), w2)
                    ** 2).mean()
            loss.backward(retain_graph=True)
            loss.backward()
            return [w1.grad.numpy(), w2.grad.numpy()]
        self._both_modes(run)

    def test_create_graph_second_order(self):
        def run():
            w1, w2, x = _params()
            loss = (pt.matmul(pt.ops.tanh(pt.matmul(x, w1)), w2)
                    ** 2).mean()
            (g,) = pt.autograd.grad(loss, [w1], create_graph=True)
            (gg,) = pt.autograd.grad(g.sum(), [w1])
            return [gg.numpy()]
        self._both_modes(run)

    def test_multi_consumer_fan_in(self):
        def run():
            w1, w2, x = _params()
            y = pt.ops.tanh(pt.matmul(x, w1))
            z = (y * y + pt.ops.tanh(y) + pt.matmul(y, w2)).mean()
            z.backward()
            return [w1.grad.numpy(), w2.grad.numpy()]
        self._both_modes(run)

    def test_dead_output_slot_uses_zero_cache(self):
        def run():
            w1, _, x = _params()
            h = pt.matmul(x, w1)
            a, b = pt.split(h, 2, axis=1)    # b's cotangent slot is dead
            loss = (a ** 2).mean()
            loss.backward()
            return [w1.grad.numpy()]
        dq.clear_const_caches()
        self._both_modes(run)
        assert dq._ZEROS               # the dead slot hit the cache

    def test_grad_targets_and_explicit_seed(self):
        def run():
            w1, w2, x = _params()
            h = pt.ops.tanh(pt.matmul(x, w1))
            loss = (pt.matmul(h, w2) ** 2).mean()
            seed = pt.to_tensor(np.float32(2.0))
            (gh, gw) = pt.autograd.grad(loss, [h, w1],
                                        grad_outputs=[seed],
                                        allow_unused=True)
            return [gh.numpy(), gw.numpy()]
        self._both_modes(run)

    def test_fused_optimizer_end_to_end(self):
        def run():
            rng = np.random.default_rng(7)
            lin1, lin2 = pt.nn.Linear(16, 16), pt.nn.Linear(16, 16)
            for p in lin1.parameters() + lin2.parameters():
                p.set_value(pt.to_tensor(
                    rng.standard_normal(p.shape).astype(np.float32)))
            opt = pt.optimizer.AdamW(
                learning_rate=1e-2,
                parameters=lin1.parameters() + lin2.parameters())
            x = pt.to_tensor(
                rng.standard_normal((4, 16)).astype(np.float32))
            for _ in range(3):
                loss = (lin2(pt.ops.tanh(lin1(x))) ** 2).mean()
                loss.backward()
                opt.step()
                opt.clear_grad()
            return [p.numpy()
                    for p in lin1.parameters() + lin2.parameters()]
        self._both_modes(run)


# ---------------------------------------------------------------------------
# fusion behavior: runs form, degrade, and stay observable
# ---------------------------------------------------------------------------
class TestFusion:
    def _batch_series(self):
        return obs.snapshot()[
            "paddle_tpu_dispatch_batch_size"]["series"].get(())

    def test_chain_fuses_into_one_dispatch(self):
        obs.enable()
        w1, w2, x = _params()
        with dq.backward_dispatch_mode("batched"):
            loss = (pt.matmul(pt.ops.tanh(pt.matmul(x, w1)), w2)
                    ** 2).mean()
            loss.backward()
        val = self._batch_series()
        # the 5-node chain (matmul-tanh-matmul-pow-mean) is one run
        assert val["count"] == 1
        assert val["max"] == 5
        gap = obs.snapshot()[
            "paddle_tpu_dispatch_gap_seconds"]["series"][()]
        assert gap["count"] == 0       # no inter-dispatch host gaps

    def test_mid_chain_hook_degrades_to_per_node(self):
        obs.enable()
        w1, w2, x = _params()
        with dq.backward_dispatch_mode("batched"):
            h = pt.ops.tanh(pt.matmul(x, w1))
            h.register_hook(lambda g: g)
            loss = (pt.matmul(h, w2) ** 2).mean()
            loss.backward()
        val = self._batch_series()
        # the hooked node breaks the run: >1 dispatch, none covering
        # the whole 5-node graph
        assert val["count"] > 1
        assert val["max"] < 5
        assert val["sum"] == 5         # every node still dispatched

    def test_per_node_mode_records_no_batch_sizes(self):
        obs.enable()
        w1, w2, x = _params()
        with dq.backward_dispatch_mode("per_node"):
            (pt.matmul(pt.ops.tanh(pt.matmul(x, w1)), w2)
             ** 2).mean().backward()
        assert self._batch_series()["count"] == 0

    def test_fused_chain_executable_is_cached(self):
        dq.clear_chain_cache()
        w1, w2, x = _params()
        with dq.backward_dispatch_mode("batched"):
            for _ in range(3):
                loss = (pt.matmul(pt.ops.tanh(pt.matmul(x, w1)), w2)
                        ** 2).mean()
                loss.backward()
                w1.clear_gradient()
                w2.clear_gradient()
        assert dq.chain_cache_size() == 1   # one chain shape, reused

    def test_failed_composition_degrades_and_pins_entries(self):
        # a chain whose fused call raises is disabled (per-node from
        # then on) but STAYS cached holding its entry refs, so an
        # exec-cache eviction + id reuse can never alias its key
        dq.clear_chain_cache()
        w1, w2, x = _params()
        with dq.backward_dispatch_mode("batched"):
            loss = (pt.matmul(pt.ops.tanh(pt.matmul(x, w1)), w2)
                    ** 2).mean()
            loss.backward(retain_graph=True)
            (key, fused), = dq._CHAIN_CACHE.items()
            fused.disabled = True          # simulate a failed trace
            w1.clear_gradient()
            w2.clear_gradient()
            # degrades: head dispatches per-node, and the REMAINDER of
            # the graph may legitimately fuse as a fresh sub-chain
            loss.backward()
        assert w1.grad is not None
        assert dq._CHAIN_CACHE[key].disabled      # stays disabled
        assert dq._CHAIN_CACHE[key].entries       # refs still pinned
        assert dq.chain_cache_size() == \
            sum(1 for v in dq._CHAIN_CACHE.values() if not v.disabled)

    def test_backward_fused_compile_family_records(self):
        dq.clear_chain_cache()
        obs.enable()
        w1, w2, x = _params()
        with dq.backward_dispatch_mode("batched"):
            (pt.matmul(pt.ops.tanh(pt.matmul(x, w1)), w2)
             ** 2).mean().backward()
        comp = obs.snapshot()["paddle_tpu_compile_total"]["series"]
        assert comp[("backward_fused", "compile")] == 1
        fl = obs.snapshot()["paddle_tpu_executable_flops"]["series"]
        assert fl[("backward_fused",)] > 0


# ---------------------------------------------------------------------------
# whole-graph fusion (ISSUE 13): fan-in crossing, graph trace cache,
# degradation ladder
# ---------------------------------------------------------------------------
class TestWholeGraph:
    def _snap(self, name):
        return obs.snapshot()[name]["series"]

    def _graph_cache(self):
        # zero-valued rows are label sets other tests registered
        # before obs.reset() (reset zeroes values but keeps series)
        s = self._snap("paddle_tpu_backward_graph_cache_total")
        return {k[0]: int(v) for k, v in s.items() if v}

    def _fan_in_loss(self, w1, w2, x):
        # y feeds THREE consumers: the PR 10 chain engine fragments
        # here, the whole-graph engine accumulates y's cotangent
        # inside the fused trace
        y = pt.ops.tanh(pt.matmul(x, w1))
        return (y * y + pt.ops.tanh(y) + pt.matmul(y, w2)).mean()

    def test_fan_in_fuses_into_one_dispatch(self):
        dq.clear_chain_cache()
        obs.enable()
        w1, w2, x = _params()
        with dq.backward_dispatch_mode("whole_graph"):
            self._fan_in_loss(w1, w2, x).backward()
        batch = self._snap("paddle_tpu_dispatch_batch_size")[()]
        assert batch["count"] == 1          # the WHOLE graph, one call
        assert batch["max"] == batch["sum"] >= 6
        gap = self._snap("paddle_tpu_dispatch_gap_seconds")[()]
        assert gap["count"] == 0
        assert self._graph_cache() == {"miss": 1}

    def test_chain_mode_fragments_the_same_graph(self):
        # the A/B rung: batched (PR 10) stops at the fan-in junction,
        # whole_graph does not — same graph, different dispatch counts
        dq.clear_chain_cache()
        obs.enable()
        w1, w2, x = _params()
        with dq.backward_dispatch_mode("batched"):
            self._fan_in_loss(w1, w2, x).backward()
        batch = self._snap("paddle_tpu_dispatch_batch_size")[()]
        assert batch["count"] > 1
        # chain mode records no whole-graph cache outcomes
        assert self._graph_cache() == {}

    def test_steady_state_hits_graph_cache(self):
        dq.clear_chain_cache()
        obs.enable()
        w1, w2, x = _params()
        with dq.backward_dispatch_mode("whole_graph"):
            for _ in range(3):
                self._fan_in_loss(w1, w2, x).backward()
                w1.clear_gradient()
                w2.clear_gradient()
        assert self._graph_cache() == {"miss": 1, "hit": 2}
        assert dq.chain_cache_size() == 1   # one whole-graph entry

    def test_root_seeded_interior_and_queue_absorption(self):
        # two roots backward()ed together: the second root is an
        # interior node of the first's graph AND sits ready in the
        # queue when the walk starts — both PR 10 exclusions (root
        # seeds, non-empty queue) must now ride the fused run
        def run():
            w1, w2, x = _params()
            h = pt.ops.tanh(pt.matmul(x, w1))
            loss = (pt.matmul(h, w2) ** 2).mean() + h.sum()
            loss.backward()
            return [w1.grad.numpy(), w2.grad.numpy()]
        with dq.backward_dispatch_mode("per_node"):
            ref = run()
        obs.enable()
        with dq.backward_dispatch_mode("whole_graph"):
            got = run()
        for a, b in zip(ref, got):
            assert _bit_identical(a, b)
        batch = self._snap("paddle_tpu_dispatch_batch_size")[()]
        assert batch["count"] == 1          # still ONE fused dispatch
        assert self._graph_cache().get("bypass", 0) == 0

    def test_mid_graph_hook_degrades_only_locally(self):
        # a hook on one interior tensor splits the graph into two
        # fused segments around the hooked node — it does NOT collapse
        # the backward to per-node, and the hooked node itself heads
        # the second segment after its hook fires host-side
        dq.clear_chain_cache()
        obs.enable()
        fired = []
        w1, w2, x = _params()
        with dq.backward_dispatch_mode("whole_graph"):
            h = pt.ops.tanh(pt.matmul(x, w1))
            h.register_hook(lambda g: fired.append(1) or g * 2)
            loss = (pt.matmul(h, w2) ** 2).mean()
            loss.backward()
        assert fired == [1]
        batch = self._snap("paddle_tpu_dispatch_batch_size")[()]
        assert batch["count"] == 2          # two segments, no 1-runs
        assert batch["min"] >= 2
        assert batch["sum"] == 5            # every node dispatched
        assert self._graph_cache() == {"bypass": 1}

    def test_cache_invalidation_on_topology_change(self):
        dq.clear_chain_cache()
        obs.enable()
        w1, w2, x = _params()
        with dq.backward_dispatch_mode("whole_graph"):
            self._fan_in_loss(w1, w2, x).backward()
            w1.clear_gradient()
            w2.clear_gradient()
            # different topology (extra consumer of y) must MISS
            y = pt.ops.tanh(pt.matmul(x, w1))
            (y * y + pt.ops.tanh(y) + pt.matmul(y, w2)
             + y.sum()).mean().backward()
        gc = self._graph_cache()
        assert gc["miss"] == 2 and "hit" not in gc
        assert dq.chain_cache_size() == 2

    def test_cache_invalidation_on_exec_entry_change(self):
        # a re-created exec-cache entry has a NEW uid: the whole-graph
        # key must miss instead of silently reusing a trace derived
        # from the dead entry
        dq.clear_chain_cache()
        obs.enable()
        w1, w2, x = _params()
        with dq.backward_dispatch_mode("whole_graph"):
            self._fan_in_loss(w1, w2, x).backward()
            w1.clear_gradient()
            w2.clear_gradient()
            pt.ops.tanh.op_def.exec_cache.clear()   # entries rebuild
            self._fan_in_loss(w1, w2, x).backward()
        gc = self._graph_cache()
        assert gc["miss"] == 2 and "hit" not in gc

    def test_clear_chain_cache_clears_graph_cache(self):
        dq.clear_chain_cache()
        obs.enable()
        w1, w2, x = _params()
        with dq.backward_dispatch_mode("whole_graph"):
            self._fan_in_loss(w1, w2, x).backward()
            w1.clear_gradient()
            w2.clear_gradient()
            dq.clear_chain_cache()          # ONE cache for both tiers
            assert dq.chain_cache_size() == 0
            self._fan_in_loss(w1, w2, x).backward()
        assert self._graph_cache() == {"miss": 2}

    def test_disabled_segment_memoizes_head(self):
        # an untraceable whole-graph composition must not cost a
        # re-plan (O(remaining) host work) on every later backward:
        # the head's entry uid is memoized, the head dispatches
        # per-node outright, and the REMAINDER still fuses
        dq.clear_chain_cache()
        obs.enable()
        w1, w2, x = _params()
        with dq.backward_dispatch_mode("whole_graph"):
            loss = self._fan_in_loss(w1, w2, x)
            loss.backward(retain_graph=True)        # miss, whole graph
            (_key, fused), = dq._CHAIN_CACHE.items()
            fused.disabled = True                   # simulate bad trace
            w1.clear_gradient()
            w2.clear_gradient()
            loss.backward(retain_graph=True)        # disabled hit
            assert dq._DISABLED_HEAD_UIDS           # head memoized
            w1.clear_gradient()
            w2.clear_gradient()
            loss.backward()                         # memo: no re-plan
        gc = self._graph_cache()
        # first backward covered the whole graph; the two degraded
        # ones fragmented (head per-node + fused remainder)
        assert gc == {"miss": 1, "bypass": 2}
        batch = self._snap("paddle_tpu_dispatch_batch_size")[()]
        assert batch["min"] == 1                    # the degraded head
        # N + 2*(1 + (N-1)) = 3N nodes dispatched over the 3 backwards
        assert batch["sum"] == 3 * batch["max"]
        assert w1.grad is not None
        dq.clear_chain_cache()
        assert not dq._DISABLED_HEAD_UIDS           # cleared with cache

    def test_retain_graph_whole_graph_bit_identical(self):
        def run():
            w1, w2, x = _params()
            loss = self._fan_in_loss(w1, w2, x)
            loss.backward(retain_graph=True)
            loss.backward()
            return [w1.grad.numpy(), w2.grad.numpy()]
        with dq.backward_dispatch_mode("per_node"):
            ref = run()
        with dq.backward_dispatch_mode("whole_graph"):
            got = run()
        for a, b in zip(ref, got):
            assert _bit_identical(a, b)

    def test_create_graph_second_order_fan_in(self):
        def run():
            w1, w2, x = _params()
            loss = self._fan_in_loss(w1, w2, x)
            (g,) = pt.autograd.grad(loss, [w1], create_graph=True)
            (gg,) = pt.autograd.grad(g.sum(), [w1])
            return [gg.numpy()]
        with dq.backward_dispatch_mode("per_node"):
            ref = run()
        with dq.backward_dispatch_mode("whole_graph"):
            got = run()
        assert _bit_identical(ref[0], got[0])

    def test_dead_output_slot_fan_in(self):
        def run():
            w1, _, x = _params()
            h = pt.matmul(x, w1)
            a, b = pt.split(h, 2, axis=1)   # b's cotangent slot dead
            loss = (a ** 2).mean() + (a * a).sum()
            loss.backward()
            return [w1.grad.numpy()]
        with dq.backward_dispatch_mode("per_node"):
            ref = run()
        with dq.backward_dispatch_mode("whole_graph"):
            got = run()
        assert _bit_identical(ref[0], got[0])


# ---------------------------------------------------------------------------
# backward compile-family budget (ISSUE 13 satellite): steady-state
# eager training is O(1) executables and O(1) dispatches per step
# ---------------------------------------------------------------------------
class TestBackwardFamilyBudget:
    BUDGET = 2      # ONE whole-graph executable expected for a fixed
                    # MLP train loop; 2 leaves headroom for a seed-
                    # layout variant, never a per-step zoo

    def test_mlp_train_loop_is_one_fused_dispatch_per_step(self):
        dq.clear_chain_cache()
        rng = np.random.default_rng(11)
        layers = [pt.nn.Linear(16, 16) for _ in range(3)]
        for lyr in layers:
            for p in lyr.parameters():
                p.set_value(pt.to_tensor(
                    rng.standard_normal(p.shape).astype(np.float32)))
        params = [p for lyr in layers for p in lyr.parameters()]
        opt = pt.optimizer.SGD(learning_rate=1e-3, parameters=params)
        x = pt.to_tensor(rng.standard_normal((4, 16)).astype(np.float32))

        def step():
            h = x
            for lyr in layers[:-1]:
                h = pt.ops.tanh(lyr(h))
            loss = (layers[-1](h) ** 2).mean()
            loss.backward()
            opt.step()
            opt.clear_grad()

        with dq.backward_dispatch_mode("whole_graph"):
            for _ in range(2):              # warmup: trace + compile
                step()
            obs.enable()
            for _ in range(3):              # steady state, observed
                step()
        snap = obs.snapshot()
        gc = {k[0]: int(v) for k, v in snap[
            "paddle_tpu_backward_graph_cache_total"]["series"].items()
            if v}
        assert gc == {"hit": 3}             # every step: cached whole graph
        batch = snap["paddle_tpu_dispatch_batch_size"]["series"][()]
        assert batch["count"] == 3          # EXACTLY 1 fused call/step
        assert batch["min"] == batch["max"] >= 6
        comp = snap["paddle_tpu_compile_total"]["series"]
        fused_compiles = sum(v for (fam, _out), v in comp.items()
                             if fam == "backward_fused" and v)
        # steady state compiled NOTHING new (warmup predates obs)
        assert fused_compiles == 0
        # the process-global cache holds the one whole-graph entry
        # this loop uses (other tests' entries were cleared above)
        assert dq.chain_cache_size() <= self.BUDGET


# ---------------------------------------------------------------------------
# mode controls
# ---------------------------------------------------------------------------
class TestModeControls:
    def test_default_is_whole_graph(self):
        assert dq.dispatch_mode() == "whole_graph"
        assert dq._VALID_MODES == ("whole_graph", "batched", "per_node")

    def test_set_and_restore(self):
        old = dq.set_dispatch_mode("per_node")
        assert old == "whole_graph"
        assert dq.dispatch_mode() == "per_node"
        dq.set_dispatch_mode(old)

    def test_invalid_mode_raises(self):
        with pytest.raises(ValueError):
            dq.set_dispatch_mode("warp_speed")

    def test_context_manager_restores_on_error(self):
        with pytest.raises(RuntimeError):
            with dq.backward_dispatch_mode("per_node"):
                assert dq.dispatch_mode() == "per_node"
                raise RuntimeError("boom")
        assert dq.dispatch_mode() == "whole_graph"


# ---------------------------------------------------------------------------
# const caches
# ---------------------------------------------------------------------------
class TestConstCaches:
    def test_zero_cotangent_cached_per_aval(self):
        import jax
        dq.clear_const_caches()
        aval = jax.ShapeDtypeStruct((3, 4), np.dtype("float32"))
        z1 = dq.zero_cotangent_array(aval)
        z2 = dq.zero_cotangent_array(aval)
        assert z1 is z2
        assert np.asarray(z1).sum() == 0.0

    def test_float0_zeros_for_integer_avals(self):
        import jax
        dq.clear_const_caches()
        aval = jax.ShapeDtypeStruct((2,), np.dtype("int32"))
        z = dq.zero_cotangent_array(aval)
        assert isinstance(z, np.ndarray)
        assert z.dtype == jax.dtypes.float0
        assert dq.is_float0(z)

    def test_ones_seed_cached(self):
        dq.clear_const_caches()
        s1 = dq.ones_seed_array((), np.dtype("float32"))
        s2 = dq.ones_seed_array((), np.dtype("float32"))
        assert s1 is s2
        assert float(np.asarray(s1)) == 1.0

    def test_is_float0_cheap_path(self):
        import jax.numpy as jnp
        assert not dq.is_float0(jnp.zeros((2,)))
        assert not dq.is_float0(np.zeros((2,), np.float32))


# ---------------------------------------------------------------------------
# bandwidth-window-validated autotune (ISSUE 10 flash prong)
# ---------------------------------------------------------------------------
class TestAutotuneWindow:
    @pytest.fixture(autouse=True)
    def _isolated_cache(self, tmp_path, monkeypatch):
        monkeypatch.setenv("PADDLE_TPU_CACHE_DIR", str(tmp_path))
        autotune.clear()
        autotune.drain_sweeps()
        yield
        autotune.clear()
        autotune.drain_sweeps()

    def test_sweep_in_window_persists_winner(self, monkeypatch):
        monkeypatch.setattr(autotune, "measure_effective_bw",
                            lambda **kw: 250e9)
        times = {(1,): 0.5, (2,): 0.1}
        win = autotune.tune(("t", "case_a"), [(1,), (2,)],
                            lambda c: times[c],
                            bw_window=(233e9, 314e9))
        assert win == (2,)
        # persisted: a fresh lookup hits without re-measuring
        assert autotune.lookup(("t", "case_a")) == (2,)
        (sweep,) = autotune.drain_sweeps()
        assert sweep["window_validated"] and sweep["persisted"]
        assert sweep["winner"] == [2]
        assert sweep["candidates"]["(2,)"] == pytest.approx(0.1)

    def test_degraded_window_discards_sweep(self, monkeypatch):
        monkeypatch.setattr(autotune, "measure_effective_bw",
                            lambda **kw: 50e9)     # far below window
        times = {(1,): 0.5, (2,): 0.1}
        win = autotune.tune(("t", "case_b"), [(1,), (2,)],
                            lambda c: times[c],
                            bw_window=(233e9, 314e9))
        assert win == (1,)                  # defaults, not the winner
        assert autotune.lookup(("t", "case_b")) is None   # NOT frozen
        (sweep,) = autotune.drain_sweeps()
        assert sweep["window_validated"] is False
        assert not sweep["persisted"]

    def test_post_sweep_probe_outside_window_discards(self, monkeypatch):
        probes = iter([250e9])             # pre ok, post degraded

        def probe(**kw):
            return next(probes, 50e9)
        monkeypatch.setattr(autotune, "measure_effective_bw", probe)
        win = autotune.tune(("t", "case_c"), [(1,), (2,)],
                            lambda c: {(1,): 0.5, (2,): 0.1}[c],
                            bw_window=(233e9, 314e9))
        assert win == (1,)
        assert autotune.lookup(("t", "case_c")) is None

    def test_no_window_keeps_legacy_behavior(self):
        win = autotune.tune(("t", "case_d"), [(1,), (2,)],
                            lambda c: {(1,): 0.5, (2,): 0.1}[c])
        assert win == (2,)
        assert autotune.lookup(("t", "case_d")) == (2,)
        (sweep,) = autotune.drain_sweeps()
        assert sweep["bw_window"] is None
        assert sweep["window_validated"] is None

    def test_kill_switch_bypasses(self, monkeypatch):
        monkeypatch.setenv("PADDLE_TPU_PALLAS_AUTOTUNE", "0")
        assert not autotune.enabled()
        # the flash use site returns hand-tuned defaults untouched
        # (import_module: the package __init__ shadows the submodule
        # name with the function it re-exports)
        from importlib import import_module
        fa = import_module("paddle_tpu.kernels.pallas.flash_attention")
        out = fa._autotuned_blocks(
            "fwd", (256, 256, 128, "float32"), 2, 2, True, False,
            (256, 1024),
            run_shape=None, normalize=lambda bq, bk: (bq, bk))
        assert out == (256, 1024)

    def test_dedup_candidates_shared_helper(self):
        norm = lambda bq, bk: (min(bq, 128), min(bk, 128))
        # all collapse to (128, 128): one effective candidate
        assert autotune.dedup_candidates(
            [(256, 512), (128, 1024), (512, 512)], norm) == [(128, 128)]
        kept = autotune.dedup_candidates(
            [(256, 512), (128, 1024), (512, 512)], norm,
            keep_original=True)
        assert kept == [(256, 512)]

    def test_measure_effective_bw_returns_rate(self):
        bw = autotune.measure_effective_bw(nbytes=1 << 20, iters=2)
        assert bw is None or bw > 0
