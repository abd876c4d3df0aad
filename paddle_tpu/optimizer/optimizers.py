"""Concrete optimizers (ref: python/paddle/optimizer/{sgd,momentum,adam,
adamw,lamb}.py). Update rules are pure jax — reused by both eager step()
and the jit train-step compiler."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .optimizer import Optimizer


class SGD(Optimizer):
    def __init__(self, learning_rate=0.001, parameters=None,
                 weight_decay=None, grad_clip=None, multi_precision=False,
                 name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip,
                         multi_precision, name)

    def _update_rule(self, param, grad, state, lr, group):
        grad = self._apply_decay(param, grad, group)
        return param - lr * grad, state


class Momentum(Optimizer):
    def __init__(self, learning_rate=0.001, momentum=0.9, parameters=None,
                 use_nesterov=False, weight_decay=None, grad_clip=None,
                 multi_precision=False, name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip,
                         multi_precision, name)
        self.momentum = momentum
        self.use_nesterov = use_nesterov

    def _hyper_fingerprint(self):
        return super()._hyper_fingerprint() + (self.momentum,
                                               self.use_nesterov)

    def _state_names(self):
        return ["velocity"]

    def _init_state(self, p):
        return {"velocity": jnp.zeros_like(
            self._master(p) if self._master(p) is not None else p._data)}

    def _update_rule(self, param, grad, state, lr, group):
        grad = self._apply_decay(param, grad, group)
        v = state.get("velocity")
        if v is None:
            v = jnp.zeros_like(param)
        v = self.momentum * v + grad
        if self.use_nesterov:
            update = grad + self.momentum * v
        else:
            update = v
        return param - lr * update, {"velocity": v}


class Adam(Optimizer):
    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-8, parameters=None, weight_decay=None,
                 grad_clip=None, lazy_mode=False, multi_precision=False,
                 use_multi_tensor=False, moment_dtype=None, name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip,
                         multi_precision, name)
        self.beta1 = beta1
        self.beta2 = beta2
        self.epsilon = epsilon
        # moment_dtype='bfloat16' stores m/v in bf16 (update math stays
        # f32): 8 bytes/param instead of 4+4 f32 — the HBM lever that lets
        # billion-parameter configs train on one 16GB chip (same trade the
        # reference ships as multi-tensor fp16 moments in
        # paddle/phi/kernels/gpu/adamw_kernel.cu's MP path, inverted for
        # TPU where params stay f32 and moments shrink)
        self.moment_dtype = moment_dtype

    def _hyper_fingerprint(self):
        return super()._hyper_fingerprint() + (self.beta1, self.beta2,
                                               self.epsilon)

    def _state_names(self):
        return ["moment1", "moment2", "beta1_pow", "beta2_pow"]

    def _moment_dtype(self, base):
        if self.moment_dtype is None:
            return base.dtype
        from ..core import dtype as dtypes
        return dtypes.to_jnp(self.moment_dtype)

    def _init_state(self, p):
        base = self._master(p) if self._master(p) is not None else p._data
        mdt = self._moment_dtype(base)
        # a moment is born where its parameter lives: one laid over a
        # mesh has moments in the same shards, never whole on one device
        laid = getattr(base, "sharding", None)
        if not isinstance(laid, jax.sharding.NamedSharding):
            laid = None
        return {
            "moment1": jnp.zeros(base.shape, mdt, device=laid),
            "moment2": jnp.zeros(base.shape, mdt, device=laid),
            "beta1_pow": jnp.asarray(1.0, jnp.float32),
            "beta2_pow": jnp.asarray(1.0, jnp.float32),
        }

    def _decayed_grad(self, param, grad, group):
        return self._apply_decay(param, grad, group)

    def _update_rule(self, param, grad, state, lr, group):
        grad = self._decayed_grad(param, grad, group)
        mdt = state["moment1"].dtype
        m = state["moment1"].astype(jnp.float32)
        v = state["moment2"].astype(jnp.float32)
        grad32 = grad.astype(jnp.float32)
        b1p = state["beta1_pow"] * self.beta1
        b2p = state["beta2_pow"] * self.beta2
        m = self.beta1 * m + (1 - self.beta1) * grad32
        v = self.beta2 * v + (1 - self.beta2) * jnp.square(grad32)
        m_hat = m / (1 - b1p)
        v_hat = v / (1 - b2p)
        upd = (lr * m_hat / (jnp.sqrt(v_hat) + self.epsilon)).astype(
            param.dtype)
        new_param = param - upd
        new_param = self._post_update(new_param, param, lr, group)
        return new_param, {"moment1": m.astype(mdt), "moment2": v.astype(mdt),
                           "beta1_pow": b1p, "beta2_pow": b2p}

    def _post_update(self, new_param, param, lr, group):
        return new_param


class AdamW(Adam):
    """Decoupled weight decay (ref: python/paddle/optimizer/adamw.py:40)."""

    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-8, parameters=None, weight_decay=0.01,
                 lr_ratio=None, apply_decay_param_fun=None, grad_clip=None,
                 lazy_mode=False, multi_precision=False, moment_dtype=None,
                 name=None):
        super().__init__(learning_rate, beta1, beta2, epsilon, parameters,
                         None, grad_clip, lazy_mode, multi_precision,
                         moment_dtype=moment_dtype)
        self.weight_decay = weight_decay or 0.0
        self.apply_decay_param_fun = apply_decay_param_fun
        self._current_param_name = None

    def _decayed_grad(self, param, grad, group):
        return grad  # decoupled: no L2 into grad

    def step(self):
        # track param names for apply_decay_param_fun
        super().step()

    def _update_rule(self, param, grad, state, lr, group):
        new_param, new_state = super()._update_rule(param, grad, state, lr,
                                                    group)
        return new_param, new_state

    def _post_update(self, new_param, param, lr, group):
        wd = group.get("weight_decay", self.weight_decay) or 0.0
        if wd and self._decay_applies():
            new_param = new_param - lr * wd * param
        return new_param

    def _decay_applies(self):
        if self.apply_decay_param_fun is None:
            return True
        if self._current_param_name is None:
            return True
        return self.apply_decay_param_fun(self._current_param_name)


class Adamax(Optimizer):
    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-8, parameters=None, weight_decay=None,
                 grad_clip=None, name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip)
        self.beta1, self.beta2, self.epsilon = beta1, beta2, epsilon

    def _hyper_fingerprint(self):
        return super()._hyper_fingerprint() + (self.beta1, self.beta2,
                                               self.epsilon)

    def _state_names(self):
        return ["moment", "inf_norm", "beta1_pow"]

    def _init_state(self, p):
        return {"moment": jnp.zeros_like(p._data),
                "inf_norm": jnp.zeros_like(p._data),
                "beta1_pow": jnp.asarray(1.0, jnp.float32)}

    def _update_rule(self, param, grad, state, lr, group):
        grad = self._apply_decay(param, grad, group)
        m = self.beta1 * state["moment"] + (1 - self.beta1) * grad
        u = jnp.maximum(self.beta2 * state["inf_norm"], jnp.abs(grad))
        b1p = state["beta1_pow"] * self.beta1
        new_param = param - lr / (1 - b1p) * m / (u + self.epsilon)
        return new_param, {"moment": m, "inf_norm": u, "beta1_pow": b1p}


class Adagrad(Optimizer):
    def __init__(self, learning_rate, epsilon=1e-6, parameters=None,
                 weight_decay=None, grad_clip=None,
                 initial_accumulator_value=0.0, name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip)
        self.epsilon = epsilon
        self.initial_accumulator_value = initial_accumulator_value

    def _hyper_fingerprint(self):
        return super()._hyper_fingerprint() + (self.epsilon,)

    def _state_names(self):
        return ["moment"]

    def _init_state(self, p):
        return {"moment": jnp.full_like(p._data,
                                        self.initial_accumulator_value)}

    def _update_rule(self, param, grad, state, lr, group):
        grad = self._apply_decay(param, grad, group)
        mom = state["moment"] + jnp.square(grad)
        return param - lr * grad / (jnp.sqrt(mom) + self.epsilon), {
            "moment": mom}


class RMSProp(Optimizer):
    def __init__(self, learning_rate, rho=0.95, epsilon=1e-6, momentum=0.0,
                 centered=False, parameters=None, weight_decay=None,
                 grad_clip=None, name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip)
        self.rho, self.epsilon = rho, epsilon
        self.momentum, self.centered = momentum, centered

    def _hyper_fingerprint(self):
        return super()._hyper_fingerprint() + (self.rho, self.epsilon,
                                               self.momentum,
                                               self.centered)

    def _state_names(self):
        return ["mean_square", "mean_grad", "momentum_acc"]

    def _init_state(self, p):
        return {"mean_square": jnp.zeros_like(p._data),
                "mean_grad": jnp.zeros_like(p._data),
                "momentum_acc": jnp.zeros_like(p._data)}

    def _update_rule(self, param, grad, state, lr, group):
        grad = self._apply_decay(param, grad, group)
        ms = self.rho * state["mean_square"] + (1 - self.rho) * jnp.square(grad)
        if self.centered:
            mg = self.rho * state["mean_grad"] + (1 - self.rho) * grad
            denom = jnp.sqrt(ms - jnp.square(mg) + self.epsilon)
        else:
            mg = state["mean_grad"]
            denom = jnp.sqrt(ms + self.epsilon)
        mom = self.momentum * state["momentum_acc"] + lr * grad / denom
        return param - mom, {"mean_square": ms, "mean_grad": mg,
                             "momentum_acc": mom}


class Lamb(Optimizer):
    """(ref: python/paddle/optimizer/lamb.py; fused native twin
    operators/optimizers/distributed_fused_lamb_op.cu)."""

    def __init__(self, learning_rate=0.001, lamb_weight_decay=0.01,
                 beta1=0.9, beta2=0.999, epsilon=1e-6, parameters=None,
                 grad_clip=None, exclude_from_weight_decay_fn=None,
                 multi_precision=False, name=None):
        super().__init__(learning_rate, parameters, None, grad_clip,
                         multi_precision)
        self.lamb_weight_decay = lamb_weight_decay
        self.beta1, self.beta2, self.epsilon = beta1, beta2, epsilon
        self.exclude_fn = exclude_from_weight_decay_fn

    def _hyper_fingerprint(self):
        return super()._hyper_fingerprint() + (self.lamb_weight_decay,
                                               self.beta1, self.beta2,
                                               self.epsilon)

    def _state_names(self):
        return ["moment1", "moment2", "beta1_pow", "beta2_pow"]

    def _init_state(self, p):
        return {"moment1": jnp.zeros_like(p._data),
                "moment2": jnp.zeros_like(p._data),
                "beta1_pow": jnp.asarray(1.0, jnp.float32),
                "beta2_pow": jnp.asarray(1.0, jnp.float32)}

    def _update_rule(self, param, grad, state, lr, group):
        m = self.beta1 * state["moment1"] + (1 - self.beta1) * grad
        v = self.beta2 * state["moment2"] + (1 - self.beta2) * jnp.square(grad)
        b1p = state["beta1_pow"] * self.beta1
        b2p = state["beta2_pow"] * self.beta2
        m_hat = m / (1 - b1p)
        v_hat = v / (1 - b2p)
        r = m_hat / (jnp.sqrt(v_hat) + self.epsilon)
        r = r + self.lamb_weight_decay * param
        w_norm = jnp.linalg.norm(param.reshape(-1))
        r_norm = jnp.linalg.norm(r.reshape(-1))
        trust = jnp.where((w_norm > 0) & (r_norm > 0), w_norm / r_norm, 1.0)
        return param - lr * trust * r, {
            "moment1": m, "moment2": v, "beta1_pow": b1p, "beta2_pow": b2p}


class AdamW8bitStub(AdamW):
    pass


class Adadelta(Optimizer):
    def __init__(self, learning_rate=0.001, epsilon=1e-6, rho=0.95,
                 parameters=None, weight_decay=None, grad_clip=None,
                 name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip)
        self.epsilon, self.rho = epsilon, rho

    def _hyper_fingerprint(self):
        return super()._hyper_fingerprint() + (self.epsilon, self.rho)

    def _state_names(self):
        return ["avg_squared_grad", "avg_squared_update"]

    def _init_state(self, p):
        return {"avg_squared_grad": jnp.zeros_like(p._data),
                "avg_squared_update": jnp.zeros_like(p._data)}

    def _update_rule(self, param, grad, state, lr, group):
        grad = self._apply_decay(param, grad, group)
        asg = self.rho * state["avg_squared_grad"] + (
            1 - self.rho) * jnp.square(grad)
        update = -jnp.sqrt(state["avg_squared_update"] + self.epsilon) / \
            jnp.sqrt(asg + self.epsilon) * grad
        asu = self.rho * state["avg_squared_update"] + (
            1 - self.rho) * jnp.square(update)
        return param + lr * update, {"avg_squared_grad": asg,
                                     "avg_squared_update": asu}
