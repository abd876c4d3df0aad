"""The plain reference of the GPT configurations: a pre-norm decoder-only
transformer (Radford et al. 2019; Brown et al. 2020) in straightforward
`jax.numpy`, float32, matmuls at `highest` precision — no kernel, no
cache, no batching trick. It imports nothing of the program and is
given nothing the program made: its weights come from the seed.

Departures from the papers, all shared with the program so that the two
compute the same model: learned absolute positions, tanh-approximated
GELU, tied input/output embedding, no dropout, biases initialised to 0
and the residual projections scaled by 1/sqrt(2 * layers).

It works layer by layer and in blocks of rows, so that the float32
1.3B model fits next to its activations on one 16 GB chip.

The *control* is this same code with every matmul operand rounded to
fp8 (e4m3, scaled per tensor): the nearest precision below the bf16
the configurations state. `correct` has to tell it from the program.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from . import weights

LEAVES_PER_LAYER = 12


def param_specs(cfg: dict) -> list:
    """[(name, shape, init)] in the order of the parameter list. The
    names are the published structure's, spelled as the program spells
    them so that the harness can hand each array to its parameter."""
    h, v = cfg["hidden_size"], cfg["vocab_size"]
    n_layers, inter = cfg["num_layers"], cfg["intermediate_size"]
    std = cfg["initializer_range"]
    out_std = std / math.sqrt(2 * n_layers)
    one, zero = ("const", 1.0), ("const", 0.0)
    specs = [("gpt.embeddings.word_embeddings.weight", (v, h),
              ("normal", std)),
             ("gpt.embeddings.position_embeddings.weight",
              (cfg["max_position_embeddings"], h), ("normal", std))]
    for i in range(n_layers):
        p = f"gpt.layers.{i}."
        specs += [
            (p + "ln1.weight", (h,), one), (p + "ln1.bias", (h,), zero),
            (p + "attn.qkv_proj.weight", (h, 3 * h), ("normal", std)),
            (p + "attn.qkv_proj.bias", (3 * h,), zero),
            (p + "attn.out_proj.weight", (h, h), ("normal", out_std)),
            (p + "attn.out_proj.bias", (h,), zero),
            (p + "ln2.weight", (h,), one), (p + "ln2.bias", (h,), zero),
            (p + "mlp.fc1.weight", (h, inter), ("normal", std)),
            (p + "mlp.fc1.bias", (inter,), zero),
            (p + "mlp.fc2.weight", (inter, h), ("normal", out_std)),
            (p + "mlp.fc2.bias", (h,), zero)]
    specs += [("gpt.final_norm.weight", (h,), one),
              ("gpt.final_norm.bias", (h,), zero)]
    return specs


# -- the two arithmetics ----------------------------------------------------
def exact(x):
    return x


def fp8(x):
    """Round to float8 e4m3 with one scale per tensor, as an fp8 matmul
    path would; straight-through for gradients."""
    scale = jax.lax.stop_gradient(
        jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _ein(spec, a, b, rnd):
    return jnp.einsum(spec, rnd(a), rnd(b),
                      precision=jax.lax.Precision.HIGHEST,
                      preferred_element_type=jnp.float32)


# -- the model --------------------------------------------------------------
def _layer_norm(x, w, b, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * w + b


def _gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def block(p, x, *, heads, eps, rnd):
    """One decoder block on x [rows, seq, hidden]; p: its 12 leaves."""
    r, s, h = x.shape
    d = h // heads
    y = _layer_norm(x, p[0], p[1], eps)
    qkv = (_ein("rsh,hk->rsk", y, p[2], rnd) + p[3]).reshape(
        r, s, 3, heads, d)
    q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
    att = _ein("rqnd,rknd->rnqk", q, k, rnd) / math.sqrt(d)
    causal = jnp.tril(jnp.ones((s, s), bool))
    att = jax.nn.softmax(jnp.where(causal, att, -jnp.inf), axis=-1)
    o = _ein("rnqk,rknd->rqnd", att, v, rnd).reshape(r, s, h)
    x = x + _ein("rsh,hk->rsk", o, p[4], rnd) + p[5]
    y = _layer_norm(x, p[6], p[7], eps)
    y = _gelu_tanh(_ein("rsh,hk->rsk", y, p[8], rnd) + p[9])
    return x + _ein("rsk,kh->rsh", y, p[10], rnd) + p[11]


def embed(wte, wpe, ids):
    return wte[ids] + wpe[jnp.arange(ids.shape[1])][None]


def head_logits(x, lnw, lnb, wte, *, eps, rnd):
    return _ein("...h,vh->...v", _layer_norm(x, lnw, lnb, eps), wte, rnd)


def head_loss(x, lnw, lnb, wte, labels, *, eps, rnd):
    """Sum (not mean) of the next-token cross-entropy over x's rows."""
    logits = head_logits(x, lnw, lnb, wte, eps=eps, rnd=rnd)
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.sum(jnp.take_along_axis(logp, labels[..., None], -1))


class Model:
    """The reference model of one configuration and one seed."""

    def __init__(self, cfg: dict, seed: int, dtype="float32", rnd=exact):
        self.cfg = cfg
        self.seed = seed
        self.specs = param_specs(cfg)
        # drawn in `dtype` (the type the configuration holds them in),
        # computed with in float32
        self.params = [p.astype(jnp.float32) for p in
                       weights.make(seed, self.specs, jnp.dtype(dtype))]
        kw = dict(eps=cfg["layer_norm_eps"], rnd=rnd)
        self._block = jax.jit(functools.partial(
            block, heads=cfg["num_heads"], **kw))
        self._embed = jax.jit(embed)
        self._logits = jax.jit(functools.partial(head_logits, **kw))
        self._kw = kw

    def layer(self, i):
        lo = 2 + i * LEAVES_PER_LAYER
        return self.params[lo:lo + LEAVES_PER_LAYER]

    def hidden(self, ids):
        x = self._embed(self.params[0], self.params[1], ids)
        for i in range(self.cfg["num_layers"]):
            x = self._block(self.layer(i), x)
        return x

    def logits_at(self, ids, positions):
        """ids [seq] -> float32 logits [len(positions), vocab]: one full
        forward over the sequence, the head only where it is asked."""
        x = self.hidden(jnp.asarray(ids, jnp.int32)[None])[0]
        return self._logits(x[jnp.asarray(positions)], self.params[-2],
                            self.params[-1], self.params[0])


class Trainer(Model):
    """The training reference: loss, gradients and AdamW, one layer and
    one block of rows at a time, for `n_steps` steps. What it keeps
    between steps is cut to what the next step needs, so that 1.3B
    float32 parameters and their optimizer state fit one chip without a
    trip to the host: after the first step only the first moment (the
    second is then exactly (1 - b2) * (m / (1 - b1))**2, as both are
    multiples of the first gradient and its square), and after the last
    step nothing."""

    def __init__(self, cfg, seed, opt: dict, n_steps: int, rnd=exact,
                 row_block=1):
        super().__init__(cfg, seed, "float32", rnd)
        self.opt = opt
        self.n_steps = n_steps
        self.row_block = row_block
        self.m = [None] * len(self.params)
        self.v = [None] * len(self.params)
        self.t = 0
        kw = self._kw

        def block_vjp(p, x, dy):
            y, back = jax.vjp(functools.partial(
                block, heads=cfg["num_heads"], **kw), p, x)
            return back(dy)

        def head_vjp(x, lnw, lnb, wte, labels):
            return jax.value_and_grad(
                functools.partial(head_loss, **kw), argnums=(0, 1, 2, 3))(
                    x, lnw, lnb, wte, labels)

        self._block_vjp = jax.jit(block_vjp)
        self._head_vjp = jax.jit(head_vjp)
        self._adamw = jax.jit(functools.partial(
            adamw, lr=opt["learning_rate"], b1=opt["beta1"],
            b2=opt["beta2"], eps=opt["epsilon"], wd=opt["weight_decay"]),
            static_argnames=("state",))
        self._add = jax.jit(lambda a, b: jax.tree.map(jnp.add, a, b))

    def _update(self, i, g, count):
        """AdamW on leaf i with the gradient g / count; returns the
        gradient's norm. Keeps of the state only what the next step
        needs (see the class)."""
        state = {1: "none", 2: "first_moment"}.get(self.t, "both")
        self.params[i], m, v, norm = self._adamw(
            self.params[i], g, self.m[i], self.v[i], self.t, count,
            state=state)
        if self.t == self.n_steps:
            m = v = None
        elif self.t == 1:
            v = None
        self.m[i], self.v[i] = m, v
        return norm

    def step(self, ids, labels):
        """One optimizer step on ids/labels [rows, seq]. Returns the mean
        loss and the norm of every leaf's gradient."""
        self.t += 1
        rows, n_layers = ids.shape[0], self.cfg["num_layers"]
        rb, count = self.row_block, float(ids.size)
        blocks = [slice(a, min(a + rb, rows)) for a in range(0, rows, rb)]
        ids = [jnp.asarray(ids[b]) for b in blocks]
        labels = [jnp.asarray(labels[b]) for b in blocks]
        # forward, keeping every layer's input, block of rows by block
        xs = [[self._embed(self.params[0], self.params[1], i) for i in ids]]
        for i in range(n_layers):
            lay = self.layer(i)
            xs.append([self._block(lay, x) for x in xs[-1]])
        loss, dx, g_head = 0.0, [], None
        for x, y in zip(xs[-1], labels):
            part, g = self._head_vjp(x, self.params[-2], self.params[-1],
                                     self.params[0], y)
            loss = loss + part / count
            dx.append(g[0])
            g_head = g[1:] if g_head is None else self._add(g_head, g[1:])
        norms = [None] * len(self.params)
        last = len(self.params) - 1
        norms[last - 1] = self._update(last - 1, g_head[0], count)
        norms[last] = self._update(last, g_head[1], count)
        # backward, layer by layer, each updated as soon as it is known
        for i in reversed(range(n_layers)):
            lay = self.layer(i)
            g_lay = None
            for b, (x, d) in enumerate(zip(xs[i], dx)):
                gp, dx[b] = self._block_vjp(lay, x, d)
                g_lay = gp if g_lay is None else self._add(g_lay, gp)
            xs[i + 1] = None
            for j, g in enumerate(g_lay):
                k = 2 + i * LEAVES_PER_LAYER + j
                norms[k] = self._update(k, g, count)
        g_wte, g_wpe = self._embed_grads(g_head[2], self.params[1], ids, dx)
        norms[1] = self._update(1, g_wpe, count)
        norms[0] = self._update(0, g_wte, count)
        return float(loss), [float(n) for n in jax.device_get(norms)]

    @staticmethod
    @jax.jit
    def _embed_grads(g_wte, wpe, ids, dx):
        """The embedding tables' gradients: the head's share of the tied
        token table plus the rows looked up; positions summed over rows."""
        g_wpe = jnp.zeros_like(wpe)
        for i, d in zip(ids, dx):
            g_wte = g_wte.at[i.reshape(-1)].add(d.reshape(-1, d.shape[-1]))
            g_wpe = g_wpe.at[:d.shape[1]].add(jnp.sum(d, axis=0))
        return g_wte, g_wpe

    def change_norms(self):
        return weights.change_norms(self.params, self.specs, self.seed)


def adamw(p, g, m, v, t, count, *, lr, b1, b2, eps, wd, state):
    """AdamW with decoupled decay on every leaf (Loshchilov & Hutter) for
    the gradient g / count. `state` says what was kept: "none" (first
    step: both moments are zero), "first_moment" (second step: the second
    moment is the first one's square, rescaled) or "both". Returns the
    new leaf, the moments and the gradient's norm."""
    g = g / count
    if state == "none":
        m = v = jnp.zeros_like(p)
    elif state == "first_moment":
        v = (1 - b2) * jnp.square(m / (1 - b1))
    m = b1 * m + (1 - b1) * g
    v = b2 * v + (1 - b2) * jnp.square(g)
    m_hat = m / (1 - b1 ** t)
    v_hat = v / (1 - b2 ** t)
    p = p - lr * m_hat / (jnp.sqrt(v_hat) + eps) - lr * wd * p
    return p, m, v, jnp.sqrt(jnp.sum(jnp.square(g)))
