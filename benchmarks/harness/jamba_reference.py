"""The plain reference of the Jamba configurations: a hybrid of Mamba-1
state-space layers and attention layers (Lieber et al. 2024; Gu & Dao
2023; the `jamba` model type of the public config.json) in
straightforward `jax.numpy`, float32, matmuls at `highest` precision: no
kernel, no cache, no batching trick. It imports nothing of the program
and is given nothing the program made: its weights come from the seed.

    layer i:  h = x + Mixer_i(RMSNorm(x));  out = h + MLP(RMSNorm(h))
    Mixer_i:  attention where i % attn_layer_period == attn_layer_offset,
              Mamba elsewhere;  final RMSNorm;  logits = hidden . E^T
    MLP:      down(silu(gate(u)) * up(u)), no bias
    Mamba:    [xs, z] = u W_in;  xc = silu(conv(xs)), causal depthwise,
              d_conv taps, bias;  [r, B, C] = xc W_x, each RMSNormed with
              its own weight;  delta = softplus(r W_dt + b_dt);
              A = -exp(A_log);  h_t = exp(delta_t A) h_{t-1}
              + (delta_t xc_t) (x) B_t from h_0 = 0;  y_t = h_t . C_t
              + D xc_t;  out = (y * silu(z)) W_out
    attention: q = u W_q (heads x d), k, v = u W_k, u W_v (kv heads x d),
              causal softmax(q k^T / sqrt(d)) v, W_o; no bias, no
              positional signal of any kind

The recurrence is a literal `lax.scan` over time steps. Departures, none
of them in the mathematics: the time loop is a loop over stretches of
`SCAN_STRETCH` steps, each under `jax.checkpoint`, and attention is a
loop over blocks of `HEAD_BLOCK` query heads, each under
`jax.checkpoint`, so that a layer's backward holds one stretch's states
and one head block's scores beside 6.4 GB of float32 parameters and
6.4 GB of first moment on a 16 GB chip; the head and the loss go through
`TOKEN_BLOCK` tokens at a time for the same reason. Only dense
feed-forwards exist (num_experts 1).

The *control* is this same code with every matmul operand rounded to
fp8 (e4m3, scaled per tensor; `gpt_reference.fp8`, as are `exact`, the
einsum at `highest` precision and AdamW): the nearest precision below
the bf16 the configurations state. The recurrence is no matmul and stays float32 in
the control too. `correct` has to tell the control from the program.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from . import weights
from .gpt_reference import _ein, adamw, exact, fp8  # noqa: F401

SCAN_STRETCH = 256      # time steps whose states one backward holds
HEAD_BLOCK = 4          # query heads whose scores exist at once
TOKEN_BLOCK = 1024      # tokens whose logits exist at once


# -- the parameter list -------------------------------------------------------
def is_attention(cfg: dict, i: int) -> bool:
    return i % cfg["attn_layer_period"] == cfg["attn_layer_offset"]


def head_dim(cfg: dict) -> int:
    return cfg.get("head_dim") or \
        cfg["hidden_size"] // cfg["num_attention_heads"]


def layer_specs(cfg: dict, i: int) -> list:
    """[(name, shape, init)] of layer i, in the order the program lists
    a layer's parameters. Inits as the configuration's `assumed` says."""
    h, inter = cfg["hidden_size"], cfg["intermediate_size"]
    std = cfg["initializer_range"]
    w, one = ("normal", std), ("const", 1.0)
    p = f"jamba.layers.{i}."
    specs = [(p + "input_layernorm.weight", (h,), one)]
    if is_attention(cfg, i):
        d = head_dim(cfg)
        q, kv = cfg["num_attention_heads"] * d, cfg["num_key_value_heads"] * d
        specs += [(p + "attn.q_proj.weight", (h, q), w),
                  (p + "attn.k_proj.weight", (h, kv), w),
                  (p + "attn.v_proj.weight", (h, kv), w),
                  (p + "attn.o_proj.weight", (q, h), w)]
    else:
        e = cfg["mamba_expand"] * h
        n, r = cfg["mamba_d_state"], cfg["mamba_dt_rank"]
        m = p + "mamba."
        specs += [(m + "A_log", (e, n), ("normal", 1.0)),
                  (m + "D", (e,), one),
                  (m + "in_proj.weight", (h, 2 * e), w),
                  (m + "conv1d.weight", (e, cfg["mamba_d_conv"]), w),
                  (m + "conv1d.bias", (e,), ("const", 0.0)),
                  (m + "x_proj.weight", (e, r + 2 * n), w),
                  (m + "dt_layernorm.weight", (r,), one),
                  (m + "b_layernorm.weight", (n,), one),
                  (m + "c_layernorm.weight", (n,), one),
                  (m + "dt_proj.weight", (r, e), w),
                  (m + "dt_proj.bias", (e,), ("const", -4.6)),
                  (m + "out_proj.weight", (e, h), w)]
    specs += [(p + "pre_ff_layernorm.weight", (h,), one),
              (p + "mlp.gate_proj.weight", (h, inter), w),
              (p + "mlp.up_proj.weight", (h, inter), w),
              (p + "mlp.down_proj.weight", (inter, h), w)]
    return specs


def param_specs(cfg: dict) -> list:
    specs = [("jamba.embed_tokens.weight",
              (cfg["vocab_size"], cfg["hidden_size"]),
              ("normal", cfg["initializer_range"]))]
    for i in range(cfg["num_hidden_layers"]):
        specs += layer_specs(cfg, i)
    return specs + [("jamba.final_layernorm.weight",
                     (cfg["hidden_size"],), ("const", 1.0))]


# -- the model --------------------------------------------------------------
def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * w


def silu(x):
    return x * jax.nn.sigmoid(x)


def mlp(p, u, rnd):
    gate, up, down = p
    return _ein("rsk,kh->rsh", silu(_ein("rsh,hk->rsk", u, gate, rnd))
                * _ein("rsh,hk->rsk", u, up, rnd), down, rnd)


def attention(p, u, *, heads, kv_heads, d, rnd):
    wq, wk, wv, wo = p
    r, s, _ = u.shape
    q = _ein("rsh,hk->rsk", u, wq, rnd).reshape(r, s, heads, d)
    k = _ein("rsh,hk->rsk", u, wk, rnd).reshape(r, s, kv_heads, d)
    v = _ein("rsh,hk->rsk", u, wv, rnd).reshape(r, s, kv_heads, d)
    group = heads // kv_heads           # query heads to a key/value head
    hb = max(n for n in range(1, HEAD_BLOCK + 1) if group % n == 0)
    causal = jnp.tril(jnp.ones((s, s), bool))

    @jax.checkpoint
    def some_heads(qkv):                # q [r, s, hb, d]; k, v [r, s, d]
        q, k, v = qkv
        att = _ein("rqnd,rkd->rnqk", q, k, rnd) / math.sqrt(d)
        att = jax.nn.softmax(jnp.where(causal, att, -jnp.inf), axis=-1)
        return _ein("rnqk,rkd->rqnd", att, v, rnd)

    # block b of query heads reads key/value head b // (group / hb)
    shared = jnp.repeat(jnp.arange(kv_heads), group // hb)
    qb = jnp.moveaxis(q.reshape(r, s, heads // hb, hb, d), 2, 0)
    o = jax.lax.map(some_heads, (qb, jnp.moveaxis(k, 2, 0)[shared],
                                 jnp.moveaxis(v, 2, 0)[shared]))
    o = jnp.moveaxis(o, 0, 2).reshape(r, s, heads * d)
    return _ein("rsk,kh->rsh", o, wo, rnd)


def causal_conv(xs, w, b):
    """out[t] = b + sum_k w[:, k] * xs[t - (K-1) + k], xs before 0 zero."""
    taps, s = w.shape[1], xs.shape[1]
    padded = jnp.pad(xs, ((0, 0), (taps - 1, 0), (0, 0)))
    return b + sum(padded[:, k:k + s] * w[:, k] for k in range(taps))


def recurrence(xc, delta, A, B, C, D):
    """The selective state-space recurrence, one time step after the
    other. xc, delta [r, s, e]; A [e, n]; B, C [r, s, n]; D [e]. The
    state is held as [r, n, e]."""
    At = A.T

    def step(h, inp):
        x_t, d_t, b_t, c_t = inp
        h = jnp.exp(d_t[:, None, :] * At) * h \
            + b_t[:, :, None] * (d_t * x_t)[:, None, :]
        return h, jnp.sum(h * c_t[:, :, None], axis=1) + D * x_t

    @jax.checkpoint
    def stretch(h, inp):
        return jax.lax.scan(step, h, inp)

    r, s, e = xc.shape
    seq = [jnp.moveaxis(a, 1, 0) for a in (xc, delta, B, C)]
    h = jnp.zeros((r, A.shape[1], e), jnp.float32)
    if s % SCAN_STRETCH:                # a short sequence: one stretch
        _h, y = stretch(h, seq)
    else:
        _h, y = jax.lax.scan(stretch, h, [
            a.reshape((s // SCAN_STRETCH, SCAN_STRETCH) + a.shape[1:])
            for a in seq])
        y = y.reshape((s,) + y.shape[2:])
    return jnp.moveaxis(y, 0, 1)


def mamba(p, u, *, n, rank, eps, rnd):
    (a_log, D, w_in, conv_w, conv_b, w_x, ln_dt, ln_b, ln_c, w_dt, b_dt,
     w_out) = p
    xs, z = jnp.split(_ein("rsh,hk->rsk", u, w_in, rnd), 2, axis=-1)
    xc = silu(causal_conv(xs, conv_w, conv_b))
    rbc = _ein("rse,ek->rsk", xc, w_x, rnd)
    r, B, C = rbc[..., :rank], rbc[..., rank:rank + n], rbc[..., rank + n:]
    r, B, C = (rms_norm(r, ln_dt, eps), rms_norm(B, ln_b, eps),
               rms_norm(C, ln_c, eps))
    delta = jax.nn.softplus(_ein("rsk,ke->rse", r, w_dt, rnd) + b_dt)
    y = recurrence(xc, delta, -jnp.exp(a_log), B, C, D)
    return _ein("rse,eh->rsh", y * silu(z), w_out, rnd)


def block(p, x, *, cfg, attn: bool, rnd):
    """One layer on x [rows, seq, hidden]; p: its leaves in list order."""
    eps = cfg["rms_norm_eps"]
    u = rms_norm(x, p[0], eps)
    if attn:
        x = x + attention(p[1:5], u, heads=cfg["num_attention_heads"],
                          kv_heads=cfg["num_key_value_heads"],
                          d=head_dim(cfg), rnd=rnd)
    else:
        x = x + mamba(p[1:13], u, n=cfg["mamba_d_state"],
                      rank=cfg["mamba_dt_rank"], eps=eps, rnd=rnd)
    return x + mlp(p[-3:], rms_norm(x, p[-4], eps), rnd)


def head_logits(x, lnw, wte, *, eps, rnd):
    return _ein("...h,vh->...v", rms_norm(x, lnw, eps), wte, rnd)


def head_loss(x, lnw, wte, labels, *, eps, rnd):
    """Sum (not mean) of the next-token cross-entropy over x's tokens."""
    logp = jax.nn.log_softmax(head_logits(x, lnw, wte, eps=eps, rnd=rnd),
                              axis=-1)
    return -jnp.sum(jnp.take_along_axis(logp, labels[..., None], -1))


class Model:
    """The reference model of one configuration and one seed."""

    def __init__(self, cfg: dict, seed: int, dtype="float32", rnd=exact):
        self.cfg = cfg
        self.seed = seed
        self.specs = param_specs(cfg)
        self.params = [p.astype(jnp.float32) for p in
                       weights.make(seed, self.specs, jnp.dtype(dtype))]
        self.bounds, lo = [], 1
        for i in range(cfg["num_hidden_layers"]):
            n = len(layer_specs(cfg, i))
            self.bounds.append((lo, lo + n))
            lo += n
        self._kw = dict(eps=cfg["rms_norm_eps"], rnd=rnd)
        self._kinds = {attn: functools.partial(block, cfg=cfg, attn=attn,
                                               rnd=rnd)
                       for attn in (False, True)}
        self._blocks = {a: jax.jit(f) for a, f in self._kinds.items()}
        self._logits = jax.jit(functools.partial(head_logits, **self._kw))

    def layer(self, i):
        lo, hi = self.bounds[i]
        return self.params[lo:hi]

    def kind(self, i):
        return is_attention(self.cfg, i)

    def hidden(self, ids):
        x = self.params[0][ids]
        for i in range(self.cfg["num_hidden_layers"]):
            x = self._blocks[self.kind(i)](self.layer(i), x)
        return x

    def logits(self, ids):
        """ids [rows, seq] -> float32 logits [rows, seq, vocab]."""
        return self._logits(self.hidden(jnp.asarray(ids, jnp.int32)),
                            self.params[-1], self.params[0])


class Trainer(Model):
    """The training reference: loss, gradients and AdamW, one layer and
    one block of rows at a time, for `n_steps` steps. What it keeps
    between steps is cut to what the next step needs, so that the
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

        def block_vjp(fn, p, x, dy):
            _y, back = jax.vjp(fn, p, x)
            return back(dy)

        self._block_vjps = {a: jax.jit(functools.partial(block_vjp, f))
                            for a, f in self._kinds.items()}
        self._head_vjp = jax.jit(jax.value_and_grad(
            functools.partial(head_loss, **self._kw), argnums=(0, 1, 2)))
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

    def _head(self, x, labels):
        """Loss and gradients of the head over one block of rows, the
        tokens `TOKEN_BLOCK` at a time."""
        loss, dx, g = 0.0, [], None
        for lo in range(0, x.shape[1], TOKEN_BLOCK):
            sl = slice(lo, lo + TOKEN_BLOCK)
            part, gs = self._head_vjp(x[:, sl], self.params[-1],
                                      self.params[0], labels[:, sl])
            loss = loss + part
            dx.append(gs[0])
            g = gs[1:] if g is None else self._add(g, gs[1:])
        return loss, jnp.concatenate(dx, axis=1), g

    def step(self, ids, labels):
        """One optimizer step on ids/labels [rows, seq]. Returns the mean
        loss and the norm of every leaf's gradient."""
        self.t += 1
        rows, n_layers = ids.shape[0], self.cfg["num_hidden_layers"]
        rb, count = self.row_block, float(ids.size)
        blocks = [slice(a, min(a + rb, rows)) for a in range(0, rows, rb)]
        ids = [jnp.asarray(ids[b]) for b in blocks]
        labels = [jnp.asarray(labels[b]) for b in blocks]
        # forward, keeping every layer's input, block of rows by block
        xs = [[self.params[0][i] for i in ids]]
        for i in range(n_layers):
            lay, fn = self.layer(i), self._blocks[self.kind(i)]
            xs.append([fn(lay, x) for x in xs[-1]])
        loss, dx, g_head = 0.0, [], None
        for x, y in zip(xs[-1], labels):
            part, d, g = self._head(x, y)
            loss = loss + part / count
            dx.append(d)
            g_head = g if g_head is None else self._add(g_head, g)
        norms = [None] * len(self.params)
        last = len(self.params) - 1
        norms[last] = self._update(last, g_head[0], count)
        # backward, layer by layer, each updated as soon as it is known
        for i in reversed(range(n_layers)):
            lay, vjp = self.layer(i), self._block_vjps[self.kind(i)]
            g_lay = None
            for b, (x, d) in enumerate(zip(xs[i], dx)):
                gp, dx[b] = vjp(lay, x, d)
                g_lay = gp if g_lay is None else self._add(g_lay, gp)
            xs[i + 1] = None
            for j, g in enumerate(g_lay):
                k = self.bounds[i][0] + j
                norms[k] = self._update(k, g, count)
            del g_lay
        norms[0] = self._update(
            0, self._embed_grad(g_head[1], ids, dx), count)
        return float(loss), [float(n) for n in jax.device_get(norms)]

    @staticmethod
    @jax.jit
    def _embed_grad(g_wte, ids, dx):
        """The embedding's gradient: the head's share of the tied table
        plus the rows looked up."""
        for i, d in zip(ids, dx):
            g_wte = g_wte.at[i.reshape(-1)].add(d.reshape(-1, d.shape[-1]))
        return g_wte

    def change_norms(self):
        return weights.change_norms(self.params, self.specs, self.seed)
