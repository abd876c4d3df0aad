"""The plain reference of the ZAYA1 configurations (the `zaya` model type
of Zyphra's ZAYA1-8B config.json) in straightforward `jax.numpy`,
float32, matmuls at `highest` precision: no kernel, no sort, no
`lax.conv`, no cache. It imports nothing of the program and is given
nothing the program made: its weights come from the seed.

The config.json gives the sizes; the forms are those of Zyphra's
"Compressed Convolutional Attention" (arXiv:2510.04476) and the ZAYA1
technical report (arXiv:2511.17127), each listed under `assumed` in the
configuration's file. With d the hidden size, H query heads on Hk
key/value heads of d_h, G = H / Hk, u = RMSNorm(x) before each sublayer:

    attention (CCA, all of it in the compressed latent):
      q~ = u W_q [d, H d_h], k~ = u W_k [d, Hk d_h]
      v  = concat(u_t W_v1, u_{t-1} W_v2), each [d, d_h]   (u_{-1} = 0)
      z  = concat(q~, k~)                      (H + Hk heads of d_h)
      z'_t[c]  = a_0[c] z_{t-1}[c] + a_1[c] z_t[c] + b[c]    (depthwise)
      z''_t[h] = z'_{t-1}[h] A_0[h] + z'_t[h] A_1[h] + b'[h] (a head a
                 group, A_i[h] [d_h, d_h]); before the row's start: 0
      m_h = (q~_h + k~_{h // G}) / 2;  mbar_g = mean over g's heads of m_h
      q_h = z''_h + m_h;  k_g = z''_{H + g} + mbar_g
      q_h <- sqrt(d_h) q_h / |q_h|;  k_g <- tau_g sqrt(d_h) k_g / |k_g|
      rotate-half RoPE on the first d_h * partial_rotary_factor dims
      causal softmax(q k^T / sqrt(d_h)) v, query head h on key/value
      head h // G;  out = concat(heads) W_o [H d_h, d]
    feed-forward:
      r_l = u W_down [d, R] + gamma_l r_{l-1}      (r before layer 0: 0)
      p = softmax(gelu(gelu(r_l W_1) W_2) W_3)  over the published
          expert count; e = argmax p;  y = p_e SwiGLU_e(u)
    residual, each sublayer f:
      x <- (alpha_r * x + beta_r) + (alpha_o * f(u) + beta_o)
    final RMSNorm; logits = norm(x) E^T with the embedding E (tied).

The share (benchmarks/configs/*.json, `deployment`): this chip holds the
experts `expert_first` .. + `num_experts` of the published count and the
rows of the tied embedding below `vocab_size`. The routed term is
written as the equations have it, over the experts held here: for each
held expert, p_e where it is the argmax and zero elsewhere, times its
SwiGLU of every token. A token whose expert is held elsewhere gets
nothing from this sublayer but the residual's own vectors, here as in
the program.

Departures from the published description, none in the mathematics: the
four projections are one matrix [d, (H + 2 Hk) d_h] whose columns are
W_q | W_k | W_v1 | W_v2 (one seeded draw cut in four is four draws); the
two taps of the grouped convolution are one [2 d_h, d_h] matrix a head,
A_0 over A_1; attention runs a block of `Q_BLOCK` query rows and one
key/value head's group of query heads at a time, each under
`jax.checkpoint`, under explicit masks against all keys; the held
experts run one at a time, each under `jax.checkpoint`; the head and
the loss go through `TOKEN_BLOCK` tokens at a time: so that a layer's
backward fits one 16 GB chip beside the float32 parameters and a
moment. The argmax is `p >= max p` (no sort); ties have measure zero.
|x| is max(|x|, 1e-12) (`F.normalize`'s guard; never met).

The *control* is this same code with every matmul operand rounded to
fp8 (`gpt_reference.fp8`), the router's products included: the nearest
precision below the bf16 the configuration states.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from . import weights
from .gpt_reference import _ein, adamw, exact, fp8  # noqa: F401

Q_BLOCK = 1024          # query rows whose scores exist at once
TOKEN_BLOCK = 1024      # tokens whose logits exist at once
LAYER_LEAVES = 24       # leaves of one layer, in `layer_specs`' order


# -- the parameter list -------------------------------------------------------
def router_width(cfg: dict) -> int:
    """The router's outputs: the published expert count."""
    return cfg.get("published", {}).get("num_experts", cfg["num_experts"])


def latent(cfg: dict):
    """(query heads, key/value heads, head size)."""
    return (cfg["num_attention_heads"], cfg["num_key_value_heads"],
            cfg["head_dim"])


def layer_specs(cfg: dict, i: int) -> list:
    """[(name, shape, init)] of layer i, in the order the program lists
    a layer's parameters. The draws: `seeded_draws` of the
    configuration (why each: its `assumed.weights`). The router's
    down-projection is ("orthonormal", gain) and its MLP's three
    matrices ("paired", gain, rows, columns) (`leaf`)."""
    d, (H, Hk, dh) = cfg["hidden_size"], latent(cfg)
    R, wide = cfg["router_hidden_size"], cfg["moe_intermediate_size"]
    draw = cfg["seeded_draws"]
    one, zero = ("const", 1.0), ("const", 0.0)
    w = ("normal", cfg["initializer_range"])
    out = ("normal", draw["residual_output"])
    fan = lambda n: ("normal", 1.0 / math.sqrt(n))          # noqa: E731
    ch = (H + Hk) * dh
    p = f"zaya.layers.{i}."
    res = lambda s: [(p + s + ".alpha_r", (d,), one),          # noqa: E731
                     (p + s + ".beta_r", (d,), zero),
                     (p + s + ".alpha_o", (d,), one),
                     (p + s + ".beta_o", (d,), zero)]
    return [
        (p + "input_layernorm.weight", (d,), one),
        (p + "attn.conv_dw_weight", (2, ch), fan(2)),       # a_0, a_1
        (p + "attn.conv_dw_bias", (ch,), zero),
        (p + "attn.conv_group_weight", (H + Hk, 2 * dh, dh),
         fan(2 * dh)),                                      # A_0 over A_1
        (p + "attn.conv_group_bias", (ch,), zero),
        (p + "attn.temperature", (Hk,), one),
        (p + "attn.qkv_proj.weight", (d, (H + 2 * Hk) * dh), w),
        (p + "attn.o_proj.weight", (H * dh, d), out),
        *res("attn_res"),
        (p + "post_attention_layernorm.weight", (d,), one),
        (p + "moe.gate_up_proj", (cfg["num_experts"], d, 2 * wide), w),
        (p + "moe.down_proj", (cfg["num_experts"], wide, d), out),
        (p + "moe.router.down_proj", (d, R),
         ("orthonormal", draw["router_down"])),
        (p + "moe.router.eda_scale", (1,), one),            # gamma
        (p + "moe.router.fc1", (R, R),
         ("paired", draw["router_fc1"], False, True)),
        (p + "moe.router.fc2", (R, R),
         ("paired", draw["router_fc2"], True, True)),
        (p + "moe.router.fc3", (R, router_width(cfg)),
         ("paired", draw["router_out"], True, False)),
        *res("moe_res"),
    ]


def param_specs(cfg: dict) -> list:
    d = cfg["hidden_size"]
    specs = [("zaya.embed_tokens.weight", (cfg["vocab_size"], d),
              ("normal", cfg["seeded_draws"]["embedding"]))]
    for i in range(cfg["num_hidden_layers"]):
        specs += layer_specs(cfg, i)
    return specs + [("zaya.norm.weight", (d,),
                     ("const", cfg["seeded_draws"]["final_norm"]))]


# -- weights from the seed ---------------------------------------------------
def _orthonormal(key, index, shape):
    """Q of leaf `index`'s normal draw's QR, float32: orthonormal columns
    (the rows of a square one too, and of one wider than tall only
    they), with the sign that makes the factorisation unique."""
    a = weights.leaf(key, index, shape, ("normal", 1.0), jnp.float32)
    wide = shape[0] < shape[1]      # then the rows are orthonormal
    with jax.default_matmul_precision("highest"):
        q, r = jnp.linalg.qr(a.T if wide else a)
    q = q * jnp.sign(jnp.diagonal(r))       # R's diagonal > 0
    return q.T if wide else q


def leaf(key, index, shape, init, dtype):
    """`weights.leaf`, and two kinds of its own, each a function of the
    seed and the leaf alone, so that the program's glue and this
    reference make the same array without either taking the other's:

    ("orthonormal", gain): orthonormal columns times the gain.
    ("paired", gain, rows, cols): an orthonormal block B (of half the
    rows where `rows`, half the columns where `cols`) laid out as
    [B, -B] along the paired axes, times the gain. Hidden units then
    come in pairs (p, -p) that the next matrix reads with weights
    (w, -w), and gelu(p) - gelu(-p) = p exactly: an MLP drawn so is
    its own linear part, at any scale of its input."""
    if init[0] == "orthonormal":
        return (_orthonormal(key, index, shape) * init[1]).astype(dtype)
    if init[0] != "paired":
        return weights.leaf(key, index, shape, init, dtype)
    _kind, gain, rows, cols = init
    b = _orthonormal(key, index, (shape[0] // (1 + rows),
                                  shape[1] // (1 + cols)))
    if cols:
        b = jnp.concatenate([b, -b], axis=1)
    if rows:
        b = jnp.concatenate([b, -b], axis=0)
    return (b * gain).astype(dtype)


def make(seed: int, specs, dtype):
    """`weights.make` with this module's `leaf`: all leaves, one
    program."""
    key = weights.key_of(seed)

    @jax.jit
    def build(key):
        return [leaf(key, i, tuple(s), tuple(init), dtype)
                for i, (_n, s, init) in enumerate(specs)]

    return build(key)


def change_norms(params, specs, seed: int):
    """`weights.change_norms` with this module's `leaf`."""
    key = weights.key_of(seed)

    @jax.jit
    def one(p, p0):
        return jnp.sqrt(jnp.sum(jnp.square(p - p0)))

    return [float(one(p, leaf(key, i, tuple(s), tuple(init), jnp.float32)))
            for i, (p, (_n, s, init)) in enumerate(zip(params, specs))]


# -- the model --------------------------------------------------------------
def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * w


def silu(x):
    return x * jax.nn.sigmoid(x)


def gelu(x):
    return 0.5 * x * (1.0 + jax.lax.erf(x / math.sqrt(2.0)))


def shift(x):
    """x_{t-1} along axis 1, zero before the row's start."""
    return jnp.concatenate([jnp.zeros_like(x[:, :1]), x[:, :-1]], axis=1)


def unit(x):
    """x / |x| over the last axis."""
    n = jnp.sqrt(jnp.sum(jnp.square(x), -1, keepdims=True))
    return x / jnp.maximum(n, 1e-12)


def rope_table(seq: int, head_dim: int, rp: dict):
    """(cos, sin) [seq, rot] of a layer kind's `rope_parameters`."""
    if rp.get("rope_type", "default") != "default":
        raise NotImplementedError(rp["rope_type"])
    rot = int(head_dim * rp.get("partial_rotary_factor", 1.0))
    i = np.arange(rot // 2, dtype=np.float64)
    inv = float(rp["rope_theta"]) ** (-2.0 * i / rot)
    ang = np.arange(seq, dtype=np.float64)[:, None] * inv[None]
    ang = np.concatenate([ang, ang], axis=1)
    return (jnp.asarray(np.cos(ang), jnp.float32),
            jnp.asarray(np.sin(ang), jnp.float32))


def rotate(x, cos, sin):
    """x [r, s, n, d]: the first rot dims of every head rotated in pairs
    (i, i + rot/2), the rest passed through."""
    rot = cos.shape[-1]
    xr, rest = x[..., :rot], x[..., rot:]
    x1, x2 = xr[..., :rot // 2], xr[..., rot // 2:]
    half = jnp.concatenate([-x2, x1], axis=-1)
    out = xr * cos[None, :, None, :] + half * sin[None, :, None, :]
    return jnp.concatenate([out, rest], axis=-1)


def cca_qkv(p, u, *, H, Hk, dh, rope, rnd, parts=()):
    """q [r, s, H, dh], k, v [r, s, Hk, dh] as the attention product
    takes them. `parts` names what a broken program leaves out (the
    tests of `correct`): "value_shift", "conv_dw", "conv_group"."""
    dw_w, dw_b, g_w, g_b, tau, w_qkv = p
    r, s, _ = u.shape
    nq, nk = H * dh, Hk * dh
    zq = _ein("rsh,hk->rsk", u, w_qkv[:, :nq], rnd)
    zk = _ein("rsh,hk->rsk", u, w_qkv[:, nq:nq + nk], rnd)
    v1 = _ein("rsh,hk->rsk", u, w_qkv[:, nq + nk:nq + nk + dh], rnd)
    u_before = u if "value_shift" in parts else shift(u)
    v2 = _ein("rsh,hk->rsk", u_before, w_qkv[:, nq + nk + dh:], rnd)
    v = jnp.stack([v1, v2], axis=2)
    z = jnp.concatenate([zq, zk], axis=-1)
    z1 = z if "conv_dw" in parts else \
        dw_w[0] * shift(z) + dw_w[1] * z + dw_b
    z1 = z1.reshape(r, s, H + Hk, dh)
    z2 = z1 if "conv_group" in parts else (
        _ein("rsnc,ncd->rsnd", shift(z1), g_w[:, :dh], rnd)
        + _ein("rsnc,ncd->rsnd", z1, g_w[:, dh:], rnd)
        + g_b.reshape(H + Hk, dh))
    qh, kh = zq.reshape(r, s, H, dh), zk.reshape(r, s, Hk, dh)
    m = (qh + jnp.repeat(kh, H // Hk, axis=2)) / 2
    mbar = jnp.mean(m.reshape(r, s, Hk, H // Hk, dh), axis=3)
    scale = math.sqrt(dh)
    q = scale * unit(z2[:, :, :H] + m)
    k = scale * tau[:, None] * unit(z2[:, :, H:] + mbar)
    cos, sin = rope
    return rotate(q, cos, sin), rotate(k, cos, sin), v


def causal_attention(q, k, v, rnd):
    """q [r, s, H, d], k, v [r, s, Hk, d] -> [r, s, H * d]."""
    r, s, H, d = q.shape
    Hk = k.shape[2]
    group = H // Hk
    qb = min(Q_BLOCK, s)
    if s % qb:
        qb = s

    kh, vh = jnp.moveaxis(k, 2, 0), jnp.moveaxis(v, 2, 0)   # [Hk, r, s, d]

    @jax.checkpoint
    def one(args):      # q [r, qb, group, d], its key/value head, first row
        q, g, q0 = args
        att = _ein("rqnd,rkd->rnqk", q, kh[g], rnd) / math.sqrt(d)
        ok = (q0 + jnp.arange(qb))[:, None] >= jnp.arange(s)[None, :]
        att = jax.nn.softmax(jnp.where(ok, att, -jnp.inf), axis=-1)
        return _ein("rnqk,rkd->rqnd", att, vh[g], rnd)

    nq = s // qb
    qg = q.reshape(r, nq, qb, Hk, group, d)
    qg = jnp.transpose(qg, (3, 1, 0, 2, 4, 5)).reshape(
        Hk * nq, r, qb, group, d)
    g = jnp.repeat(jnp.arange(Hk), nq)
    q0 = jnp.tile(jnp.arange(nq) * qb, Hk)
    o = jax.lax.map(one, (qg, g, q0))          # [Hk*nq, r, qb, group, d]
    o = o.reshape(Hk, nq, r, qb, group, d)
    return jnp.transpose(o, (2, 1, 3, 0, 4, 5)).reshape(r, s, H * d)


def router(p, u, r_before, rnd, parts=()):
    """-> (p [r, s, E] over the published experts, the state r_l)."""
    w_down, gamma, w1, w2, w3 = p
    r = _ein("rsh,hk->rsk", u, w_down, rnd)
    if "depth_averaging" not in parts:
        r = r + gamma * r_before
    h = gelu(_ein("rsk,kj->rsj", r, w1, rnd))
    h = gelu(_ein("rsk,kj->rsj", h, w2, rnd))
    return jax.nn.softmax(_ein("rsk,ke->rse", h, w3, rnd), axis=-1), r


def sparse_ffn(p, u, r_before, *, first, rnd, parts=()):
    """The held experts' part of the routed term; the router's state;
    how many tokens chose each held expert; the sum of the chosen
    probabilities over all tokens; the expert each token chose."""
    w_gu, w_down, *p_router = p
    held, wide = w_gu.shape[0], w_down.shape[1]
    prob, r = router(p_router, u, r_before, rnd, parts)
    top = jnp.max(prob, axis=-1, keepdims=True)
    chosen = jnp.where(prob >= jax.lax.stop_gradient(top), prob, 0.0)
    w_held = chosen[..., first:first + held]            # [r, s, held]

    @jax.checkpoint
    def one(args):      # one expert: its weights, and the tokens' for it
        gu, down, w = args
        a = _ein("rsh,hk->rsk", u, gu, rnd)
        act = silu(a[..., :wide]) * a[..., wide:]
        return _ein("rsk,kh->rsh", act, down, rnd) * w[..., None]

    y, _ = jax.lax.scan(
        lambda y, args: (y + one(args), None), jnp.zeros_like(u),
        (w_gu, w_down, jnp.moveaxis(w_held, -1, 0)))
    counts = jnp.sum(w_held > 0, axis=(0, 1)).astype(jnp.int32)
    choice = jnp.argmax(prob, axis=-1).astype(jnp.int32)
    return y, r, counts, jnp.sum(top), choice


def residual(p, x, f):
    alpha_r, beta_r, alpha_o, beta_o = p
    return (alpha_r * x + beta_r) + (alpha_o * f + beta_o)


def block(p, x, r_before, rope, *, cfg, rnd, parts=()):
    """One layer on x [rows, seq, hidden] and the router's state
    [rows, seq, router_hidden_size]; p: its leaves in list order.
    Returns (x, r) and, as aux, (tokens that chose each held expert,
    the sum of the chosen probabilities, each token's choice)."""
    H, Hk, dh = latent(cfg)
    eps = cfg["rms_norm_eps"]
    u = rms_norm(x, p[0], eps)
    q, k, v = cca_qkv(p[1:7], u, H=H, Hk=Hk, dh=dh, rope=rope, rnd=rnd,
                      parts=parts)
    attn = _ein("rsk,kh->rsh", causal_attention(q, k, v, rnd), p[7], rnd)
    x = residual(p[8:12], x, attn)
    u = rms_norm(x, p[12], eps)
    y, r, *tell = sparse_ffn(
        p[13:20], u, r_before, first=cfg.get("expert_first", 0), rnd=rnd,
        parts=parts)
    return (residual(p[20:24], x, y), r), tuple(tell)


def head_logits(x, lnw, wte, *, eps, rnd):
    return _ein("...h,vh->...v", rms_norm(x, lnw, eps), wte, rnd)


def head_loss(x, lnw, wte, labels, *, eps, rnd):
    """Sum (not mean) of the next-token cross-entropy over x's tokens."""
    logp = jax.nn.log_softmax(head_logits(x, lnw, wte, eps=eps, rnd=rnd),
                              axis=-1)
    return -jnp.sum(jnp.take_along_axis(logp, labels[..., None], -1))


class Model:
    """The reference model of one configuration and one seed. `parts`:
    what a deliberately broken copy leaves out (`cca_qkv`, `router`)."""

    def __init__(self, cfg: dict, seed: int, dtype="float32", rnd=exact,
                 parts=()):
        self.cfg = cfg
        self.seed = seed
        self.specs = param_specs(cfg)
        self.params = [p.astype(jnp.float32) for p in
                       make(seed, self.specs, jnp.dtype(dtype))]
        assert len(layer_specs(cfg, 0)) == LAYER_LEAVES
        self._kw = dict(eps=cfg["rms_norm_eps"], rnd=rnd)
        # every layer is of one kind: one program for them all
        self._fn = functools.partial(block, cfg=cfg, rnd=rnd,
                                     parts=tuple(parts))
        self._block = jax.jit(self._fn)
        self._logits = jax.jit(functools.partial(head_logits, **self._kw))
        self._ropes = {}

    def layer(self, i):
        lo = 1 + i * LAYER_LEAVES
        return self.params[lo:lo + LAYER_LEAVES]

    def rope(self, seq):
        if seq not in self._ropes:
            rp = self.cfg["rope_parameters"]
            self._ropes[seq] = rope_table(
                seq, self.cfg["head_dim"],
                rp[self.cfg["layer_types"][0]])
        return self._ropes[seq]

    def state0(self, x):
        return jnp.zeros(x.shape[:-1] + (self.cfg["router_hidden_size"],),
                         jnp.float32)

    def hidden(self, ids):
        x = self.params[0][ids]
        r = self.state0(x)
        for i in range(self.cfg["num_hidden_layers"]):
            (x, r), _aux = self._block(self.layer(i), x, r,
                                       self.rope(ids.shape[1]))
        return x

    def logits(self, ids):
        """ids [rows, seq] -> float32 logits [rows, seq, vocab]."""
        return self._logits(self.hidden(jnp.asarray(ids, jnp.int32)),
                            self.params[-1], self.params[0])


class Trainer(Model):
    """The training reference: loss, gradients and AdamW, one layer and
    one block of rows at a time, for `n_steps` steps; what it keeps
    between steps is cut to what the next step needs, as
    `jamba_reference.Trainer` does (after the first step only the first
    moment; after the last step nothing). Of the first step it keeps
    `held_counts` ([layers, held experts]: the tokens that chose each
    held expert), `top_weight_mean` ([layers]: the mean chosen
    probability) and `choices` ([layers, tokens]: each token's expert)."""

    def __init__(self, cfg, seed, opt: dict, n_steps: int, rnd=exact,
                 row_block=1, parts=()):
        super().__init__(cfg, seed, "float32", rnd, parts)
        self.opt = opt
        self.n_steps = n_steps
        self.row_block = row_block
        self.m = [None] * len(self.params)
        self.v = [None] * len(self.params)
        self.t = 0
        self.held_counts = self.top_weight_mean = self.choices = None

        def block_vjp(p, x, r, rope, dy):
            _y, back, _aux = jax.vjp(
                lambda p, x, r: self._fn(p, x, r, rope), p, x, r,
                has_aux=True)
            return back(dy)

        self._block_vjp = jax.jit(block_vjp)
        self._head_vjp = jax.jit(jax.value_and_grad(
            functools.partial(head_loss, **self._kw), argnums=(0, 1, 2)))
        self._adamw = jax.jit(functools.partial(
            adamw, lr=opt["learning_rate"], b1=opt["beta1"],
            b2=opt["beta2"], eps=opt["epsilon"], wd=opt["weight_decay"]),
            static_argnames=("state",))
        self._add = jax.jit(lambda a, b: jax.tree.map(jnp.add, a, b))

    def _update(self, i, g, count):
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
        """The tied head over x: loss, dx, (d norm, d embedding)."""
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
        rope = self.rope(ids.shape[1])
        rb, count = self.row_block, float(ids.size)
        blocks = [slice(a, min(a + rb, rows)) for a in range(0, rows, rb)]
        ids = [jnp.asarray(ids[b]) for b in blocks]
        labels = [jnp.asarray(labels[b]) for b in blocks]
        x0 = [self.params[0][i] for i in ids]
        states, told = [[(x, self.state0(x)) for x in x0]], []
        for i in range(n_layers):
            outs = [self._block(self.layer(i), x, r, rope)
                    for x, r in states[-1]]
            states.append([xr for xr, _aux in outs])
            told.append((sum(aux[0] for _xr, aux in outs),
                         sum(aux[1] for _xr, aux in outs) / count,
                         jnp.concatenate([aux[2].reshape(-1)
                                          for _xr, aux in outs])))
        if self.t == 1:
            self.held_counts, self.top_weight_mean, self.choices = (
                np.asarray(jax.device_get(list(a))) for a in zip(*told))
        loss, dxr, g_head = 0.0, [], None
        for (x, r), y in zip(states[-1], labels):
            part, d, g = self._head(x, y)
            loss = loss + part / count
            dxr.append((d, jnp.zeros_like(r)))
            g_head = g if g_head is None else self._add(g_head, g)
        norms = [None] * len(self.params)
        norms[-1] = self._update(len(self.params) - 1, g_head[0], count)
        g_wte = g_head[1]
        del g_head
        for i in reversed(range(n_layers)):
            lay, g_lay = self.layer(i), None
            for b, ((x, r), d) in enumerate(zip(states[i], dxr)):
                gp, dx, dr = self._block_vjp(lay, x, r, rope, d)
                dxr[b] = (dx, dr)
                g_lay = gp if g_lay is None else self._add(g_lay, gp)
            states[i + 1] = None
            for j, g in enumerate(g_lay):
                k = 1 + i * LAYER_LEAVES + j
                norms[k] = self._update(k, g, count)
            del g_lay
        # the tied leaf: the head's gradient and the rows looked up
        norms[0] = self._update(
            0, self._embed_grad(g_wte, ids, [d for d, _r in dxr]), count)
        return float(loss), [float(n) for n in jax.device_get(norms)]

    @staticmethod
    @jax.jit
    def _embed_grad(g_wte, ids, dx):
        for i, d in zip(ids, dx):
            g_wte = g_wte.at[i.reshape(-1)].add(d.reshape(-1, d.shape[-1]))
        return g_wte

    def change_norms(self):
        return change_norms(self.params, self.specs, self.seed)
