"""The plain reference of the Qwen3-Next configurations (the `qwen3_next`
model type of Qwen's Qwen3-Next-80B-A3B config.json) in straightforward
`jax.numpy`, float32, matmuls at `highest` precision: no kernel, no
chunk, no sort, no cache. It imports nothing of the program and is given
nothing the program made: its weights come from the seed.

With d the hidden size and norm(x; w) = x rsqrt(mean(x^2) + eps) (1 + w)
(the zero-centred RMSNorm):

    layer i:  h = x + Mixer_i(norm(x; w1));  out = h + MoE(norm(h; w2))
              final norm; logits = hidden . W_head   (untied)
    Mixer_i:  gated attention where (i + 1) % full_attention_interval
              == 0, else Gated DeltaNet.
    Gated DeltaNet (Hk key heads, Hv value heads of dh, G = Hv / Hk):
      qkvz = u W_qkvz viewed [.., Hk, dh + dh + G dh + G dh] -> q, k, v,
      z; ba = u W_ba viewed [.., Hk, G + G] -> b, a (a key head's value
      heads side by side). cat(q, k, v) through a causal depthwise
      convolution of `linear_conv_kernel_dim` taps (zero before the
      row's start, no bias), then silu.
      beta = sigmoid(b); g = -exp(A_log) softplus(a + dt_bias), a value
      head; q~ = q rsqrt(sum q^2 + 1e-6) / sqrt(dh), k~ = k rsqrt(sum
      k^2 + 1e-6), each repeated to its G value heads.
      THE RECURRENCE, token by token, S_0 = 0, S [dh, dh] a value head:
        S_t = e^{g_t} S_{t-1} + beta_t k~_t (v_t - e^{g_t} S_{t-1}^T k~_t)^T
        o_t = S_t^T q~_t
      y = o rsqrt(mean(o^2) + eps) w_n * silu(z) over a value head's dh
      (a plain weight), then y W_o.
    Gated attention: q_proj writes [.., H, 2 dh']: a head's query and
      its gate; q = norm(q; w_q), k = norm(k; w_k) over a head's dh';
      rotate-half RoPE on the first dh' * partial_rotary_factor
      dimensions; causal softmax(q k^T / sqrt(dh')) v, H heads on Hkv;
      o * sigmoid(gate); W_o.
    MoE: p = softmax(u W_r) over the published expert count; the
      num_experts_per_tok largest chosen; w_e = p_e / sum_chosen p;
      y = sum_chosen w_e SwiGLU_e(u) + sigmoid(u w_g) SwiGLU_shared(u).

The share (benchmarks/configs/*.json, `deployment`): this chip holds the
experts `expert_first` .. + `num_experts` of the published count and the
rows of the embedding and of the head below `vocab_size`. The routed sum
is written as the equations have it, over the experts held here; what
the experts held elsewhere would add is left out, here as in the program.

Departures from the published description, none in the mathematics: the
multi-token-prediction module the family describes has no key in the
config.json and is not here; the recurrence is a nested `lax.scan` whose
outer blocks of `STATE_BLOCK` tokens are recomputed (`jax.checkpoint`),
so that its gradient keeps a state a block and not a state a token;
the Gated DeltaNet runs `HEAD_GROUP` key heads at a time between its two
projections (everything there is a head's own), each group under
`jax.checkpoint`; attention runs a block of query rows and one key/value
head's group at a time (`zaya_reference.causal_attention`); the held
experts run `EXPERT_BLOCK` at a time under `jax.checkpoint`; the head
and the loss go through `TOKEN_BLOCK` tokens at a time; a layer's
gradient is taken a sublayer at a time: so that a layer's backward fits
one 16 GB chip beside the float32 parameters and a moment.

The *control* is this same code with every matmul operand that the
configuration states in bfloat16 rounded to fp8 (`gpt_reference.fp8`:
the projections, attention's products, the experts', the router's and
the head's); the recurrence's own products stay float32, as the
configuration states them. `parts` names what a deliberately broken copy
leaves out: "delta_correction" (S_t = e^{g_t} S_{t-1} + beta_t k_t
v_t^T), "output_gate", "qk_unit_norm" (the delta rule's q and k as the
convolution leaves them, q still over sqrt(dh)), "zero_centered" (w for
1 + w in every such norm), and "rule_float32": the recurrence's three
products (S^T k, the rank-one update, S^T q) with their operands, and
their cotangents, rounded to bfloat16, the precision below the float32
the configuration states for the rule; the state is still summed in
float32, as a bfloat16 product on the matrix unit would leave it.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from . import laguna_reference, weights
from .gpt_reference import _ein, adamw, exact, fp8  # noqa: F401
from .laguna_reference import rope_table, rotate, silu, swiglu
from .zaya_reference import causal_attention

STATE_BLOCK = 128       # tokens of the recurrence between kept states
HEAD_GROUP = 4          # key heads of a Gated DeltaNet computed at once
EXPERT_BLOCK = 8        # held experts computed at once
TOKEN_BLOCK = 1024      # tokens whose logits exist at once
_HI = jax.lax.Precision.HIGHEST


# -- the parameter list -------------------------------------------------------
def router_width(cfg: dict) -> int:
    """The router's outputs: the published expert count."""
    return cfg.get("published", {}).get("num_experts", cfg["num_experts"])


def is_full(cfg: dict, i: int) -> bool:
    return (i + 1) % cfg["full_attention_interval"] == 0


def linear_heads(cfg: dict):
    """(key heads, value heads, head size) of the Gated DeltaNet."""
    return (cfg["linear_num_key_heads"], cfg["linear_num_value_heads"],
            cfg["linear_key_head_dim"])


def layer_specs(cfg: dict, i: int) -> list:
    """[(name, shape, init)] of layer i, in the order the program lists
    a layer's parameters. The draws: `seeded_draws` of the configuration
    (why each: its `assumed.weights`)."""
    h, draw = cfg["hidden_size"], cfg["seeded_draws"]
    w = ("normal", cfg["initializer_range"])
    out = ("normal", draw["residual_output"])
    zero_w = ("around", 0.0, draw["norm_weight"])   # used as 1 + w
    p = f"model.layers.{i}."
    specs = [(p + "input_layernorm.weight", (h,), zero_w)]
    if is_full(cfg, i):
        H, Hk, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                    cfg["head_dim"])
        specs += [(p + "attn.q_proj.weight", (h, 2 * H * d), w),
                  (p + "attn.k_proj.weight", (h, Hk * d), w),
                  (p + "attn.v_proj.weight", (h, Hk * d), w),
                  (p + "attn.o_proj.weight", (H * d, h), out),
                  (p + "attn.q_norm.weight", (d,), zero_w),
                  (p + "attn.k_norm.weight", (d,), zero_w)]
    else:
        Hk, Hv, d = linear_heads(cfg)
        taps = cfg["linear_conv_kernel_dim"]
        specs += [
            (p + "gdn.conv_weight", ((2 * Hk + Hv) * d, taps),
             ("normal", 1.0 / math.sqrt(taps))),
            (p + "gdn.dt_bias", (Hv,), ("dt_bias", *draw["dt"])),
            (p + "gdn.A_log", (Hv,), ("log_uniform", *draw["A"])),
            (p + "gdn.norm_weight", (d,),
             ("around", 1.0, draw["norm_weight"])),
            (p + "gdn.in_proj_qkvz.weight", (h, 2 * (Hk + Hv) * d), w),
            (p + "gdn.in_proj_ba.weight", (h, 2 * Hv), w),
            (p + "gdn.out_proj.weight", (Hv * d, h), out)]
    held, wide = cfg["num_experts"], cfg["moe_intermediate_size"]
    sw = cfg["shared_expert_intermediate_size"]
    m = p + "moe."
    return specs + [
        (p + "post_attention_layernorm.weight", (h,), zero_w),
        (m + "gate_up_proj", (held, h, 2 * wide), w),   # gate | up
        (m + "down_proj", (held, wide, h), out),
        (m + "router.weight", (h, router_width(cfg)), w),
        (m + "shared_expert.gate_proj.weight", (h, sw), w),
        (m + "shared_expert.up_proj.weight", (h, sw), w),
        (m + "shared_expert.down_proj.weight", (sw, h), out),
        (m + "shared_expert_gate.weight", (h, 1), w)]


def param_specs(cfg: dict) -> list:
    h, draw = cfg["hidden_size"], cfg["seeded_draws"]
    specs = [("model.embed_tokens.weight", (cfg["vocab_size"], h),
              ("normal", draw["embedding"]))]
    for i in range(cfg["num_hidden_layers"]):
        specs += layer_specs(cfg, i)
    return specs + [
        ("model.norm.weight", (h,), ("around", 0.0, draw["norm_weight"])),
        ("lm_head.weight", (h, cfg["vocab_size"]),
         ("normal", cfg["initializer_range"]))]


# -- weights from the seed ---------------------------------------------------
def leaf(key, index, shape, init, dtype):
    """`weights.leaf`, and three kinds of its own, each a function of
    the seed and the leaf alone:

    ("around", centre, std): centre + std * normal.
    ("log_uniform", lo, hi): log(u), u uniform in [lo, hi) (`A_log`).
    ("dt_bias", lo, hi): the inverse softplus of a step drawn
    log-uniform in [lo, hi): dt + log(-expm1(-dt))."""
    kind = init[0]
    if kind in ("normal", "const"):
        # the experts' stacked leaves as [rows, last dimension]: the same
        # numbers (a draw is a function of the key and the element's
        # flat index) in a third of the compile
        flat = (math.prod(shape[:-1]), shape[-1]) if len(shape) > 2 \
            else shape
        return weights.leaf(key, index, flat, init, dtype).reshape(shape)
    k = jax.random.fold_in(key, index)
    if kind == "around":
        x = init[1] + init[2] * jax.random.normal(k, shape, jnp.float32)
    elif kind == "log_uniform":
        x = jnp.log(jax.random.uniform(k, shape, jnp.float32, init[1],
                                       init[2]))
    elif kind == "dt_bias":
        dt = jnp.exp(jax.random.uniform(
            k, shape, jnp.float32, math.log(init[1]), math.log(init[2])))
        x = dt + jnp.log(-jnp.expm1(-dt))
    else:
        raise KeyError(kind)
    return x.astype(dtype)


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
    """`weights.change_norms` with this module's `leaf`: a leaf at a
    time, drawn eagerly. (All leaves in one program compile for 13 s on
    a cold chip's host where these draws are loads: `make`'s program
    has just compiled the same shapes.)"""
    key = weights.key_of(seed)

    @jax.jit
    def one(p, p0):
        return jnp.sqrt(jnp.sum(jnp.square(p - p0)))

    return [float(one(p, leaf(key, i, tuple(s), tuple(init), jnp.float32)))
            for i, (p, (_n, s, init)) in enumerate(zip(params, specs))]


# -- the model --------------------------------------------------------------
def rms(x, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps)


def norm(x, w, eps, parts=()):
    """The zero-centred RMSNorm: w is drawn around 0."""
    return rms(x, eps) * (w if "zero_centered" in parts else 1.0 + w)


def decay(g):
    """e^g of a token's log decay, g <= 0, as 1 + expm1(g): for a slow
    head (g of -1e-5) the sum is right to float32's last place, where a
    chip's exponential may be off by more, and 16,384 such factors are
    multiplied into the state one after the other."""
    return 1.0 + jnp.expm1(g)


def recurrence(q, k, v, g, beta, parts=()):
    """The gated delta rule token by token. q, k, v [r, s, H, d]; g,
    beta [r, s, H] -> o [r, s, H, d]."""
    r, s, H, d = v.shape
    tb = STATE_BLOCK if s % STATE_BLOCK == 0 else s

    def blocks(x):      # [r, s, H, ...] -> [s / tb, tb, r * H, ...]
        x = jnp.moveaxis(x, 1, 0)
        return x.reshape((s // tb, tb, r * H) + x.shape[3:])

    def low(x):         # an operand as a bfloat16 product would read it
        if "rule_float32" in parts:
            return x.astype(jnp.bfloat16).astype(jnp.float32)
        return x

    def token(S, x):
        q, k, v, g, b = x               # [N, d] x 3, [N] x 2
        S = decay(g)[:, None, None] * S
        if "delta_correction" not in parts:
            v = v - jnp.einsum("nkv,nk->nv", low(S), low(k), precision=_HI)
        S = S + low(b[:, None] * k)[:, :, None] * low(v)[:, None, :]
        return S, jnp.einsum("nkv,nk->nv", low(S), low(q), precision=_HI)

    @jax.checkpoint
    def block(S, xs):
        return jax.lax.scan(token, S, xs)

    _S, o = jax.lax.scan(block, jnp.zeros((r * H, d, d), jnp.float32),
                         tuple(blocks(x) for x in (q, k, v, g, beta)))
    return jnp.moveaxis(o.reshape(s, r, H, d), 0, 1)


def gated_delta_net(p, u, *, cfg, rnd, parts=()):
    """Between the two projections everything is a key head's own (its
    q, k, its G value heads' v and z, their b and a; the convolution a
    channel; the norms and the state a head): `HEAD_GROUP` key heads at
    a time, each group under `jax.checkpoint`."""
    conv_w, dt_bias, a_log, norm_w, w_qkvz, w_ba, w_out = p
    Hk, Hv, d = linear_heads(cfg)
    G, taps = Hv // Hk, conv_w.shape[1]
    r, s, _ = u.shape
    qkvz = _ein("rsh,hk->rsk", u, w_qkvz, rnd).reshape(
        r, s, Hk, (2 + 2 * G) * d)
    ba = _ein("rsh,hk->rsk", u, w_ba, rnd).reshape(r, s, Hk, 2 * G)
    # the taps by key head: conv_w's rows are q | k | v, each by head
    w_head = jnp.concatenate([
        conv_w[:Hk * d].reshape(Hk, d, taps),
        conv_w[Hk * d:2 * Hk * d].reshape(Hk, d, taps),
        conv_w[2 * Hk * d:].reshape(Hk, G * d, taps)], axis=1)
    hg = max(n for n in range(1, HEAD_GROUP + 1) if Hk % n == 0)

    @jax.checkpoint
    def some(args):     # hg key heads
        x, ba, w, dt_b, a_l = args
        z = x[..., (2 + G) * d:].reshape(r, s, hg * G, d)
        xp = jnp.pad(x[..., :(2 + G) * d],
                     ((0, 0), (taps - 1, 0), (0, 0), (0, 0)))
        mixed = silu(sum(xp[:, j:j + s] * w[..., j] for j in range(taps)))
        q, k = mixed[..., :d], mixed[..., d:2 * d]
        v = mixed[..., 2 * d:].reshape(r, s, hg * G, d)
        beta = jax.nn.sigmoid(ba[..., :G].reshape(r, s, hg * G))
        g = -jnp.exp(a_l) * jax.nn.softplus(
            ba[..., G:].reshape(r, s, hg * G) + dt_b)
        if "qk_unit_norm" not in parts:
            q = q * jax.lax.rsqrt(jnp.sum(q * q, -1, keepdims=True) + 1e-6)
            k = k * jax.lax.rsqrt(jnp.sum(k * k, -1, keepdims=True) + 1e-6)
        o = recurrence(jnp.repeat(q / math.sqrt(d), G, axis=2),
                       jnp.repeat(k, G, axis=2), v, g, beta, parts)
        return rms(o, cfg["rms_norm_eps"]) * norm_w * silu(z)

    def groups(x, axis):    # split `axis` (key or value heads) by group
        x = jnp.moveaxis(x, axis, 0)
        return x.reshape((Hk // hg, x.shape[0] * hg // Hk) + x.shape[1:])

    y = jax.lax.map(some, (
        jnp.moveaxis(groups(qkvz, 2), 1, 3), jnp.moveaxis(groups(ba, 2), 1, 3),
        groups(w_head, 0), groups(dt_bias, 0), groups(a_log, 0)))
    y = jnp.moveaxis(y, 0, 2).reshape(r, s, Hv * d)     # value-head order
    return _ein("rsk,kh->rsh", y, w_out, rnd)


def gated_attention(p, u, rope, *, cfg, rnd, parts=()):
    wq, wk, wv, wo, w_qn, w_kn = p
    H, Hk, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                cfg["head_dim"])
    r, s, _ = u.shape
    eps = cfg["rms_norm_eps"]
    qg = _ein("rsh,hk->rsk", u, wq, rnd).reshape(r, s, H, 2 * d)
    q, gate = qg[..., :d], qg[..., d:].reshape(r, s, H * d)
    k = _ein("rsh,hk->rsk", u, wk, rnd).reshape(r, s, Hk, d)
    v = _ein("rsh,hk->rsk", u, wv, rnd).reshape(r, s, Hk, d)
    cos, sin = rope
    q = rotate(norm(q, w_qn, eps, parts), cos, sin)
    k = rotate(norm(k, w_kn, eps, parts), cos, sin)
    o = causal_attention(q, k, v, rnd)                  # [r, s, H * d]
    if "output_gate" not in parts:
        o = o * jax.nn.sigmoid(gate)
    return _ein("rsk,kh->rsh", o, wo, rnd)


def sparse_ffn(p, u, *, top_k, first, rnd):
    """The held experts' part of the routed sum, and the gated shared
    expert; beside it how many tokens chose each held expert."""
    w_gu, w_down, w_router, sg, su, sd, w_gate = p
    held, wide = w_gu.shape[0], w_down.shape[1]
    prob = jax.nn.softmax(_ein("rsh,he->rse", u, w_router, rnd), axis=-1)
    kth = jax.lax.top_k(prob, top_k)[0][..., -1:]
    chosen = jnp.where(prob >= jax.lax.stop_gradient(kth), prob, 0.0)
    w_all = chosen / jnp.sum(chosen, -1, keepdims=True)
    w_held = w_all[..., first:first + held]             # [r, s, held]

    eb = max(n for n in range(1, EXPERT_BLOCK + 1) if held % n == 0)

    @jax.checkpoint
    def some(args):     # eb experts: their weights, and the tokens' for them
        gu, down, w = args
        a = _ein("rsh,ehk->ersk", u, gu, rnd)
        act = silu(a[..., :wide]) * a[..., wide:]
        y = _ein("ersk,ekh->ersh", act, down, rnd)
        return jnp.sum(y * w[..., None], axis=0)

    routed = jax.lax.map(some, (
        w_gu.reshape((held // eb, eb) + w_gu.shape[1:]),
        w_down.reshape((held // eb, eb) + w_down.shape[1:]),
        jnp.moveaxis(w_held, -1, 0).reshape((held // eb, eb)
                                            + w_held.shape[:-1])))
    shared = swiglu((sg, su, sd), u, rnd) * jax.nn.sigmoid(
        _ein("rsh,hk->rsk", u, w_gate, rnd))
    counts = jnp.sum(w_held > 0, axis=(0, 1)).astype(jnp.int32)
    return jnp.sum(routed, axis=0) + shared, counts


N_MIXER = {True: 6, False: 7}       # a mixer's leaves: full, linear


def mixer_sublayer(p, x, rope, *, cfg, full, rnd, parts=()):
    """x + Mixer(norm(x)); p: the first norm's and the mixer's leaves."""
    u = norm(x, p[0], cfg["rms_norm_eps"], parts)
    if full:
        return x + gated_attention(p[1:], u, rope, cfg=cfg, rnd=rnd,
                                   parts=parts)
    return x + gated_delta_net(p[1:], u, cfg=cfg, rnd=rnd, parts=parts)


def ffn_sublayer(p, x, *, cfg, rnd, parts=()):
    """(x + MoE(norm(x)), tokens that chose each held expert); p: the
    second norm's and the feed-forward's leaves."""
    y, counts = sparse_ffn(
        p[1:], norm(x, p[0], cfg["rms_norm_eps"], parts),
        top_k=cfg["num_experts_per_tok"], first=cfg.get("expert_first", 0),
        rnd=rnd)
    return x + y, counts


def block(p, x, rope, *, cfg, full, rnd, parts=()):
    """One layer on x [rows, seq, hidden]; p: its leaves in list order.
    Returns (out, tokens that chose each held expert)."""
    n = 1 + N_MIXER[full]
    h = mixer_sublayer(p[:n], x, rope, cfg=cfg, full=full, rnd=rnd,
                       parts=parts)
    return ffn_sublayer(p[n:], h, cfg=cfg, rnd=rnd, parts=parts)


def head_logits(x, lnw, w_head, *, eps, rnd, parts=()):
    return _ein("...h,hv->...v", norm(x, lnw, eps, parts), w_head, rnd)


def head_loss(x, lnw, w_head, labels, *, eps, rnd, parts=()):
    """Sum (not mean) of the next-token cross-entropy over x's tokens."""
    logp = jax.nn.log_softmax(
        head_logits(x, lnw, w_head, eps=eps, rnd=rnd, parts=parts), axis=-1)
    return -jnp.sum(jnp.take_along_axis(logp, labels[..., None], -1))


class Model:
    """The reference model of one configuration and one seed. `parts`:
    what a deliberately broken copy leaves out."""

    def __init__(self, cfg: dict, seed: int, dtype="float32", rnd=exact,
                 parts=()):
        self.cfg = cfg
        self.seed = seed
        self.specs = param_specs(cfg)
        self.params = [p.astype(jnp.float32) for p in
                       make(seed, self.specs, jnp.dtype(dtype))]
        self.bounds, lo = [], 1
        for i in range(cfg["num_hidden_layers"]):
            n = len(layer_specs(cfg, i))
            self.bounds.append((lo, lo + n))
            lo += n
        self._kw = dict(eps=cfg["rms_norm_eps"], rnd=rnd,
                        parts=tuple(parts))
        self._kinds = {
            full: functools.partial(block, cfg=cfg, full=full, rnd=rnd,
                                    parts=tuple(parts))
            for full in {is_full(cfg, i)
                         for i in range(cfg["num_hidden_layers"])}}
        self._blocks = {k: jax.jit(f) for k, f in self._kinds.items()}
        self._logits = jax.jit(functools.partial(head_logits, **self._kw))
        self._ropes = {}

    def layer(self, i):
        lo, hi = self.bounds[i]
        return self.params[lo:hi]

    def kind(self, i):
        return is_full(self.cfg, i)

    def rope(self, _i, seq):
        if seq not in self._ropes:
            self._ropes[seq] = rope_table(seq, self.cfg["head_dim"], {
                "rope_type": "default",
                "rope_theta": self.cfg["rope_theta"],
                "partial_rotary_factor": self.cfg["partial_rotary_factor"]})
        return self._ropes[seq]

    def hidden(self, ids):
        x = self.params[0][ids]
        for i in range(self.cfg["num_hidden_layers"]):
            x, _counts = self._blocks[self.kind(i)](
                self.layer(i), x, self.rope(i, ids.shape[1]))
        return x

    def logits(self, ids):
        """ids [rows, seq] -> float32 logits [rows, seq, vocab]."""
        return self._logits(self.hidden(jnp.asarray(ids, jnp.int32)),
                            self.params[-2], self.params[-1])


class Trainer(Model, laguna_reference.Trainer):
    """The training reference: `laguna_reference.Trainer`'s step (loss,
    gradients and AdamW, one layer and one block of rows at a time; an
    untied head; what it keeps between steps cut to what the next step
    needs) over this module's layers, head and seeded draws.
    `held_counts` is the first step's [layers, held experts] count of
    tokens that chose each held expert."""

    def __init__(self, cfg, seed, opt: dict, n_steps: int, rnd=exact,
                 row_block=1, parts=()):
        Model.__init__(self, cfg, seed, "float32", rnd, parts)
        self.opt = opt
        self.n_steps = n_steps
        self.row_block = row_block
        self.m = [None] * len(self.params)
        self.v = [None] * len(self.params)
        self.t = 0
        self.held_counts = None

        kw = dict(cfg=cfg, rnd=rnd, parts=tuple(parts))
        ffn = jax.jit(lambda p, h: ffn_sublayer(p, h, **kw))
        ffn_vjp = jax.jit(lambda p, h, dy: jax.vjp(
            lambda p, h: ffn_sublayer(p, h, **kw), p, h,
            has_aux=True)[1](dy))

        def sublayers(full):
            """(a layer's forward, its gradient), a sublayer at a time,
            each its own program: what one keeps for its backward is
            gone before the other's begins, and the forward pass runs
            the mixer's program that the gradient runs again, not a
            third one of the whole layer."""
            n = 1 + N_MIXER[full]
            mixer = functools.partial(mixer_sublayer, full=full, **kw)
            forward = jax.jit(mixer)
            backward = jax.jit(lambda p, x, rope, dh: jax.vjp(
                lambda p, x: mixer(p, x, rope), p, x)[1](dh))

            def fwd(p, x, rope):
                return ffn(p[n:], forward(p[:n], x, rope))

            def vjp(p, x, rope, dy):
                g_ffn, dh = ffn_vjp(p[n:], forward(p[:n], x, rope), dy)
                g_mixer, dx = backward(p[:n], x, rope, dh)
                return list(g_mixer) + list(g_ffn), dx
            return fwd, vjp

        made = {full: sublayers(full) for full in self._kinds}
        self._blocks = {full: fwd for full, (fwd, _vjp) in made.items()}
        self._block_vjps = {full: vjp for full, (_fwd, vjp) in made.items()}
        self._head_vjp = jax.jit(jax.value_and_grad(
            functools.partial(head_loss, **self._kw), argnums=(0, 1, 2)))
        self._adamw = jax.jit(functools.partial(
            adamw, lr=opt["learning_rate"], b1=opt["beta1"],
            b2=opt["beta2"], eps=opt["epsilon"], wd=opt["weight_decay"]),
            static_argnames=("state",))
        self._add = jax.jit(lambda a, b: jax.tree.map(jnp.add, a, b))

    def change_norms(self):
        return change_norms(self.params, self.specs, self.seed)

