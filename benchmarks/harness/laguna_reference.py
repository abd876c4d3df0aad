"""The plain reference of the Laguna configurations (the `laguna` model
type of poolside's Laguna-XS.2 config.json) in straightforward
`jax.numpy`, float32, matmuls at `highest` precision: no kernel, no
sort, no permutation, no cache. It imports nothing of the program and is
given nothing the program made: its weights come from the seed.

    layer l:   h = x + Attn_l(RMSNorm(x));  out = h + FFN_l(RMSNorm(h))
               final RMSNorm;  logits = hidden . W_head   (untied)
    Attn_l:    H_l = num_attention_heads_per_layer[l] query heads on
               num_key_value_heads key/value heads of head_dim; query head
               j reads key/value head j // (H_l / kv); scores q k^T /
               sqrt(head_dim), causal, softmax, W_o; no bias. In a
               `sliding_attention` layer row i sees keys i - window + 1
               .. i. Rotate-half RoPE on q and k over the first
               rot = head_dim * partial_rotary_factor dimensions of a head:
               default: inv_freq_i = theta^(-2i/rot);  yarn: with
               f_i = theta^(-2i/rot), c(n) = rot ln(L / (2 pi n)) /
               (2 ln theta), low = max(floor(c(beta_fast)), 0), high =
               min(ceil(c(beta_slow)), rot - 1), ramp_i = clip((i - low) /
               (high - low), 0, 1): inv_freq_i = f_i / factor * ramp_i +
               f_i (1 - ramp_i), and cos, sin times attention_factor.
    FFN_l:     `dense`: (silu(u W_g) * u W_u) W_d, width intermediate_size.
               `sparse`: s = sigmoid(u W_r) over the published expert
               count; the num_experts_per_tok largest chosen; w_e =
               scale * s_e / sum_chosen s;  y = sum_chosen w_e SwiGLU_e(u)
               + SwiGLU_shared(u).

The share (benchmarks/configs/*.json, `deployment`): this chip holds the
experts `expert_first` .. + `num_experts` of the published count and the
rows of the embedding and of the head below `vocab_size`. The routed sum
is written as the equations have it, over the experts held here: for
each held expert its weight per token, zero where the token did not
choose it, times its SwiGLU of every token. What the experts held
elsewhere would add is left out, here as in the program.

Departures from the published description, none in the mathematics:
attention runs a block of `Q_BLOCK` query rows and one key/value head's
group of query heads at a time, each under `jax.checkpoint`, under
explicit masks against all keys or, in a window layer, against the
`Q_BLOCK` + window keys that hold every key a row of the block sees; the held experts run `EXPERT_BLOCK` at a
time, each block under `jax.checkpoint`; the head and the loss go
through `TOKEN_BLOCK` tokens at a time: so that a layer's backward fits
one 16 GB chip beside the float32 parameters and a moment. The chosen
set is found with `jax.lax.top_k` on the scores (a selection, no
permutation of anything); ties have measure zero.

The *control* is this same code with every matmul operand rounded to
fp8 (`gpt_reference.fp8`), the router's product included: the nearest
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
EXPERT_BLOCK = 8        # held experts computed at once
TOKEN_BLOCK = 1024      # tokens whose logits exist at once


# -- the parameter list -------------------------------------------------------
def router_width(cfg: dict) -> int:
    """The router's outputs: the published expert count."""
    return cfg.get("published", {}).get("num_experts", cfg["num_experts"])


def layer_kind(cfg: dict, i: int):
    return (cfg["layer_types"][i], cfg["mlp_layer_types"][i],
            cfg["num_attention_heads_per_layer"][i])


def draws(cfg: dict):
    """(matrices, matrices that write to the residual stream, the
    embedding): the seeded draws' standard deviations, the
    configuration's `seeded_draws` where it has them (why: its
    `assumed.weights`), else `initializer_range` for all."""
    std, own = cfg["initializer_range"], cfg.get("seeded_draws", {})
    return (("normal", std), ("normal", own.get("residual_output", std)),
            ("normal", own.get("embedding", std)))


def layer_specs(cfg: dict, i: int) -> list:
    """[(name, shape, init)] of layer i, in the order the program lists
    a layer's parameters."""
    h, d = cfg["hidden_size"], cfg["head_dim"]
    kind, ffn, heads = layer_kind(cfg, i)
    (w, out, _emb), one = draws(cfg), ("const", 1.0)
    q, kv = heads * d, cfg["num_key_value_heads"] * d
    p = f"laguna.layers.{i}."
    specs = [(p + "input_layernorm.weight", (h,), one),
             (p + "attn.q_proj.weight", (h, q), w),
             (p + "attn.k_proj.weight", (h, kv), w),
             (p + "attn.v_proj.weight", (h, kv), w),
             (p + "attn.o_proj.weight", (q, h), out),
             (p + "post_attention_layernorm.weight", (h,), one)]
    if ffn == "dense":
        inter = cfg["intermediate_size"]
        return specs + [(p + "mlp.gate_proj.weight", (h, inter), w),
                        (p + "mlp.up_proj.weight", (h, inter), w),
                        (p + "mlp.down_proj.weight", (inter, h), out)]
    held, wide = cfg["num_experts"], cfg["moe_intermediate_size"]
    sw = cfg["shared_expert_intermediate_size"]
    m = p + "moe."
    return specs + [
        (m + "gate_up_proj", (held, h, 2 * wide), w),   # gate | up
        (m + "down_proj", (held, wide, h), out),
        (m + "router.weight", (h, router_width(cfg)), w),
        (m + "shared_expert.gate_proj.weight", (h, sw), w),
        (m + "shared_expert.up_proj.weight", (h, sw), w),
        (m + "shared_expert.down_proj.weight", (sw, h), out)]


def param_specs(cfg: dict) -> list:
    h = cfg["hidden_size"]
    w, _out, emb = draws(cfg)
    specs = [("laguna.embed_tokens.weight", (cfg["vocab_size"], h), emb)]
    for i in range(cfg["num_hidden_layers"]):
        specs += layer_specs(cfg, i)
    return specs + [("laguna.norm.weight", (h,), ("const", 1.0)),
                    ("lm_head.weight", (h, cfg["vocab_size"]), w)]


# -- the model --------------------------------------------------------------
def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * w


def silu(x):
    return x * jax.nn.sigmoid(x)


def swiglu(p, u, rnd):
    gate, up, down = p
    return _ein("rsk,kh->rsh", silu(_ein("rsh,hk->rsk", u, gate, rnd))
                * _ein("rsh,hk->rsk", u, up, rnd), down, rnd)


def rope_table(seq: int, head_dim: int, rp: dict):
    """(cos, sin) [seq, rot] of one layer kind's `rope_parameters`."""
    rot = int(head_dim * rp.get("partial_rotary_factor", 1.0))
    theta = float(rp["rope_theta"])
    i = np.arange(rot // 2, dtype=np.float64)
    inv = theta ** (-2.0 * i / rot)
    scale = 1.0
    if rp["rope_type"] == "yarn":
        L = rp["original_max_position_embeddings"]

        def c(n):
            return rot * math.log(L / (2 * math.pi * n)) \
                / (2 * math.log(theta))
        low = max(math.floor(c(rp["beta_fast"])), 0)
        high = min(math.ceil(c(rp["beta_slow"])), rot - 1)
        ramp = np.clip((i - low) / max(high - low, 1e-3), 0.0, 1.0)
        inv = inv / rp["factor"] * ramp + inv * (1.0 - ramp)
        scale = rp["attention_factor"]
    elif rp["rope_type"] != "default":
        raise NotImplementedError(rp["rope_type"])
    ang = np.arange(seq, dtype=np.float64)[:, None] * inv[None]
    ang = np.concatenate([ang, ang], axis=1)
    return (jnp.asarray(np.cos(ang) * scale, jnp.float32),
            jnp.asarray(np.sin(ang) * scale, jnp.float32))


def rotate(x, cos, sin):
    """x [r, s, n, d]: the first rot dims of every head rotated in pairs
    (i, i + rot/2), the rest passed through."""
    rot = cos.shape[-1]
    xr, rest = x[..., :rot], x[..., rot:]
    x1, x2 = xr[..., :rot // 2], xr[..., rot // 2:]
    half = jnp.concatenate([-x2, x1], axis=-1)
    out = xr * cos[None, :, None, :] + half * sin[None, :, None, :]
    return jnp.concatenate([out, rest], axis=-1)


def attention(p, u, *, heads, kv_heads, d, window, rope, rnd):
    wq, wk, wv, wo = p
    r, s, _ = u.shape
    cos, sin = rope
    q = rotate(_ein("rsh,hk->rsk", u, wq, rnd).reshape(r, s, heads, d),
               cos, sin)
    k = rotate(_ein("rsh,hk->rsk", u, wk, rnd).reshape(r, s, kv_heads, d),
               cos, sin)
    v = _ein("rsh,hk->rsk", u, wv, rnd).reshape(r, s, kv_heads, d)
    group = heads // kv_heads
    qb = min(Q_BLOCK, s)
    if s % qb:
        qb = s
    # the keys a block of rows is computed against: all of them, or under
    # a window the qb + window that hold every key a row of it sees
    span = s if window is None else min(qb + window, s)

    @jax.checkpoint
    def one(args):      # q [r, qb, group, d]; k, v [r, s, d]; first row
        q, k, v, q0 = args
        first = jnp.clip(q0 + qb - span, 0, s - span)
        k = jax.lax.dynamic_slice_in_dim(k, first, span, axis=1)
        v = jax.lax.dynamic_slice_in_dim(v, first, span, axis=1)
        key_pos = first + jnp.arange(span)
        att = _ein("rqnd,rkd->rnqk", q, k, rnd) / math.sqrt(d)
        q_pos = q0 + jnp.arange(qb)
        ok = q_pos[:, None] >= key_pos[None, :]
        if window is not None:
            ok = ok & (q_pos[:, None] - key_pos[None, :] < window)
        att = jax.nn.softmax(jnp.where(ok, att, -jnp.inf), axis=-1)
        return _ein("rnqk,rkd->rqnd", att, v, rnd)

    nq = s // qb
    # [kv head, q block] pairs, flattened for one map
    qg = q.reshape(r, nq, qb, kv_heads, group, d)
    qg = jnp.transpose(qg, (3, 1, 0, 2, 4, 5)).reshape(
        kv_heads * nq, r, qb, group, d)
    kk = jnp.repeat(jnp.moveaxis(k, 2, 0), nq, axis=0)
    vv = jnp.repeat(jnp.moveaxis(v, 2, 0), nq, axis=0)
    q0 = jnp.tile(jnp.arange(nq) * qb, kv_heads)
    o = jax.lax.map(one, (qg, kk, vv, q0))     # [kv*nq, r, qb, group, d]
    o = o.reshape(kv_heads, nq, r, qb, group, d)
    o = jnp.transpose(o, (2, 1, 3, 0, 4, 5)).reshape(r, s, heads * d)
    return _ein("rsk,kh->rsh", o, wo, rnd)


def sparse_ffn(p, u, *, top_k, scale, first, rnd):
    """The held experts' part of the routed sum, and the shared expert;
    beside it how many tokens chose each held expert."""
    w_gu, w_down, w_router, sg, su, sd = p
    held, wide = w_gu.shape[0], w_down.shape[1]
    scores = jax.nn.sigmoid(_ein("rsh,he->rse", u, w_router, rnd))
    kth = jax.lax.top_k(scores, top_k)[0][..., -1:]
    chosen = jnp.where(scores >= jax.lax.stop_gradient(kth), scores, 0.0)
    w_all = scale * chosen / jnp.sum(chosen, -1, keepdims=True)
    w_held = w_all[..., first:first + held]             # [r, s, held]

    eb = max(n for n in range(1, EXPERT_BLOCK + 1) if held % n == 0)

    @jax.checkpoint
    def some(args):     # eb experts: their weights, and the tokens' for them
        gu, down, w = args
        a = _ein("rsh,ehk->ersk", u, gu, rnd)
        act = silu(a[..., :wide]) * a[..., wide:]
        y = _ein("ersk,ekh->ersh", act, down, rnd)
        return jnp.sum(y * w[..., None], axis=0)

    parts = jax.lax.map(some, (
        w_gu.reshape((held // eb, eb) + w_gu.shape[1:]),
        w_down.reshape((held // eb, eb) + w_down.shape[1:]),
        jnp.moveaxis(w_held, -1, 0).reshape((held // eb, eb)
                                            + w_held.shape[:-1])))
    counts = jnp.sum(w_held > 0, axis=(0, 1)).astype(jnp.int32)
    return jnp.sum(parts, axis=0) + swiglu((sg, su, sd), u, rnd), counts


def block(p, x, rope, *, cfg, kind, rnd):
    """One layer on x [rows, seq, hidden]; p: its leaves in list order.
    Returns (out, tokens that chose each held expert: none in a dense
    layer)."""
    layer_type, ffn, heads = kind
    eps = cfg["rms_norm_eps"]
    x = x + attention(
        p[1:5], rms_norm(x, p[0], eps), heads=heads,
        kv_heads=cfg["num_key_value_heads"], d=cfg["head_dim"],
        window=cfg["sliding_window"] if layer_type == "sliding_attention"
        else None, rope=rope, rnd=rnd)
    u = rms_norm(x, p[5], eps)
    if ffn == "dense":
        return x + swiglu(p[6:9], u, rnd), jnp.zeros((0,), jnp.int32)
    y, counts = sparse_ffn(
        p[6:12], u, top_k=cfg["num_experts_per_tok"],
        scale=cfg["moe_routed_scaling_factor"],
        first=cfg.get("expert_first", 0), rnd=rnd)
    return x + y, counts


def head_logits(x, lnw, w_head, *, eps, rnd):
    return _ein("...h,hv->...v", rms_norm(x, lnw, eps), w_head, rnd)


def head_loss(x, lnw, w_head, labels, *, eps, rnd):
    """Sum (not mean) of the next-token cross-entropy over x's tokens."""
    logp = jax.nn.log_softmax(head_logits(x, lnw, w_head, eps=eps, rnd=rnd),
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
        kinds = {layer_kind(cfg, i)
                 for i in range(cfg["num_hidden_layers"])}
        self._kinds = {k: functools.partial(block, cfg=cfg, kind=k, rnd=rnd)
                       for k in kinds}
        self._blocks = {k: jax.jit(f) for k, f in self._kinds.items()}
        self._logits = jax.jit(functools.partial(head_logits, **self._kw))
        self._ropes = {}

    def layer(self, i):
        lo, hi = self.bounds[i]
        return self.params[lo:hi]

    def kind(self, i):
        return layer_kind(self.cfg, i)

    def rope(self, i, seq):
        key = (self.cfg["layer_types"][i], seq)
        if key not in self._ropes:
            self._ropes[key] = rope_table(
                seq, self.cfg["head_dim"],
                self.cfg["rope_parameters"][key[0]])
        return self._ropes[key]

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


class Trainer(Model):
    """The training reference: loss, gradients and AdamW, one layer and
    one block of rows at a time, for `n_steps` steps; what it keeps
    between steps is cut to what the next step needs, as
    `jamba_reference.Trainer` does (after the first step only the first
    moment; after the last step nothing). `held_counts` is the first
    step's [sparse layers, held experts] count of tokens that chose each
    held expert."""

    def __init__(self, cfg, seed, opt: dict, n_steps: int, rnd=exact,
                 row_block=1):
        super().__init__(cfg, seed, "float32", rnd)
        self.opt = opt
        self.n_steps = n_steps
        self.row_block = row_block
        self.m = [None] * len(self.params)
        self.v = [None] * len(self.params)
        self.t = 0
        self.held_counts = None

        def block_vjp(fn, p, x, rope, dy):
            _y, back, _counts = jax.vjp(lambda p, x: fn(p, x, rope), p, x,
                                        has_aux=True)
            return back(dy)

        self._block_vjps = {k: jax.jit(functools.partial(block_vjp, f))
                            for k, f in self._kinds.items()}
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
        loss, dx, g = 0.0, [], None
        for lo in range(0, x.shape[1], TOKEN_BLOCK):
            sl = slice(lo, lo + TOKEN_BLOCK)
            part, gs = self._head_vjp(x[:, sl], self.params[-2],
                                      self.params[-1], labels[:, sl])
            loss = loss + part
            dx.append(gs[0])
            g = gs[1:] if g is None else self._add(g, gs[1:])
        return loss, jnp.concatenate(dx, axis=1), g

    def step(self, ids, labels):
        """One optimizer step on ids/labels [rows, seq]. Returns the mean
        loss and the norm of every leaf's gradient."""
        self.t += 1
        rows, n_layers = ids.shape[0], self.cfg["num_hidden_layers"]
        seq = ids.shape[1]
        rb, count = self.row_block, float(ids.size)
        blocks = [slice(a, min(a + rb, rows)) for a in range(0, rows, rb)]
        ids = [jnp.asarray(ids[b]) for b in blocks]
        labels = [jnp.asarray(labels[b]) for b in blocks]
        xs, counts = [[self.params[0][i] for i in ids]], []
        for i in range(n_layers):
            lay, fn = self.layer(i), self._blocks[self.kind(i)]
            outs = [fn(lay, x, self.rope(i, seq)) for x in xs[-1]]
            xs.append([y for y, _c in outs])
            if outs[0][1].size:
                counts.append(sum(c for _y, c in outs))
        if self.t == 1:
            self.held_counts = np.asarray(jax.device_get(counts))
        loss, dx, g_head = 0.0, [], None
        for x, y in zip(xs[-1], labels):
            part, d, g = self._head(x, y)
            loss = loss + part / count
            dx.append(d)
            g_head = g if g_head is None else self._add(g_head, g)
        norms = [None] * len(self.params)
        last = len(self.params) - 1
        norms[last - 1] = self._update(last - 1, g_head[0], count)
        norms[last] = self._update(last, g_head[1], count)
        del g_head
        for i in reversed(range(n_layers)):
            lay, vjp = self.layer(i), self._block_vjps[self.kind(i)]
            g_lay = None
            for b, (x, d) in enumerate(zip(xs[i], dx)):
                gp, dx[b] = vjp(lay, x, self.rope(i, seq), d)
                g_lay = gp if g_lay is None else self._add(g_lay, gp)
            xs[i + 1] = None
            for j, g in enumerate(g_lay):
                k = self.bounds[i][0] + j
                norms[k] = self._update(k, g, count)
            del g_lay
        norms[0] = self._update(
            0, self._embed_grad(jnp.zeros_like(self.params[0]), ids, dx),
            count)
        return float(loss), [float(n) for n in jax.device_get(norms)]

    @staticmethod
    @jax.jit
    def _embed_grad(g_wte, ids, dx):
        """The embedding's gradient: the rows looked up (the head is
        untied)."""
        for i, d in zip(ids, dx):
            g_wte = g_wte.at[i.reshape(-1)].add(d.reshape(-1, d.shape[-1]))
        return g_wte

    def change_norms(self):
        return weights.change_norms(self.params, self.specs, self.seed)
