"""The plain reference of the DeepSeek-V2 configurations (the
`deepseek_v2` model type of DeepSeek-AI's DeepSeek-V2-Lite config.json;
arXiv:2405.04434, section 2.1 and appendix B) in straightforward
`jax.numpy`, float32, matmuls at `highest` precision: no kernel, no
cache, no sort. It imports nothing of the program and is given nothing
the program made: its weights come from the seed.

With d the hidden size, H heads, dn / dr / dv the sizes of a head's part
of a key without position, of the shared rotary part and of a value,
and norm(x; w) = x rsqrt(mean(x^2) + eps) w:

    layer l:  h = x + MLA(norm(x; w1));  out = h + FFN_l(norm(h; w2))
              final norm;  logits = hidden . W_head   (untied, no bias)
    MLA(u):   q = u W_q, viewed [H, dn + dr]: q_j = its first dn, q'_j
              its last dr.  (c, k') = u W_kva, c the first kv_lora_rank,
              k' the last dr: ONE head that all H query heads read.
              (k_j, v_j) = norm(c; w_c) W_kvb viewed [H, dn + dv].
              Rotate-half RoPE on q'_j and k' alone: YaRN's frequencies
              over dr dimensions (`laguna_reference.rope_table`: theta,
              factor, original_max_position_embeddings, beta_fast,
              beta_slow), cos and sin times mscale(factor, mscale) /
              mscale(factor, mscale_all_dim), with mscale(s, m) =
              0.1 m ln s + 1.
              score_j[i, t] = (q_j[i] . k_j[t] + q'_j[i] . k'[t]) * scale
              for t <= i, scale = (dn + dr)^(-1/2) *
              mscale(factor, mscale_all_dim)^2; softmax; o_j = sum p v_j;
              y = o W_o.
    FFN_l:    l < first_k_dense_replace: (silu(u W_g) * u W_u) W_d of
              intermediate_size. Else s = softmax(u W_r) over the
              published expert count; the num_experts_per_tok largest
              chosen (greedy, one group); w_e = routed_scaling_factor *
              s_e, NOT divided by the chosen ones' sum (norm_topk_prob
              false); y = sum_chosen w_e SwiGLU_e(u) + SwiGLU_shared(u),
              the shared experts one SwiGLU of n_shared_experts *
              moe_intermediate_size.
    loss:     the mean next-token cross-entropy plus aux_loss_alpha times
              the mean, over sparse layers and rows, of sum_e f_e P_e:
              over a row of T tokens f_e = E / (K T) * (the tokens that
              chose e) and P_e = mean_t s[t, e] (seq_aux; the gradient
              reaches the router through P, the counts are constants).

The share (benchmarks/configs/*.json, `deployment`): this chip holds the
experts `expert_first` .. + `n_routed_experts` of the published count
and the rows of the embedding and of the head below `vocab_size`. The
routed sum is written as the equations have it, over the experts held
here: for each held expert its weight per token, zero where the token
did not choose it, times its SwiGLU of every token. What the experts
held elsewhere would add is left out, here as in the program. The
balance term runs over all the router's outputs and is whole.

Departures from the published description, none in the mathematics:
the keys are assembled as concat(k_j, k') at dn + dr a head and
attention runs one head and a block of `Q_BLOCK` query rows at a time
under `jax.checkpoint`, against explicit masks; the held experts run
`EXPERT_BLOCK` at a time under `jax.checkpoint`; the head and the loss
go through `TOKEN_BLOCK` tokens at a time; a layer's gradient is taken a
sublayer at a time: so that a layer's backward at 32,768 tokens fits one
16 GB chip beside the float32 parameters and a moment. The rotary 64
are in the rotate-half layout (the checkpoint's interleaved columns are
a permutation at load time, which seeded weights do not see).

The *control* is this same code with every matmul operand that the
configuration states in bfloat16 rounded to fp8 (`gpt_reference.fp8`).
`parts` names what a deliberately broken copy gets wrong:
"shared_rotary_key" (head j reads k' rolled by j dimensions, a key part
a head instead of the one all share), "mscale" (the scale without
mscale^2), "latent_norm" (c without its norm), "weights_as_scored"
(the chosen weights divided by their sum), "balance_loss" (the loss
without the balance term).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from . import laguna_reference
from .gpt_reference import _ein, adamw, exact, fp8  # noqa: F401
from .laguna_reference import rope_table, rotate, silu, swiglu
from .qwen3next_reference import change_norms, leaf, make  # noqa: F401

Q_BLOCK = 1024          # query rows whose scores exist at once
EXPERT_BLOCK = 4        # held experts computed at once
TOKEN_BLOCK = 1024      # tokens whose logits exist at once
N_MIXER = 6             # a layer's first norm and its attention's leaves


# -- the parameter list -------------------------------------------------------
def router_width(cfg: dict) -> int:
    """The router's outputs: the published expert count."""
    return cfg.get("published", {}).get("n_routed_experts",
                                        cfg["n_routed_experts"])


def is_sparse(cfg: dict, i: int) -> bool:
    return i >= cfg["first_k_dense_replace"]


def head_sizes(cfg: dict):
    """(heads, dn, dr, dv, the latent's rank)."""
    return (cfg["num_attention_heads"], cfg["qk_nope_head_dim"],
            cfg["qk_rope_head_dim"], cfg["v_head_dim"], cfg["kv_lora_rank"])


def layer_specs(cfg: dict, i: int) -> list:
    """[(name, shape, init)] of layer i, in the order the program lists
    a layer's parameters. The draws: `seeded_draws` of the configuration
    (why each: its `assumed.weights`)."""
    h, draw = cfg["hidden_size"], cfg["seeded_draws"]
    H, dn, dr, dv, rank = head_sizes(cfg)
    w = ("normal", cfg["initializer_range"])
    out = ("normal", draw["residual_output"])
    one = ("around", 1.0, draw["norm_weight"])
    p = f"model.layers.{i}."
    specs = [(p + "input_layernorm.weight", (h,), one),
             (p + "attn.q_proj.weight", (h, H * (dn + dr)), w),
             (p + "attn.kv_a_proj_with_mqa.weight", (h, rank + dr), w),
             (p + "attn.kv_a_layernorm.weight", (rank,), one),
             (p + "attn.kv_b_proj.weight", (rank, H * (dn + dv)), w),
             (p + "attn.o_proj.weight", (H * dv, h), out),
             (p + "post_attention_layernorm.weight", (h,), one)]
    if not is_sparse(cfg, i):
        inter = cfg["intermediate_size"]
        return specs + [(p + "mlp.gate_proj.weight", (h, inter), w),
                        (p + "mlp.up_proj.weight", (h, inter), w),
                        (p + "mlp.down_proj.weight", (inter, h), out)]
    held, wide = cfg["n_routed_experts"], cfg["moe_intermediate_size"]
    sw = cfg["n_shared_experts"] * wide
    m = p + "moe."
    return specs + [
        (m + "gate_up_proj", (held, h, 2 * wide), w),   # gate | up
        (m + "down_proj", (held, wide, h), out),
        (m + "router.weight", (h, router_width(cfg)), w),
        (m + "shared_expert.gate_proj.weight", (h, sw), w),
        (m + "shared_expert.up_proj.weight", (h, sw), w),
        (m + "shared_expert.down_proj.weight", (sw, h), out)]


def param_specs(cfg: dict) -> list:
    h, draw = cfg["hidden_size"], cfg["seeded_draws"]
    specs = [("model.embed_tokens.weight", (cfg["vocab_size"], h),
              ("normal", draw["embedding"]))]
    for i in range(cfg["num_hidden_layers"]):
        specs += layer_specs(cfg, i)
    return specs + [
        ("model.norm.weight", (h,), ("around", 1.0, draw["norm_weight"])),
        ("lm_head.weight", (h, cfg["vocab_size"]),
         ("normal", cfg["initializer_range"]))]


def n_params(cfg: dict) -> int:
    return sum(math.prod(s) for _n, s, _i in param_specs(cfg))


# -- the model --------------------------------------------------------------
def norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * w


def mscale(factor: float, m: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * m * math.log(factor) + 1.0


def softmax_scale(cfg: dict, parts=()) -> float:
    _H, dn, dr, _dv, _rank = head_sizes(cfg)
    rs = cfg["rope_scaling"]
    scale = (dn + dr) ** -0.5
    if "mscale" in parts:
        return scale
    return scale * mscale(rs["factor"], rs["mscale_all_dim"]) ** 2


def rope(cfg: dict, seq: int):
    """(cos, sin) [seq, dr]."""
    rs = cfg["rope_scaling"]
    return rope_table(seq, cfg["qk_rope_head_dim"], {
        "rope_type": "yarn", "rope_theta": cfg["rope_theta"],
        "factor": rs["factor"], "beta_fast": rs["beta_fast"],
        "beta_slow": rs["beta_slow"],
        "original_max_position_embeddings":
            rs["original_max_position_embeddings"],
        "attention_factor": mscale(rs["factor"], rs["mscale"])
        / mscale(rs["factor"], rs["mscale_all_dim"])})


def latent_core(q, q_pe, k, k_pe, v, *, scale, rnd=exact, parts=()):
    """Causal attention with the key in two parts: q, k [r, s, H, dn],
    q_pe [r, s, H, dr], k_pe [r, s, 1, dr], v [r, s, H, dv] ->
    o [r, s, H, dv]. The keys are assembled at dn + dr a head; one head
    and `Q_BLOCK` rows at a time."""
    r, s, H, _dn = q.shape
    if "shared_rotary_key" in parts:
        kp = jnp.stack([jnp.roll(k_pe[:, :, 0], j, axis=-1)
                        for j in range(H)], axis=2)
    else:
        kp = jnp.broadcast_to(k_pe, (r, s, H, k_pe.shape[-1]))
    qf = jnp.concatenate([q, q_pe], axis=-1)
    kf = jnp.concatenate([k, kp], axis=-1)
    qb = min(Q_BLOCK, s)
    if s % qb:
        qb = s
    nq = s // qb

    @jax.checkpoint
    def one(args):      # q [r, qb, d]; k [r, s, d]; v [r, s, dv]
        q, k, v, q0 = args
        att = _ein("rqd,rkd->rqk", q, k, rnd) * scale
        ok = (q0 + jnp.arange(qb))[:, None] >= jnp.arange(s)[None, :]
        att = jax.nn.softmax(jnp.where(ok, att, -jnp.inf), axis=-1)
        return _ein("rqk,rkd->rqd", att, v, rnd)

    def head(args):     # one head's q blocks against its keys and values
        q, k, v = args
        return jax.lax.map(lambda a: one((a[0], k, v, a[1])),
                           (q, jnp.arange(nq) * qb))

    # [head, q block]: a map over the heads of a map over a head's blocks
    qg = jnp.moveaxis(qf.reshape(r, nq, qb, H, -1), (3, 1), (0, 1))
    o = jax.lax.map(head, (qg, jnp.moveaxis(kf, 2, 0),
                           jnp.moveaxis(v, 2, 0)))     # [H, nq, r, qb, dv]
    return jnp.transpose(o, (2, 1, 3, 0, 4)).reshape(r, s, H, -1)


def latent_attention(p, u, tables, *, cfg, rnd, parts=()):
    wq, wkva, w_c, wkvb, wo = p
    H, dn, dr, dv, rank = head_sizes(cfg)
    r, s, _ = u.shape
    cos, sin = tables
    q = _ein("rsh,hk->rsk", u, wq, rnd).reshape(r, s, H, dn + dr)
    ckv = _ein("rsh,hk->rsk", u, wkva, rnd)
    c, k_pe = ckv[..., :rank], ckv[..., rank:].reshape(r, s, 1, dr)
    if "latent_norm" not in parts:
        c = norm(c, w_c, cfg["rms_norm_eps"])
    kv = _ein("rsc,ck->rsk", c, wkvb, rnd).reshape(r, s, H, dn + dv)
    o = latent_core(q[..., :dn], rotate(q[..., dn:], cos, sin),
                    kv[..., :dn], rotate(k_pe, cos, sin), kv[..., dn:],
                    scale=softmax_scale(cfg, parts), rnd=rnd, parts=parts)
    return _ein("rsk,kh->rsh", o.reshape(r, s, H * dv), wo, rnd)


def balance_term(scores, chose):
    """scores [r, T, E] the router's; chose [r, T, E] 1 where a token
    chose an expert -> the rows' mean of sum_e f_e P_e."""
    T, E = scores.shape[1:]
    K = jnp.sum(chose[0, 0])
    f = jnp.sum(chose, axis=1) * (E / (K * T))
    return jnp.mean(jnp.sum(jax.lax.stop_gradient(f)
                            * jnp.mean(scores, axis=1), axis=-1))


def sparse_ffn(p, u, *, top_k, scale, first, rnd, parts=()):
    """(the held experts' part of the routed sum and the shared experts,
    the balance term; the tokens that chose each held expert)."""
    w_gu, w_down, w_router, sg, su, sd = p
    held, wide = w_gu.shape[0], w_down.shape[1]
    prob = jax.nn.softmax(_ein("rsh,he->rse", u, w_router, rnd), axis=-1)
    kth = jax.lax.top_k(prob, top_k)[0][..., -1:]
    chose = prob >= jax.lax.stop_gradient(kth)
    w_all = scale * jnp.where(chose, prob, 0.0)
    if "weights_as_scored" in parts:
        w_all = w_all / jnp.sum(w_all, -1, keepdims=True)
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
    counts = jnp.sum(chose[..., first:first + held],
                     axis=(0, 1)).astype(jnp.int32)
    y = jnp.sum(routed, axis=0) + swiglu((sg, su, sd), u, rnd)
    return (y, balance_term(prob, chose.astype(jnp.float32))), counts


def mixer_sublayer(p, x, tables, *, cfg, rnd, parts=()):
    """x + MLA(norm(x)); p: the first norm's and the attention's leaves."""
    return x + latent_attention(
        p[1:], norm(x, p[0], cfg["rms_norm_eps"]), tables, cfg=cfg, rnd=rnd,
        parts=parts)


def ffn_sublayer(p, x, *, cfg, sparse, rnd, parts=()):
    """((x + FFN(norm(x)), the layer's balance term), the tokens that
    chose each held expert): 0 and none from a dense layer."""
    u = norm(x, p[0], cfg["rms_norm_eps"])
    if not sparse:
        return (x + swiglu(p[1:], u, rnd), jnp.float32(0.0)), \
            jnp.zeros((0,), jnp.int32)
    (y, term), counts = sparse_ffn(
        p[1:], u, top_k=cfg["num_experts_per_tok"],
        scale=cfg["routed_scaling_factor"],
        first=cfg.get("expert_first", 0), rnd=rnd, parts=parts)
    return (x + y, term), counts


def block(p, x, tables, *, cfg, sparse, rnd, parts=()):
    """One layer on x [rows, seq, hidden]; p: its leaves in list order.
    Returns ((out, balance term), counts)."""
    h = mixer_sublayer(p[:N_MIXER], x, tables, cfg=cfg, rnd=rnd, parts=parts)
    return ffn_sublayer(p[N_MIXER:], h, cfg=cfg, sparse=sparse, rnd=rnd,
                        parts=parts)


def head_logits(x, lnw, w_head, *, eps, rnd):
    return _ein("...h,hv->...v", norm(x, lnw, eps), w_head, rnd)


def head_loss(x, lnw, w_head, labels, *, eps, rnd):
    """Sum (not mean) of the next-token cross-entropy over x's tokens."""
    logp = jax.nn.log_softmax(head_logits(x, lnw, w_head, eps=eps, rnd=rnd),
                              axis=-1)
    return -jnp.sum(jnp.take_along_axis(logp, labels[..., None], -1))


def n_sparse(cfg: dict) -> int:
    return sum(is_sparse(cfg, i) for i in range(cfg["num_hidden_layers"]))


class Model:
    """The reference model of one configuration and one seed. `parts`:
    what a deliberately broken copy gets wrong."""

    def __init__(self, cfg: dict, seed: int, dtype="float32", rnd=exact,
                 parts=()):
        self.cfg = cfg
        self.seed = seed
        self.parts = tuple(parts)
        self.specs = param_specs(cfg)
        self.params = [p.astype(jnp.float32) for p in
                       make(seed, self.specs, jnp.dtype(dtype))]
        self.bounds, lo = [], 1
        for i in range(cfg["num_hidden_layers"]):
            n = len(layer_specs(cfg, i))
            self.bounds.append((lo, lo + n))
            lo += n
        self._kw = dict(eps=cfg["rms_norm_eps"], rnd=rnd)
        self._kinds = {
            sparse: functools.partial(block, cfg=cfg, sparse=sparse, rnd=rnd,
                                      parts=self.parts)
            for sparse in {is_sparse(cfg, i)
                           for i in range(cfg["num_hidden_layers"])}}
        self._blocks = {k: jax.jit(f) for k, f in self._kinds.items()}
        self._logits = jax.jit(functools.partial(head_logits, **self._kw))
        self._ropes = {}

    def layer(self, i):
        lo, hi = self.bounds[i]
        return self.params[lo:hi]

    def kind(self, i):
        return is_sparse(self.cfg, i)

    def rope(self, _i, seq):
        if seq not in self._ropes:
            self._ropes[seq] = rope(self.cfg, seq)
        return self._ropes[seq]

    def hidden(self, ids):
        x = self.params[0][ids]
        for i in range(self.cfg["num_hidden_layers"]):
            (x, _term), _counts = self._blocks[self.kind(i)](
                self.layer(i), x, self.rope(i, ids.shape[1]))
        return x

    def logits(self, ids):
        """ids [rows, seq] -> float32 logits [rows, seq, vocab]."""
        return self._logits(self.hidden(jnp.asarray(ids, jnp.int32)),
                            self.params[-2], self.params[-1])


class Trainer(Model, laguna_reference.Trainer):
    """The training reference: loss (the cross-entropy and the balance
    loss), gradients and AdamW, one layer, one sublayer and one block of
    rows at a time, for `n_steps` steps; what it keeps between steps is
    cut to what the next step needs (`laguna_reference.Trainer`, whose
    `_update`, `_head` and `_embed_grad` these are). `held_counts` is
    the first step's [sparse layers, held experts] count of tokens that
    chose each held expert, `balance` every step's mean balance term."""

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
        self.balance = []
        self.alpha = 0.0 if "balance_loss" in self.parts \
            else cfg["aux_loss_alpha"]

        kw = dict(cfg=cfg, rnd=rnd, parts=self.parts)
        mixer = functools.partial(mixer_sublayer, **kw)
        forward = jax.jit(mixer)
        backward = jax.jit(lambda p, x, tables, dh: jax.vjp(
            lambda p, x: mixer(p, x, tables), p, x)[1](dh))

        def sublayers(sparse):
            """(a layer's forward, its gradient), a sublayer at a time,
            each its own program (`qwen3next_reference.Trainer`)."""
            ffn = jax.jit(functools.partial(ffn_sublayer, sparse=sparse,
                                            **kw))
            ffn_vjp = jax.jit(lambda p, h, dy, dterm: jax.vjp(
                lambda p, h: ffn(p, h), p, h, has_aux=True)[1](
                    (dy, dterm)))

            def fwd(p, x, tables):
                return ffn(p[N_MIXER:], forward(p[:N_MIXER], x, tables))

            def vjp(p, x, tables, dy, dterm):
                g_ffn, dh = ffn_vjp(p[N_MIXER:],
                                    forward(p[:N_MIXER], x, tables), dy,
                                    dterm)
                g_mixer, dx = backward(p[:N_MIXER], x, tables, dh)
                return list(g_mixer) + list(g_ffn), dx
            return fwd, vjp

        made = {sparse: sublayers(sparse) for sparse in self._kinds}
        self._blocks = {k: fwd for k, (fwd, _vjp) in made.items()}
        self._block_vjps = {k: vjp for k, (_fwd, vjp) in made.items()}
        self._head_vjp = jax.jit(jax.value_and_grad(
            functools.partial(head_loss, **self._kw), argnums=(0, 1, 2)))
        self._adamw = jax.jit(functools.partial(
            adamw, lr=opt["learning_rate"], b1=opt["beta1"],
            b2=opt["beta2"], eps=opt["epsilon"], wd=opt["weight_decay"]),
            static_argnames=("state",))
        self._add = jax.jit(lambda a, b: jax.tree.map(jnp.add, a, b))

    def step(self, ids, labels):
        """One optimizer step on ids/labels [rows, seq]. Returns the loss
        (the mean cross-entropy plus alpha times the mean balance term)
        and the norm of every leaf's gradient."""
        self.t += 1
        rows, n_layers = ids.shape[0], self.cfg["num_hidden_layers"]
        seq = ids.shape[1]
        rb, count = self.row_block, float(ids.size)
        blocks = [slice(a, min(a + rb, rows)) for a in range(0, rows, rb)]
        ids = [jnp.asarray(ids[b]) for b in blocks]
        labels = [jnp.asarray(labels[b]) for b in blocks]
        # a block of rows weighs by its rows in the mean over rows
        share = [(b.stop - b.start) / rows for b in blocks]
        xs, counts, terms = [[self.params[0][i] for i in ids]], [], []
        for i in range(n_layers):
            lay, fn = self.layer(i), self._blocks[self.kind(i)]
            outs = [fn(lay, x, self.rope(i, seq)) for x in xs[-1]]
            xs.append([y for (y, _t), _c in outs])
            if self.kind(i):
                counts.append(sum(c for _o, c in outs))
                terms.append(sum(w * t for w, ((_y, t), _c)
                                 in zip(share, outs)))
        if self.t == 1:
            self.held_counts = np.asarray(jax.device_get(counts))
        balance = float(sum(terms) / max(len(terms), 1))
        self.balance.append(balance)
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
        # `_update` divides a gradient by `count` (the cross-entropy's
        # are sums over tokens): the balance term's cotangent is scaled
        # to come out as alpha / sparse layers, a block's rows' share
        for i in reversed(range(n_layers)):
            lay, vjp = self.layer(i), self._block_vjps[self.kind(i)]
            g_lay = None
            for b, (x, d) in enumerate(zip(xs[i], dx)):
                dterm = jnp.float32(
                    self.alpha * count * share[b] / max(len(terms), 1))
                gp, dx[b] = vjp(lay, x, self.rope(i, seq), d, dterm)
                g_lay = gp if g_lay is None else self._add(g_lay, gp)
            xs[i + 1] = None
            for j, g in enumerate(g_lay):
                k = self.bounds[i][0] + j
                norms[k] = self._update(k, g, count)
            del g_lay
        norms[0] = self._update(
            0, self._embed_grad(jnp.zeros_like(self.params[0]), ids, dx),
            count)
        return float(loss) + self.alpha * balance, \
            [float(n) for n in jax.device_get(norms)]

    def change_norms(self):
        return change_norms(self.params, self.specs, self.seed)
