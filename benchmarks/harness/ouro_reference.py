"""The plain reference of the Ouro configurations (the `ouro` model type
of ByteDance's Ouro-2.6B config.json; "Scaling Latent Reasoning via
Looped Language Models", Zhu et al., 2025) in straightforward
`jax.numpy`, float32, matmuls at `highest` precision: no kernel, no
scan over the passes, no cache. It imports nothing of the program and is
given nothing the program made: its weights come from the seed.

With N(x; w) = x rsqrt(mean(x^2) + eps) w:

    layer:  a = x + N(Attn(N(x; w1)); w2);  y = a + N(SwiGLU(N(a; w3)); w4)
    Attn:   H heads on Hk key/value heads of d, rotate-half RoPE over the
            whole head at rope_theta, causal softmax(q k^T / sqrt(d)) v,
            W_o; no bias.
    model:  h_0 = E[ids];  for t = 1 .. T (total_ut_steps), A PYTHON LOOP:
              h_t = N(Stack(h_{t-1}); w_f)       (the same weights each t)
              logits_t = h_t W_head;  lambda_t = sigmoid(h_t . w_g + b_g)
    exit:   p_1 = lambda_1;  p_t = lambda_t prod_{j<t} (1 - lambda_j);
            p_T = prod_{j<T} (1 - lambda_j)
    loss:   mean over tokens of  sum_t p_t CE_t - beta H(p),
            CE_t the next-token cross-entropy of logits_t,
            H(p) = - sum_t p_t log p_t.

Departures from the published description, none in the mathematics:
attention runs a block of query rows and one key/value head's group at a
time (`zaya_reference.causal_attention`); the head, the gate and the
loss go through `TOKEN_BLOCK` tokens of all T passes at a time; the
gradient is taken a layer application at a time, from the last pass's
last layer back to the first pass's first (`jax.vjp` of one layer on the
input the forward loop kept), each layer's gradients summed over its T
applications: so that the step fits one 16 GB chip beside the float32
parameters, a moment and the summed gradients.

The *control* is this same code with every matmul operand that the
configuration states in bfloat16 rounded to fp8 (`gpt_reference.fp8`:
the projections, attention's products, the feed-forward's and the
head's); the gate's product stays float32, as the configuration states
it. `parts` names what a deliberately broken copy leaves out or changes:
"final_norm" (pass t + 1 starts from the stream before the final norm;
the head and the gate still read the normed one), "post_norms" (N2 and
N4 left out: the pre-norm layer), "gate_gradient" (p_t held constant
under the gradient of the first term: the gate learns from the entropy
alone), "entropy" (beta = 0) and "passes" (T - 1 passes for T).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from . import laguna_reference
from .gpt_reference import _ein, adamw, exact, fp8  # noqa: F401
from .laguna_reference import rope_table, rotate, swiglu
# weights from the seed: `("around", centre, std)` beside the plain draws
from .qwen3next_reference import change_norms, leaf, make  # noqa: F401
from .zaya_reference import causal_attention

TOKEN_BLOCK = 1024      # tokens whose logits (of every pass) exist at once
N_LAYER = 11            # a layer's leaves
_HI = jax.lax.Precision.HIGHEST


# -- the parameter list -------------------------------------------------------
def layer_specs(cfg: dict, i: int) -> list:
    """[(name, shape, init)] of layer i, in the order the program lists
    a layer's parameters. The draws: `seeded_draws` of the configuration
    (why each: its `assumed.weights`)."""
    h, d, draw = cfg["hidden_size"], cfg["head_dim"], cfg["seeded_draws"]
    q, kv = cfg["num_attention_heads"] * d, cfg["num_key_value_heads"] * d
    inter = cfg["intermediate_size"]
    w = ("normal", cfg["initializer_range"])
    out = ("normal", draw["residual_output"])
    one = ("around", 1.0, draw["norm_weight"])
    p = f"model.layers.{i}."
    return [(p + "input_layernorm.weight", (h,), one),
            (p + "attn.q_proj.weight", (h, q), w),
            (p + "attn.k_proj.weight", (h, kv), w),
            (p + "attn.v_proj.weight", (h, kv), w),
            (p + "attn.o_proj.weight", (q, h), out),
            (p + "input_layernorm_2.weight", (h,), one),
            (p + "post_attention_layernorm.weight", (h,), one),
            (p + "mlp.gate_proj.weight", (h, inter), w),
            (p + "mlp.up_proj.weight", (h, inter), w),
            (p + "mlp.down_proj.weight", (inter, h), out),
            (p + "post_attention_layernorm_2.weight", (h,), one)]


def param_specs(cfg: dict) -> list:
    h, draw = cfg["hidden_size"], cfg["seeded_draws"]
    w = ("normal", cfg["initializer_range"])
    specs = [("model.embed_tokens.weight", (cfg["vocab_size"], h),
              ("normal", draw["embedding"]))]
    for i in range(cfg["num_hidden_layers"]):
        specs += layer_specs(cfg, i)
    return specs + [
        ("model.norm.weight", (h,), ("around", 1.0, draw["norm_weight"])),
        ("lm_head.weight", (h, cfg["vocab_size"]), w),
        ("exit_gate.weight", (h, 1), w),
        ("exit_gate.bias", (1,), ("const", 0.0))]


def n_params(cfg: dict, layers: int = None) -> int:
    """The parameters of `layers` layers (default: the configuration's)
    and the rest, by this module's own list."""
    specs = param_specs(cfg)
    a_layer = sum(math.prod(s) for _n, s, _i in layer_specs(cfg, 0))
    held = sum(math.prod(s) for _n, s, _i in specs)
    if layers is None:
        return held
    return held + (layers - cfg["num_hidden_layers"]) * a_layer


# -- the model --------------------------------------------------------------
def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * w


def passes(cfg: dict, parts=()) -> int:
    return cfg["total_ut_steps"] - ("passes" in parts)


def block(p, x, rope, *, cfg, rnd, parts=()):
    """One layer on x [rows, seq, hidden]; p: its leaves in list order."""
    n1, wq, wk, wv, wo, n2, n3, wg, wu, wd, n4 = p
    eps, d = cfg["rms_norm_eps"], cfg["head_dim"]
    H, Hk = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    r, s, _ = x.shape
    cos, sin = rope
    u = rms_norm(x, n1, eps)
    q = rotate(_ein("rsh,hk->rsk", u, wq, rnd).reshape(r, s, H, d), cos, sin)
    k = rotate(_ein("rsh,hk->rsk", u, wk, rnd).reshape(r, s, Hk, d),
               cos, sin)
    v = _ein("rsh,hk->rsk", u, wv, rnd).reshape(r, s, Hk, d)
    a = _ein("rsk,kh->rsh", causal_attention(q, k, v, rnd), wo, rnd)
    if "post_norms" not in parts:
        a = rms_norm(a, n2, eps)
    x = x + a
    m = swiglu((wg, wu, wd), rms_norm(x, n3, eps), rnd)
    if "post_norms" not in parts:
        m = rms_norm(m, n4, eps)
    return x + m


def gate_logits(hs, w_g, b_g):
    """hs [T, ..., h] -> [T, ...]: float32 at highest precision whatever
    the arithmetic of the rest (the configuration states the gate so)."""
    return jnp.einsum("...h,h->...", hs, w_g[:, 0], precision=_HI) + b_g[0]


def exit_distribution(lam):
    """lambda [T, ...] -> p [T, ...], by the product formula."""
    T = lam.shape[0]
    stay, ps = 1.0, []
    for t in range(T):
        ps.append(stay * lam[t] if t < T - 1 else stay * jnp.ones_like(lam[t]))
        stay = stay * (1.0 - lam[t])
    return jnp.stack(ps)


def exit_loss(hs, w_head, w_g, b_g, labels, *, beta, rnd, parts=()):
    """hs [T, rows, tokens, h], labels [rows, tokens] -> (the SUM over
    the tokens of sum_t p_t CE_t - beta H(p), (the sums over the tokens
    of CE_t and of p_t, each [T]))."""
    logp = jax.nn.log_softmax(
        _ein("trsh,hv->trsv", hs, w_head, rnd), axis=-1)
    ce = -jnp.take_along_axis(
        logp, jnp.broadcast_to(labels, hs.shape[:3])[..., None], -1)[..., 0]
    p = exit_distribution(jax.nn.sigmoid(gate_logits(hs, w_g, b_g)))
    entropy = -jnp.sum(p * jnp.log(p), axis=0)
    weight = jax.lax.stop_gradient(p) if "gate_gradient" in parts else p
    beta = 0.0 if "entropy" in parts else beta
    total = jnp.sum(weight * ce) - beta * jnp.sum(entropy)
    return total, (jnp.sum(ce, axis=(1, 2)), jnp.sum(p, axis=(1, 2)))


class Model:
    """The reference model of one configuration and one seed. `parts`:
    what a deliberately broken copy leaves out."""

    def __init__(self, cfg: dict, seed: int, dtype="float32", rnd=exact,
                 parts=()):
        self.cfg = cfg
        self.seed = seed
        self.parts = tuple(parts)
        self.T = passes(cfg, parts)
        self.beta = cfg["training"]["exit_entropy_beta"]
        self.specs = param_specs(cfg)
        self.params = [p.astype(jnp.float32) for p in
                       make(seed, self.specs, jnp.dtype(dtype))]
        self.n_layers = cfg["num_hidden_layers"]
        kw = dict(cfg=cfg, rnd=rnd, parts=self.parts)
        self._block = jax.jit(functools.partial(block, **kw))
        self._final = jax.jit(functools.partial(
            rms_norm, eps=cfg["rms_norm_eps"]))
        self._heads = jax.jit(lambda hs, w_head, w_g, b_g: (
            _ein("trsh,hv->trsv", hs, w_head, rnd),
            gate_logits(hs, w_g, b_g)))
        self._ropes = {}

    def layer(self, i):
        lo = 1 + i * N_LAYER
        return self.params[lo:lo + N_LAYER]

    @property
    def tail(self):
        """(final norm, head, gate weight, gate bias)."""
        return self.params[1 + self.n_layers * N_LAYER:]

    def rope(self, seq):
        if seq not in self._ropes:
            self._ropes[seq] = rope_table(seq, self.cfg["head_dim"], {
                "rope_type": "default",
                "rope_theta": self.cfg["rope_theta"]})
        return self._ropes[seq]

    def streams(self, ids, keep=None):
        """The T normed streams of ids [rows, seq], a Python loop of T
        passes over the layers. `keep`, a dict: every layer
        application's input under (t, i) and every pass's stream before
        the final norm under t."""
        x = self.params[0][jnp.asarray(ids, jnp.int32)]
        rope, hs = self.rope(ids.shape[1]), []
        for t in range(self.T):
            for i in range(self.n_layers):
                if keep is not None:
                    keep[t, i] = x
                x = self._block(self.layer(i), x, rope)
            if keep is not None:
                keep[t] = x
            hs.append(self._final(x, self.tail[0]))
            if "final_norm" not in self.parts:
                x = hs[-1]
        return hs

    def logits(self, ids):
        """ids [rows, seq] -> (float32 logits [T, rows, seq, vocab], the
        gate's logits [T, rows, seq])."""
        _lnw, w_head, w_g, b_g = self.tail
        return self._heads(jnp.stack(self.streams(ids)), w_head, w_g, b_g)


class Trainer(Model, laguna_reference.Trainer):
    """The training reference: the loss above, its gradients and AdamW
    (`laguna_reference.Trainer`'s update: what it keeps between steps
    cut to what the next step needs), one layer application and one
    block of rows at a time, for `n_steps` steps. `aux` is the first
    step's [2, T]: the mean over the tokens of CE_t and of p_t."""

    def __init__(self, cfg, seed, opt: dict, n_steps: int, rnd=exact,
                 row_block=1, parts=()):
        Model.__init__(self, cfg, seed, "float32", rnd, parts)
        self.opt = opt
        self.n_steps = n_steps
        self.row_block = row_block
        self.m = [None] * len(self.params)
        self.v = [None] * len(self.params)
        self.t = 0
        self.aux = None

        kw = dict(cfg=cfg, rnd=rnd, parts=self.parts)
        self._block_vjp = jax.jit(lambda p, x, rope, dy: jax.vjp(
            lambda p, x: block(p, x, rope, **kw), p, x)[1](dy))
        self._final_vjp = jax.jit(lambda x, w, dh: jax.vjp(
            functools.partial(rms_norm, eps=cfg["rms_norm_eps"]),
            x, w)[1](dh))
        self._exit_vjp = jax.jit(jax.value_and_grad(
            functools.partial(exit_loss, beta=self.beta, rnd=rnd,
                              parts=self.parts),
            argnums=(0, 1, 2, 3), has_aux=True))
        self._adamw = jax.jit(functools.partial(
            adamw, lr=opt["learning_rate"], b1=opt["beta1"],
            b2=opt["beta2"], eps=opt["epsilon"], wd=opt["weight_decay"]),
            static_argnames=("state",))
        self._add = jax.jit(lambda a, b: jax.tree.map(jnp.add, a, b))

    def _sum(self, total, part):
        return part if total is None else self._add(total, part)

    def _exit(self, hs, labels):
        """The heads, the gate and the loss of one block of rows,
        `TOKEN_BLOCK` tokens of every pass at a time: (sum, aux sums,
        d hs [T, rows, seq, h], the gradients of the head and the
        gate)."""
        _lnw, w_head, w_g, b_g = self.tail
        total = aux = g = None
        dhs = []
        for lo in range(0, labels.shape[1], TOKEN_BLOCK):
            sl = slice(lo, lo + TOKEN_BLOCK)
            (part, sums), gs = self._exit_vjp(
                hs[:, :, sl], w_head, w_g, b_g, labels[:, sl])
            total, aux = self._sum(total, part), self._sum(aux, sums)
            dhs.append(gs[0])
            g = self._sum(g, gs[1:])
        return total, aux, jnp.concatenate(dhs, axis=2), g

    def step(self, ids, labels):
        """One optimizer step on ids/labels [rows, seq]. Returns the mean
        loss and the norm of every leaf's gradient."""
        self.t += 1
        rows, seq = ids.shape
        L, T = self.n_layers, self.T
        rb, count = self.row_block, float(ids.size)
        rope = self.rope(seq)
        final_norm = "final_norm" not in self.parts
        total = aux = g_tail = None
        g_layers = [None] * L
        d_embed = []
        blocks = [slice(a, min(a + rb, rows)) for a in range(0, rows, rb)]
        for b in blocks:
            keep = {}
            hs = jnp.stack(self.streams(ids[b], keep))
            part, sums, dhs, g = self._exit(hs, jnp.asarray(labels[b]))
            total, aux = self._sum(total, part), self._sum(aux, sums)
            del hs
            g_lnw, dx = None, None      # dx: the next pass's gradient
            for t in reversed(range(T)):
                dh = dhs[t] if dx is None or not final_norm \
                    else dhs[t] + dx
                dpre, g_w = self._final_vjp(keep.pop(t), self.tail[0], dh)
                g_lnw = self._sum(g_lnw, g_w)
                if dx is not None and not final_norm:
                    dpre = dpre + dx    # the next pass read this stream
                dx = dpre
                for i in reversed(range(L)):
                    gp, dx = self._block_vjp(
                        self.layer(i), keep.pop((t, i)), rope, dx)
                    g_layers[i] = self._sum(g_layers[i], gp)
            g_tail = self._sum(g_tail, (g_lnw,) + tuple(g))
            d_embed.append(dx)
        if self.t == 1:
            self.aux = jnp.stack(aux) / count
        norms = [None] * len(self.params)
        first_tail = 1 + L * N_LAYER
        for j, g in enumerate(g_tail):
            norms[first_tail + j] = self._update(first_tail + j, g, count)
        del g_tail
        for i in reversed(range(L)):
            for j, g in enumerate(g_layers[i]):
                k = 1 + i * N_LAYER + j
                norms[k] = self._update(k, g, count)
            g_layers[i] = None
        norms[0] = self._update(0, self._embed_grad(
            jnp.zeros_like(self.params[0]),
            [jnp.asarray(ids[b]) for b in blocks], d_embed), count)
        return float(total / count), \
            [float(n) for n in jax.device_get(norms)]

    def change_norms(self):
        return change_norms(self.params, self.specs, self.seed)
