"""The plain reference of the Mellum2 configurations (the `mellum` model
type of JetBrains' Mellum2-12B-A2.5B config.json) in straightforward
`jax.numpy`, float32, matmuls at `highest` precision: no kernel, no
sort, no permutation, no `shard_map`, no collective. ONE model: all the
experts and the whole vocabulary. It imports nothing of the program and
is given nothing the program made: its weights come from the seed.

    layer l:   h = x + Attn_l(RMSNorm(x));  out = h + MoE(RMSNorm(h))
               final RMSNorm;  logits = hidden . W_head   (untied)
    Attn_l:    32 query heads on 4 key/value heads of 128 (query heads
               8j .. 8j+7 read key/value head j); scores q k^T /
               sqrt(128), causal, softmax, W_o; no bias, no q/k norm. In
               a `sliding_attention` layer row i sees keys i - 1023 .. i.
               Rotate-half RoPE over all 128 dimensions of q and k by the
               layer kind's entry of `rope_parameters`: `default`, or
               `yarn` with `attention_factor` on cos and sin (the forms
               in `laguna_reference.rope_table`, whose attention, norm,
               head and loss this file uses as they are: the equations
               are the same).
    MoE:       s = softmax(u W_r) over all 64; the 8 largest chosen;
               w_e = s_e / sum_chosen s;  y = sum_chosen w_e SwiGLU_e(u),
               SwiGLU_e(u) = (silu(u W_g,e) * u W_u,e) W_d,e, width 896.
               No shared expert, no scaling factor, no auxiliary loss.

The routed sum is written as the equations have it: for every expert its
weight per token, zero where the token did not choose it, times its
SwiGLU of every token.

Where it runs. 2.124 B float32 parameters with their gradients do not fit
one 16 GB chip, so on a host of `DEVICES` or more devices every leaf is
MADE in a layout over the first four (`layout`: the stacked experts'
leading dimension, the embedding's and the head's vocabulary in four
parts; everything else whole on each) and stays there: placement of
arrays (`out_shardings` of the draw; `jax.device_put` would do the
same) and nothing else. The functions below are jitted as they stand
and the compiler partitions them from where their arguments lie. For
that the experts run `groups` parts side by side (`sparse_ffn`: the
leading dimension [groups, blocks, EXPERT_BLOCK], a `lax.map` over the
blocks), so that a block's experts lie one part a device; with one group
that is `laguna_reference.sparse_ffn`'s loop. On fewer devices (the CPU
tests' tiny sizes) everything is on one.

Departures from the published description, none in the mathematics:
the blocks of `laguna_reference` (query rows, experts, tokens of the
head) and its `Trainer`'s steps, one row of the batch at a time.

`parts` names what a deliberately broken copy gets wrong (the controls
of `tools/limits_mellum2.py`, each of which must fail a limit):
  "chip_out"        one chip's experts (16 .. 31) left out of the sum:
                    what an exchange that loses a device's part gives;
  "weights_as_scored"  the chosen scores not divided by their sum;
  "no_window"       a sliding layer sees every earlier key;
  "no_yarn_factor"  a full layer's cos and sin without attention_factor.
The precision control is this code with every matmul operand rounded to
fp8 (`gpt_reference.fp8`), the nearest precision below the bf16 the
configuration states.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import weights
from .gpt_reference import _ein, adamw, exact, fp8  # noqa: F401
from . import laguna_reference
from .laguna_reference import (attention, head_logits, head_loss, rms_norm,
                               rope_table, silu)
from .qwen3next_reference import leaf

EXPERT_BLOCK = 8        # experts of a group computed at once
DEVICES = 4             # the devices a layout is over
LAYER_LEAVES = 9


# -- the parameter list -------------------------------------------------------
def draws(cfg: dict):
    """(matrices, matrices that write to the stream, the embedding, norm
    weights): the configuration's `seeded_draws`."""
    std, own = cfg["initializer_range"], cfg["seeded_draws"]
    return (("normal", std), ("normal", own["residual_output"]),
            ("normal", own["embedding"]), ("around", 1.0, own["norm_weight"]))


def layer_specs(cfg: dict, i: int) -> list:
    """[(name, shape, init)] of layer i, in the order the program lists
    a layer's parameters."""
    h, d = cfg["hidden_size"], cfg["head_dim"]
    w, out, _emb, norm = draws(cfg)
    q, kv = cfg["num_attention_heads"] * d, cfg["num_key_value_heads"] * d
    experts, wide = cfg["num_experts"], cfg["moe_intermediate_size"]
    p = f"laguna.layers.{i}."
    return [(p + "input_layernorm.weight", (h,), norm),
            (p + "attn.q_proj.weight", (h, q), w),
            (p + "attn.k_proj.weight", (h, kv), w),
            (p + "attn.v_proj.weight", (h, kv), w),
            (p + "attn.o_proj.weight", (q, h), out),
            (p + "post_attention_layernorm.weight", (h,), norm),
            (p + "moe.gate_up_proj", (experts, h, 2 * wide), w),  # gate | up
            (p + "moe.down_proj", (experts, wide, h), out),
            (p + "moe.router.weight", (h, experts), w)]


def param_specs(cfg: dict) -> list:
    if set(cfg["mlp_layer_types"]) != {"sparse"}:
        raise NotImplementedError("every layer's feed-forward is sparse")
    h = cfg["hidden_size"]
    w, _out, emb, norm = draws(cfg)
    specs = [("laguna.embed_tokens.weight", (cfg["vocab_size"], h), emb)]
    for i in range(cfg["num_hidden_layers"]):
        specs += layer_specs(cfg, i)
    return specs + [("laguna.norm.weight", (h,), norm),
                    ("lm_head.weight", (h, cfg["vocab_size"]), w)]


def n_params(cfg: dict) -> int:
    return sum(int(np.prod(s)) for _n, s, _i in param_specs(cfg))


# -- where the leaves lie ------------------------------------------------------
def layout(specs):
    """A sharding for every leaf over the first `DEVICES` devices, or
    None where there are fewer: the stacked experts by their leading
    dimension, the embedding by its rows and the head by its columns
    (both the vocabulary), the rest whole on each."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    if jax.device_count() < DEVICES:
        return None
    mesh = Mesh(np.array(jax.devices()[:DEVICES]), ("part",))

    def spec(name, shape):
        if len(shape) == 3:
            return P("part")
        if name.endswith("embed_tokens.weight"):
            return P("part", None)
        if name.endswith("lm_head.weight"):
            return P(None, "part")
        return P()
    return [NamedSharding(mesh, spec(n, s)) for n, s, _i in specs]


@functools.partial(jax.jit, static_argnums=(2, 3, 4, 5))
def _draw(key, index, shape, init, dtype, laid):
    """Leaf `index` of a parameter list, made where `laid` says it lies.
    `index` is traced: the leaves of one shape, draw and layout (a
    layer's, in every layer) are one compiled program, which `make` and
    `change_norms` share (38 leaves, 12 programs; each leaf a program of
    its own cost a cold run a minute and more of compiles, PR 49)."""
    x = leaf(key, index, shape, init, dtype)
    return x if laid is None else jax.lax.with_sharding_constraint(x, laid)


def make(seed: int, specs, dtype, shardings=None):
    """Every leaf from the seed, each made where `shardings` says it
    lies (a draw is a function of the key and the element's index,
    whatever the layout)."""
    key = weights.key_of(seed)
    laid = shardings or [None] * len(specs)
    return [_draw(key, i, tuple(s), tuple(init), jnp.dtype(dtype), at)
            for i, ((_n, s, init), at) in enumerate(zip(specs, laid))]


def change_norms(params, specs, seed: int):
    """Norm of every leaf's change since the seed's weights, each drawn
    again where the leaf lies."""
    key = weights.key_of(seed)

    @jax.jit
    def one(p, p0):
        return jnp.sqrt(jnp.sum(jnp.square(p - p0)))

    def laid(p):
        return p.sharding if isinstance(
            p.sharding, jax.sharding.NamedSharding) else None

    return [float(one(p, _draw(key, i, tuple(s), tuple(init),
                               jnp.dtype(jnp.float32), laid(p))))
            for i, (p, (_n, s, init)) in enumerate(zip(params, specs))]


# -- the model --------------------------------------------------------------
def rope_of(cfg: dict, kind: str, seq: int, parts=()):
    rp = dict(cfg["rope_parameters"][kind])
    if "no_yarn_factor" in parts and rp["rope_type"] == "yarn":
        rp["attention_factor"] = 1.0
    return rope_table(seq, cfg["head_dim"], rp)


def routing(u, w_router, *, top_k, rnd, parts=()):
    """Every expert's weight per token [r, s, E], zero where the token
    did not choose it."""
    scores = jax.nn.softmax(_ein("rsh,he->rse", u, w_router, rnd), axis=-1)
    kth = jax.lax.top_k(scores, top_k)[0][..., -1:]
    chosen = jnp.where(scores >= jax.lax.stop_gradient(kth), scores, 0.0)
    if "weights_as_scored" in parts:
        return chosen
    return chosen / jnp.sum(chosen, -1, keepdims=True)


def sparse_ffn(p, u, *, top_k, rnd, parts=(), groups=1):
    """The routed sum over all the experts; beside it how many tokens
    chose each."""
    w_gu, w_down, w_router = p
    experts, wide = w_gu.shape[0], w_down.shape[1]
    w_all = routing(u, w_router, top_k=top_k, rnd=rnd, parts=parts)
    counts = jnp.sum(w_all > 0, axis=(0, 1)).astype(jnp.int32)
    if "chip_out" in parts:     # experts 16 .. 31 of 64: a quarter
        e = jnp.arange(experts)
        w_all = jnp.where((e >= experts // 4) & (e < experts // 2), 0.0,
                          w_all)
    per = experts // groups
    eb = max(n for n in range(1, EXPERT_BLOCK + 1) if per % n == 0)

    def blocks(a):      # [experts, ...] -> [blocks, groups, eb, ...]
        a = a.reshape((groups, per // eb, eb) + a.shape[1:])
        return jnp.moveaxis(a, 1, 0)

    @jax.checkpoint
    def some(args):     # eb experts of every group, and the tokens' weights
        gu, down, w = args
        a = _ein("rsh,gehk->gersk", u, gu, rnd)
        act = silu(a[..., :wide]) * a[..., wide:]
        y = _ein("gersk,gekh->gersh", act, down, rnd)
        return jnp.sum(y * w[..., None], axis=(0, 1))

    parts_sum = jax.lax.map(some, (
        blocks(w_gu), blocks(w_down), blocks(jnp.moveaxis(w_all, -1, 0))))
    return jnp.sum(parts_sum, axis=0), counts


def block(p, x, rope, *, cfg, kind, rnd, parts=(), groups=1):
    """One layer on x [rows, seq, hidden]; p: its leaves in list order.
    Returns (out, tokens that chose each expert)."""
    eps = cfg["rms_norm_eps"]
    window = cfg["sliding_window"] \
        if kind == "sliding_attention" and "no_window" not in parts else None
    x = x + attention(
        p[1:5], rms_norm(x, p[0], eps), heads=cfg["num_attention_heads"],
        kv_heads=cfg["num_key_value_heads"], d=cfg["head_dim"],
        window=window, rope=rope, rnd=rnd)
    y, counts = sparse_ffn(
        p[6:9], rms_norm(x, p[5], eps), top_k=cfg["num_experts_per_tok"],
        rnd=rnd, parts=parts, groups=groups)
    return x + y, counts


class Model:
    """The reference model of one configuration and one seed."""

    def __init__(self, cfg: dict, seed: int, dtype="float32", rnd=exact,
                 parts=()):
        self.cfg, self.seed, self.parts = cfg, seed, tuple(parts)
        self.specs = param_specs(cfg)
        shardings = layout(self.specs)
        self.groups = DEVICES if shardings else 1
        self.params = [p.astype(jnp.float32) for p in make(
            seed, self.specs, jnp.dtype(dtype), shardings)]
        self.bounds = [(1 + i * LAYER_LEAVES, 1 + (i + 1) * LAYER_LEAVES)
                       for i in range(cfg["num_hidden_layers"])]
        self._kw = dict(eps=cfg["rms_norm_eps"], rnd=rnd)
        self._kinds = {k: functools.partial(
            block, cfg=cfg, kind=k, rnd=rnd, parts=self.parts,
            groups=self.groups) for k in set(cfg["layer_types"])}
        self._blocks = {k: jax.jit(f) for k, f in self._kinds.items()}
        self._logits = jax.jit(functools.partial(head_logits, **self._kw))
        self._ropes = {}

    def layer(self, i):
        lo, hi = self.bounds[i]
        return self.params[lo:hi]

    def kind(self, i):
        return self.cfg["layer_types"][i]

    def rope(self, i, seq):
        key = (self.kind(i), seq)
        if key not in self._ropes:
            self._ropes[key] = rope_of(self.cfg, key[0], seq, self.parts)
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


class Trainer(Model, laguna_reference.Trainer):
    """The training reference: `laguna_reference.Trainer`'s steps (loss,
    gradients and AdamW, one layer and one block of rows at a time; what
    it keeps between steps cut to what the next step needs) over this
    module's `Model`: its leaves, its layout, its blocks. `held_counts`
    is the first step's [layers, experts] count of tokens that chose
    each expert."""

    def __init__(self, cfg, seed, opt: dict, n_steps: int, rnd=exact,
                 row_block=1, parts=()):
        Model.__init__(self, cfg, seed, "float32", rnd, parts)
        self.n_steps, self.row_block = n_steps, row_block
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

    def change_norms(self):
        return change_norms(self.params, self.specs, self.seed)
