"""Activation recompute (gradient checkpointing).

Reference: RecomputeFunction
(/root/reference/python/paddle/distributed/fleet/recompute/recompute.py:108)
— drop a block's activations in forward, re-run it inside backward.

TPU rendering: `jax.checkpoint` IS this feature. The block is
functionalised (Layer params become explicit vjp inputs) and wrapped in
jax.checkpoint, so the eager tape's vjp closure holds only the block
inputs and re-runs the forward during backward; under jit the same code
gives XLA rematerialisation.

What a block keeps besides its inputs is its `policy` (`_POLICIES`):
"full" nothing, "dots" / "dots_no_batch" its matmul outputs, and
"flash_outputs" the flash forward kernel's `o` and `lse`
(2*b*s*h*d + 32*b*s*h bytes a layer), so the kernel runs once a layer.

`scan_passes` runs one function several times over, each pass's output
the next one's input, as ONE traced body (`jax.lax.scan`): the weights
are the loop's invariants and the body is lowered once, whatever the
number of passes. What such a loop keeps a pass for its backward is what
the body's blocks keep (each recomputed block its input and what its
policy names, stacked over the passes by the scan) and the pass's output;
the weights' cotangents are summed over the passes in the transposed
scan's carry, in the weights' own type, and are whole only after the
first pass's backward.
"""
from __future__ import annotations

import functools

import jax

from ...core.tensor import Tensor
from ...core.generator import rng_scope, next_key
from ...nn.layer import Layer, traced_scope
from ...ops.registry import OpDef
from ...ops import registry as _op_registry
from ...autograd import tape
from ...kernels.pallas.flash_attention import FLASH_O, FLASH_LSE


#: Named rematerialisation policies (the reference's
#: recompute_granularity knob, fleet/meta_parallel dygraph_sharding —
#: rendered as jax.checkpoint save-policies). "full" saves only the
#: block inputs (max memory savings, re-runs every matmul in backward);
#: "dots" saves matmul outputs (recompute only the cheap elementwise
#: tail — ~1/3 less recompute FLOPs at ~9*b*s*h extra bytes per block);
#: "dots_no_batch" is the jax checkpoint_dots_with_no_batch_dims policy
#: (saves plain matmuls, recomputes batched ones like attention scores);
#: "flash_outputs" saves the flash forward kernel's `o` and `lse` and
#: nothing else (2*b*s*h*d bytes of bf16 `o` and 32*b*s*h of float32
#: `lse`, a head's row over a sublane tile of 8: at 32768 tokens of 8
#: heads of 128, 67 + 8 MB a layer). The backward kernel reads q, k, v,
#: o and lse; q, k and v are a projection to make again, o and lse the
#: whole forward kernel, which a block that keeps them does not run a
#: second time. Inert in a block with no flash kernel (nothing is named).
_POLICIES = {
    "full": None,       # jax.checkpoint default: save only block inputs
    "dots": lambda: jax.checkpoint_policies.dots_saveable,
    "dots_no_batch":
        lambda: jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
    "flash_outputs":
        lambda: jax.checkpoint_policies.save_only_these_names(
            FLASH_O, FLASH_LSE),
}


def _resolve_policy(policy):
    if policy is None or callable(policy):
        return policy
    try:
        entry = _POLICIES[policy]
    except KeyError:
        raise ValueError(
            f"unknown recompute policy {policy!r}; expected one of "
            f"{sorted(_POLICIES)} or a jax checkpoint policy callable")
    return entry() if entry is not None else None


def flash_policy(attention):
    """The policy of a recomputed block whose attention layer is
    `attention` (None: it has none, as a layer whose mixer is a
    recurrence: `models/jamba.py`'s Mamba layers, `models/qwen3_next.py`'s
    Gated DeltaNet layers): "flash_outputs" where every key is in a
    query's sight, else None.

    What keeping buys for a byte of `o` is the number of keys a query row
    meets: 16,384 on average over 32,768 causal tokens (ZAYA1, 243 ms a
    GB kept on a v5e), 4,096 over 8,192 (Laguna's full layers, 51 ms a
    GB), at most the window under one (512 at Laguna: 13 ms a GB, less
    than a matmul output buys, so a window layer runs its forward again
    and its bytes stay free). A layer with no flash kernel names nothing
    and gets no policy."""
    if (attention is None or not attention.use_flash_attention
            or getattr(attention, "window", None) is not None):
        return None
    return "flash_outputs"


def note_flash_kept(policies):
    """Say in `compile_record(<family>)["flash_kept"]` in how many of a
    model's recomputed layers (`policies`: one entry each) the flash
    kernel's outputs are kept."""
    from ...observability import perf
    if policies:
        kept = sum(p == "flash_outputs" for p in policies)
        perf.trace_note(
            "flash_kept", f"o and lse kept across recompute in {kept} of "
            f"{len(policies)} recomputed layers")


def layer_calls(layers, remat, interval=1):
    """A decoder stack's walk over its layers: for each of `layers` in
    turn, what the model calls with the layer's arguments. That is the
    layer itself, or, with `remat`, for every `interval`-th layer from
    the first, `recompute` of it under `flash_policy` of its `attn` (a
    layer without one: no policy). What a layer takes and hands on is
    the model's business: `for call in layer_calls(...): x = call(x)`.
    Once the walk is through, `note_flash_kept` says how many of the
    recomputed layers keep their flash outputs."""
    kept = []
    for i, layer in enumerate(layers):
        if remat and i % interval == 0:
            kept.append(flash_policy(getattr(layer, "attn", None)))
            yield functools.partial(recompute, layer, policy=kept[-1])
        else:
            yield layer
    note_flash_kept(kept)


def recompute(function, *args, use_reentrant=True, preserve_rng_state=True,
              policy=None, **kwargs):
    """ref: recompute.py recompute(function, *args). `function` may be a
    Layer (its parameters join the differentiable inputs) or a pure
    function of its tensor arguments.

    `policy` selects WHAT gets saved across the forward (the
    recompute_granularity analog): None/"full" saves only block inputs;
    "dots" / "dots_no_batch" save matmul outputs so backward re-runs
    only the elementwise tail; "flash_outputs" saves the flash forward
    kernel's two outputs so backward does not run it again; or pass any
    jax.checkpoint_policies callable directly."""
    if isinstance(function, Layer):
        layer = function
        # forward is called past Layer.__call__: the block's
        # jax.named_scope is entered here instead
        scope = layer.scope_name()

        def fn(*inputs, **kw):
            with traced_scope(scope):
                return layer.forward(*inputs, **kw)
    else:
        layer = getattr(function, "__self__", None)
        layer = layer if isinstance(layer, Layer) else None
        fn = function

    ptensors = list(layer.parameters()) if layer is not None else []
    jpolicy = _resolve_policy(policy)

    from ...jit import _functional_params

    def raw(seed, params, inputs, kw):
        def body(seed, params, inputs, kw):
            with rng_scope(seed):
                with _functional_params(ptensors, list(params)):
                    with tape.no_grad():
                        out = fn(*inputs, **kw)
            flat, treedef = jax.tree_util.tree_flatten(
                out, is_leaf=lambda x: isinstance(x, Tensor))
            flat = [o._data if isinstance(o, Tensor) else o for o in flat]
            raw._out_tree = treedef
            return tuple(flat)

        if jpolicy is None:
            return jax.checkpoint(body)(seed, params, inputs, kw)
        return jax.checkpoint(body, policy=jpolicy)(seed, params, inputs,
                                                    kw)

    opdef = OpDef(f"recompute_{getattr(fn, '__name__', 'fn')}", raw)
    seed = next_key() if preserve_rng_state else jax.random.PRNGKey(0)
    out = _op_registry.dispatch(opdef, (seed, list(ptensors), list(args), dict(kwargs)),
                   {})
    flat, _ = jax.tree_util.tree_flatten(
        out, is_leaf=lambda x: isinstance(x, Tensor))
    return jax.tree_util.tree_unflatten(raw._out_tree, flat)


def scan_passes(function, passes, x, *args, parameters):
    """`function(x, *args) -> x'` run `passes` times over, each pass from
    the one before's output, with the same weights: one `jax.lax.scan`
    whose carry is x, whose invariants are the parameters (made explicit
    inputs, as `recompute` makes a block's) and `args`, and whose stacked
    outputs, [passes, *x.shape], are what is returned (the last of them
    is the loop's result). x' has x's shape and type.

    `function` is a bound method or a plain function; `parameters` are
    the tensors it reads, every one of them (a weight left out would be
    a constant of the body and get no gradient).
    Blocks inside it may be recomputed (`recompute`): the scan stacks
    what each keeps over the passes. Differentiated, it is JAX's
    transposed scan: every parameter's cotangent is summed over the
    passes in the carry. One form: traced under a step it is a `while`
    of the lowered program; called eagerly it is the same scan, run
    op by op's rules (the tape sees one op)."""
    ptensors = list(parameters)
    passes = int(passes)

    from ...jit import _functional_params

    def raw(seed, params, x, inputs):
        def one(carry, t):
            with rng_scope(jax.random.fold_in(seed, t)), \
                    _functional_params(ptensors, list(params)), \
                    tape.no_grad():
                out = function(carry, *inputs)
            out = out._data if isinstance(out, Tensor) else out
            return out, out

        return jax.lax.scan(one, x, jax.numpy.arange(passes))[1]

    opdef = OpDef(f"scan_passes_{function.__name__}", raw)
    return _op_registry.dispatch(
        opdef, (next_key(), ptensors, x, list(args)), {})


def recompute_sequential(ctx, functions, *args, **kwargs):
    """ref: recompute_sequential — chunk a Sequential and recompute each
    chunk."""
    segments = ctx.get("segments", 1) if isinstance(ctx, dict) else 1
    sublayers = list(functions) if isinstance(
        functions, (list, tuple)) else list(functions.children())
    n = len(sublayers)
    per = max(1, n // segments)
    x = args[0] if len(args) == 1 else args

    class _Chunk(Layer):
        def __init__(self, mods):
            super().__init__()
            from ...nn.layers.container import LayerList
            self.mods = LayerList(mods)

        def forward(self, inp):
            for m in self.mods:
                inp = m(inp)
            return inp

    i = 0
    while i < n:
        chunk = _Chunk(sublayers[i:i + per])
        x = recompute(chunk, x, **kwargs)
        i += per
    return x
