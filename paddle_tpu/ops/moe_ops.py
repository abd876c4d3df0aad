"""Dropless mixture-of-experts ops: the three routers (`moe_route` with
sigmoid or softmax scores, `moe_route_mlp`), the permutation of
assignments to the experts held here, and the expert products around
`kernels/pallas/grouped_matmul.py`.

No capacity and no dropped token: every assignment to a held expert is
computed, whatever the imbalance. Shapes are static and sized for the
worst case (every one of the tokens x top_k assignments held here); what
is gathered into the experts' order, multiplied and activated follows
the used prefix of the rows, which is known only on the device: the
kernels skip the tiles past it and the XLA parts are loops over `_CHUNK`
rows with a traced trip count (inside `jax.custom_vjp`s, so nothing
differentiates through a loop). The way back to the tokens' order
(`_way_back`: `combine_rows` forward, `take_rows` backward) sums the rows
that hold an assignment and moves no other: on a TPU the kernel
`kernels/pallas/moe_sum_rows.py`, which fetches them in the 16-row
windows the chip's DMAs take; elsewhere (the CPU tests) `_sum_slots`, a
plain gather over all tokens x top_k slots.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..core.mesh_plan import expert_axis_plan
from ..kernels.pallas import grouped_matmul as _gm
from ..kernels.pallas import moe_sum_rows as _sr
from ..kernels.pallas.flash_attention import _pallas_available
from ..observability import perf as _pf
from .registry import register_op

__all__ = ["moe_route", "moe_route_mlp", "moe_sequence_balance",
           "moe_experts"]

_CHUNK = 2048       # rows a loop iteration gathers or activates
F32 = jnp.float32


@register_op("moe_route", amp_policy="black")
def moe_route(x, router_weight, top_k, routed_scale=1.0, score="sigmoid",
              normalize=True, with_scores=False):
    """x [T, d], router_weight [d, E] -> (weights [T, top_k] float32,
    experts [T, top_k] int32): scores = sigmoid(x W), or with
    `score="softmax"` softmax(x W) over all E, in float32 (the product
    at `highest` precision: a TPU's default rounds a float32 matmul's
    operands to bf16, and a top-k choice turns on the last digits), the
    top_k largest chosen, their scores normalised to one and multiplied
    by routed_scale (of a softmax that is the softmax over the chosen
    logits). On amp's black list. The sigmoid is the router of
    `laguna-xs2-l5-e64`, the softmax of `qwen3-next-80b-l4-e64`;
    `zaya1-8b-l5-e8`'s is `moe_route_mlp`.

    `normalize=False`: the weights are the chosen scores themselves times
    routed_scale, not divided by their sum (`norm_topk_prob: false`;
    `deepseek-v2-lite-e8`). `with_scores=True`: the scores [T, E] are a
    third output, for a balance loss over all E."""
    squash = {"sigmoid": jax.nn.sigmoid, "softmax": jax.nn.softmax}[score]
    scores = squash(jnp.matmul(
        x.astype(F32), router_weight.astype(F32),
        precision=jax.lax.Precision.HIGHEST))
    top, experts = _top_k(scores, top_k)
    if normalize:
        weights = top * (routed_scale
                         / jnp.sum(top, axis=-1, keepdims=True))
    else:
        weights = top * routed_scale
    return (weights, experts, scores) if with_scores else (weights, experts)


@register_op("moe_sequence_balance", amp_policy="black")
def moe_sequence_balance(scores, experts, rows=1):
    """The sequence-wise balance term of a router (DeepSeek-V2,
    arXiv:2405.04434, eq. 12-14; `seq_aux`): scores [rows * T, E] the
    router's scores over ALL E experts, experts [rows * T, K] the chosen
    ones. Over each row of T tokens f_e = E / (K T) * (the tokens that
    chose e) and P_e = mean_t scores[t, e]; returns the rows' mean of
    sum_e f_e P_e, float32 (1 where the load is even). The counts are
    constants: the gradient reaches the router through P alone."""
    E, K = scores.shape[-1], experts.shape[-1]
    s = scores.astype(F32).reshape(rows, -1, E)
    chosen = experts.reshape(rows, -1, K)
    lanes = jnp.arange(E, dtype=jnp.int32)
    chose = sum((chosen[..., j, None] == lanes).astype(F32)
                for j in range(K))                          # [rows, T, E]
    f = jnp.sum(chose, axis=1) * (E / (K * s.shape[1]))
    return jnp.mean(jnp.sum(jax.lax.stop_gradient(f) * jnp.mean(s, axis=1),
                            axis=-1))


@register_op("moe_route_mlp", amp_policy="black")
def moe_route_mlp(x, state, w_down, state_scale, w1, w2, w3):
    """The `zaya` router: a down-projection, depth averaging, a
    two-hidden-layer MLP, softmax, one expert a token.

    x [T, d], state [T, R] (the router's state of the layer before,
    zero where there is none), w_down [d, R], state_scale [1],
    w1, w2 [R, R], w3 [R, E] ->
    (weights [T, 1] float32, experts [T, 1] int32, state [T, R] float32):

        r = x W_down + state_scale * state          (the state handed on)
        p = softmax(gelu(gelu(r W_1) W_2) W_3);  e = argmax p;  w = p_e

    The weight is the chosen probability itself, not normalised over the
    chosen (it would be 1). All of it in float32 with the products at
    `highest` precision, as `moe_route`: one choice a token decides the
    token's whole routed output, and it turns on the last digits where
    the two largest probabilities nearly tie. On amp's black list."""
    hi = jax.lax.Precision.HIGHEST
    r = jnp.matmul(x.astype(F32), w_down.astype(F32), precision=hi) \
        + state_scale.astype(F32) * state.astype(F32)
    h = jax.nn.gelu(jnp.matmul(r, w1.astype(F32), precision=hi),
                    approximate=False)
    h = jax.nn.gelu(jnp.matmul(h, w2.astype(F32), precision=hi),
                    approximate=False)
    p = jax.nn.softmax(jnp.matmul(h, w3.astype(F32), precision=hi), axis=-1)
    experts = jnp.argmax(p, axis=-1).astype(jnp.int32)[:, None]
    return jnp.max(p, axis=-1, keepdims=True), experts, r


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _top_k(scores, k):
    """`jax.lax.top_k` whose gradient is k masked passes over the scores
    and no scatter of tokens x k scalars."""
    top, experts = jax.lax.top_k(scores, k)
    return top, experts.astype(jnp.int32)


def _top_k_fwd(scores, k):
    top, experts = _top_k(scores, k)
    return (top, experts), (experts, jnp.zeros((0, scores.shape[-1]),
                                               scores.dtype))


def _top_k_bwd(k, res, cot):
    experts, like = res
    d_top = cot[0]
    lanes = jnp.arange(like.shape[-1], dtype=jnp.int32)
    d_scores = sum(
        jnp.where(experts[..., j, None] == lanes, d_top[..., j, None], 0)
        for j in range(k))
    return (d_scores.astype(like.dtype),)


_top_k.defvjp(_top_k_fwd, _top_k_bwd)


# -- the permutation ----------------------------------------------------------
def permutation(experts, first, count, tile=None):
    """Where each assignment to a held expert (first <= e < first + count)
    goes among the rows of `grouped_matmul.group_layout`, sorted by
    expert, slots of one expert in token order.

    experts [T, k] int32 -> dict of
      counts [count]        assignments to every held expert
      rows_used ()          rows of the used prefix (a multiple of ROW_TILE)
      slot_of_row [R]       the assignment (t * k + j) a row holds, 0 for
                            padding and unused rows
      live_row [R]          the row holds an assignment
      row_of_slot [T * k]   the row an assignment went to, 0 if not held
      held_slot [T * k]     the assignment's expert is held here
    and, with `tile` (tokens),
      way_back              `moe_sum_rows.plan`: what the kernel of the
                            way back reads
    R = grouped_matmul.padded_rows(T * k, count), rounded up to _CHUNK."""
    A = experts.size
    R = -(-_gm.padded_rows(A, count) // _CHUNK) * _CHUNK
    local = experts.reshape(A) - first
    held = jnp.logical_and(local >= 0, local < count)
    local = jnp.where(held, local, count)
    # one sort of unique keys (group major, slot minor), and one more for
    # the inverse permutation: every later index is a gather, and the
    # counts are where the sorted keys change group (a scatter-add of
    # every assignment into a few bins would run one update at a time)
    slots = jnp.arange(A, dtype=jnp.int32)
    keys = jnp.sort(local * A + slots)
    order = keys % A                                # rank -> slot
    # the rank at which each group's slots of each token tile begin (one
    # tile without `tile`: where the group begins)
    per_tile = A if tile is None else tile * experts.shape[1]
    begins = jnp.searchsorted(keys, (
        jnp.arange(count + 1, dtype=jnp.int32)[:, None] * A
        + jnp.arange(0, A, per_tile, dtype=jnp.int32)[None]).reshape(-1)
    ).astype(jnp.int32).reshape(count + 1, -1)
    counts = jnp.diff(begins[:, 0])
    _, rank = jax.lax.sort((order, slots), num_keys=1)   # slot -> rank
    starts, tile_group, tiles_used = _gm.group_layout(
        counts, R // _gm.ROW_TILE)
    first_rank = jnp.cumsum(counts) - counts        # group -> its first rank
    rows = jnp.arange(R, dtype=jnp.int32)

    def of_row(per_group):      # a group's value on every row of its tiles
        return jnp.repeat(per_group[tile_group], _gm.ROW_TILE)
    within = rows - of_row(starts)
    rows_used = tiles_used * _gm.ROW_TILE
    live_row = jnp.logical_and(within < of_row(counts), rows < rows_used)
    slot_of_row = jnp.where(
        live_row, order[jnp.minimum(of_row(first_rank) + within, A - 1)], 0)
    g = jnp.minimum(local, count - 1)
    row_of_slot = jnp.where(held, starts[g] + rank - first_rank[g], 0)
    p = dict(counts=counts, rows_used=rows_used, slot_of_row=slot_of_row,
             live_row=live_row, row_of_slot=row_of_slot, held_slot=held)
    if tile is not None:
        # the first row of an expert that holds an assignment of a token
        # >= i * tile: tile i's rows of expert g are [i, g] .. [i + 1, g]
        before = begins[:count] - begins[:count, :1]    # [count, tiles]
        tile_rows = jnp.concatenate(
            [starts[None] + before.T, (starts + counts)[None]])
        p["way_back"] = _sr.plan(*(a.reshape(experts.shape) for a in (
            row_of_slot, held, g)), tile_rows, tile)
    return p


def _buffer(shape, dtype, rows_used):
    """An uninitialised [R, ...] buffer for a loop over the used prefix to
    fill. On one device `jax.lax.empty`, as ever. Under a mesh plan's
    expert axis the buffer is sized for EVERY device's assignments, and
    the TPU compiler, for which an allocation without operands is ready
    at once, hoists it far ahead of its loop: the allocations of the
    layers still to come, live beside the one at work, were 6 GiB of
    the 11.2 GiB of temporaries a chip's `mellum2-12b-l4` step needed
    (PERF.md, PR 49). There it is the result of a conditional on
    `rows_used` (always the branch that allocates and writes nothing),
    which cannot run before the permutation that gives `rows_used`."""
    if expert_axis_plan() is None:
        return jax.lax.empty(shape, dtype)
    return jax.lax.cond(rows_used >= 0,
                        lambda: jax.lax.empty(shape, dtype),
                        lambda: jnp.zeros(shape, dtype))


def _over_chunks(rows_used, body, init):
    """body(lo, carry) over the used prefix, `_CHUNK` rows at a time."""
    n = (rows_used + _CHUNK - 1) // _CHUNK
    return jax.lax.fori_loop(
        0, n, lambda i, c: body(i * _CHUNK, c), init)


def _rows(a, lo):
    return jax.lax.dynamic_slice_in_dim(a, lo, _CHUNK, axis=0)


def _put(buf, chunk, lo):
    return jax.lax.dynamic_update_slice_in_dim(buf, chunk, lo, axis=0)


def _gather_rows(x, index, rows_used):
    """out[r] = x[index[r]] for r in the used prefix; the rest of out
    [R, d] is unspecified."""
    out = _buffer((index.shape[0], x.shape[1]), x.dtype, rows_used)
    return _over_chunks(
        rows_used, lambda lo, out: _put(out, x[_rows(index, lo)], lo), out)


_TOKENS = 2048      # tokens whose top_k rows `_sum_slots` holds at once


def _sum_slots(vals, row_of_slot, held_slot, k, scale=None):
    """out[t] = sum over token t's held assignments j of
    (scale[t * k + j] *) vals[row_of_slot[t * k + j]], in float32; out
    [T, d]. The way back from the experts' order off the TPU, and what
    the kernel is tested against: a gather over all tokens x k slots,
    what is not held read from row 0 and masked."""
    T = row_of_slot.shape[0] // k
    chunk = _TOKENS if T % _TOKENS == 0 else T

    def some(args):
        rows, held, scale = args
        g = vals[rows].astype(F32)                  # [chunk * k, d]
        if scale is not None:
            g = g * scale[:, None]
        g = jnp.where(held[:, None], g, 0)
        return jnp.sum(g.reshape(chunk, k, -1), axis=1)

    parts = jax.lax.map(some, tuple(
        None if a is None else a.reshape(T // chunk, chunk * k)
        for a in (row_of_slot, held_slot, scale)))
    return parts.reshape(T, -1)


def way_back_reads_held_rows_only() -> bool:
    """Whether the way back of a program traced now is the kernel."""
    return _pallas_available()


def way_back_path() -> str:
    """The form of the way back a program traced now takes."""
    if way_back_reads_held_rows_only():
        return f"held rows in windows of {_sr.WINDOW} (moe_sum_rows)"
    return "all slots (gather)"


def _way_back(vals, p, k, way, scale=None):
    """out[t] = sum over token t's held assignments j, in slot order, of
    (scale[t, j] *) float32(vals[row of (t, j)]), rounded once to vals's
    type; out [T, d]. `way`: None for `_sum_slots`, or `sum_rows`'s
    (tile, interpret), the tile `p["way_back"]` was made for."""
    if way is None:
        flat = None if scale is None else scale.reshape(-1)
        return _sum_slots(vals, p["row_of_slot"], p["held_slot"], k,
                          flat).astype(vals.dtype)
    tile, interpret = way
    return _sr.sum_rows(vals, p["way_back"], scale, tile=tile,
                        out_dtype=vals.dtype, interpret=interpret)


# rows of x in the experts' order, and back
@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def take_rows(x, p, k, way):
    return _gather_rows(x, p["slot_of_row"] // k, p["rows_used"])


def _take_fwd(x, p, k, way):
    return take_rows(x, p, k, way), p


def _take_bwd(k, way, p, d_rows):
    return _way_back(d_rows, p, k, way), None


take_rows.defvjp(_take_fwd, _take_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def combine_rows(ys, weights, p, way):
    """out[t] = sum over token t's held assignments j of weights[t, j] *
    ys[row of (t, j)], in float32 and returned in ys's type; weights
    [T, k] float32, p the `permutation`."""
    return _way_back(ys, p, weights.shape[1], way, weights)


def _combine_fwd(ys, weights, p, way):
    return combine_rows(ys, weights, p, way), (ys, weights, p)


def _combine_bwd(way, res, dy):
    ys, weights, p = res
    k = weights.shape[1]
    flat = weights.reshape(-1)

    def body(lo, carry):
        d_ys, d_w = carry
        slot, live = _rows(p["slot_of_row"], lo), _rows(p["live_row"], lo)
        g = dy[slot // k].astype(F32)                   # [chunk, d]
        w = jnp.where(live, flat[slot], 0)
        d_ys = _put(d_ys, (g * w[:, None]).astype(ys.dtype), lo)
        dot = jnp.sum(g * _rows(ys, lo).astype(F32), axis=-1)
        return d_ys, _put(d_w, jnp.where(live, dot, 0), lo)

    # a row's weight gradient is read by its slot: zero past the prefix
    d_ys, d_w_row = _over_chunks(
        p["rows_used"], body,
        (_buffer(ys.shape, ys.dtype, p["rows_used"]),
         jnp.zeros(p["live_row"].shape, F32)))
    d_w = jnp.where(p["held_slot"], d_w_row[p["row_of_slot"]], 0)
    return d_ys, d_w.reshape(weights.shape), None


combine_rows.defvjp(_combine_fwd, _combine_bwd)


@jax.custom_vjp
def swiglu_rows(gate_up, rows_used):
    """silu(gate) * up of the used prefix of gate_up [R, 2 * w] (gate in
    the first w columns) -> [R, w]."""
    return _swiglu(gate_up, rows_used)


def _swiglu(gate_up, rows_used):
    w = gate_up.shape[1] // 2

    def body(lo, out):
        gu = _rows(gate_up, lo).astype(F32)
        return _put(out, (jax.nn.silu(gu[:, :w]) * gu[:, w:]
                          ).astype(out.dtype), lo)
    return _over_chunks(rows_used, body,
                        _buffer((gate_up.shape[0], w), gate_up.dtype,
                                rows_used))


def _swiglu_fwd(gate_up, rows_used):
    return _swiglu(gate_up, rows_used), (gate_up, rows_used)


def _swiglu_bwd(res, dh):
    gate_up, rows_used = res
    w = gate_up.shape[1] // 2

    def body(lo, out):
        gu = _rows(gate_up, lo).astype(F32)
        g, u = gu[:, :w], gu[:, w:]
        d = _rows(dh, lo).astype(F32)
        sig = jax.nn.sigmoid(g)
        dg = d * u * sig * (1 + g * (1 - sig))
        return _put(out, jnp.concatenate([dg, d * g * sig], axis=1
                                         ).astype(out.dtype), lo)
    return (_over_chunks(rows_used, body,
                         _buffer(gate_up.shape, gate_up.dtype, rows_used)),
            None)


swiglu_rows.defvjp(_swiglu_fwd, _swiglu_bwd)


def _held_part(xs, weights, experts, w_gate_up, w_down, first, interpret,
               lean=False):
    """`moe_experts` on operands already in the products' type: the part
    of the routed sum that the experts first .. first + count give
    (`first` may be traced: a device's rank times its count). `lean`:
    the rows in the experts' order are not kept for the backward pass
    but gathered again there (`_rows_product`)."""
    dt = xs.dtype
    count = w_gate_up.shape[0]
    T, k = experts.shape
    way = None
    if interpret or way_back_reads_held_rows_only():
        way = (_sr.token_tile(T, k, count, xs.shape[1], dt), bool(interpret))
    with jax.named_scope("permute"):
        p = permutation(experts, first, count, way and way[0])
        used = p["rows_used"]
        if not lean:
            rows = take_rows(xs, p, k, way)
    with jax.named_scope("experts"):
        if lean:
            gate_up = _rows_product(xs, w_gate_up.astype(dt), p, k, way,
                                    interpret)
        else:
            gate_up = _gm.gmm(rows, w_gate_up.astype(dt), p["counts"],
                              interpret=interpret)
        h = swiglu_rows(gate_up, used)
        ys = _gm.gmm(h, w_down.astype(dt), p["counts"], interpret=interpret)
    with jax.named_scope("combine"):
        y = combine_rows(ys, weights.astype(F32), p, way)
    return y, p["counts"]


def _rows_product(xs, w, p, k, way, interpret):
    """gmm(take_rows(xs), w) that keeps its product for the backward pass
    and not its rows: a `jax.checkpoint` that saves the product by name,
    so the backward gathers the rows again (one more pass over them, no
    matmul run twice: the kernel's second product is unused and goes).
    Under the exchange the rows' buffer is sized for every device's
    assignments at once (1.13 GiB at 4 x 8192 tokens of 2304): kept, the
    step of `mellum2-12b-l4` needs 1.7 GiB more of a chip (12.2 GiB
    against 10.5) and reads over the fit guard of
    `tests/test_tpu_aot_compile.py`."""
    from jax.ad_checkpoint import checkpoint_name

    def product(xs, w):
        with jax.named_scope("permute"):
            rows = take_rows(xs, p, k, way)
        return checkpoint_name(
            _gm.gmm(rows, w, p["counts"], interpret=interpret),
            "moe_gate_up")

    return jax.checkpoint(
        product, policy=jax.checkpoint_policies.save_only_these_names(
            "moe_gate_up"))(xs, w)


def exchange_bytes(tokens: int, k: int, d: int, itemsize: int, n: int):
    """What one device sends in one forward of the exchanged layer, in
    bytes: (the way out, the way back). Out, its `tokens` rows of `d`
    values with their k weights and k experts (4 bytes each) go to the
    n - 1 other devices; back, it sends each of them their rows of its
    partial sums."""
    row = d * itemsize
    return ((n - 1) * tokens * (row + 8 * k), (n - 1) * tokens * row)


def _exchanged(plan, xs, weights, experts, w_gate_up, w_down, interpret):
    """The whole routed sum with the experts laid over a mesh axis of n
    devices, `num_experts / n` a device, for tokens whose rows are split
    over the same axis: every device's rows, weights and choices are
    gathered on all (the way out), each device runs `_held_part` for its
    own experts on all n x T of them, and the partial sums are summed
    over the axis and scattered to the rows' owners (the way back). A
    token's row travels to n - 1 devices and n - 1 partial sums of it
    travel back, whatever it chose: dropless, static shapes, and the
    permutation and kernels of one device unchanged. Differentiated, the
    two collectives change places. counts come out over all
    `num_experts`, of all the axis's tokens."""
    from jax.sharding import PartitionSpec as P
    from ..observability import comms
    mesh, axis, n = plan
    num_experts = w_gate_up.shape[0]
    if num_experts % n or xs.shape[0] % n:
        raise ValueError(
            f"moe_experts: {num_experts} experts and {xs.shape[0]} tokens "
            f"do not divide over the {n} devices of axis {axis!r}")
    held = num_experts // n
    T, k = experts.shape[0] // n, experts.shape[1]
    row = xs.shape[1] * xs.dtype.itemsize
    # a rank's own message, as `comms` counts: its rows, weights and
    # choices out; all n x T rows of its partial sums back
    comms.count("all_gather", axis, T * (row + 8 * k), n=3)
    comms.count("reduce_scatter", axis, n * T * row)
    out_b, back_b = exchange_bytes(T, k, xs.shape[1], xs.dtype.itemsize, n)
    _pf.trace_note(
        "moe_exchange",
        f"gather and reduce-scatter over {axis!r}: {n} devices, {held} "
        f"experts each, {T} rows of {xs.shape[1]} {xs.dtype.name} a device, "
        f"a forward sends {out_b} B out and {back_b} B back")

    def on_device(xs, weights, experts, w_gate_up, w_down):
        with jax.named_scope("exchange_out"):
            xs, weights, experts = (
                jax.lax.all_gather(a, axis, axis=0, tiled=True)
                for a in (xs, weights, experts))
        y, counts = _held_part(xs, weights, experts, w_gate_up, w_down,
                               jax.lax.axis_index(axis) * held, interpret,
                               lean=True)
        with jax.named_scope("exchange_back"):
            y = jax.lax.psum_scatter(y, axis, scatter_dimension=0,
                                     tiled=True)
        return y, counts

    lead = P(axis)
    return jax.shard_map(on_device, mesh=mesh, in_specs=(lead,) * 5,
                         out_specs=(lead, lead), check_vma=False)(
        xs, weights, experts, w_gate_up, w_down)


@register_op("moe_experts", amp_policy="keep")
def moe_experts(x, weights, experts, w_gate_up, w_down, first=0,
                interpret=False):
    """The held experts' part of a sparse feed-forward's routed sum.

    x [T, d]; weights, experts [T, k] (`moe_route`); w_gate_up
    [count, d, 2 * width] (gate in the first `width` columns), w_down
    [count, width, d]: the experts first .. first + count. Returns
    (y [T, d] in x's type, counts [count] int32):

        y[t] = sum over j with first <= experts[t, j] < first + count of
               weights[t, j] * SwiGLU_{experts[t, j]}(x[t])

    Under amp the products take bf16 operands (cast here: the op keeps
    its routing weights float32) and accumulate in float32.

    Traced under a `mesh_plan` with an `expert_axis`, the weights are all
    the experts, laid over that axis, and the op runs their exchange
    (`_exchanged`): y is the whole routed sum and counts is over all the
    experts."""
    from ..amp.state import amp_state
    st = amp_state()
    dt = st.dtype.np_dtype if st.enabled else x.dtype
    xs = x.astype(dt)
    plan = expert_axis_plan()
    if plan is not None:
        if first:
            raise ValueError("moe_experts: under an expert axis the layer "
                             "holds all its experts, from the first")
        y, counts = _exchanged(plan, xs, weights, experts, w_gate_up,
                               w_down, interpret)
    else:
        y, counts = _held_part(xs, weights, experts, w_gate_up, w_down,
                               first, interpret)
    return y.astype(x.dtype), counts
