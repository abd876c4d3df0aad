"""`models/mellum2.py` and what it forced, on the CPU at a tiny size (8
virtual devices: `tests/conftest.py`): the translation to the decoder
family's configuration, the expert layer's exchange under a mesh plan
(`ops.moe_experts`), the fused head loss over a vocabulary in slices
(`ops.linear_cross_entropy(over=)`), `TrainStep(mesh=, expert_axis=)`
against the same step on one device, `shard_plans`' rule, and arrays born
in their shards. The program against the plain reference is
`benchmarks/tests/test_mellum2.py`."""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

import paddle_tpu as pt
from paddle_tpu import amp
from paddle_tpu.core.mesh_plan import (current_mesh_plan, expert_axis_plan,
                                       mesh_plan)
from paddle_tpu.jit import TrainStep
from paddle_tpu.models import (GPTPretrainingCriterion, Mellum2Config,
                               Mellum2ForCausalLM, mellum2_tiny)
from paddle_tpu.models.shard_plans import expert_parallel_rules
from paddle_tpu.observability import perf
from paddle_tpu.ops import moe_ops
from paddle_tpu.ops.nn_ops import linear_cross_entropy
from paddle_tpu.optimizer import AdamW

CHIPS = 4


@pytest.fixture(scope="module")
def mesh():
    return Mesh(np.array(jax.devices()[:CHIPS]), ("ep",))


# -- the configuration ----------------------------------------------------------
def test_the_published_defaults_are_mellum2_12b():
    c = Mellum2Config()
    assert c.layer_types[:8] == (["sliding_attention"] * 3
                                 + ["full_attention"]) * 2
    assert c.mlp_layer_types == ["sparse"] * 28
    assert (c.num_experts, c.num_experts_per_tok,
            c.moe_intermediate_size) == (64, 8, 896)
    assert c.rope_parameters["full_attention"]["attention_factor"] \
        == pytest.approx(0.1 * np.log(16) + 1)
    with pytest.raises(NotImplementedError):
        Mellum2Config(norm_topk_prob=False)


def test_the_decoder_family_holds_it_whole():
    lag = Mellum2Config(num_hidden_layers=4).laguna()
    assert lag.num_attention_heads_per_layer == [32] * 4
    assert (lag.router_score, lag.moe_routed_scaling_factor,
            lag.shared_expert_intermediate_size) == ("softmax", 1.0, 0)
    assert tuple(lag.experts_held) == (0, 64)
    assert lag.sliding_window == 1024 and lag.vocab_size == 98304
    assert "partial_rotary_factor" not in lag.rope_parameters[
        "full_attention"]


def test_from_dict_reads_the_benchmarks_file():
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmarks", "configs",
        "mellum2-12b-l4.json")
    with open(path) as f:
        c = Mellum2Config.from_dict(json.load(f), recompute=True)
    assert (c.num_hidden_layers, c.hidden_size, c.vocab_size) == (
        4, 2304, 98304)
    assert c.layer_types == ["sliding_attention"] * 3 + ["full_attention"]
    assert c.recompute


def test_the_model_is_the_familys_with_no_shared_expert_and_no_dense_layer():
    pt.seed(0)
    model = Mellum2ForCausalLM(mellum2_tiny())
    layers = model.laguna.layers
    assert [lay.attn.window for lay in layers] == [8, 8, 8, None]
    assert all(hasattr(lay, "moe") and not hasattr(lay, "mlp")
               for lay in layers)
    assert all(lay.moe.shared_expert is None
               and lay.moe.router.score == "softmax" for lay in layers)
    names = [n for n, _p in model.named_parameters()]
    assert names[:2] == ["laguna.embed_tokens.weight",
                         "laguna.layers.0.input_layernorm.weight"]
    assert names[7:10] == ["laguna.layers.0.moe.gate_up_proj",
                           "laguna.layers.0.moe.down_proj",
                           "laguna.layers.0.moe.router.weight"]
    assert not any("shared_expert" in n for n in names)


# -- the rule and the plan --------------------------------------------------
@pytest.mark.parametrize("name,shape,spec", [
    ("laguna.layers.2.moe.gate_up_proj", (64, 2304, 1792), P("ep")),
    ("laguna.layers.2.moe.down_proj", (64, 896, 2304), P("ep")),
    ("laguna.embed_tokens.weight", (98304, 2304), P("ep", None)),
    ("lm_head.weight", (2304, 98304), P(None, "ep")),
    ("laguna.layers.2.moe.router.weight", (2304, 64), P()),
    ("laguna.layers.2.attn.o_proj.weight", (4096, 2304), P()),
    ("laguna.layers.2.attn.q_proj.weight", (2304, 4096), P()),
    ("laguna.norm.weight", (2304,), P()),
])
def test_the_expert_parallel_rule_by_name_and_shape(name, shape, spec):
    assert expert_parallel_rules("ep")(name, shape) == spec


def test_the_plan_names_the_expert_axis(mesh):
    assert current_mesh_plan() is None and expert_axis_plan() is None
    with mesh_plan(mesh, ("ep",), "ep"):
        assert current_mesh_plan() == (mesh, ("ep",), "ep")
        assert expert_axis_plan() == (mesh, "ep", CHIPS)
    with mesh_plan(mesh, ("ep",)):
        assert expert_axis_plan() is None
    with pytest.raises(ValueError, match="no axis"):
        with mesh_plan(mesh, (), "experts"):
            pass


# -- the exchanged layer -----------------------------------------------------
def _layer(seed=0, tokens=64, d=32, experts=8, k=2, width=16):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.standard_normal((tokens, d)), jnp.float32)
    w_r = jnp.asarray(rng.standard_normal((d, experts)) * 0.5, jnp.float32)
    w_gu = jnp.asarray(rng.standard_normal((experts, d, 2 * width)) * 0.3,
                       jnp.float32)
    w_down = jnp.asarray(rng.standard_normal((experts, width, d)) * 0.3,
                         jnp.float32)
    weights, chosen = moe_ops.moe_route.op_def.fn(x, w_r, k, 1.0, "softmax")
    return x, weights, chosen, w_gu, w_down


def _dense(x, weights, chosen, w_gu, w_down):
    """The routed sum as the equations have it."""
    width = w_down.shape[1]
    a = jnp.einsum("td,edk->etk", x, w_gu)
    y = jnp.einsum("etk,ekd->etd",
                   jax.nn.silu(a[..., :width]) * a[..., width:], w_down)
    w_all = jnp.zeros((x.shape[0], w_gu.shape[0])).at[
        jnp.arange(x.shape[0])[:, None], chosen].set(weights)
    return jnp.einsum("te,etd->td", w_all, y)


def test_the_four_shares_parts_add_up_to_the_whole_layer():
    """The guide's tie: `held` = each quarter, no mesh."""
    x, weights, chosen, w_gu, w_down = _layer()
    parts, counts = [], []
    for rank in range(CHIPS):
        lo = rank * 2
        y, c = moe_ops.moe_experts.op_def.fn(
            x, weights, chosen, w_gu[lo:lo + 2], w_down[lo:lo + 2], lo)
        parts.append(y)
        counts.append(np.asarray(c))
    np.testing.assert_allclose(sum(parts), _dense(
        x, weights, chosen, w_gu, w_down), atol=2e-5)
    assert np.concatenate(counts).sum() == chosen.size


def _exchanged(mesh, *operands):
    def fn(*operands):
        with mesh_plan(mesh, ("ep",), "ep"):
            return moe_ops.moe_experts.op_def.fn(*operands)
    return jax.jit(fn)(*operands)


def test_the_exchanged_layer_gives_that_sum_on_every_chips_own_rows(mesh):
    x, weights, chosen, w_gu, w_down = _layer()
    y, counts = _exchanged(mesh, x, weights, chosen, w_gu, w_down)
    np.testing.assert_allclose(y, _dense(x, weights, chosen, w_gu, w_down),
                               atol=2e-5)
    want = np.bincount(np.asarray(chosen).ravel(), minlength=8)
    assert (np.asarray(counts) == want).all()
    # and its gradients are the whole layer's
    def loss(fn, x, w_gu, w_down, weights):
        return jnp.sum(jnp.sin(fn(x, weights, chosen, w_gu, w_down)))
    got = jax.grad(lambda *a: loss(
        lambda *o: _exchanged(mesh, *o)[0], *a), argnums=(0, 1, 2, 3))(
        x, w_gu, w_down, weights)
    want = jax.grad(lambda *a: loss(_dense, *a), argnums=(0, 1, 2, 3))(
        x, w_gu, w_down, weights)
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, atol=5e-5)


def test_the_worst_imbalance_loses_no_assignment(mesh):
    """Every token of every chip chooses chip 2's two experts."""
    x, weights, _chosen, w_gu, w_down = _layer()
    chosen = jnp.tile(jnp.asarray([[4, 5]], jnp.int32), (x.shape[0], 1))
    y, counts = _exchanged(mesh, x, weights, chosen, w_gu, w_down)
    assert np.asarray(counts).tolist() == [0, 0, 0, 0, 64, 64, 0, 0]
    np.testing.assert_allclose(y, _dense(x, weights, chosen, w_gu, w_down),
                               atol=2e-5)


def test_the_exchange_counts_its_collectives_and_says_what_it_sends(mesh):
    from paddle_tpu import observability as obs
    x, weights, chosen, w_gu, w_down = _layer()
    out_b, back_b = moe_ops.exchange_bytes(16, 2, 32, 4, CHIPS)
    assert out_b == 3 * 16 * (32 * 4 + 16) and back_b == 3 * 16 * 32 * 4
    obs.enable()
    try:
        obs.reset()
        _exchanged(mesh, x + 1, weights, chosen, w_gu, w_down)
        series = obs.snapshot()["paddle_tpu_collective_bytes_total"]["series"]
        launches = obs.snapshot()[
            "paddle_tpu_collective_launches_total"]["series"]
    finally:
        obs.disable()
        obs.reset()
    sent = {op: v for (op,), v in series.items() if v}
    assert {k: v for k, v in launches.items() if v} == {
        ("all_gather", "in_trace"): 3, ("reduce_scatter", "in_trace"): 1}
    # nccl's convention, a rank's own message: its rows, weights and
    # choices out; every chip's rows of its partial sums back
    assert sent == {"all_gather": out_b / 3, "reduce_scatter": back_b / 3 * 4}


def test_a_share_and_an_axis_do_not_go_together(mesh):
    x, weights, chosen, w_gu, w_down = _layer()
    with pytest.raises(ValueError, match="from the first"):
        with mesh_plan(mesh, ("ep",), "ep"):
            moe_ops.moe_experts.op_def.fn(x, weights, chosen, w_gu, w_down, 2)
    with pytest.raises(ValueError, match="do not divide"):
        with mesh_plan(mesh, ("ep",), "ep"):
            moe_ops.moe_experts.op_def.fn(x, weights, chosen, w_gu[:6],
                                          w_down[:6])


# -- the head over a vocabulary in slices ----------------------------------
@pytest.mark.parametrize("transpose_y", [False, True])
def test_the_sliced_head_loss_is_the_whole_one(mesh, transpose_y):
    rng = np.random.default_rng(1)
    n, h, v = 64, 32, 128
    hidden = jnp.asarray(rng.standard_normal((n, h)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((v, h) if transpose_y else (h, v))
                    * 0.3, jnp.float32)
    labels = jnp.asarray(rng.integers(0, v, n), jnp.int32).at[3].set(-100)
    fn = linear_cross_entropy.op_def.fn

    def both(over):
        def loss(hidden, w):
            return fn(hidden, w, labels, None, transpose_y=transpose_y,
                      chunk=16, over=over)
        return jax.jit(jax.value_and_grad(loss, argnums=(0, 1)))(hidden, w)

    (want, (dh, dw)), (got, (gh, gw)) = both(None), both((mesh, "ep"))
    np.testing.assert_allclose(got, want, rtol=1e-6)
    np.testing.assert_allclose(gh, dh, atol=1e-7)
    np.testing.assert_allclose(gw, dw, atol=1e-7)
    with pytest.raises(NotImplementedError):
        fn(hidden, w, labels, None, transpose_y=transpose_y,
           with_rows=True, over=(mesh, "ep"))


# -- the step ----------------------------------------------------------------
def _step(mesh, level, recompute=True):
    pt.seed(0)
    model = Mellum2ForCausalLM(mellum2_tiny(recompute=recompute))
    model.train()
    opt = AdamW(learning_rate=1e-3, parameters=model.parameters(),
                weight_decay=0.1, moment_dtype="bfloat16")
    crit = GPTPretrainingCriterion()

    def loss_fn(m, ids, labels):
        with amp.auto_cast(enable=level != "O0", level="O1",
                           dtype="bfloat16"):
            logits = m(ids)
        return crit(logits, labels), m.expert_counts

    kw = {} if mesh is None else dict(
        mesh=mesh, shard_param=expert_parallel_rules("ep"),
        shard_data=P("ep", None), expert_axis="ep")
    return TrainStep(model, opt, loss_fn, has_aux=True, **kw)


def _run(step, steps=3):
    toks = np.random.default_rng(0).integers(
        0, 512, (steps, 4, 33)).astype(np.int32)
    losses, counts = [], []
    for k in range(steps):
        losses.append(float(step(toks[k][:, :-1], toks[k][:, 1:]).numpy()))
        counts.append(np.asarray(step.aux))
    return losses, counts, [np.asarray(p) for p in step.params]


def test_a_four_way_mesh_step_is_the_one_device_step(mesh):
    """float32 throughout: loss, the counts, and the parameters after
    three updates (the first moments are the gradients)."""
    one, over = _run(_step(None, "O0")), _run(_step(mesh, "O0"))
    np.testing.assert_allclose(over[0], one[0], rtol=2e-6)
    for a, b in zip(over[1], one[1]):
        assert a.shape == (4, 8) and (a == b).all()
    assert all(c.sum(axis=1).tolist() == [4 * 32 * 2] * 4 for c in over[1])
    for a, b in zip(over[2], one[2]):
        np.testing.assert_allclose(a, b, atol=1e-4)


def test_the_mesh_step_under_amp_says_which_paths_it_took(mesh):
    step = _step(mesh, "O1")
    one = _run(_step(None, "O1"), steps=2)
    over = _run(step, steps=2)
    np.testing.assert_allclose(over[0], one[0], rtol=1e-3)
    record = perf.compile_record("train_step")
    assert record["head_loss"] == \
        "fused, chunks 1, vocabulary in 4 slices of 128"
    assert record["moe_exchange"] == (
        "gather and reduce-scatter over 'ep': 4 devices, 2 experts each, "
        "32 rows of 64 bfloat16 a device, a forward sends 13824 B out and "
        "12288 B back")
    # every array in its shards: experts, embedding and head laid over
    # the axis, and the moments with their parameters
    laid = {n: p.sharding.spec for n, p in zip(step._pnames, step.params)}
    assert laid["laguna.layers.1.moe.gate_up_proj"] == P("ep")
    assert laid["lm_head.weight"] == P(None, "ep")
    assert laid["laguna.layers.1.attn.q_proj.weight"] == P()
    i = step._pnames.index("laguna.layers.1.moe.down_proj")
    assert step.opt_states[i]["moment1"].sharding.spec == P("ep")


def test_under_any_other_mesh_the_head_is_whole(mesh):
    pt.seed(0)
    model = Mellum2ForCausalLM(mellum2_tiny())
    model.train()
    crit = GPTPretrainingCriterion()
    step = TrainStep(
        model, AdamW(learning_rate=1e-3, parameters=model.parameters()),
        lambda m, ids, labels: crit(m(ids), labels), mesh=mesh,
        shard_data=P("ep", None))
    toks = np.random.default_rng(0).integers(0, 512, (4, 33)).astype(np.int32)
    assert np.isfinite(float(step(toks[:, :-1], toks[:, 1:]).numpy()))
    # (the family's record keeps earlier programs' other notes)
    assert perf.compile_record("train_step")["head_loss"] == "whole"


# -- arrays born in their shards ---------------------------------------------
def test_placeholders_and_moments_are_born_in_their_shards(mesh):
    lead = NamedSharding(mesh, P("ep"))
    with pt.LazyGuard(place=lambda shape: lead if len(shape) == 3 else None):
        model = Mellum2ForCausalLM(mellum2_tiny())
    gate_up = model.laguna.layers[0].moe.gate_up_proj
    assert gate_up._data.sharding == lead
    assert not isinstance(model.lm_head.weight._data.sharding, NamedSharding)
    assert pt.LazyGuard.place is None
    opt = AdamW(learning_rate=1e-3, parameters=model.parameters(),
                moment_dtype="bfloat16")
    state = opt._get_state(gate_up)
    assert state["moment1"].sharding == lead
    assert state["moment1"].dtype == jnp.bfloat16
    plain = opt._get_state(model.lm_head.weight)
    assert not isinstance(plain["moment1"].sharding, NamedSharding)
