"""The Mellum2 configuration and cell at a size a CPU test can hold (8
virtual devices: the cell's mesh of four is real here): the program
against the plain reference on seeded weights (logits, loss, every
gradient leaf, two AdamW steps through the four-way mesh), the
reference's layout against the same reference on one device, the one
command end to end, the comparison's verdicts on the controls, the
configuration's file against the published row, the entries of
`BENCHMARK.json`, and the counted costs against hand counts. The readers
against a trace recorded on the chip are in `test_mellum2_trace.py`."""
import json
import os

import numpy as np
import pytest

from harness.spec import BENCH_DIR, REPO, Spec

CONFIG, CELL = "mellum2-12b-l4", "mellum2-12b-l4.train-8k-ep4"
TRAFFIC = "pretrain-8k-x4"
# the tiny size's own: 128 tokens a step and weights of 0.15 make a bf16
# step's worst leaf read 0.002 to 0.02, the fp8 control's 0.4 and more and
# the window left off 0.14
TINY_LIMITS = {"grad_norm_worst_leaf": 0.06, "change_norm_median_leaf": 0.02,
               "exchange_gap": 0.05}
# smaller than `tiny.TINY_MODEL` leaves it: few narrow experts, two to a
# chip, a window shorter than the 32-token rows
SMALL = dict(moe_intermediate_size=32, num_experts=8, num_experts_per_tok=2,
             num_attention_heads=4, num_key_value_heads=2, sliding_window=8)


@pytest.fixture
def four_devices():
    """The cell's mesh is real in these tests: a whole run of `tests/`
    gives every process 8 virtual CPU devices (`tests/conftest.py`); by
    hand, `XLA_FLAGS=--xla_force_host_platform_device_count=8`."""
    import jax
    if jax.device_count() < 4:
        pytest.skip("the cell's mesh needs four devices: run through "
                    "tests/, or set XLA_FLAGS="
                    "--xla_force_host_platform_device_count=8")


@pytest.fixture
def tiny_spec(tmp_path, four_devices):
    import tiny
    return Spec(tiny.make_tiny_repo(str(tmp_path / "r"), limits=TINY_LIMITS))


@pytest.fixture
def small(tiny_spec):
    """(cfg, reference module, driver module, mix) at the SMALL size, four
    rows a step: one a chip."""
    cfg = tiny_spec.data("configs", CONFIG)
    cfg.update(SMALL)
    mix = tiny_spec.data("traffic", TRAFFIC)
    mix["batch"] = 4
    return (cfg, tiny_spec.module("reference", CONFIG),
            tiny_spec.module("drivers", "mellum2_train_window"), mix)


def _ids(cfg, seed=0, rows=2, seq=32):
    toks = np.random.default_rng(seed).integers(
        0, cfg["real_vocab_size"], (rows, seq + 1)).astype(np.int32)
    return toks[:, :-1], toks[:, 1:]


# -- the program against the reference ------------------------------------
def test_logits_loss_and_every_gradient_leaf_match_the_reference(small):
    import jax
    import jax.numpy as jnp
    import paddle_tpu as pt
    from harness import mellum2_program, mellum2_reference as mr
    from paddle_tpu.models import GPTPretrainingCriterion
    cfg, ref, _tw, _mix = small
    ids, labels = _ids(cfg)
    model = mellum2_program.build_model(cfg, 5, ref,
                                        mellum2_program.mesh_of(cfg))
    model.eval()
    plain = ref.Model(cfg, 5)
    logits = model(pt.to_tensor(ids))
    np.testing.assert_allclose(logits.numpy(), plain.logits(ids),
                               atol=3e-5)
    # every expert's count is that of the reference's choices
    x, want = plain.params[0][jnp.asarray(ids)], []
    for i in range(cfg["num_hidden_layers"]):
        x, counts = plain._blocks[plain.kind(i)](
            plain.layer(i), x, plain.rope(i, ids.shape[1]))
        want.append(np.asarray(counts))
    assert (model.expert_counts.numpy() == np.stack(want)).all()
    assert np.stack(want).sum(axis=1).tolist() == [2 * 32 * 2] * 4

    loss = GPTPretrainingCriterion()(logits, pt.to_tensor(labels))
    loss.backward()

    def ref_loss(params):
        x = params[0][jnp.asarray(ids)]
        for i in range(cfg["num_hidden_layers"]):
            x, _counts = mr.block(
                params[1 + 9 * i:10 + 9 * i], x,
                plain.rope(i, ids.shape[1]), cfg=cfg, kind=plain.kind(i),
                rnd=mr.exact)
        return mr.head_loss(x, params[-2], params[-1], jnp.asarray(labels),
                            eps=cfg["rms_norm_eps"], rnd=mr.exact) / ids.size

    want_loss, want = jax.value_and_grad(ref_loss)(plain.params)
    np.testing.assert_allclose(float(loss.numpy()), float(want_loss),
                               rtol=2e-6)
    for (name, p), g in zip(model.named_parameters(), want):
        scale = float(jnp.abs(g).max()) + 1e-12
        np.testing.assert_allclose(p.grad.numpy() / scale, g / scale,
                                   atol=2e-4, err_msg=name)


def test_two_adamw_steps_through_the_mesh_match_the_reference(small):
    """The timed path's own objects in float32 (no amp): `TrainStep` over
    the four-way mesh, its experts exchanged and its head in slices,
    against the reference's `Trainer`: every leaf's first gradient norm
    and change after two steps, and every expert's count."""
    from drivers.train_window import leaf_gaps
    cfg, ref, tw, mix = small
    cfg = json.loads(json.dumps(cfg))
    cfg["training"]["amp"] = {"level": "O0", "dtype": "float32"}
    cfg["training"]["optimizer"]["moment_dtype"] = "float32"
    step = tw.build_step(cfg, 3, ref, mix["batch"])
    assert step.step.mesh.shape == {"ep": 4}
    prog = tw.first_steps(step, cfg, mix, 3, ref, 2)
    plain = tw.reference_steps(cfg, mix, 3, ref, 2)
    np.testing.assert_allclose(prog["losses"], plain["losses"], rtol=5e-6)
    assert max(leaf_gaps(prog["grad_norms"], plain["grad_norms"])) < 2e-3
    assert max(leaf_gaps(prog["change_norms"], plain["change_norms"])) < 2e-3
    counts = np.asarray(step.counts[0])
    assert counts.shape == (4, 8) and counts.dtype == np.int32
    assert (counts == plain["held_counts"]).all()


def test_the_seeds_arrays_are_made_by_shard_and_are_the_same_numbers(small):
    """`ref.make` under a layout draws what it draws on one device, and
    the program's leaves lie where `shard_plans`' rule puts them."""
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from harness import mellum2_program
    cfg, ref, _tw, _mix = small
    specs = ref.param_specs(cfg)
    laid = ref.layout(specs)
    assert laid is not None and len(laid) == len(specs)
    for a, b in zip(ref.make(7, specs, jnp.float32, laid),
                    ref.make(7, specs, jnp.float32)):
        assert (np.asarray(a) == np.asarray(b)).all()
    model = mellum2_program.build_model(cfg, 7, ref,
                                        mellum2_program.mesh_of(cfg))
    by = dict(model.named_parameters())
    mesh = mellum2_program.mesh_of(cfg)
    for name, spec in (("laguna.layers.0.moe.gate_up_proj", P("ep")),
                       ("laguna.embed_tokens.weight", P("ep", None)),
                       ("lm_head.weight", P(None, "ep")),
                       ("laguna.layers.0.attn.q_proj.weight", P())):
        array = by[name]._data
        assert array.sharding.is_equivalent_to(
            NamedSharding(mesh, spec), array.ndim), name
    norm = np.asarray(by["laguna.norm.weight"]._data)
    assert 0.05 < norm.std() < 0.2 and abs(norm.mean() - 1) < 0.1


def test_the_references_groups_are_its_one_loop(small):
    """The layout's four parts side by side against every expert in one
    loop on one device: the same sum, counts and gradients."""
    import jax
    import jax.numpy as jnp
    from harness import mellum2_reference as mr
    rng = np.random.default_rng(0)
    u = jnp.asarray(rng.standard_normal((2, 16, 24)), jnp.float32)
    p = [jnp.asarray(rng.standard_normal(s) * 0.3, jnp.float32)
         for s in ((16, 24, 12), (16, 6, 24), (24, 16))]

    def run(groups, parts=()):
        def f(p, u):
            y, counts = mr.sparse_ffn(p, u, top_k=3, rnd=mr.exact,
                                      parts=parts, groups=groups)
            return jnp.sum(jnp.sin(y)), (y, counts)
        (_l, (y, counts)), g = jax.value_and_grad(f, has_aux=True)(p, u)
        return y, counts, g

    one, four = run(1), run(4)
    np.testing.assert_allclose(four[0], one[0], atol=1e-5)
    assert (np.asarray(four[1]) == np.asarray(one[1])).all()
    assert int(one[1].sum()) == 2 * 16 * 3
    for a, b in zip(four[2], one[2]):
        np.testing.assert_allclose(a, b, atol=1e-5)
    # the exchange's own control leaves a quarter of the experts out
    assert not np.allclose(run(4, ("chip_out",))[0], one[0], atol=1e-3)
    # ... and the weights as scored are not the weights over the chosen
    w = mr.routing(u, p[2], top_k=3, rnd=mr.exact)
    np.testing.assert_allclose(jnp.sum(w, -1), 1.0, atol=1e-6)
    scored = mr.routing(u, p[2], top_k=3, rnd=mr.exact,
                        parts=("weights_as_scored",))
    assert float(jnp.max(jnp.sum(scored, -1))) < 1.0


# -- the one command ---------------------------------------------------------
def test_the_cell_runs_end_to_end_and_is_correct(rehearse, four_devices):
    line = rehearse(CELL, seconds=0.5, limits=TINY_LIMITS)
    assert line["correct"] is True and line["failed"] == 0, line
    assert set(line["metrics"]) == {"train_tok_s_chip", "setup_s"}
    assert set(line["compared"]) == {
        "loss_step1", "loss_step2", "grad_norm_worst_leaf",
        "change_norm_median_leaf", "exchange_gap"}


def test_a_traced_rehearsal_reads_what_a_cpu_can_and_does_not_raise(
        rehearse, four_devices):
    line = rehearse(CELL, seconds=0.5, trace=1, limits=TINY_LIMITS)
    assert line["correct"] is True
    # no TPU plane in a CPU trace: every device reader returns nothing;
    # the program's own counter is read all the same
    assert not {"moe_exchange_ms.train", "moe_exchange_ici_share.train",
                "mfu_mellum2.train", "gmm_ep_roofline.train",
                "moe_ffn_ms.train"} & set(line["metrics"])
    assert line["metrics"]["moe_load_max_over_mean.train"]["value"] >= 1.0


_EXACT = {}     # the exact reference's first steps, a seed


def _exact(small, seed):
    cfg, ref, tw, mix = small
    if seed not in _EXACT:
        _EXACT[seed] = tw.reference_steps(cfg, mix, seed, ref,
                                          ref.CHECK_STEPS)
    return _EXACT[seed]


def _sides(small, seed, **control):
    cfg, ref, tw, mix = small
    broken = tw.reference_steps(cfg, mix, seed, ref, ref.CHECK_STEPS,
                                **control)
    return tw.compare(broken, _exact(small, seed), TINY_LIMITS)


def test_the_fp8_control_reads_above_the_program(small):
    cfg, ref, tw, mix = small
    n = ref.CHECK_STEPS
    step = tw.build_step(cfg, 2, ref, mix["batch"])
    prog = tw.first_steps(step, cfg, mix, 2, ref, n)
    prog["exchange_gaps"] = tw.exchange_gaps(cfg, mix, 2, ref)
    sound = tw.compare(prog, _exact(small, 2), TINY_LIMITS)
    assert all(v["value"] <= v["limit"] for v in sound.values()), sound
    broken = _sides(small, 2, rnd=ref.fp8)
    assert any(v["value"] > v["limit"] for v in broken.values()), broken


@pytest.mark.parametrize("part", ["chip_out", "weights_as_scored",
                                  "no_window", "no_yarn_factor"])
def test_a_part_of_the_mathematics_wrong_is_not_correct(small, part):
    """Each control of `tools/limits_mellum2.py` fails a limit: one
    chip's experts' part left out of the sum, weights not normalised
    over the chosen, the window left off, YaRN's factor left off."""
    broken = _sides(small, 2, parts=(part,))
    assert any(v["value"] > v["limit"] for v in broken.values()), broken


@pytest.mark.parametrize("part", ["chip_out", "weights_as_scored"])
def test_the_exchanged_layer_alone_tells_a_broken_layer(small, part):
    cfg, ref, tw, mix = small
    sound = tw.exchange_gaps(cfg, mix, 4, ref)
    assert set(sound) == set(tw.EXCHANGE_OUTPUTS)
    assert max(sound.values()) < TINY_LIMITS["exchange_gap"], sound
    broken = tw.exchange_gaps(cfg, mix, 4, ref, parts=(part,))
    assert max(broken.values()) > 0.1, broken


def test_a_chips_part_lost_in_the_program_is_not_correct(small, monkeypatch):
    """The exchange's control made in the program itself: the way back
    sums three chips' partial sums and not four."""
    import jax
    from paddle_tpu.ops import moe_ops
    cfg, ref, tw, mix = small
    real = jax.lax.psum_scatter

    def lossy(x, axis, **kw):
        keep = jax.lax.axis_index(axis) != 1
        return real(x * keep.astype(x.dtype), axis, **kw)

    monkeypatch.setattr(moe_ops.jax.lax, "psum_scatter", lossy)
    gaps = tw.exchange_gaps(cfg, mix, 6, ref)
    assert max(gaps.values()) > 0.1, gaps


# -- the configuration's file --------------------------------------------------
def _row():
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.isfile(path):
        pytest.skip("the catalog is not beside this checkout")
    with open(path) as f:
        return next(r for r in map(json.loads, f)
                    if r["name"] == "Mellum2-12B-A2.5B-Instruct")


PUBLISHED = {   # the row's `config`, but for the two per-layer lists
    "attention_bias": False, "head_dim": 128, "hidden_act": "silu",
    "hidden_size": 2304, "intermediate_size": 7168,
    "max_position_embeddings": 131072, "max_window_layers": 0,
    "model_type": "mellum", "moe_intermediate_size": 896,
    "norm_topk_prob": True, "num_attention_heads": 32, "num_experts": 64,
    "num_experts_per_tok": 8, "num_hidden_layers": 28,
    "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
    "rope_parameters": {
        "full_attention": {
            "rope_type": "yarn", "rope_theta": 500000, "factor": 16,
            "original_max_position_embeddings": 8192, "beta_fast": 32,
            "beta_slow": 1, "attention_factor": 1.2772588722239782},
        "sliding_attention": {"rope_type": "default", "rope_theta": 500000}},
    "sliding_window": 1024, "tie_word_embeddings": False,
    "vocab_size": 98304, "use_sliding_window": True,
}


def test_the_file_holds_the_published_row_but_for_what_reduced_names():
    spec = Spec(REPO)
    cfg = spec.data("configs", CONFIG)
    entry = next(c for c in spec.doc["configs"] if c["name"] == CONFIG)
    assert cfg["source"] == entry["source"] == (
        "https://huggingface.co/JetBrains/Mellum2-12B-A2.5B-Instruct/blob/"
        "main/config.json")
    assert entry["file"] == f"benchmarks/configs/{CONFIG}.json"
    lists = {"layer_types", "mlp_layer_types"}
    assert set(entry["reduced"]) == set(cfg["reduced"]) \
        == {"num_hidden_layers"} | lists
    for key, value in PUBLISHED.items():
        assert cfg[key] == (4 if key == "num_hidden_layers" else value), key
    assert cfg["published"]["num_hidden_layers"] == 28
    # one whole period, every layer sparse; no width, expert count or
    # vocabulary is cut
    assert cfg["layer_types"] == ["sliding_attention"] * 3 + [
        "full_attention"]
    assert cfg["mlp_layer_types"] == ["sparse"] * 4
    assert {"qk_norm", "rope", "window", "router", "experts", "mtp_head",
            "initializer_range", "weights", "learning_rate"} \
        <= set(cfg["assumed"])
    assert "28 chips" in cfg["deployment"]
    assert "Nothing of a layer is left out" in cfg["deployment"]
    assert cfg["training"]["optimizer"]["learning_rate"] == 1e-4
    assert cfg["training"]["mesh"] == {
        "axes": ["ep"], "shape": [4], "expert_axis": "ep",
        "batch_axis": "ep"}


def test_the_file_against_the_catalogs_row():
    row, cfg = _row(), Spec(REPO).data("configs", CONFIG)
    assert cfg["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key == "num_hidden_layers":
            assert (cfg[key], value) == (4, 28)
        elif key in ("layer_types", "mlp_layer_types"):
            assert cfg[key] == value[:4] and len(value) == 28
        else:
            assert cfg[key] == value, key


def test_the_size_check_is_the_parameter_lists_own():
    spec = Spec(REPO)
    cfg, ref = spec.data("configs", CONFIG), spec.module("reference", CONFIG)
    here = ref.n_params(cfg)
    assert here == 2123976960
    layer = (here - 2 * 98304 * 2304 - 2304) // 4
    assert layer == 21233664 + 147456 + 64 * 6193152 + 2 * 2304
    whole = 28 * layer + 2 * 98304 * 2304 + 2304
    assert round(whole / 1e9, 2) == 12.15
    active = whole - 28 * 56 * 6193152
    assert round(active / 1e9, 2) == 2.44
    chip = 4 * (layer - 48 * 6193152) + 2 * 24576 * 2304 + 2304
    assert chip == 595153152
    for number in ("417.748M", "12.150B", "2.440B", "2.124B", "595.153M",
                   "4.761 GB", "7.142 GB"):
        assert number in cfg["size_check"], number


# -- the entries of BENCHMARK.json ----------------------------------------------
OWN = ["moe_exchange_ms.train", "moe_exchange_ici_share.train",
       "mfu_mellum2.train", "gmm_ep_roofline.train"]
JOINED = {"step_device_ms.train", "device_idle.train", "head_loss_ms.train",
          "optimizer_unfused_ms.train", "recompute_ms.train",
          "host_step_ms.train", "step_lower_s.train", "step_compile_s.train",
          "setup_import_s.train", "setup_build_s.train", "step_trace_s.train",
          "step_first_run_s.train", "setup_other_programs_s.train",
          "setup_named_share.train", "rope_ms.train", "moe_ffn_ms.train",
          "moe_route_ms.train", "moe_load_max_over_mean.train"}
# readers that take chip 0's time against every chip's rows: not joined
# (PERF.md section 7)
NOT_OVER_FOUR_PLANES = {"gmm_roofline.train", "flash_window_roofline.train"}
BEFORE = ["gpt3-1.3b.train-2k", "gpt2-small.train-1k",
          "jamba2-3b-l14.train-4k", "laguna-xs2-l5-e64.train-8k",
          "zaya1-8b-l5-e8.train-32k", "qwen3-next-80b-l4-e64.train-16k",
          "ouro-2.6b-l8.train-4k", "deepseek-v2-lite-e8.train-32k"]


def test_the_cell_joins_the_shared_metrics_and_brings_its_own():
    """The lists by their beginnings and by membership, so that a cell or
    a metric a later PR appends by files alone leaves this passing."""
    spec = Spec(REPO)
    doc = spec.doc
    cell = next(w for w in doc["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, TRAFFIC, 4)
    config = next(c for c in doc["configs"] if c["name"] == CONFIG)
    # the driver refuses a `why` over 200 characters before any run
    assert all(1 <= len(e["why"]) <= 200 and e["why"].isprintable()
               for e in (cell, config))
    assert cell["why"] == spec.data("cells", CELL)["why"]
    # the first cell on four chips, and the only one so far: the exchange
    # exists only across chips
    assert [w["chips"] for w in doc["workloads"][:9]] == [1] * 8 + [4]
    four = sum(w["chips"] == 4 for w in doc["workloads"])
    assert four <= max(1, len(doc["workloads"]) // 4)
    assert [w["name"] for w in doc["workloads"][:9]] == BEFORE + [CELL]
    assert [c["name"] for c in doc["configs"][:9]][-1] == CONFIG
    mine = {m["name"] for m in doc["per_layer"]
            if CELL in m.get("workloads", [])}
    assert mine >= JOINED | set(OWN) and not mine & NOT_OVER_FOUR_PLANES
    assert next(m for m in doc["end_to_end"]
                if m["name"] == "train_tok_s_chip")["workloads"][:9] == \
        BEFORE + [CELL]
    layer = next(m["layer"] for m in doc["per_layer"]
                 if m["name"] == "step_device_ms.train")
    kernel_layer = next(m["layer"] for m in doc["per_layer"]
                        if m["name"] == "flash_roofline.train")
    for m in doc["per_layer"]:
        if m["name"] in OWN:
            kernels = m["name"].endswith("_roofline.train")
            assert m == {"name": m["name"], "unit": m["unit"],
                         "better": m["better"], "source": "device_trace",
                         "layer": kernel_layer if kernels else layer,
                         "moves": "train_tok_s_chip", "workloads": [CELL]}
            assert m["unit"] in ("ms", "%")
            assert callable(spec.module("layer_metrics", m["name"]).read)
        elif m["name"] in JOINED:
            # a new cell goes to the end of a list it joins: after every
            # cell that was there
            assert m["workloads"].index(CELL) > max(
                m["workloads"].index(c) for c in BEFORE
                if c in m["workloads"])
    names = [m["name"] for m in doc["per_layer"]]
    first = names.index(OWN[0])
    assert names[first:first + 4] == OWN


# the four tests that assert `all(w["chips"] == 1 ...)`: this cell is the
# first on four chips and they cannot be edited (`tests/conftest.py:
# _PINNED`). Each is run here as it stands, on a `BENCHMARK.json` in which
# this one cell asks for one chip: every other assertion of it holds, and
# the other cells' chips are still checked.
PINNED_BY_THIS_CELL = [
    ("test_deepseek_v2",
     "test_the_cell_joins_the_shared_metrics_and_brings_its_own", 0),
    ("test_ouro",
     "test_the_cell_joins_the_shared_metrics_and_brings_its_own", 0),
    ("test_rope_trace",
     "test_the_two_cells_report_what_they_did_and_the_rotary", 0),
    ("test_rope_trace",
     "test_the_two_cells_report_what_they_did_and_the_rotary", 1),
]


@pytest.mark.parametrize("module,test,case", PINNED_BY_THIS_CELL)
def test_every_other_assertion_of_a_test_this_cell_pinned(
        monkeypatch, module, test, case):
    import importlib
    mod = importlib.import_module(module)

    class ThisCellOnOneChip(Spec):
        def __init__(self, repo=REPO):
            super().__init__(repo)
            for w in self.doc["workloads"]:
                if w["name"] == CELL:
                    w["chips"] = 1

    monkeypatch.setattr(mod, "Spec", ThisCellOnOneChip)
    fn = getattr(mod, test)
    marks = [m for m in getattr(fn, "pytestmark", [])
             if m.name == "parametrize"]
    args = marks[0].args[1][case] if marks else ()
    fn(*args)
    # and as it stands it fails on that assertion alone
    monkeypatch.undo()
    with pytest.raises(AssertionError):
        fn(*args)


def test_the_cells_files_are_found_by_name():
    spec = Spec(REPO)
    cell = spec.data("cells", CELL)
    assert set(cell["limits"]) == {"loss", "grad_norm_worst_leaf",
                                   "change_norm_median_leaf", "exchange_gap"}
    mix = spec.data("traffic", TRAFFIC)
    assert (mix["driver"], mix["batch"], mix["seq"]) == (
        "mellum2_train_window", 4, 8192)
    ref = spec.module("reference", CONFIG)
    assert (ref.ROW_BLOCK, ref.CHECK_STEPS) == (1, 2)
    for kind, name in (("drivers", "mellum2_train_window"),
                       ("harness", "mellum2_program"),
                       ("harness", "mellum2_reference"),
                       ("harness", "mellum2_flops"),
                       ("harness", "trace_chips"),
                       ("tools", "limits_mellum2"),
                       ("tools", "record_mellum2_trace")):
        assert os.path.isfile(os.path.join(BENCH_DIR, kind, name + ".py"))
    with open(os.path.join(BENCH_DIR, "harness", "ici_peaks.json")) as f:
        peaks = json.load(f)
    assert peaks["TPU v5 lite"] == {"ici_bytes_per_s": 200e9}
    assert "1,600 Gbit/s" in peaks["source"]


# -- counted costs -------------------------------------------------------------
def test_flops_against_the_issues_count():
    from harness import mellum2_flops
    cfg = Spec(REPO).data("configs", CONFIG)
    parts = mellum2_flops.parts_per_token(cfg, 8192)
    assert parts["routed_experts"] == 6.0 * 8 * 3 * 2304 * 896 * 4
    assert parts["head"] == 6.0 * 2304 * 98304
    assert parts["full_attention"] == 12.0 * 4096 * (8192 + 1) / 2
    window = 12.0 * 4096 * (1024 * (8192 - 1024) + 1024 * 1025 / 2) / 8192
    assert parts["window_attention"] == pytest.approx(3 * window)
    assert parts["other"] == 6.0 * 4 * (21233664 + 2304 * 64)
    total = mellum2_flops.train_flops_per_token(cfg, 8192)
    assert total == sum(parts.values())
    # the issue's count: the head is 44% of the matmuls' products before
    # attention's scores (2 x 2304 x 98304 against 4 x 2 x 70.9M forward)
    layers = parts["routed_experts"] + parts["other"]
    assert round(parts["head"] / (parts["head"] + layers), 2) == 0.44
    assert [round(parts[k] / 1e9, 2) for k in (
        "routed_experts", "full_attention", "window_attention", "head",
        "other")] == [1.19, 0.2, 0.14, 1.36, 0.51]


def test_exchange_bytes_against_hand_counts():
    from harness import mellum2_flops
    from paddle_tpu.ops.moe_ops import exchange_bytes
    cfg = Spec(REPO).data("configs", CONFIG)
    mix = Spec(REPO).data("traffic", TRAFFIC)
    out, back = exchange_bytes(8192, 8, 2304, 2, 4)
    assert out == 3 * 8192 * (2304 * 2 + 64) == 114819072
    assert back == 3 * 8192 * 2304 * 2 == 113246208
    # a layer: forward, the forward again, and a backward that sends the
    # cotangent's rows the way the sums came and the rows' gradients
    # (bfloat16) with the weights' (float32) the way the rows came
    layer = 2 * (out + back) + back + 3 * 8192 * (2304 * 2 + 32)
    assert mellum2_flops.exchange_bytes_per_step(cfg, mix, 4) == 4 * layer
    assert round(4 * layer / 1e9, 2) == 2.73
