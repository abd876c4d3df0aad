"""`harness/trace_scopes.py`: the wire decoder against the two traces
recorded on the chip, an operation's scope from its `op_name`, and a CPU
rehearsal of a traced training run (every new reader finds no TPU plane
and returns nothing)."""
import os

import pytest

from harness import trace_scopes
from harness.trace_reduce import Trace
from harness.trace_scopes import ScopedTrace, XSpace, op_name_of, scope_of

HERE = os.path.dirname(os.path.abspath(__file__))
SMALL = os.path.join(HERE, "data", "small.xplane.pb")
# tools/record_scoped_trace.py on a TPU v5 lite, cut as its docstring
# says: four steps of a two-block GPT TrainStep (jax.checkpoint on the
# first block, the flash kernels), the first compiling inside the session
SCOPED = os.path.join(HERE, "data", "scoped.xplane.pb")
NEW_READERS = ("head_loss_ms.train", "optimizer_unfused_ms.train",
               "recompute_ms.train", "host_step_ms.train",
               "step_lower_s.train", "step_compile_s.train")


@pytest.mark.parametrize("path", [SMALL, SCOPED])
def test_the_decoder_reads_what_profiledata_reads(path):
    """Same planes, lines, events, names and times as `jax.profiler.
    ProfileData`; the metadata's stats are what it adds."""
    from jax.profiler import ProfileData
    theirs = {p.name: p for p in ProfileData.from_file(path).planes}
    for plane in XSpace.from_file(path).planes:
        lines = {ln.name: list(ln.events) for ln in theirs[plane.name].lines}
        assert [n for n, _e in plane.lines] == list(lines)
        for name, events in plane.lines:
            assert len(events) == len(lines[name])
            for (mid, start, end), ev in zip(events, lines[name]):
                assert plane.event_names[mid] == ev.name
                assert start == pytest.approx(ev.start_ns * 1e-9, abs=1e-9)
                assert end - start == pytest.approx(ev.duration_ns * 1e-9,
                                                    abs=1e-9)


def test_the_metadata_holds_the_op_name_and_the_costs():
    dev = XSpace.from_file(SMALL).device_planes()[0]
    assert dev.name == "/device:TPU:0"
    fusions = [s for mid, s in dev.event_stats.items()
               if dev.event_names[mid].startswith("%fusion")]
    assert len(fusions) == 3
    for stats in fusions:
        assert stats["tf_op"] == "jit(small_step)/dot_general:"
        assert stats["hlo_category"] == "convolution fusion"
        assert stats["flops"] == 2 * 1024 ** 3 + 4 * 1024 ** 2
        assert stats["bytes_accessed"] == 3 * 2 * 1024 ** 2
    assert op_name_of("jit(small_step)/dot_general:") == \
        "jit(small_step)/dot_general"
    assert op_name_of("jit(f)/a/reshape;jit(f)/a/squeeze:") == \
        "jit(f)/a/reshape"


GPT = "gptforcausallm"


@pytest.mark.parametrize("op_name,instruction,want", [
    (f"jit(step)/jvp({GPT})/gpt/layers/3/attn/qkv_proj/dot_general",
     "fusion.7", ("step", f"{GPT}/gpt/layers/3/attn/qkv_proj", "forward")),
    (f"jit(step)/transpose(jvp({GPT}))/gpt/layers/3/mlp/fc1/dot_general",
     "fusion.9", ("step", f"{GPT}/gpt/layers/3/mlp/fc1", "backward")),
    # jax.checkpoint starts the path again inside the block
    (f"jit(step)/transpose(jvp({GPT}))/gpt/jvp({GPT})/gpt/checkpoint/"
     "layers/0/attn/transpose(jvp())/pallas_call", "flash_bwd_transpose.2",
     ("step", f"{GPT}/gpt/layers/0/attn", "backward")),
    (f"jit(step)/transpose(jvp({GPT}))/gpt/jvp({GPT})/gpt/checkpoint/"
     "rematted_computation/layers/0/attn/flash_fwd/pallas_call",
     "flash_fwd.3", ("step", f"{GPT}/gpt/layers/0/attn/flash_fwd",
                     "recompute")),
    # XLA's own clone keeps the metadata and says so in its name
    (f"jit(step)/jvp({GPT})/lm_head/dot_general", "fusion.12.remat2",
     ("step", f"{GPT}/lm_head", "xla_remat")),
    ("jit(step)/transpose(jvp(gptpretrainingcriterion))/"
     "jit(take_along_axis)/scatter-add", "fusion.1",
     ("step", "gptpretrainingcriterion", "backward")),
    ("jit(step)/optimizer/sub", "fusion.5", ("step", "optimizer", "plain")),
    # a function's name is no scope; a wrapped element may hold a slash
    ("jit(step)/transpose(jvp(jit(_take)))/scatter-add", "fusion.2",
     ("step", "", "backward")),
    ("jit(f)/jvp(layers/0)/attn/tanh", "fusion",
     ("f", "layers/0/attn", "forward")),
    ("jit(step)/convert_element_type", "copy.1", ("step", "", "plain")),
    ("", "copy.2", ("", "", "plain")),
])
def test_scope_of_an_operation(op_name, instruction, want):
    assert scope_of(op_name, instruction) == want


def test_an_unscoped_trace_is_all_unscoped_and_sums_to_its_self_times():
    """`small.xplane.pb` was recorded before the program named anything."""
    scoped = ScopedTrace.from_file(SMALL)
    trace = Trace.from_file(SMALL)
    window = scoped.by_scope()
    assert set(window) == {(trace_scopes.UNSCOPED, "plain")}
    # ProfileData rounds to the nanosecond, the file holds picoseconds
    assert sum(window.values()) == pytest.approx(
        sum(t for _n, t in trace.op_self_times()), rel=1e-3)
    assert scoped.unscoped_share_pct() == pytest.approx(100.0)
    # four whole runs, none cut: per step is a quarter of the window
    per_step = scoped.by_scope(r"jit_small_step")
    assert len(scoped.runs(r"jit_small_step")) == 4
    assert sum(per_step.values()) == pytest.approx(
        sum(window.values()) / 4, rel=1e-6)
    assert scoped.step_ms(r"jit_small_step",
                          lambda c: c == "optimizer") is None
    assert scoped.step_ms(r"jit_small_step",
                          phases=("recompute", "xla_remat")) == 0.0
    assert scoped.step_ms(r"jit_no_such_program") is None
    assert scoped.spans() == []


# ---------------------------------------------------------------------------
# the trace recorded from a scoped TrainStep
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def scoped():
    return ScopedTrace.from_file(SCOPED)


def test_components_and_phases_of_a_recorded_step(scoped):
    assert os.path.getsize(SCOPED) < 100_000
    assert len(scoped.runs(r"jit_step")) == 4
    table = scoped.by_scope(r"jit_step")
    components = {c for c, _p in table}
    root = "gptforcausallm/gpt/layers"
    for want in (f"{root}/0/attn/qkv_proj", f"{root}/1/mlp/fc1",
                 f"{root}/0/attn/flash_fwd",
                 f"{root}/1/attn/flash_bwd_transpose",
                 "gptforcausallm/gpt/embeddings/word_embeddings",
                 "gptforcausallm/lm_head", "gptpretrainingcriterion",
                 "optimizer", trace_scopes.UNSCOPED):
        assert want in components, want
    by_phase = {}
    for (_c, phase), seconds in table.items():
        by_phase[phase] = by_phase.get(phase, 0.0) + 1e6 * seconds
    assert by_phase == pytest.approx(
        {"forward": 155.09, "backward": 243.62, "recompute": 48.12,
         "plain": 39.40}, rel=1e-3)
    # only the checkpointed block's forward runs again, its kernel too;
    # the backward kernel runs once a block
    kernels = {k: 1e6 * v for k, v in table.items() if "flash" in k[0]}
    assert kernels == pytest.approx({
        (f"{root}/0/attn/flash_fwd", "forward"): 17.44,
        (f"{root}/0/attn/flash_fwd", "recompute"): 17.16,
        (f"{root}/1/attn/flash_fwd", "forward"): 17.34,
        (f"{root}/0/attn/flash_bwd_transpose", "backward"): 14.98,
        (f"{root}/1/attn/flash_bwd_transpose", "backward"): 14.98},
        rel=1e-3)
    assert not any(c.startswith(f"{root}/1") and p == "recompute"
                   for c, p in table)
    # the operations' self times leave out the gaps between them: a
    # 0.539 ms run of this tiny step holds 0.486 ms of operations
    runs = scoped.runs(r"jit_step")
    assert sum(e - s for s, e in runs) / 4 == pytest.approx(539.1e-6,
                                                            rel=1e-3)
    assert sum(table.values()) == pytest.approx(486.2e-6, rel=1e-3)


def test_what_the_readers_read_from_it(scoped):
    head = scoped.step_ms(r"jit_step", lambda c: "lm_head" in c.split("/"))
    assert head == pytest.approx(0.018517, rel=1e-3)
    assert scoped.step_ms(r"jit_step", lambda c: c == "optimizer") \
        == pytest.approx(0.002358, rel=1e-3)
    assert scoped.step_ms(r"jit_step", phases=("recompute", "xla_remat")) \
        == pytest.approx(0.048118, rel=1e-3)
    assert scoped.unscoped_share_pct() == pytest.approx(7.797, abs=1e-3)
    # what is under no scope is XLA's own: copies it inserted, waits on
    # them, a fusion it made up; none has an op_name in the trace
    kinds = scoped.unscoped_by_category()
    assert {"copy-done", "loop fusion", "data formatting",
            "async-done"} <= set(kinds)


def test_the_programs_spans_and_their_self_times(scoped):
    stats = scoped.span_stats()
    assert {n: c for n, (c, _m, _s) in stats.items()} == {
        "train_step": 4, "train_step.feed": 4, "train_step.dispatch": 4,
        "compile.lower": 1, "compile.backend": 1, "compile.first_run": 1}
    assert scoped.span_median_s("train_step") == pytest.approx(3.602e-3,
                                                               rel=1e-3)
    assert stats["compile.lower"][1] == pytest.approx(1.4493, rel=1e-3)
    assert stats["compile.backend"][1] == pytest.approx(8.5783, rel=1e-3)
    spans = scoped.spans()
    # a span's self time is its duration less what its children cover:
    # the first dispatch held the compile, the first step the dispatch
    first_step = spans[0]
    assert first_step[0] == "train_step"
    assert first_step[2] - first_step[1] == pytest.approx(10.0568, rel=1e-4)
    assert first_step[3] == pytest.approx(3.79e-3, rel=2e-2)
    first_dispatch = next(s for s in spans if s[0] == "train_step.dispatch")
    assert first_dispatch[3] == pytest.approx(10.69e-3, rel=1e-2)
    for name, start, end, self_s in spans:
        assert 0 <= self_s <= end - start + 1e-12
    # one train_step per run of the step's program, and where the loop
    # waits for each loss the device's clock reads some 0.6 ms behind
    offsets = scoped.dispatch_offsets_s(r"jit_step")
    assert len(offsets) == 4
    assert sorted(offsets)[1:] == pytest.approx(
        [-0.632e-3, -0.614e-3, -0.468e-3], abs=2e-6)


@pytest.mark.parametrize("cell", ["gpt3-1.3b.train-2k",
                                  "gpt2-small.train-1k"])
def test_a_cpu_rehearsal_reads_nothing_and_does_not_raise(rehearse, cell):
    line = rehearse(cell, seconds=0.8, trace=1,
                    limits={"loss": 10.0, "grad_norm_worst_leaf": 10.0,
                            "change_norm_median_leaf": 10.0})
    assert line["failed"] == 0
    assert not set(NEW_READERS) & set(line["metrics"])
