"""Set-up told from inside the program: `perf.setup_record()` (the
phases between process start and a step's first run, each on
`time.perf_counter`), `compile_record(family)`'s `trace_s` and
`trace_by_scope`, and `perf.program_log()` (every program JAX builds or
loads, by JAX's own events). All of it is written metrics on or off, and
none of it by a warm step."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import amp, nn
from paddle_tpu import observability as obs
from paddle_tpu.jit import TrainStep
from paddle_tpu.models import GPTForCausalLM, GPTPretrainingCriterion
from paddle_tpu.models.gpt import gpt_tiny
from paddle_tpu.observability import perf
from paddle_tpu.optimizer import AdamW

BACKEND_EVENT = "/jax/core/compile/backend_compile_duration"
STEP_PHASES = ("train_step.lower", "train_step.trace", "train_step.backend",
               "train_step.first_run")
BUILD_PHASES = ("build.model", "build.params", "build.train_step",
                "build.optimizer")


def _rows(since=0):
    return perf.program_log()["rows"][since:]


@pytest.fixture(scope="module", autouse=True)
def _room_in_the_log():
    """The log is the process's and bounded: the tests that ran before
    in this worker may have filled it."""
    with perf._LOCK:
        perf._PROGRAMS.clear()


@pytest.fixture(scope="module")
def first_call():
    """A tiny GPT's TrainStep built and called once, with nothing of an
    earlier test's in the records: (step, ids, set-up record, compile
    record, rows the build and the call added)."""
    obs.disable()
    for name in list(perf._SETUP):
        if name != "import":
            del perf._SETUP[name]
    perf._FAMILY_COMPILE.pop("train_step", None)
    before = len(_rows())
    pt.seed(0)
    model = GPTForCausalLM(gpt_tiny(recompute=True, recompute_interval=2))
    model.train()
    crit = GPTPretrainingCriterion()
    opt = AdamW(learning_rate=1e-3, parameters=model.parameters())

    def loss_fn(m, ids, labels):
        with amp.auto_cast(enable=True, level="O1", dtype="bfloat16"):
            logits = m(ids)
        return crit(logits, labels)

    step = TrainStep(model, opt, loss_fn)
    ids = np.zeros((2, 32), np.int32)
    step(ids, ids)
    return (step, ids, perf.setup_record(),
            perf.compile_record("train_step"), _rows(before))


def test_every_phase_is_in_the_record_in_clock_order(first_call):
    _step, _ids, setup, _rec, _rows_ = first_call
    assert set(setup) == {"import", *BUILD_PHASES, *STEP_PHASES}
    for name, phase in setup.items():
        assert phase["t0"] <= phase["t1"], name
        assert phase["n"] >= 1 and phase["s"] >= 0.0, name
        assert phase["s"] <= (phase["t1"] - phase["t0"]) * 1.001 + 1e-6, name
    order = [("import", "t0"), ("import", "t1"),
             ("build.model", "t0"), ("build.params", "t0"),
             ("build.params", "t1"), ("build.model", "t1"),
             ("build.train_step", "t0"), ("build.optimizer", "t0"),
             ("build.optimizer", "t1"), ("build.train_step", "t1"),
             ("train_step.lower", "t0"), ("train_step.trace", "t0"),
             ("train_step.trace", "t1"), ("train_step.lower", "t1"),
             ("train_step.backend", "t0"), ("train_step.backend", "t1"),
             ("train_step.first_run", "t0"), ("train_step.first_run", "t1")]
    clock = [setup[name][end] for name, end in order]
    assert clock == sorted(clock), list(zip(order, clock))


def test_the_stretches_are_what_ran_with_no_phase_around_it(first_call):
    _step, _ids, setup, _rec, _rows_ = first_call
    # the model and the criterion; the initialisers ran inside them
    assert len(setup["build.model"]["stretches"]) == 2
    for name in ("build.params", "build.optimizer", "train_step.trace"):
        assert setup[name]["stretches"] == []
    for name in ("import", "build.train_step", "train_step.lower",
                 "train_step.backend", "train_step.first_run"):
        phase = setup[name]
        assert phase["stretches"] == [(phase["t0"], phase["t1"])]
        assert phase["s"] == phase["t1"] - phase["t0"]
    for name, phase in setup.items():
        for t0, t1 in phase["stretches"]:
            assert phase["t0"] <= t0 <= t1 <= phase["t1"], name


def test_a_phase_knows_the_phase_it_ran_inside(first_call):
    _step, _ids, setup, _rec, _rows_ = first_call
    assert {name: phase["parent"] for name, phase in setup.items()} == {
        "import": None, "build.model": None,
        "build.params": "build.model", "build.train_step": None,
        "build.optimizer": "build.train_step",
        "train_step.lower": None, "train_step.trace": "train_step.lower",
        "train_step.backend": None, "train_step.first_run": None}


def test_the_models_constructors_count_once_and_the_parameters_by_bytes(
        first_call):
    step, _ids, setup, _rec, _rows_ = first_call
    model, params = setup["build.model"], setup["build.params"]
    # every constructor is an entry, the seconds are the outermost's:
    # the extent on the clock holds them and the initialisers' seconds
    assert model["n"] > len(step._ptensors)
    assert params["s"] <= model["s"] <= model["t1"] - model["t0"]
    assert params["n"] == params["params"] == len(step._ptensors)
    assert params["bytes"] == sum(p._data.nbytes for p in step._ptensors)
    assert setup["build.optimizer"]["n"] == len(step._ptensors)
    assert setup["build.train_step"]["n"] == 1


def test_the_first_calls_parts_are_the_compile_records_seconds(first_call):
    _step, _ids, setup, rec, _rows_ = first_call
    assert rec["compiles"] == 1
    for part in ("lower", "trace", "backend", "first_run"):
        assert rec[part + "_s"] == setup["train_step." + part]["s"]
    assert 0 < rec["trace_s"] <= rec["lower_s"]


def test_the_trace_by_scope_lies_within_the_trace(first_call):
    _step, _ids, _setup, rec, _rows_ = first_call
    by_scope = rec["trace_by_scope"]
    assert all(seconds >= 0 for seconds in by_scope.values())
    assert 0 < sum(by_scope.values()) <= rec["trace_s"]
    # layer indices are folded; a recomputed block (traced past
    # Layer.__call__) is under its own name too; what is no Layer is there
    for key in ("gptforcausallm/gpt/layers/*/attn/qkv_proj",
                "gptforcausallm/gpt/layers/*/mlp/fc1",
                "gptforcausallm/gpt/final_norm",
                "gptpretrainingcriterion/lm_head", "optimizer"):
        assert key in by_scope, sorted(by_scope)
    assert not any(part.isdigit() for key in by_scope
                   for part in key.split("/"))
    by_scope["optimizer"] = -1.0                # a copy: the record is safe
    assert perf.compile_record("train_step")["trace_by_scope"][
        "optimizer"] > 0


def test_the_steps_programs_are_told_by_family_not_by_name(first_call):
    _step, _ids, setup, _rec, rows = first_call
    mine = [r for r in rows if r.family == "train_step"]
    assert {r.kind for r in mine} >= {"trace", "lower", "backend"}
    assert all(r.step == 0 for r in mine)
    assert all(r.phase in STEP_PHASES for r in mine)
    traced, = [r for r in mine if r.kind == "trace" and r.fun_name == "step"]
    lo, hi = setup["train_step.trace"]["t0"], setup["train_step.trace"]["t1"]
    # JAX times the event on another clock: a millisecond of room
    assert lo - 1e-3 <= traced.t - traced.seconds and traced.t <= hi
    # the model's own initialisers and the accumulators (those that an
    # earlier test of this process has not built already): programs that
    # are not the step's, each inside the phase that asked for it
    other = [r for r in rows if r.family is None]
    assert {r.phase for r in other} <= {
        "build.model", "build.params", "build.train_step",
        "build.optimizer", None}
    assert all(r.step is None for r in other)


def test_a_jitted_function_shows_in_the_log_under_its_name():
    def a_function_of_this_test(x):
        return jnp.sin(x) * 2.0

    before = len(_rows())
    jax.jit(a_function_of_this_test)(jnp.ones((3, 5)))
    mine = [r for r in _rows(before)
            if r.fun_name == "a_function_of_this_test"]
    kinds = [r.kind for r in mine]
    assert kinds[:2] == ["trace", "lower"]
    assert kinds[-1] == "backend"               # a compile, or a load in it
    assert set(kinds[2:-1]) <= {"load"}
    assert all(r.family is None and r.phase is None and r.step is None
               and r.seconds >= 0 for r in mine)
    ends = [r.t for r in mine]
    assert ends == sorted(ends)
    # built inside a phase, a program carries the innermost one's name
    perf._SETUP.pop("t.outer", None), perf._SETUP.pop("t.inner", None)
    before = len(_rows())
    with perf.setup_phase("t.outer"), perf.setup_phase("t.inner"):
        jax.jit(lambda x: jnp.cos(x) + 4.0)(jnp.ones((3, 7)))
    inside = [r for r in _rows(before) if r.fun_name == "<lambda>"]
    assert [r.kind for r in inside][:2] == ["trace", "lower"]
    assert all(r.phase == "t.inner" for r in inside)
    assert perf._SETUP.pop("t.inner")["parent"] == "t.outer"
    assert perf._SETUP.pop("t.outer")["parent"] is None


def test_warm_steps_add_no_row_and_enter_no_phase(first_call):
    step, ids, _setup, _rec, _rows_ = first_call
    step(ids, ids)
    rows, totals = len(_rows()), perf.program_log()["totals"]
    setup = perf.setup_record()
    for _ in range(5):
        float(step(ids, ids).numpy())
    assert len(_rows()) == rows
    assert perf.program_log()["totals"] == totals
    assert perf.setup_record() == setup
    assert perf._TRACE_NOTES.tracing == 0


def test_a_new_batch_shape_adds_rows_that_carry_the_step(first_call):
    """`CompileTimed` serves a new signature through the polymorphic
    function without a word: the log says which step built a program."""
    step, _ids, _setup, _rec, _rows_ = first_call
    before = len(_rows())
    step_id = step._step_count
    short = np.zeros((2, 16), np.int32)
    step(short, short)
    new = _rows(before)
    assert {r.kind for r in new} >= {"trace", "lower", "backend"}
    assert all(r.step == step_id and r.family == "train_step" for r in new)
    assert all(r.phase is None for r in new)    # no first call: no phase
    assert perf.compile_record("train_step")["compiles"] == 1
    again = len(_rows())
    step(short, short)
    assert len(_rows()) == again


def test_an_eager_layer_call_times_nothing(monkeypatch):
    asked = []
    real = perf.trace_timed
    monkeypatch.setattr(perf, "trace_timed",
                        lambda *a, **kw: asked.append(a) or real(*a, **kw))
    lin = nn.Linear(4, 4)
    x = pt.Tensor(np.ones((2, 4), np.float32))
    lin(x)
    assert asked == []
    # traced outside any first call (to_static, an engine's builder):
    # asked, and the shared null object answers
    from paddle_tpu.jit import _collect_params, _functional_params
    _n, ptensors, _b, _bt = _collect_params(lin)
    with _functional_params(ptensors, [p._data for p in ptensors]):
        lin(x)
    assert asked == [("linear",)]
    assert real("linear") is perf._NOT_TIMED
    assert real("flash_fwd", path=False) is perf._NOT_TIMED


def test_a_kernels_entry_is_keyed_by_its_name_and_taken_off_its_layer():
    scopes = {}
    th = perf._TRACE_NOTES
    th.scopes = scopes
    try:
        @perf.trace_timed_call("a_kernel")
        def entry(x):
            return x + 1

        with perf.trace_timed("model"):
            with perf.trace_timed("layers/3"):
                assert entry(1) == 2
            with perf.trace_timed("layers/11"):
                assert entry(2) == 3
    finally:
        th.scopes = None
    assert set(scopes) == {"model", "model/layers/*", "a_kernel"}
    assert all(seconds >= 0 for seconds in scopes.values())
    assert entry.__name__ == "entry"
    assert th.timed is None


def test_trace_raising_falls_back_with_trace_s_absent():
    jitted = jax.jit(lambda x: x * 3.0)

    class NoTrace:
        lower = staticmethod(jitted.lower)

        def trace(self, *args):
            raise RuntimeError("no trace here")

        def __call__(self, *args):
            return jitted(*args)

    perf._FAMILY_COMPILE.pop("t_no_trace", None)
    fn = perf.CompileTimed(NoTrace(), "t_no_trace")
    out = fn(jnp.ones((2,)))
    assert np.allclose(np.asarray(out), 3.0)
    rec = perf.compile_record("t_no_trace")
    assert "trace_s" not in rec and "trace_by_scope" not in rec
    assert rec["compiles"] == 1 and rec["lower_s"] > 0
    assert rec["outcome"] == "compile"
    assert fn.expected is not None              # the AOT path was kept
    # a function with no `.trace` at all lowers in one call too
    class LowerOnly:
        lower = staticmethod(jitted.lower)
        __call__ = staticmethod(jitted)

    perf._FAMILY_COMPILE.pop("t_lower_only", None)
    perf.CompileTimed(LowerOnly(), "t_lower_only")(jnp.ones((2,)))
    assert "trace_s" not in perf.compile_record("t_lower_only")


@pytest.mark.parametrize("asked,options", [
    ({}, None),
    ({"xla_cpu_enable_fast_min_max": True},
     "xla_cpu_enable_fast_min_max=True"),
    ({"no_such_option_of_any_compiler": 1},
     "no_such_option_of_any_compiler=1"),
], ids=["nothing asked", "an option", "an option the compiler refuses"])
def test_the_traced_code_asks_the_compile_for_an_option(asked, options):
    """`trace_compile_option` from the traced body reaches
    `lowered.compile(compiler_options=...)` of the first call and the
    family's record; a compile that raises on it falls back to plain
    jit dispatch, as on any other compile error; outside a first call it
    is dropped."""
    seen = []

    def body(x):
        for key, value in asked.items():
            perf.trace_compile_option(key, value)
        return x * 3.0

    jitted = jax.jit(body)

    class Spy:                      # no `.trace`: lowered in one call
        __call__ = staticmethod(jitted)

        def lower(self, *args):
            lowered = jitted.lower(*args)
            compile_ = lowered.compile

            def compile(**kw):
                seen.append(kw)
                return compile_(**kw)

            lowered.compile = compile
            return lowered

    family = f"t_option_{len(asked)}_{options}"
    perf._FAMILY_COMPILE.pop(family, None)
    fn = perf.CompileTimed(Spy(), family)
    assert np.allclose(np.asarray(fn(jnp.ones((2,)))), 3.0)
    assert seen == [{"compiler_options": asked} if asked else {}]
    rec = perf.compile_record(family)
    assert rec.get("compile_options") == options
    assert (fn.expected is None) == ("no_such" in str(options))
    perf.trace_compile_option("dropped", 1)     # nobody is tracing
    assert perf._TRACE_NOTES.options is None


def test_a_phase_opened_inside_itself_counts_its_seconds_once():
    perf._SETUP.pop("t.nested", None)
    with perf.setup_phase("t.nested") as outer:
        with perf.setup_phase("t.nested"):
            with perf.setup_phase("t.nested") as inner:
                inner.count(things=2)
        outer.count(things=1)
    rec = perf.setup_record()["t.nested"]
    assert rec["n"] == 3 and rec["things"] == 3 and rec["parent"] is None
    assert rec["s"] == pytest.approx(outer.seconds)
    assert rec["s"] <= rec["t1"] - rec["t0"]
    assert rec["stretches"] == [(rec["t0"], rec["t1"])]
    assert perf._TRACE_NOTES.phases == ()
    perf._SETUP.pop("t.nested")


def test_past_its_room_a_phases_last_stretch_grows(monkeypatch):
    monkeypatch.setattr(perf, "PHASE_STRETCHES", 3)
    perf._SETUP.pop("t.many", None)
    for _ in range(6):
        with perf.setup_phase("t.many"):
            pass
    rec = perf._SETUP.pop("t.many")
    assert rec["n"] == 6 and len(rec["stretches"]) == 3
    assert rec["stretches"][0][0] == rec["t0"]
    assert rec["stretches"][-1][1] == rec["t1"]
    ends = [t for stretch in rec["stretches"] for t in stretch]
    assert ends == sorted(ends)


def test_the_log_stops_at_its_cap_and_the_totals_go_on(monkeypatch):
    log = perf.program_log()
    monkeypatch.setattr(perf, "PROGRAM_LOG_ROWS", len(log["rows"]) + 2)
    for _ in range(5):
        jax.monitoring.record_event_duration_secs(
            BACKEND_EVENT, 0.5, fun_name="jit(a_program)")
    after = perf.program_log()
    assert len(after["rows"]) == len(log["rows"]) + 2
    assert after["rows"][-1].fun_name == "a_program"
    assert after["rows"][-1].kind == "backend"
    assert after["totals"]["backend"]["n"] == \
        log["totals"]["backend"]["n"] + 5
    assert after["totals"]["backend"]["s"] == pytest.approx(
        log["totals"]["backend"]["s"] + 2.5)
    # an event that is none of the four is not a program
    jax.monitoring.record_event_duration_secs("/jax/some/other", 1.0)
    assert perf.program_log()["totals"] == after["totals"]
    # the rows drop back with the patch: take the three extra totals off
    with perf._LOCK:
        perf._PROGRAM_TOTALS["backend"]["n"] -= 5
        perf._PROGRAM_TOTALS["backend"]["s"] -= 2.5
        del perf._PROGRAMS[len(log["rows"]):]
