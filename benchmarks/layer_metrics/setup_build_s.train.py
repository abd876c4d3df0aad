"""Seconds of set-up constructing the program's objects: the phases
`build.model` (every `Layer` constructor; the initialisers' calls,
`build.params`, are inside it), `build.optimizer` (the accumulators) and
`build.train_step` (`TrainStep.__init__`) of `perf.setup_record()`, as
they lie on the clock between the run's two ends. The harness's own
weights are not in it: `weights.make` is a program of
`setup_other_programs_s.train` (`build`) between two of these."""

PHASES = ("build.model", "build.params", "build.optimizer",
          "build.train_step")


def read(run):
    return run.spec.module("layer_metrics", "setup_named_share.train") \
        .phases_s(run, PHASES)
