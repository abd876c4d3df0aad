"""The copied generator: the same schedule from the same seed in any
process, another from another seed, and the original's from the
original's parameters."""
import json
import subprocess
import sys

import tiny
from harness.spec import BENCH_DIR, REPO
from harness.traffic_model import Cohort, TrafficModel

DIGEST = """
import sys, hashlib
sys.path[:0] = {path!r}
import json
from harness.traffic_model import TrafficModel
mix = json.load(open({serving!r}))["traffic"]["chat-sessions-steady"]
h = hashlib.sha256()
for ev in TrafficModel.from_mix(mix, 11, 50257).events(200):
    h.update(repr((ev.t, ev.rid, ev.session, ev.cohort, ev.turn,
                   ev.max_new)).encode() + ev.prompt.tobytes())
print(h.hexdigest())
"""


def test_same_schedule_in_two_processes():
    code = DIGEST.format(path=[REPO, BENCH_DIR], serving=tiny.SERVING)
    a, b = (subprocess.run([sys.executable, "-c", code], check=True,
                           capture_output=True, text=True,
                           env={"PYTHONHASHSEED": seed, "PATH": ""})
            .stdout.strip() for seed in ("1", "2"))
    assert a == b and len(a) == 64


def test_the_schedule_is_a_function_of_the_seed():
    with open(tiny.SERVING) as f:
        mix = json.load(f)["traffic"]["chat-sessions-flood"]

    def sizes(seed):
        return [(e.t, e.session, e.turn, len(e.prompt), e.max_new)
                for e in TrafficModel.from_mix(mix, seed, 50257).events(120)]

    assert sizes(1) == sizes(1)
    assert sizes(1) != sizes(2147483999)    # arrivals and lengths too


def test_differs_from_the_original_only_in_its_parameters():
    from paddle_tpu.inference import traffic as original
    kw = dict(n_sessions=1000, vocab=977, base_rate=5.0, burst_rate=11.0,
              off_s=1.5, on_s=0.5, reuse=0.6, min_body=3, max_body=50,
              min_out=2, max_out=20, active_window=64)
    cohorts = [dict(name="a", weight=0.6, prefix_len=12, body_mu=2.5,
                    body_sigma=0.7, out_mu=2.0, out_sigma=0.5,
                    mean_turns=3.0),
               dict(name="b", weight=0.4, prefix_len=5, body_mu=3.0,
                    body_sigma=0.4, out_mu=1.5, out_sigma=0.3,
                    mean_turns=1.0)]
    theirs = original.TrafficModel(
        cohorts=[original.Cohort(**c) for c in cohorts], seed=9, **kw)
    ours = TrafficModel(cohorts=[Cohort(**c) for c in cohorts], seed=9,
                        **kw)
    for x, y in zip(theirs.events(150), ours.events(150)):
        assert (x.t, x.rid, x.session, x.cohort, x.turn, x.max_new) == \
            (y.t, y.rid, y.session, y.cohort, y.turn, y.max_new)
        assert (x.prompt == y.prompt).all()
