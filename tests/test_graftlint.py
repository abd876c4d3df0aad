"""graftlint: the tier-1 static-analysis gate + self-tests.

Fast and device-free: graftlint is pure stdlib (never imports jax), so
this whole file runs in seconds under JAX_PLATFORMS=cpu or anywhere
else. Covers, per ISSUE 6:

  * one known-bad AND one known-good fixture per rule family
    (donation, purity, recompile, obs);
  * the acceptance self-test — re-adding ``donate_argnums=(1, 3)`` to
    the fused optimizer makes the donation-safety rule fail, while the
    shipped source is clean;
  * suppression semantics (one line exactly), baseline semantics
    (line-shift survival, new-violation failure, occurrence counts);
  * the repo gate: zero non-baselined findings over paddle_tpu/ +
    tools/ with the checked-in baseline;
  * the per-path exemption list pin and the check_metric_names shim.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from tools.graftlint import core as gl                      # noqa: E402
from tools.graftlint import config as glconfig              # noqa: E402


def analyze(src, rules=None, readme="", path="fixture.py"):
    return gl.analyze_source(textwrap.dedent(src), path=path,
                             rule_ids=rules, readme_text=readme)


def rule_ids(findings):
    return [f.rule for f in findings]


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------
def test_rule_registry_covers_four_families():
    rules = gl.rules()
    fams = {r.family for r in rules.values()}
    assert {"donation", "purity", "recompile", "obs"} <= fams
    for r in rules.values():
        assert r.severity in gl.SEVERITIES
        assert r.invariant and r.history, r.id


# ---------------------------------------------------------------------------
# family 1: donation safety
# ---------------------------------------------------------------------------
class TestDonation:
    def test_bad_lambda_returns_donated_param(self):
        fs = analyze("""
            import jax
            def build():
                return jax.jit(lambda a, b: a, donate_argnums=(0,))
        """, rules={"donate-return-alias"})
        assert rule_ids(fs) == ["donate-return-alias"]
        assert "'a'" in fs[0].message

    def test_bad_function_returns_alias_through_local(self):
        fs = analyze("""
            import jax
            def build():
                def step(x, y):
                    z = x
                    return z, y + 1
                return jax.jit(step, donate_argnums=(0,))
        """, rules={"donate-return-alias"})
        assert rule_ids(fs) == ["donate-return-alias"]

    def test_bad_function_stores_donated_on_object(self):
        fs = analyze("""
            import jax
            def build(holder):
                def step(x):
                    holder.kept = x
                    return x + 1
                return jax.jit(step, donate_argnums=(0,))
        """, rules={"donate-return-alias"})
        assert rule_ids(fs) == ["donate-return-alias"]
        assert "holder.kept" in fs[0].message

    def test_good_rebind_through_call_is_clean(self):
        # the canonical donate-input/return-successor pattern
        # (models/generation.py): rebinding through a call CLEARS the
        # alias, so returning the successor is clean
        fs = analyze("""
            import jax
            def build(fwd):
                def step(x, caches):
                    y, caches = fwd(x, caches)
                    out = (y, caches)
                    return out
                return jax.jit(step, donate_argnums=(1,))
        """, rules={"donate-return-alias"})
        assert fs == []

    def test_bad_call_site_donates_external_buffer(self):
        fs = analyze("""
            import jax
            class Opt:
                def step(self, params, upd):
                    work = []
                    for p in params:
                        work.append(p._data)
                    states = [self._own(p) for p in params]
                    e = jax.jit(upd, donate_argnums=(0,)).lower(
                        work, states).compile()
                    return e(work, states)
        """, rules={"donate-external-buffer"})
        assert rule_ids(fs) == ["donate-external-buffer"]
        assert "p._data" in fs[0].message

    def test_good_call_site_donates_owned_state(self):
        # donating the accessor-call results (owned-by-contract) at
        # position 1 while the external buffers ride a NON-donated
        # position is the fixed-optimizer shape
        fs = analyze("""
            import jax
            class Opt:
                def step(self, params, upd):
                    work = []
                    for p in params:
                        work.append(p._data)
                    states = [self._own(p) for p in params]
                    e = jax.jit(upd, donate_argnums=(1,)).lower(
                        work, states).compile()
                    return e(work, states)
        """, rules={"donate-external-buffer"})
        assert fs == []

    # -- the acceptance self-test -------------------------------------
    def _optimizer_src(self):
        with open(os.path.join(ROOT, "paddle_tpu", "optimizer",
                               "optimizer.py"), encoding="utf-8") as f:
            return f.read()

    def test_fixed_optimizer_is_clean(self):
        fs = [f for f in analyze(self._optimizer_src(),
                                 path="paddle_tpu/optimizer/optimizer.py")
              if f.rule.startswith("donate")]
        assert fs == []

    def test_readding_old_donate_argnums_fails(self):
        """Deleting the donation guard — donating work/grads again via
        donate_argnums=(1, 3) — must trip donation-safety: `work` is
        built from p._data, an externally visible Tensor buffer."""
        src = self._optimizer_src()
        bad = src.replace("donate_argnums=(3,)", "donate_argnums=(1, 3)")
        assert bad != src, "donation guard moved — update this test"
        fs = [f for f in analyze(bad) if f.rule.startswith("donate")]
        assert any(f.rule == "donate-external-buffer" and
                   "p._data" in f.message for f in fs), fs


# ---------------------------------------------------------------------------
# family 2: trace purity / host sync
# ---------------------------------------------------------------------------
class TestPurity:
    def test_bad_scan_body_touches_host(self):
        fs = analyze("""
            import jax
            def body(c, x):
                v = float(c.sum())
                print(v)
                return c, x
            def outer(xs):
                return jax.lax.scan(body, 0, xs)
        """, rules={"host-sync-in-trace"})
        assert rule_ids(fs) == ["host-sync-in-trace"] * 2
        assert "float()" in fs[0].message and "print" in fs[1].message

    def test_bad_one_level_reachability(self):
        # np.asarray one bare-name call below a decorated jit function
        fs = analyze("""
            import jax
            import numpy as np
            def helper(x):
                return np.asarray(x)
            @jax.jit
            def fn(x):
                return helper(x)
        """, rules={"host-sync-in-trace"})
        assert rule_ids(fs) == ["host-sync-in-trace"]
        assert "called from traced" in fs[0].message

    def test_nested_traced_def_reports_once(self):
        # an outer jit function whose nested scan body is ALSO traced:
        # the violation inside the body must be reported exactly once
        # (the nested def gets its own walk; the outer walk skips it)
        fs = analyze("""
            import jax
            import numpy as np
            @jax.jit
            def outer(xs):
                def body(c, x):
                    return c, np.asarray(x)
                return jax.lax.scan(body, 0, xs)
        """, rules={"host-sync-in-trace"})
        assert len(fs) == 1

    def test_bad_time_in_while_body(self):
        fs = analyze("""
            import jax, time
            def cond(c):
                return c[0] < 4
            def body(c):
                t = time.perf_counter()
                return (c[0] + 1, t)
            def run(c0):
                return jax.lax.while_loop(cond, body, c0)
        """, rules={"host-sync-in-trace"})
        assert rule_ids(fs) == ["host-sync-in-trace"]
        assert "trace time" in fs[0].message

    def test_good_device_ops_in_jit_are_clean(self):
        fs = analyze("""
            import jax
            import jax.numpy as jnp
            @jax.jit
            def fn(x):
                y = jnp.asarray(x)          # device-side: fine
                return jnp.argmax(y, axis=-1).astype(jnp.int32)
        """, rules={"host-sync-in-trace", "host-sync"})
        assert fs == []

    def test_host_sync_outside_trace_is_warning_only(self):
        fs = analyze("""
            import numpy as np
            def collect(arr):
                return [int(t) for t in np.asarray(arr)]
        """)
        assert rule_ids(fs) == ["host-sync"]
        assert fs[0].severity == "warning"

    def test_host_clock_flagged_on_dispatch_path_only(self):
        src = """
            import time
            def walk(nodes):
                t0 = time.perf_counter()
                return t0
        """
        fs = analyze(src, rules={"host-clock-in-dispatch"},
                     path="paddle_tpu/autograd/some_walker.py")
        assert rule_ids(fs) == ["host-clock-in-dispatch"]
        assert fs[0].severity == "warning"
        # the registry file is audited too
        fs = analyze(src, rules={"host-clock-in-dispatch"},
                     path="paddle_tpu/ops/registry.py")
        assert rule_ids(fs) == ["host-clock-in-dispatch"]
        # everything off the dispatch hot path is not
        fs = analyze(src, rules={"host-clock-in-dispatch"},
                     path="paddle_tpu/inference/llm_engine.py")
        assert fs == []

    def test_host_clock_ignores_non_clock_time_attrs(self):
        fs = analyze("""
            import time
            def nap():
                time.sleep(0.1)
        """, rules={"host-clock-in-dispatch"},
            path="paddle_tpu/autograd/tape.py")
        assert fs == []


# ---------------------------------------------------------------------------
# family 3: recompile hazards
# ---------------------------------------------------------------------------
class TestRecompile:
    def test_bad_repr_in_fingerprint(self):
        fs = analyze("""
            class Opt:
                def _hyper_fingerprint(self):
                    return (repr(self.weight_decay),)
        """, rules={"unstable-cache-key"})
        assert rule_ids(fs) == ["unstable-cache-key"]
        assert "repr()" in fs[0].message

    def test_bad_fstring_cache_key(self):
        fs = analyze("""
            class Eng:
                def get(self, sb, npb):
                    key = f"{sb}x{npb}"
                    return self._decode_fns.get(key)
        """, rules={"unstable-cache-key"})
        assert rule_ids(fs) == ["unstable-cache-key"]
        assert "f-string" in fs[0].message

    def test_bad_id_in_cache_subscript(self):
        fs = analyze("""
            class Eng:
                def get(self, obj):
                    return self._cache[id(obj)]
        """, rules={"unstable-cache-key"})
        assert rule_ids(fs) == ["unstable-cache-key"]

    def test_good_structural_key_is_clean(self):
        fs = analyze("""
            class Eng:
                def get(self, sb, npb):
                    key = (sb, npb, "verify")
                    return self._decode_fns.get(key)
                def _hyper_fingerprint(self):
                    return (self.beta1, self.beta2)
        """, rules={"unstable-cache-key"})
        assert fs == []

    def test_bad_fstring_key_to_persistent_store(self):
        # the persistent-store verbs (load/save/...) are audited like
        # dict verbs: an f-string key reaching disk is never hit again
        fs = analyze("""
            class Eng:
                def save(self, fam, shape, compiled):
                    self._exec_cache.save(f"{fam}-{shape}", compiled)
        """, rules={"unstable-cache-key"})
        assert rule_ids(fs) == ["unstable-cache-key"]
        assert "f-string" in fs[0].message

    def test_bad_repr_key_built_then_loaded_from_store(self):
        fs = analyze("""
            class Eng:
                def warm(self, obj):
                    key = repr(obj)
                    return self.store.load(key)
        """, rules={"unstable-cache-key"})
        assert rule_ids(fs) == ["unstable-cache-key"]
        assert "repr()" in fs[0].message

    def test_good_structural_key_to_persistent_store(self):
        fs = analyze("""
            class Eng:
                def save(self, key, compiled):
                    self._exec_cache.save(key, compiled, family="eng")
                def warm(self, key):
                    return self.store.load(key)
        """, rules={"unstable-cache-key"})
        assert fs == []

    def test_good_identity_map_not_a_store(self):
        # an id()-keyed identity dict does not speak the persistent-
        # store verb surface and must stay clean (tape.py node_store)
        fs = analyze("""
            class Tape:
                def remember(self, node, val):
                    self.node_store[id(node)] = val
                    return self.node_store.get(id(node))
        """, rules={"unstable-cache-key"})
        assert fs == []

    def test_bad_unhashable_static_arg(self):
        fs = analyze("""
            import jax
            def run(f, x):
                return jax.jit(f, static_argnums=(1,))(x, [4, 8])
        """, rules={"unhashable-static-arg"})
        assert rule_ids(fs) == ["unhashable-static-arg"]

    def test_good_hashable_static_arg(self):
        fs = analyze("""
            import jax
            def run(f, x):
                return jax.jit(f, static_argnums=(1,))(x, (4, 8))
        """, rules={"unhashable-static-arg"})
        assert fs == []


# ---------------------------------------------------------------------------
# family 4: observability discipline
# ---------------------------------------------------------------------------
_README = ("paddle_tpu_good_total paddle_tpu_lat_seconds engine.step "
           "request.prefill engine.decode.seq stats documented: "
           "decode_tokens prefills autopilot actions: rollback_resume "
           "evict_rank elastic_restart escalate")


class TestObsDiscipline:
    def test_bad_metric_counter_without_total(self):
        fs = analyze("""
            c = registry().counter("paddle_tpu_bad_count", "help")
        """, rules={"metric-naming"}, readme=_README + " paddle_tpu_bad_count")
        assert rule_ids(fs) == ["metric-naming"]
        assert "_total" in fs[0].message

    def test_bad_metric_undocumented(self):
        fs = analyze("""
            c = registry().counter("paddle_tpu_undoc_total", "help")
        """, rules={"metric-naming"}, readme=_README)
        assert rule_ids(fs) == ["metric-naming"]
        assert "not documented" in fs[0].message

    def test_good_metric_clean(self):
        fs = analyze("""
            c = registry().counter("paddle_tpu_good_total", "help")
            h = r.histogram("paddle_tpu_lat_seconds", "help", ("op",))
        """, rules={"metric-naming"}, readme=_README)
        assert fs == []

    def test_bad_span_name_undocumented(self):
        fs = analyze("""
            def step(_ot):
                with _ot.span("engine.mystery"):
                    pass
        """, rules={"span-naming"}, readme=_README)
        assert rule_ids(fs) == ["span-naming"]

    def test_good_span_name(self):
        fs = analyze("""
            def step(_ot):
                with _ot.span("engine.step"):
                    _ot.add_event("request.prefill", 0.0, 1.0)
        """, rules={"span-naming"}, readme=_README)
        assert fs == []

    def test_bad_fault_point_undocumented(self):
        fs = analyze("""
            def seq(faults):
                faults.fault_point("engine.unknown.seq", rid=1)
        """, rules={"fault-point-naming"}, readme=_README)
        assert rule_ids(fs) == ["fault-point-naming"]

    def test_good_fault_point(self):
        fs = analyze("""
            def seq(faults):
                faults.fault_point("engine.decode.seq", rid=1)
        """, rules={"fault-point-naming"}, readme=_README)
        assert fs == []

    def test_bad_stats_key_undocumented(self):
        fs = analyze("""
            class E:
                def __init__(self):
                    self.stats = _EngineStats(decode_tokens=0)
                def step(self):
                    self.stats["mystery_key"] += 1
        """, rules={"stats-key-naming"}, readme=_README)
        assert rule_ids(fs) == ["stats-key-naming"]
        assert "mystery_key" in fs[0].message

    def test_bad_autopilot_action_undocumented(self):
        fs = analyze("""
            def _plan(self):
                return [{"action": "reboot_datacenter"}]
        """, rules={"autopilot-action-documented"},
            readme=_README,
            path="paddle_tpu/resilience/supervisor.py")
        assert rule_ids(fs) == ["autopilot-action-documented"]
        assert "reboot_datacenter" in fs[0].message

    def test_good_autopilot_actions(self):
        fs = analyze("""
            def _plan(self):
                return [{"action": "rollback_resume"},
                        {"action": "evict_rank"}]

            def go(self, ep):
                self.act("escalate", ep)
        """, rules={"autopilot-action-documented"},
            readme=_README,
            path="paddle_tpu/resilience/supervisor.py")
        assert fs == []

    def test_autopilot_rule_scoped_to_resilience(self):
        fs = analyze("""
            PLAN = [{"action": "reboot_datacenter"}]
        """, rules={"autopilot-action-documented"},
            readme=_README, path="paddle_tpu/engine/thing.py")
        assert fs == []

    def test_bad_autoscale_action_undocumented(self):
        fs = analyze("""
            SCALE_ACTIONS = ("grow", "annihilate")

            def scan(self):
                self._decide("annihilate", 3, trigger={})
        """, rules={"autoscale-action-documented"},
            readme=_README + " autoscaler actions: grow retire",
            path="paddle_tpu/inference/autoscaler.py")
        assert rule_ids(fs) == ["autoscale-action-documented"]
        assert "annihilate" in fs[0].message

    def test_good_autoscale_actions(self):
        fs = analyze("""
            SCALE_ACTIONS = ("grow", "retire")

            def scan(self):
                self._decide("grow", 1, trigger={})
                self._decide("retire", 2, trigger={})
        """, rules={"autoscale-action-documented"},
            readme=_README + " autoscaler actions: grow retire",
            path="paddle_tpu/inference/autoscaler.py")
        assert fs == []

    def test_autoscale_rule_scoped_to_autoscaler(self):
        fs = analyze("""
            SCALE_ACTIONS = ("annihilate",)
        """, rules={"autoscale-action-documented"},
            readme=_README, path="paddle_tpu/inference/router.py")
        assert fs == []

    def test_bad_role_literal_undocumented(self):
        fs = analyze("""
            ROLES = ("prefill", "shredder")

            def launch(factory):
                return factory(role="engine_shredder")
        """, rules={"role-literal-documented"},
            readme=_README + " pool roles: prefill decode "
                             "engine_prefill engine_decode",
            path="paddle_tpu/inference/disagg.py")
        assert rule_ids(fs) == ["role-literal-documented"] * 2
        assert "shredder" in fs[0].message
        assert "engine_shredder" in fs[1].message

    def test_good_role_literals(self):
        fs = analyze("""
            ROLES = ("prefill", "decode")
            PROCESS_ROLES = ("engine_prefill", "engine_decode")

            def launch(factory):
                return factory(role="engine_prefill")
        """, rules={"role-literal-documented"},
            readme=_README + " pool roles: prefill decode "
                             "engine_prefill engine_decode",
            path="paddle_tpu/inference/disagg.py")
        assert fs == []

    def test_role_rule_scoped_to_inference(self):
        fs = analyze("""
            ROLES = ("shredder",)
        """, rules={"role-literal-documented"},
            readme=_README, path="paddle_tpu/resilience/thing.py")
        assert fs == []

    def test_good_stats_keys(self):
        fs = analyze("""
            class E:
                def __init__(self):
                    self.stats = _EngineStats(decode_tokens=0)
                def step(self):
                    self.stats["prefills"] += 1
        """, rules={"stats-key-naming"}, readme=_README)
        assert fs == []

    def test_dark_collective_flagged(self):
        fs = analyze("""
            def all_reduce(tensor, op=0, group=None, sync_op=True):
                return tensor

            def barrier(group=None):
                return None
        """, rules={"collective-instrumentation"},
            path="paddle_tpu/distributed/communication.py")
        assert rule_ids(fs) == ["collective-instrumentation"] * 2
        assert "all_reduce" in fs[0].message
        assert "barrier" in fs[1].message

    def test_instrumented_collective_clean(self):
        fs = analyze("""
            def all_reduce(tensor, op=0, group=None, sync_op=True):
                rec = _comms.start("all_reduce", "world", 4)
                _comms.finish(rec, tensor)
                return tensor

            def ppermute(x, group, perm):
                _comms.count("ppermute", "world", 4)
                return x

            def axis_index(group):      # no payload: exempt
                return 0

            def _private_helper(sync_op=True):   # private: exempt
                return None
        """, rules={"collective-instrumentation"},
            path="paddle_tpu/distributed/communication.py")
        assert fs == []

    def test_collective_rule_scoped_to_communication_module(self):
        fs = analyze("""
            def all_reduce(tensor, sync_op=True):
                return tensor
        """, rules={"collective-instrumentation"},
            path="paddle_tpu/other/module.py")
        assert fs == []

    def test_stats_rule_scoped_to_engine_stats_modules(self):
        # an unrelated stats dict (HostEmbedding.stats) is NOT audited
        fs = analyze("""
            class Table:
                def touch(self):
                    self.stats["rows_touched"] += 1
        """, rules={"stats-key-naming"}, readme=_README)
        assert fs == []


# ---------------------------------------------------------------------------
# suppression semantics: exactly one line
# ---------------------------------------------------------------------------
class TestSuppression:
    SRC = """
        import numpy as np
        def f(a, b):
            x = np.asarray(a)  # graftlint: disable=host-sync
            y = np.asarray(b)
            return x, y
    """

    def test_suppression_covers_exactly_its_line(self):
        fs = analyze(self.SRC, rules={"host-sync"})
        assert len(fs) == 1
        assert "np.asarray(b)" in fs[0].snippet

    def test_wrong_rule_id_does_not_suppress(self):
        src = self.SRC.replace("disable=host-sync", "disable=span-naming")
        fs = analyze(src, rules={"host-sync"})
        assert len(fs) == 2

    def test_disable_all(self):
        src = self.SRC.replace("disable=host-sync", "disable=all")
        fs = analyze(src, rules={"host-sync"})
        assert len(fs) == 1


# ---------------------------------------------------------------------------
# baseline semantics
# ---------------------------------------------------------------------------
class TestBaseline:
    PATH = "pkg/mod.py"
    SRC = """
        import numpy as np
        def f(a):
            return np.asarray(a)
    """

    def _findings(self, src):
        return analyze(src, rules={"host-sync"}, path=self.PATH)

    def test_entries_survive_line_shifts(self):
        base = gl.Baseline(gl.build_baseline(self._findings(self.SRC)))
        shifted = "# one\n# two\n# three\n" + textwrap.dedent(self.SRC)
        new, old = base.split(analyze(shifted, rules={"host-sync"},
                                      path=self.PATH))
        assert new == [] and len(old) == 1

    def test_new_violation_in_baselined_file_fails(self):
        base = gl.Baseline(gl.build_baseline(self._findings(self.SRC)))
        grown = textwrap.dedent(self.SRC) + "\ndef g(b):\n" \
            "    return np.asarray(b + 1)\n"
        new, old = base.split(analyze(grown, rules={"host-sync"},
                                      path=self.PATH))
        assert len(old) == 1 and len(new) == 1
        assert "b + 1" in new[0].snippet

    def test_extra_copy_of_same_snippet_fails(self):
        # entries carry occurrence counts: one more IDENTICAL line is
        # still a new violation
        base = gl.Baseline(gl.build_baseline(self._findings(self.SRC)))
        doubled = textwrap.dedent(self.SRC) + "\ndef g(b):\n" \
            "    return np.asarray(a)\n"
        fs = analyze(doubled, rules={"host-sync"}, path=self.PATH)
        # normalize: both lines carry the same snippet
        assert len({f.baseline_key() for f in fs}) == 1
        new, old = base.split(fs)
        assert len(old) == 1 and len(new) == 1

    def test_keys_are_rule_file_snippet(self):
        f = self._findings(self.SRC)[0]
        assert f.baseline_key() == ("host-sync", self.PATH,
                                    "return np.asarray(a)")

    def test_update_carries_notes_forward(self):
        fs = self._findings(self.SRC)
        prev = gl.Baseline(gl.build_baseline(fs))
        prev.entries[0]["note"] = "justified: host API"
        entries = gl.build_baseline(fs, previous=gl.Baseline(prev.entries))
        assert entries[0]["note"] == "justified: host API"


# ---------------------------------------------------------------------------
# the repo gate + wiring
# ---------------------------------------------------------------------------
def test_repo_has_zero_new_findings():
    """The acceptance gate: paddle_tpu/ + tools/ against the checked-in
    baseline — every finding is either fixed, suppressed with a reason,
    or baselined with a note."""
    baseline = gl.Baseline.load(gl.default_baseline_path())
    rep = gl.run_paths([os.path.join(ROOT, "paddle_tpu"),
                        os.path.join(ROOT, "tools")],
                       root=ROOT, baseline=baseline)
    assert rep.parse_errors == []
    head = "\n".join(f"{f.path}:{f.line}: {f.rule}: {f.message}"
                     for f in rep.new[:8])
    assert rep.new == [], f"new graftlint findings:\n{head}"
    # the baseline is a burn-down list, not a dumping ground: every
    # entry (at its full count) must still match a live finding, so
    # fixing a site forces `--update-baseline` to shrink the file
    from collections import Counter
    live = Counter(f.baseline_key() for f in rep.findings)
    stale = [e for e in baseline.entries
             if live[(e["rule"], e["path"], e["snippet"])] <
             int(e.get("count", 1))]
    assert stale == [], f"stale baseline entries (burn them down): " \
        f"{stale[:4]}"


def test_cli_json_and_exit_code():
    # a subset scan keeps this wall-clock-cheap; the full-tree gate is
    # test_repo_has_zero_new_findings (in-process, no interpreter tax)
    out = subprocess.run(
        [sys.executable, "-m", "tools.graftlint", "tools/graftlint",
         "paddle_tpu/optimizer", "--json"],
        cwd=ROOT, capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-2000:]
    data = json.loads(out.stdout)
    assert data["counts"]["new"] == 0
    assert data["counts"]["total"] == data["counts"]["baselined"]
    assert data["files"] > 10
    for f in data["findings"]:
        assert f["baselined"] is True


def test_cli_zero_files_is_a_failure():
    # a typo'd path must never read as a green gate
    out = subprocess.run(
        [sys.executable, "-m", "tools.graftlint", "no_such_dir_xyz"],
        cwd=ROOT, capture_output=True, text=True, timeout=120)
    assert out.returncode == 2
    assert "wrong path" in out.stderr


def test_exemption_list_pinned():
    """Per-path analysis exemptions are a reviewed contract: operator
    CLIs under tools/ are exempt from the host-sync inventory ONLY."""
    assert glconfig.PATH_EXEMPTIONS == {
        "tools/obs_top.py": frozenset({"host-sync"}),
        "tools/obs_dump.py": frozenset({"host-sync"}),
        "tools/gen_ops_parity.py": frozenset({"host-sync"}),
    }
    for rules_disabled in glconfig.PATH_EXEMPTIONS.values():
        assert rules_disabled == frozenset({"host-sync"})


def test_baseline_entries_carry_notes():
    base = gl.Baseline.load(gl.default_baseline_path())
    assert base.entries, "baseline missing"
    for e in base.entries:
        assert e.get("note"), f"baseline entry without justification: {e}"
        assert e["rule"] in gl.rules()


def test_check_metric_names_shim():
    sys.path.insert(0, os.path.join(ROOT, "tools"))
    try:
        import check_metric_names as cmn
    finally:
        sys.path.pop(0)
    from tools.graftlint.rules import observability as obs_rules
    # the shim delegates to the graftlint rule module (one canonical
    # implementation), and the repo stays clean through it
    assert cmn.check is obs_rules.check
    assert cmn.collect_series is obs_rules.collect_series
    assert cmn.main(ROOT) == 0
