"""Observability-discipline rules (family: obs).

The invariant: every observable NAME the runtime emits — metric
series, trace span names, resilience fault points, ``engine.stats``
keys — is part of the operator interface and must (a) follow the
naming conventions and (b) appear VERBATIM in the README tables, so an
operator can grep any name a dashboard shows straight to its
documentation. ``tools/check_metric_names.py`` pioneered this for
metric series (tier-1-wired since PR 3); this family absorbs it into
the rule registry and extends the same audit to spans, fault points
and stats keys. The old CLI remains as a thin shim importing the
legacy ``collect_series``/``check`` API from here.

Conventions enforced for metrics (unchanged from the legacy tool):
  * every series name starts with the ``paddle_tpu_`` prefix
  * monotonic counters end in ``_total``
  * histograms carry a base unit suffix (``_seconds``, ``_bytes``, or
    ``_size`` for dimensionless item counts — the Prometheus
    convention for e.g. batch sizes)
  * gauges do NOT end in ``_total`` (that suffix promises monotonicity)
  * every registration carries a NON-EMPTY help string literal
  * every registered name appears VERBATIM in README.md
"""
from __future__ import annotations

import ast
import os
import re
from typing import Dict, List, Optional, Tuple

from ..core import Rule, register
from . import _util as U

_UNIT_SUFFIXES = ("_seconds", "_bytes", "_size")

# ---------------------------------------------------------------------------
# legacy API (tools/check_metric_names.py shim imports these verbatim)
# ---------------------------------------------------------------------------
# a registration is `<registry>.counter("name", "help...", ...)` etc.
# — the name/help literals may sit on following lines (the codebase
# wraps at 72; help strings use implicit concatenation, so capturing
# the FIRST fragment is enough to prove the help is non-empty)
_REG_RE = re.compile(
    r'\.(counter|gauge|histogram)\(\s*"([A-Za-z0-9_]+)"'
    r'(?:\s*,\s*"((?:[^"\\]|\\.)*)")?')


def collect_series(root: str) -> List[Tuple[str, str, str, str]]:
    """[(kind, name, help_fragment, relpath)] for every metric
    registration under `root`/paddle_tpu (tests excluded — they
    register fixtures)."""
    found = {}
    pkg = os.path.join(root, "paddle_tpu")
    for dirpath, _, files in os.walk(pkg):
        if "__pycache__" in dirpath:
            continue
        for fn in files:
            if not fn.endswith(".py"):
                continue
            path = os.path.join(dirpath, fn)
            with open(path, encoding="utf-8") as f:
                text = f.read()
            for kind, name, help_frag in _REG_RE.findall(text):
                key = (kind, name, os.path.relpath(path, root))
                # re.findall yields "" for a missing optional group;
                # keep the best (non-empty) help seen for the site
                found[key] = max(found.get(key, ""), help_frag,
                                 key=len)
    return sorted((k, n, h, p) for (k, n, p), h in found.items())


def _series_problems(kind: str, name: str, help_frag: str,
                     where: str, readme_text: str) -> List[str]:
    problems = []
    if not name.startswith("paddle_tpu_"):
        problems.append(
            f"{where}: series must carry the paddle_tpu_ prefix")
        return problems
    if kind == "counter" and not name.endswith("_total"):
        problems.append(
            f"{where}: counters are monotonic and must end _total")
    if kind == "gauge" and name.endswith("_total"):
        problems.append(
            f"{where}: gauges must NOT end _total (reserved for "
            "monotonic counters)")
    if kind == "histogram" and not name.endswith(_UNIT_SUFFIXES):
        problems.append(
            f"{where}: histograms must carry a base-unit suffix "
            f"({' or '.join(_UNIT_SUFFIXES)})")
    if not help_frag.strip():
        problems.append(
            f"{where}: empty or missing help string (the # HELP "
            "line is required documentation)")
    if name not in readme_text:
        problems.append(
            f"{where}: not documented in the README observability "
            "table (add the FULL series name)")
    return problems


def check(series: List[Tuple[str, str, str, str]],
          readme_text: str) -> List[str]:
    """Returns the list of violations (empty = clean)."""
    problems = []
    for kind, name, help_frag, path in series:
        problems.extend(_series_problems(
            kind, name, help_frag, f"{name} ({kind}, {path})",
            readme_text))
    return problems


# ---------------------------------------------------------------------------
# rules
# ---------------------------------------------------------------------------
def _literal_str(node) -> Optional[str]:
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return node.value
    return None


@register
class MetricNaming(Rule):
    id = "metric-naming"
    family = "obs"
    severity = "error"
    invariant = ("every registered paddle_tpu_* series follows the "
                 "naming conventions (prefix, _total counters, unit-"
                 "suffixed histograms, non-empty help) and appears "
                 "verbatim in the README observability table")
    history = ("tier-1-wired since PR 3 as tools/check_metric_names.py "
               "— a series cannot land undocumented or misnamed; the "
               "CLI survives as a shim over this rule")

    def check(self, mod):
        seen: Dict[Tuple[str, str], Tuple[int, str]] = {}
        for node in ast.walk(mod.tree):
            if not (isinstance(node, ast.Call) and
                    isinstance(node.func, ast.Attribute) and
                    node.func.attr in ("counter", "gauge", "histogram")):
                continue
            name = _literal_str(node.args[0]) if node.args else None
            if name is None:
                continue
            kind = node.func.attr
            help_frag = ""
            if len(node.args) > 1:
                help_frag = _literal_str(node.args[1]) or ""
            key = (kind, name)
            line, best = seen.get(key, (node.lineno, ""))
            # registrations are get-or-create: audit each (kind, name)
            # once per file, with the best help string seen
            seen[key] = (min(line, node.lineno),
                         max(best, help_frag, key=len))
        for (kind, name), (line, help_frag) in sorted(seen.items()):
            for p in _series_problems(kind, name, help_frag, name,
                                      mod.project.readme):
                yield self.finding(mod, line, p)


def _readme_missing(name: str, readme: str) -> bool:
    return name not in readme


@register
class SpanNaming(Rule):
    id = "span-naming"
    family = "obs"
    severity = "error"
    invariant = ("every trace span / event name recorded via "
                 "span(...)/add_event(...) is a registered, README-"
                 "documented name — operators grep a span name from a "
                 "trace straight to its documentation")
    history = ("extends the PR 3 metric-name audit to the span "
               "namespace: request-tree debugging (PR 4) only works "
               "when span names are a closed, documented set")

    def check(self, mod):
        for node in ast.walk(mod.tree):
            if not isinstance(node, ast.Call):
                continue
            d = U.dotted(node.func) or ""
            leaf = d.split(".")[-1]
            if leaf not in ("span", "add_event") or not node.args:
                continue
            name = _literal_str(node.args[0])
            if name is None:
                continue
            if _readme_missing(name, mod.project.readme):
                yield self.finding(
                    mod, node.lineno,
                    f"span/event name '{name}' is not documented in "
                    "the README span-name table (add the FULL name)")


@register
class FaultPointNaming(Rule):
    id = "fault-point-naming"
    family = "obs"
    severity = "error"
    invariant = ("every resilience fault point compiled into the "
                 "runtime (fault_point(\"...\") sites) is listed in "
                 "the README fault-tolerance section")
    history = ("chaos tests target fault points by name; an "
               "undocumented point is chaos coverage nobody knows "
               "exists")

    def check(self, mod):
        for node in ast.walk(mod.tree):
            if not isinstance(node, ast.Call):
                continue
            d = U.dotted(node.func) or ""
            if d.split(".")[-1] != "fault_point" or not node.args:
                continue
            name = _literal_str(node.args[0])
            if name is None:
                continue
            if _readme_missing(name, mod.project.readme):
                yield self.finding(
                    mod, node.lineno,
                    f"fault point '{name}' is not documented in the "
                    "README fault-tolerance section (Registered "
                    "points list)")


@register
class FlightReasonDocumented(Rule):
    id = "flight-reason-documented"
    family = "obs"
    severity = "error"
    invariant = ("every flight-recorder trigger reason — the "
                 "TRIGGER_REASONS registry and every literal "
                 "flight.trigger(\"...\") site under "
                 "paddle_tpu/observability/ — appears verbatim in the "
                 "README flight-recorder documentation (the series-"
                 "table reason enum / trigger prose)")
    history = ("collective_skew (PR 14) was documented only by manual "
               "convention; numerics_divergence (ISSUE 15) made the "
               "convention a rule — an operator must be able to grep "
               "any bundle directory's reason straight to its docs")

    def check(self, mod):
        if not mod.path.startswith("paddle_tpu/observability/"):
            return
        for node in ast.walk(mod.tree):
            if isinstance(node, ast.Assign):
                names = [t.id for t in node.targets
                         if isinstance(t, ast.Name)]
                if "TRIGGER_REASONS" in names and isinstance(
                        node.value, (ast.Tuple, ast.List)):
                    for elt in node.value.elts:
                        reason = _literal_str(elt)
                        if reason and _readme_missing(
                                reason, mod.project.readme):
                            yield self.finding(
                                mod, elt.lineno,
                                f"flight trigger reason '{reason}' "
                                "(TRIGGER_REASONS) is not documented "
                                "in the README flight-recorder tables")
            if isinstance(node, ast.Call):
                d = U.dotted(node.func) or ""
                if d.split(".")[-1] != "trigger" or not node.args:
                    continue
                reason = _literal_str(node.args[0])
                if reason and _readme_missing(reason,
                                              mod.project.readme):
                    yield self.finding(
                        mod, node.lineno,
                        f"flight trigger reason '{reason}' is not "
                        "documented in the README flight-recorder "
                        "tables")


@register
class CollectiveInstrumentation(Rule):
    id = "collective-instrumentation"
    family = "obs"
    severity = "error"
    invariant = ("every public collective in "
                 "distributed/communication.py records through the "
                 "observability comms layer (a comms.start/finish/"
                 "count call in its body) — a future collective "
                 "cannot ship dark")
    history = ("PR 14: the communication layer ran dark through 13 "
               "PRs (zero spans/series across every collective) right "
               "as the multi-process GSPMD fleet work starts "
               "depending on collective latency, bandwidth and "
               "straggler lines")

    # collectives without the sync_op signature marker that must still
    # record (barrier blocks, ppermute moves payload in-trace);
    # axis_index is deliberately absent — it reads a rank index, no
    # payload moves
    EXTRA_COLLECTIVES = ("barrier", "ppermute", "batch_isend_irecv")

    def check(self, mod):
        if not mod.path.endswith("distributed/communication.py"):
            return
        for node in mod.tree.body:
            if not isinstance(node, ast.FunctionDef) or \
                    node.name.startswith("_"):
                continue
            params = {a.arg for a in (node.args.args
                                      + node.args.kwonlyargs)}
            if "sync_op" not in params and \
                    node.name not in self.EXTRA_COLLECTIVES:
                continue
            records = any(
                isinstance(n, ast.Call)
                and (U.dotted(n.func) or "").split(".")[0]
                in ("comms", "_comms")
                for n in ast.walk(node))
            if not records:
                yield self.finding(
                    mod, node.lineno,
                    f"public collective '{node.name}' never records "
                    "through the observability comms layer "
                    "(observability.comms start/finish or count)")


@register
class StatsKeyNaming(Rule):
    id = "stats-key-naming"
    family = "obs"
    severity = "error"
    invariant = ("every engine.stats key (the _EngineStats dict) is "
                 "README-documented — bench and tests read these keys "
                 "as a public contract")
    history = ("the test_observability key-list contract pins the "
               "exact stats key set; the README table is the operator-"
               "facing half of the same contract")

    def check(self, mod):
        # scoped to modules that define/use _EngineStats so arbitrary
        # stats dicts elsewhere (e.g. HostEmbedding.stats) keep their
        # own namespace
        if "_EngineStats" not in mod.src:
            return
        keys: Dict[str, int] = {}
        for node in ast.walk(mod.tree):
            if isinstance(node, ast.Call) and \
                    (U.dotted(node.func) or "").endswith("_EngineStats"):
                for kw in node.keywords:
                    if kw.arg and kw.arg not in keys:
                        keys[kw.arg] = node.lineno
            if isinstance(node, ast.Subscript):
                base = U.dotted(node.value) or ""
                if base.split(".")[-1] == "stats":
                    key = _literal_str(node.slice)
                    if key is not None and key not in keys:
                        keys[key] = node.lineno
        for key, line in sorted(keys.items(), key=lambda kv: kv[1]):
            if _readme_missing(key, mod.project.readme):
                yield self.finding(
                    mod, line,
                    f"engine.stats key '{key}' is not documented in "
                    "the README engine.stats table")


@register
class AutopilotActionDocumented(Rule):
    id = "autopilot-action-documented"
    family = "obs"
    severity = "error"
    invariant = ("every remediation action the autopilot supervisor "
                 "can commit — literal action names in act(\"...\") "
                 "calls and {\"action\": \"...\"} journal entries "
                 "under paddle_tpu/resilience/ — appears verbatim in "
                 "the README Training-autopilot policy table")
    history = ("ISSUE 16: remediation actions are what an operator "
               "sees in episode timelines, autopilot_remediation "
               "bundles and the paddle_tpu_autopilot_actions_total "
               "series; an action name the README policy table does "
               "not carry is a remediation nobody can audit")

    def check(self, mod):
        if not mod.path.startswith("paddle_tpu/resilience/"):
            return
        seen: Dict[str, int] = {}
        for node in ast.walk(mod.tree):
            if isinstance(node, ast.Call):
                d = U.dotted(node.func) or ""
                if d.split(".")[-1] == "act" and node.args:
                    name = _literal_str(node.args[0])
                    if name is not None and name not in seen:
                        seen[name] = node.lineno
            if isinstance(node, ast.Dict):
                for k, v in zip(node.keys, node.values):
                    if _literal_str(k) == "action":
                        name = _literal_str(v)
                        if name is not None and name not in seen:
                            seen[name] = v.lineno
        for name, line in sorted(seen.items(), key=lambda kv: kv[1]):
            if _readme_missing(name, mod.project.readme):
                yield self.finding(
                    mod, line,
                    f"autopilot action '{name}' is not documented in "
                    "the README Training-autopilot policy table")


@register
class AutoscaleActionDocumented(Rule):
    id = "autoscale-action-documented"
    family = "obs"
    severity = "error"
    invariant = ("every scale action the serving autoscaler can "
                 "commit — literals in the SCALE_ACTIONS vocabulary "
                 "and first-argument literals of _decide(\"...\") "
                 "calls under paddle_tpu/inference/autoscaler.py — "
                 "appears verbatim in the README Serving-SLO-control-"
                 "plane section")
    history = ("ISSUE 19: scale actions are what an operator sees in "
               "the scale journal, autoscale_decision bundles and the "
               "paddle_tpu_autoscaler_decisions_total series; an "
               "action name the README does not carry is a fleet-size "
               "change nobody can audit")

    def check(self, mod):
        if not mod.path.startswith("paddle_tpu/inference/autoscaler"):
            return
        seen: Dict[str, int] = {}
        for node in ast.walk(mod.tree):
            # the closed vocabulary: SCALE_ACTIONS = ("grow", ...)
            if isinstance(node, ast.Assign):
                targets = [U.dotted(t) or "" for t in node.targets]
                if any(t.split(".")[-1] == "SCALE_ACTIONS"
                       for t in targets) and \
                        isinstance(node.value, (ast.Tuple, ast.List)):
                    for el in node.value.elts:
                        name = _literal_str(el)
                        if name is not None and name not in seen:
                            seen[name] = el.lineno
            # commit sites: self._decide("grow", ...)
            if isinstance(node, ast.Call):
                d = U.dotted(node.func) or ""
                if d.split(".")[-1] == "_decide" and node.args:
                    name = _literal_str(node.args[0])
                    if name is not None and name not in seen:
                        seen[name] = node.lineno
        for name, line in sorted(seen.items(), key=lambda kv: kv[1]):
            if _readme_missing(name, mod.project.readme):
                yield self.finding(
                    mod, line,
                    f"autoscaler action '{name}' is not documented in "
                    "the README Serving SLO control plane section")


@register
class RoleLiteralDocumented(Rule):
    id = "role-literal-documented"
    family = "obs"
    severity = "error"
    invariant = ("every pool-role / process_role string the serving "
                 "stack can stamp on a replica — literals in *ROLES* "
                 "tuple vocabularies and role=/process_role= keyword "
                 "literals under paddle_tpu/inference/ — appears "
                 "verbatim in the README")
    history = ("ISSUE 20: role strings split fleet telemetry and "
               "capacity lines per pool "
               "(engine_prefill vs engine_decode); a role value the "
               "README does not carry is a telemetry partition an "
               "operator cannot interpret")

    def check(self, mod):
        if not mod.path.startswith("paddle_tpu/inference/"):
            return
        seen: Dict[str, int] = {}
        for node in ast.walk(mod.tree):
            # closed vocabularies: ROLES / PROCESS_ROLES = ("...",)
            if isinstance(node, ast.Assign):
                targets = [U.dotted(t) or "" for t in node.targets]
                if any(t.split(".")[-1].endswith("ROLES")
                       for t in targets) and \
                        isinstance(node.value, (ast.Tuple, ast.List)):
                    for el in node.value.elts:
                        name = _literal_str(el)
                        if name is not None and name not in seen:
                            seen[name] = el.lineno
            # hand-off sites: factory(role="engine_prefill"),
            # set_identity(process_role="...")
            if isinstance(node, ast.Call):
                for kw in node.keywords:
                    if kw.arg in ("role", "process_role"):
                        name = _literal_str(kw.value)
                        if name is not None and name not in seen:
                            seen[name] = kw.value.lineno
        for name, line in sorted(seen.items(), key=lambda kv: kv[1]):
            if _readme_missing(name, mod.project.readme):
                yield self.finding(
                    mod, line,
                    f"replica role '{name}' is not documented in the "
                    "README Prefill/decode disaggregation section")
