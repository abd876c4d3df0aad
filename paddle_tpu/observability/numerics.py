"""Training numerics & model-health plane (see README "Training
numerics & model health").

Observability covers requests, executables, the fleet and collectives
— but a diverging TRAINING run still showed up as a flat loss curve or
a silently skipped AMP step, with nothing naming which parameter went
nonfinite or when the loss scale collapsed. The reference framework
treats this as a first-class subsystem (`FLAGS_check_nan_inf`,
`paddle/fluid/framework/details/nan_inf_utils*`: per-op nonfinite
detection with tensor attribution); here the whole-graph fused
backward, the fused optimizer step and the jitted TrainStep are
exactly the places those statistics come for (near) free, computed
device-side instead of with per-tensor host syncs. Three sub-surfaces,
all one module-flag check when the plane is off (the default):

* **In-trace stats.** With `numerics.enable()`, the fused optimizer
  step and the TrainStep executable gain a *stats-on variant* (one
  extra compile per family, pinned by the family-budget tests) whose
  trace additionally emits ONE packed f32 reduction bundle —
  per-parameter grad square-norms and nonfinite element counts, the
  pre-update param square-norm, the update square-norm ‖Δw‖² and the
  post-update param nonfinite count (`pack_stats`, pure jnp: one
  definition serves the fused step, the TrainStep trace and the eager
  fallback). Whole-graph fused backward segments emit a tiny
  `[grad_sq, nonfinite]` tap over their leaf-edge cotangents the same
  way. The bundle is handed to `submit()` as a DEVICE array and
  pulled asynchronously: each step's submit publishes the *previous*
  step's bundle — by then its tiny reductions have long completed, so
  the pull (`np.asarray`, the ONE host materialization per step,
  never per-tensor) observes a finished array instead of blocking the
  loop. Published series: `paddle_tpu_train_grad_norm{group=all|g<i>}`
  (global + per-parameter-group rows), `paddle_tpu_train_param_norm`,
  `paddle_tpu_train_update_ratio` (‖Δw‖/‖w‖ against the pre-update
  norm), and `paddle_tpu_train_nonfinite_total{where=grad|param|loss}`
  (element counts; loss counts 1 per nonfinite step). Eager per-node /
  batched dispatch and non-jittable optimizer rules get the SAME
  series via a host-side fallback (`pack_stats` dispatched eagerly —
  still async, still one pull).

* **NaN/Inf sentinel + forensics.** Every publish runs a divergence
  check under a `numerics.check` span: nonfinite grads/params/loss, a
  grad-norm spike against a running window (median × `spike_factor`
  once `min_window` samples exist), or a dynamic-loss-scale collapse
  to `loss_scale_floor` (reported by `GradScaler.update`) fires ONE
  `numerics_divergence` flight bundle through the existing
  `flight.arm()` machinery — latched, so a divergence episode yields
  exactly one bundle and the latch re-arms on the next clean step.
  The bundle detail names the FIRST nonfinite parameter, carries the
  per-parameter grad stats (top offenders), the recent loss / lr /
  loss-scale history and the triggering `numerics.check` span ids
  (the span itself is in the bundle's trace.jsonl). Chaos tests drive
  the path deterministically through the `numerics.check` fault point
  (top of `Optimizer.step`, ctx `where="step"`, and `GradScaler.step`,
  ctx `where="amp"`): arming it with `exc=PoisonGradient(param=...)`
  overwrites that parameter's gradient with NaN before the check, so
  the real in-trace detection — not a mock — sees the poison.

* **AMP loss-scale forensics.** `GradScaler` records
  `paddle_tpu_amp_loss_scale`, `paddle_tpu_amp_steps_total{outcome=
  ok|skipped}` and `paddle_tpu_amp_scale_decreases_total` (see
  `paddle_tpu.amp`), and reports every scale change here
  (`note_loss_scale`) so the scale history rides divergence bundles
  and a floor collapse fires the sentinel. A skipped step's nonfinite
  grads (the optimizer never ran, so no packed bundle exists) count
  once onto `paddle_tpu_train_nonfinite_total{where=grad}` via
  `note_found_inf` — factual, but NOT latched as divergence: a
  skipped step is dynamic loss scaling working, not failing.

Disabled-mode honesty: `numerics.enable()` is required for ANY of the
above to run — off (the default), the train loop pays one module-flag
read per step (zero allocations, zero host syncs, pinned by the
tracemalloc guard in tests/test_numerics.py). Enabled, the plane adds
one packed reduction to executables that already run and ≤1 async
host pull per step, SAMPLED on the `interval` cadence (default every
64th step; `interval=1` = every-step fidelity — see `enable()` for
the detection-latency contract: divergence is absorbing, so the
cadence bounds latency, not coverage). What the default cadence costs
on the chip: not measured (no benchmark cell turns the plane on).
Stats are read-only taps: gradients and
optimizer states are bit-identical with the plane on vs off across
all three backward dispatch modes (test-pinned). The gauges ride
fleet bundles like every other series, so an aggregator sees
per-process grad norms under a `process=` label and can tell a
diverged rank from a straggling one.
"""
from __future__ import annotations

import math
from collections import deque
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from . import flight as _fl
from . import metrics as _m
from . import tracing as _t
from ..resilience import faults as _faults

__all__ = [
    "enable", "disable", "enabled", "config", "NumericsConfig",
    "PoisonGradient", "pack_stats", "submit", "note_backward_tap",
    "note_loss_scale", "note_found_inf", "check_fault", "flush",
    "last", "pulls", "want_stats", "tick", "reset_window",
]

# single-check hot-path flag (the metrics._ENABLED idiom): the train
# loop's instrumented sites read `numerics._ENABLED` directly
_ENABLED = False
_CFG: Optional["NumericsConfig"] = None


class NumericsConfig:
    __slots__ = ("window", "spike_factor", "min_window",
                 "loss_scale_floor", "history", "interval")

    def __init__(self, window=32, spike_factor=10.0, min_window=8,
                 loss_scale_floor=2.0, history=64, interval=64):
        self.window = max(2, int(window))
        self.spike_factor = float(spike_factor)
        self.min_window = max(2, int(min_window))
        self.loss_scale_floor = float(loss_scale_floor)
        self.history = max(4, int(history))
        self.interval = max(1, int(interval))


def enable(window: int = 32, spike_factor: float = 10.0,
           min_window: int = 8, loss_scale_floor: float = 2.0,
           history: int = 64, interval: int = 64) -> NumericsConfig:
    """Turn the numerics plane on, process-wide. Stats-on executable
    variants compile lazily on the next sampled step of each family;
    the sentinel knobs: a grad norm over `spike_factor` × the running
    window median (once `min_window` samples exist), any nonfinite
    grad/param/loss count, or a dynamic loss scale decreased to
    `loss_scale_floor` or below fires a `numerics_divergence` flight
    bundle (when `flight.arm()`ed).

    `interval` is the sampling cadence: the full in-trace bundle (and
    its pull) runs every `interval`-th training step — `interval=1` is
    every-step fidelity (what the chaos/correctness tests pin), the
    default 64 keeps the measured on-vs-off overhead of the eager
     3-layer-MLP loop within the ≤3% budget on a CPU box where the
    extra reduction passes are memory-bound (a TPU amortizes them far
    better). Divergence detection latency is bounded by the cadence
    and real divergence is ABSORBING — a NaN'd parameter stays NaN —
    so a diverged run is still caught at the next sampled step, with
    the same first-nonfinite attribution; only a transient
    single-step grad spike can fall between samples. AMP loss-scale
    telemetry and the scale-floor sentinel are per-step regardless
    (they ride GradScaler work that already happens)."""
    global _ENABLED, _CFG
    cfg = NumericsConfig(window, spike_factor, min_window,
                         loss_scale_floor, history, interval)
    _CFG = cfg
    _resize_windows(cfg)
    _ENABLED = True
    return cfg


def disable() -> None:
    """Turn the plane off (pending un-pulled stats are dropped; use
    flush() first to publish them)."""
    global _ENABLED, _PENDING
    _ENABLED = False
    _PENDING = None
    _STEP_TAPS.clear()


def enabled() -> bool:
    return _ENABLED


def config() -> Optional[NumericsConfig]:
    return _CFG


# ---------------------------------------------------------------------------
# state: the pending (not yet pulled) step bundle, this step's backward
# taps, the sentinel windows/histories, and the last published record
# ---------------------------------------------------------------------------
_PENDING: Optional[dict] = None
_STEP_TAPS: List = []           # device f32[2] arrays from the backward
_TAP_CAP = 512                  # bound: a pathological loop can't grow it
_STEP = 0
_TICK = 0                       # training-step counter for the cadence
_PULLS = 0
_DIVERGED = False
_GRAD_WINDOW: deque = deque(maxlen=32)
_LOSS_HISTORY: deque = deque(maxlen=64)
_LR_HISTORY: deque = deque(maxlen=64)
_SCALE_HISTORY: deque = deque(maxlen=64)
_LAST: Optional[dict] = None
_METRICS = None


def _resize_windows(cfg: NumericsConfig) -> None:
    global _GRAD_WINDOW, _LOSS_HISTORY, _LR_HISTORY, _SCALE_HISTORY
    _GRAD_WINDOW = deque(_GRAD_WINDOW, maxlen=cfg.window)
    _LOSS_HISTORY = deque(_LOSS_HISTORY, maxlen=cfg.history)
    _LR_HISTORY = deque(_LR_HISTORY, maxlen=cfg.history)
    _SCALE_HISTORY = deque(_SCALE_HISTORY, maxlen=cfg.history)


def _metrics():
    global _METRICS
    if _METRICS is None:
        r = _m.registry()
        _METRICS = {
            "grad_norm": r.gauge(
                "paddle_tpu_train_grad_norm",
                "global (group=all) and per-parameter-group (group="
                "g<i>) L2 gradient norm of the most recent published "
                "training step, computed device-side inside the fused "
                "optimizer / TrainStep stats variant and pulled "
                "asynchronously one step later",
                ("group",)),
            "param_norm": r.gauge(
                "paddle_tpu_train_param_norm",
                "L2 norm of the trainable parameters at the most "
                "recent published step (pre-update values)"),
            "update_ratio": r.gauge(
                "paddle_tpu_train_update_ratio",
                "update-to-weight ratio of the most recent published "
                "step: L2 norm of the applied parameter delta over "
                "the pre-update parameter norm"),
            "nonfinite": r.counter(
                "paddle_tpu_train_nonfinite_total",
                "nonfinite (NaN/Inf) training values detected by the "
                "numerics plane: where=grad / where=param count "
                "elements (an AMP-skipped step, whose grads never "
                "reach the optimizer bundle, counts 1), where=loss "
                "counts nonfinite loss steps",
                ("where",)),
        }
    return _METRICS


def want_stats() -> bool:
    """True when THIS training step is a sampled step: the in-trace
    bundle sites (whole-graph backward tap, fused/eager optimizer,
    their submits) all read the same decision, which holds until
    `tick()` advances the step counter at the end of the optimizer
    step. With the plane off this is one flag read."""
    if not _ENABLED:
        return False
    cfg = _CFG
    return _TICK % (cfg.interval if cfg is not None else 1) == 0


def tick() -> None:
    """Advance the training-step counter (called at the end of
    `Optimizer.step` and for an AMP-skipped step — one counter
    increment; call sites guard on the enabled flag)."""
    global _TICK
    _TICK += 1


def reset_window() -> None:
    """Drop the pending bundle, accumulated backward taps, sentinel
    windows/histories, the step/cadence counters and the divergence
    latch — the numerics half of `obs.reset()`'s fresh-measurement-
    window contract. The enabled flag, config and cumulative pull
    count survive."""
    global _PENDING, _STEP, _TICK, _DIVERGED, _LAST
    _PENDING = None
    _STEP_TAPS.clear()
    _STEP = 0
    _TICK = 0
    _DIVERGED = False
    _LAST = None
    _GRAD_WINDOW.clear()
    _LOSS_HISTORY.clear()
    _LR_HISTORY.clear()
    _SCALE_HISTORY.clear()


def rearm() -> None:
    """Clear the divergence latch WITHOUT touching windows/histories —
    a remediation (the autopilot's rollback + loss-scale re-raise)
    ended the episode, so the next collapse must count as a NEW
    episode even when no clean publish happened in between (every step
    of a floored AMP run is a skipped step: nothing publishes, so the
    clean-step re-arm never runs)."""
    global _DIVERGED
    _DIVERGED = False


def pulls() -> int:
    """Cumulative host pulls performed by the plane (exactly one per
    published step bundle — the ≤1-async-pull-per-step contract is
    test-pinned against this counter)."""
    return _PULLS


def last() -> Optional[dict]:
    """The most recently published step record (host-side plain data:
    grad_norm, per-group norms, per_param stats, param_norm,
    update_ratio, nonfinite counts, loss/lr, backward tap summary) —
    readable with metrics disabled."""
    return _LAST


# ---------------------------------------------------------------------------
# chaos: the numerics.check fault point + the PoisonGradient payload
# ---------------------------------------------------------------------------
class PoisonGradient(Exception):
    """Chaos payload for the `numerics.check` fault point: when an
    armed fault raises this, `check_fault` swallows it and overwrites
    the named parameter's gradient (or the first parameter with a
    gradient) with `value` (default NaN) — so chaos tests poison a
    REAL gradient and the genuine in-trace detection path, not a mock,
    produces the divergence bundle."""

    def __init__(self, param: Optional[str] = None,
                 value: float = float("nan")):
        super().__init__(f"poison gradient {param or '<first>'}")
        self.param = param
        self.value = value


def check_fault(where: str, pairs: Sequence[Tuple]) -> None:
    """Fire the `numerics.check` fault point (ctx: `where` — "step"
    from `Optimizer.step`, "amp" from `GradScaler.step`). Call sites
    guard on `faults._ACTIVE`, so the disarmed train loop never builds
    the `pairs` list. A raised PoisonGradient poisons the matching
    gradient in place; any other injected effect (delay, exit_code,
    foreign exc) behaves like every other fault point."""
    try:
        _faults.fault_point("numerics.check", where=where)
    except PoisonGradient as pg:
        import jax.numpy as jnp
        for prm, g in pairs:
            if g is None:
                continue
            if pg.param is None or getattr(prm, "name", None) == pg.param:
                g._set_data(jnp.full(g._data.shape, pg.value,
                                     g._data.dtype))
                return
        raise RuntimeError(
            f"numerics.check poison: no parameter named {pg.param!r} "
            "with a live gradient") from pg


# ---------------------------------------------------------------------------
# the packed reduction bundle (pure jnp — ONE definition traced into
# the fused optimizer step and the TrainStep executable, and dispatched
# eagerly by the host-side fallback)
# ---------------------------------------------------------------------------
def pack_stats(olds, grads, news):
    """Device-side stats bundle over aligned (pre-update param, grad,
    post-update param) array lists. Layout (all f32, one 1-D array):

        [0 : P]        per-parameter grad square-norms
        [P : 2P]       per-parameter grad nonfinite element counts
        [2P : 2P+3]    pre-update param square-norm, update (Δw)
                       square-norm, post-update param nonfinite count

    Safe under a jax trace (the fused optimizer / TrainStep variants
    call it mid-trace) and as eager dispatch (the fallback)."""
    import jax.numpy as jnp

    gsq, gnf = [], []
    psq = jnp.float32(0.0)
    dsq = jnp.float32(0.0)
    pnf = jnp.float32(0.0)
    for w, g, nw in zip(olds, grads, news):
        gf = g.astype(jnp.float32)
        gsq.append(jnp.sum(gf * gf))
        gnf.append(jnp.sum(~jnp.isfinite(gf)).astype(jnp.float32))
        wf = w.astype(jnp.float32)
        nwf = nw.astype(jnp.float32)
        psq = psq + jnp.sum(wf * wf)
        dsq = dsq + jnp.sum((nwf - wf) * (nwf - wf))
        pnf = pnf + jnp.sum(~jnp.isfinite(nwf)).astype(jnp.float32)
    return jnp.concatenate([jnp.stack(gsq), jnp.stack(gnf),
                            jnp.stack([psq, dsq, pnf])])


def note_backward_tap(tap) -> None:
    """One whole-graph fused backward segment's in-trace `[grad_sq,
    nonfinite]` tap over its leaf-edge cotangents (a device f32[2]
    array — nothing is materialized here). Taps accumulate per step
    and ride the next `submit()`'s bundle; a backward-only loop
    publishes them via `flush()`."""
    if not _ENABLED:
        return
    if len(_STEP_TAPS) < _TAP_CAP:
        _STEP_TAPS.append(tap)


def submit(packed, names: Sequence[str], groups: Sequence[str],
           loss=None, lr: Optional[float] = None,
           source: str = "optimizer") -> None:
    """Hand over one step's packed stats bundle (a DEVICE array in the
    pack_stats layout). Publishes the PREVIOUS step's pending bundle
    first — its reductions completed during that step's device work,
    so the pull observes finished arrays instead of blocking the loop
    — then parks this step's bundle (plus any accumulated backward
    taps and the loss scalar) until the next submit/flush. No device
    op is dispatched here: the bundle components are held as the
    executable outputs they already are."""
    global _PENDING, _STEP
    if not _ENABLED:
        return
    prev, _PENDING = _PENDING, None
    if prev is not None:
        _publish(prev)
    taps = _STEP_TAPS[:]
    _STEP_TAPS.clear()
    if loss is not None and hasattr(loss, "_data"):
        loss = loss._data
    _STEP += 1
    _PENDING = {
        "packed": packed, "taps": taps, "loss": loss,
        "names": tuple(names), "groups": tuple(groups), "lr": lr,
        "step": _STEP, "source": source,
    }


def flush() -> Optional[dict]:
    """Publish the pending bundle (and any backward taps that no
    optimizer submit has claimed) NOW — the explicit completion edge
    for the end of training and tests. Returns the last published
    record."""
    global _PENDING, _STEP
    if _PENDING is not None:
        pending, _PENDING = _PENDING, None
        _publish(pending)
    if _STEP_TAPS and _ENABLED:
        taps = _STEP_TAPS[:]
        _STEP_TAPS.clear()
        _STEP += 1
        _publish({
            "packed": None, "taps": taps, "loss": None,
            "names": (), "groups": (), "lr": None, "step": _STEP,
            "source": "backward",
        })
    return _LAST


# ---------------------------------------------------------------------------
# publish: the one host pull, gauge/counter recording, and the sentinel
# ---------------------------------------------------------------------------
def _publish(p: dict) -> dict:
    global _PULLS, _LAST
    sp = _t.span("numerics.check", step=p["step"], source=p["source"])
    with sp:
        # THE async pull: one materialization event per published step
        # — the bundle's component arrays (the packed stats, the
        # per-segment backward taps, the loss scalar) are executable
        # outputs whose device work completed a step ago, so each
        # np.asarray is a ready-buffer copy, never a stall, and the
        # count is O(1) per step, never per-tensor (graftlint
        # host-sync: baselined, pulls() is the pinned budget)
        host = (np.asarray(p["packed"], dtype=np.float32)
                if p["packed"] is not None else None)
        taps = ([np.asarray(t, dtype=np.float32) for t in p["taps"]]
                if p["taps"] else None)
        loss_val = (float(np.asarray(p["loss"]).reshape(-1)[0])
                    if p["loss"] is not None else None)
        _PULLS += 1
        rec = _parse(p, host, taps, loss_val)
        _record(rec)
        reasons = _sentinel(rec)
    if reasons:
        _fire(reasons, rec,
              trace_id=getattr(sp, "trace_id", None),
              span_id=getattr(sp, "span_id", None))
    _LAST = rec
    return rec


def _parse(p: dict, host, taps, loss_val) -> dict:
    P = len(p["names"]) if host is not None else 0
    gsq = host[:P] if host is not None else ()
    gnf = host[P:2 * P] if host is not None else ()
    param_sq = delta_sq = param_nf = None
    if P:
        param_sq, delta_sq, param_nf = (float(host[2 * P]),
                                        float(host[2 * P + 1]),
                                        float(host[2 * P + 2]))

    per_param = [(name, float(math.sqrt(s)) if s >= 0.0 else float("nan"),
                  int(n))
                 for name, s, n in zip(p["names"], gsq, gnf)]
    grad_nf = int(np.sum(gnf)) if P else 0
    if P:
        total_sq = float(np.sum(gsq))
        grad_norm = (math.sqrt(total_sq) if total_sq >= 0.0
                     and math.isfinite(total_sq) else float("nan"))
    else:
        grad_norm = None
    by_group: Dict[str, float] = {}
    for g, s in zip(p["groups"], gsq):
        by_group[g] = by_group.get(g, 0.0) + float(s)
    group_norms = {g: (math.sqrt(s) if s >= 0.0 and math.isfinite(s)
                       else float("nan"))
                   for g, s in by_group.items()}
    backward = None
    if taps:
        bsq = float(sum(t[0] for t in taps))
        backward = {
            "grad_norm": (math.sqrt(bsq) if bsq >= 0.0
                          and math.isfinite(bsq) else float("nan")),
            "nonfinite": int(sum(t[1] for t in taps)),
            "segments": len(taps),
        }
        if grad_norm is None:
            grad_norm = backward["grad_norm"]
            grad_nf = backward["nonfinite"]
    first_nf = next((name for name, _n, c in per_param if c), None)
    param_norm = (math.sqrt(param_sq) if param_sq is not None
                  and param_sq >= 0.0 and math.isfinite(param_sq)
                  else None)
    update_ratio = None
    if (param_norm and delta_sq is not None and delta_sq >= 0.0
            and math.isfinite(delta_sq)):
        update_ratio = math.sqrt(delta_sq) / param_norm
    return {
        "step": p["step"], "source": p["source"],
        "grad_norm": grad_norm, "group_norms": group_norms,
        "per_param": per_param, "first_nonfinite_param": first_nf,
        "param_norm": param_norm, "update_ratio": update_ratio,
        "nonfinite": {
            "grad": grad_nf,
            "param": int(param_nf) if param_nf is not None else 0,
            "loss": int(loss_val is not None
                        and not math.isfinite(loss_val)),
        },
        "loss": loss_val, "lr": p["lr"], "backward": backward,
    }


def _record(rec: dict) -> None:
    if not _m._ENABLED:
        return
    m = _metrics()
    if rec["grad_norm"] is not None:
        m["grad_norm"].labels(group="all").set(rec["grad_norm"])
    for g, v in rec["group_norms"].items():
        m["grad_norm"].labels(group=g).set(v)
    if rec["param_norm"] is not None:
        m["param_norm"].set(rec["param_norm"])
    if rec["update_ratio"] is not None:
        m["update_ratio"].set(rec["update_ratio"])
    nf = rec["nonfinite"]
    for where in ("grad", "param", "loss"):
        if nf[where]:
            m["nonfinite"].labels(where=where).inc(nf[where])


def _sentinel(rec: dict) -> List[str]:
    """Divergence decision for one published record; returns the
    (possibly empty) reason list and maintains the windows, histories
    and the one-bundle-per-episode latch."""
    global _DIVERGED
    cfg = _CFG or NumericsConfig()
    reasons = []
    nf = rec["nonfinite"]
    if nf["grad"] or nf["param"] or nf["loss"]:
        reasons.append("nonfinite")
    gn = rec["grad_norm"]
    clean_norm = gn is not None and math.isfinite(gn)
    if (clean_norm and not reasons
            and len(_GRAD_WINDOW) >= cfg.min_window):
        med = sorted(_GRAD_WINDOW)[len(_GRAD_WINDOW) // 2]
        if med > 0.0 and gn > cfg.spike_factor * med:
            reasons.append("grad_spike")
    if clean_norm:
        # every FINITE norm enters the window — including a spiking
        # one. A sustained legitimate regime change (lr/schedule jump)
        # then raises the median within one window length, the spike
        # stops firing, and the next clean publish re-arms the latch;
        # were spiked norms excluded, the stale median would hold
        # grad_spike (and the latch) forever and a later REAL NaN
        # event could never fire its bundle (review finding, pinned
        # by test_sustained_regime_change_releases_latch). A single
        # transient spike barely moves a maxlen-window median.
        _GRAD_WINDOW.append(gn)
    if rec["loss"] is not None:
        _LOSS_HISTORY.append(rec["loss"])
    if rec["lr"] is not None:
        _LR_HISTORY.append(rec["lr"])
    if reasons:
        if _DIVERGED:
            return []           # same episode: already reported
        _DIVERGED = True
        return reasons
    _DIVERGED = False           # clean step re-arms the latch
    return []


def _fire(reasons: List[str], rec: dict, trace_id=None,
          span_id=None) -> None:
    offenders = sorted(rec.get("per_param") or [],
                       key=lambda t: (-t[2], -(t[1] if math.isfinite(t[1])
                                               else float("inf"))))
    detail = {
        "step": rec["step"], "source": rec["source"],
        "reasons": reasons,
        "first_nonfinite_param": rec.get("first_nonfinite_param"),
        "grad_norm": rec.get("grad_norm"),
        "grad_norm_window": [round(v, 6) for v in _GRAD_WINDOW],
        "per_param": offenders[:16],
        "nonfinite": rec.get("nonfinite"),
        "loss": rec.get("loss"), "lr": rec.get("lr"),
        "loss_history": list(_LOSS_HISTORY),
        "lr_history": list(_LR_HISTORY),
        "loss_scale_history": list(_SCALE_HISTORY),
        "backward": rec.get("backward"),
    }
    if trace_id is not None:
        detail["trace_id"] = trace_id
        detail["span_id"] = span_id
    _fl.trigger("numerics_divergence", detail=detail)
    if _t.enabled():
        # structured divergence event INTO the trace ring: the fleet
        # agent ships ring events, so this is how a divergence reaches
        # the aggregator-hosted supervisor (resilience.supervisor)
        # with enough attribution to pick a remediation — the flight
        # bundle above stays on the diverging process's disk
        import time as _time
        _t.add_event("numerics.divergence",
                     _time.perf_counter() * 1e6, 0.0, args={
            "step": rec["step"], "source": rec["source"],
            "reasons": list(reasons),
            "first_nonfinite_param": rec.get("first_nonfinite_param"),
            "grad_norm": rec.get("grad_norm"),
            "loss_scale": (rec.get("nonfinite") or {}).get("loss_scale"),
        })


# ---------------------------------------------------------------------------
# AMP hooks (called by paddle_tpu.amp.GradScaler)
# ---------------------------------------------------------------------------
def note_loss_scale(scale: float, decreased: bool = False) -> None:
    """One dynamic-loss-scale reading from `GradScaler.update` — feeds
    the scale history that rides divergence bundles, and a DECREASE
    down to the configured floor fires the sentinel (a collapsed scale
    means the run cannot find a finite scale: divergence, not routine
    adjustment)."""
    global _DIVERGED
    if not _ENABLED:
        return
    cfg = _CFG or NumericsConfig()
    _SCALE_HISTORY.append(float(scale))
    if decreased and scale <= cfg.loss_scale_floor and not _DIVERGED:
        _DIVERGED = True
        _fire(["loss_scale_floor"], {
            "step": _STEP, "source": "amp", "per_param": [],
            "first_nonfinite_param": None, "grad_norm": None,
            "nonfinite": {"grad": 0, "param": 0, "loss": 0,
                          "loss_scale": float(scale)},
            "loss": None, "lr": None, "backward": None,
        })


def note_found_inf() -> None:
    """An AMP step skipped on found_inf: the optimizer never ran, so
    no packed bundle carries these grads — count the event (1, not an
    element count) onto the grad nonfinite counter. Deliberately NOT
    latched as divergence: a skipped step is dynamic loss scaling
    doing its job; the sentinel fires on the scale FLOOR instead.
    The skipped step's backward taps are DISCARDED for the same
    reason — left in place, the next clean step's submit would bundle
    their nonfinite counts and fire a false divergence (review
    finding, pinned by test_skipped_step_taps_do_not_leak)."""
    if not _ENABLED:
        return
    _STEP_TAPS.clear()
    if _m._ENABLED:
        _metrics()["nonfinite"].labels(where="grad").inc()
