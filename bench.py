"""Benchmark: training throughput on one chip.

Default (driver contract): prints ONE JSON line for the tracked headline
config (GPT-2 small causal-LM training):
  {"metric": ..., "value": N, "unit": ..., "vs_baseline": N}

More configs (BASELINE.md configs 1-4 single-chip proxies) run with
  python bench.py --config gpt1p3b|resnet50|bert   (one JSON line each)
  python bench.py --all                            (one line per config)
bench.py measures on a TPU and fails without one; --cpu-smoke runs the
toy CPU shapes as a control-flow check and prints no device metric.

The reference publishes no absolute numbers (BASELINE.md); the recorded
north star is >=45% MFU on GPT-class training, so vs_baseline = MFU/0.45.
Every config drives the framework's intended perf path:
paddle_tpu.jit.TrainStep (fwd+bwd+update fused into a single
donated-buffer XLA executable) with bf16 autocast.
"""
from __future__ import annotations

import argparse
import json
import os
import time

import numpy as np

# set by main() under --cpu-smoke: the run checks control flow on the
# CPU and its utilizations are not numbers (see _device_peak)
_CPU_SMOKE = False


# per-chip peak tables live in observability.perf, keyed by device_kind.
# Imported lazily: no paddle_tpu import may happen at module scope
# (the --window-server re-points sys.path first).
def _device_peak(device, table_name) -> float:
    """The device's entry in a perf peak table. A device the table does
    not know is an error, not a default — except under --cpu-smoke,
    where every figure derived from a peak becomes NaN."""
    from paddle_tpu.observability import perf
    if _CPU_SMOKE:
        return float("nan")
    peak = perf.lookup(device, getattr(perf, table_name))
    if peak is None:
        raise RuntimeError(
            f"no {table_name} entry for device_kind "
            f"{getattr(device, 'device_kind', None)!r}: add it to "
            "paddle_tpu/observability/perf.py with its source")
    return peak


def peak_flops(device) -> float:
    return _device_peak(device, "PEAK_BF16_FLOPS")


def _request_latency_percentiles():
    """Per-request TTFT/TPOT tail latency (ms) from the observability
    registry — serving benches attach this so the perf trajectory
    captures tails, not just throughput. None when observability is
    off (--no-obs) or no request finished in this window. Cumulative
    over the config's obs window (includes the warmup pass — the
    steady-state tail is what serving cares about anyway)."""
    from paddle_tpu import observability as obs
    if not obs.enabled():
        return None
    hists = obs.summary().get("histograms", {})
    out = {}
    for key, name in (("ttft", "paddle_tpu_request_ttft_seconds"),
                      ("tpot", "paddle_tpu_request_tpot_seconds")):
        entry = hists.get(name)
        if not entry:
            continue
        out[f"{key}_p50_ms"] = round(entry["p50"] * 1e3, 3)
        out[f"{key}_p95_ms"] = round(entry["p95"] * 1e3, 3)
        out[f"{key}_n"] = entry["count"]
    return out or None


def _require_pallas(batch, seq, heads, head_dim, kv_heads=None):
    # the flagship Pallas kernel must actually engage — fail loudly if
    # it silently fell back (VERDICT r1 weak item 3)
    from paddle_tpu.kernels.pallas.flash_attention import attention_path
    kv_heads = kv_heads or heads
    path, why = attention_path((batch, seq, heads, head_dim),
                               (batch, seq, kv_heads, head_dim))
    if path != "pallas":
        raise RuntimeError(
            f"flash attention fell back to {path!r} ({why}) on TPU — "
            "refusing to bench the non-flagship path")
    return path


def _timed_steps(step, args, steps, windows=2):
    """Compile, settle, then time `steps` calls of the TrainStep.

    Batches are staged on-device once up front: the bench measures the
    train step, not host->device transfer of the same repeated batch (a
    real input pipeline overlaps staging with compute). Best of
    `windows` timing windows: the host's cores are shared, and the
    minimum is the steady-state reading."""
    import jax
    args = tuple(jax.device_put(a) for a in args)
    step(*args)
    loss = step(*args)
    float(loss.numpy())
    best = float("inf")
    for _ in range(windows):
        t0 = time.perf_counter()
        for _ in range(steps):
            loss = step(*args)
        float(loss.numpy())  # block on the device
        best = min(best, time.perf_counter() - t0)
    return best, loss


def bench_gpt(name, cfg_kw, batch, seq, steps, on_tpu, opt_kw=None):
    import jax
    from paddle_tpu import amp
    from paddle_tpu.models import GPTForCausalLM, GPTPretrainingCriterion
    from paddle_tpu.models.gpt import GPTConfig, num_params
    from paddle_tpu.jit import TrainStep
    from paddle_tpu.optimizer import AdamW

    dev = jax.devices()[0]
    cfg = GPTConfig(**cfg_kw)
    if on_tpu:
        path = _require_pallas(batch, seq, cfg.num_heads, cfg.head_dim)
    else:
        path = "sdpa"

    model = GPTForCausalLM(cfg)
    model.train()
    opt = AdamW(learning_rate=1e-4, parameters=model.parameters(),
                weight_decay=0.01, **(opt_kw or {}))
    crit = GPTPretrainingCriterion()

    def loss_fn(m, ids, labels):
        with amp.auto_cast(enable=True, level="O1", dtype="bfloat16"):
            logits = m(ids)
        return crit(logits, labels)

    step = TrainStep(model, opt, loss_fn)
    rng = np.random.default_rng(0)
    ids = rng.integers(0, cfg.vocab_size, (batch, seq)).astype(np.int32)
    labels = rng.integers(0, cfg.vocab_size, (batch, seq)).astype(np.int32)
    dt, loss = _timed_steps(step, (ids, labels), steps)

    tokens_per_sec = batch * seq * steps / dt
    n = num_params(cfg)
    # 6ND fwd+bwd FLOPs/token; remat re-runs the block forwards in
    # backward, so the MODEL flops stay 6ND (recompute overhead shows up
    # as lower achieved MFU, not inflated work)
    mfu = 6.0 * n * tokens_per_sec / peak_flops(dev)
    return {
        "metric": f"{name}_train_tokens_per_sec_per_chip",
        "value": round(tokens_per_sec, 1),
        "unit": "tokens/s",
        "vs_baseline": round(mfu / 0.45, 4),
        "extra": {
            "mfu": round(mfu, 4), "params": n,
            "device": str(getattr(dev, "device_kind", dev.platform)),
            "batch": batch, "seq": seq, "steps": steps,
            "attn_path": path, "recompute": cfg.recompute,
            "final_loss": round(float(loss.numpy()), 4),
        },
    }


def bench_gpt2_small(on_tpu):
    if on_tpu:
        return bench_gpt(
            "gpt2_small",
            dict(vocab_size=50304, hidden_size=768, num_layers=12,
                 num_heads=12, max_position_embeddings=1024,
                 hidden_dropout_prob=0.0, attention_dropout_prob=0.0,
                 use_flash_attention=True),
            batch=16, seq=1024, steps=20, on_tpu=True)
    return bench_gpt(  # CPU smoke shape
        "gpt2_small",
        dict(vocab_size=1024, hidden_size=128, num_layers=2, num_heads=4,
             max_position_embeddings=256, hidden_dropout_prob=0.0,
             attention_dropout_prob=0.0),
        batch=2, seq=64, steps=3, on_tpu=False)


def bench_gpt_1p3b(on_tpu):
    """GPT-3 XL shape (~1.3B) @ seq 2048 with per-block remat and bf16
    AdamW moments — the single-chip proxy for BASELINE configs 3-4
    (VERDICT r2 next-step 3: exercises the FA2 backward's memory claim
    at scale)."""
    if on_tpu:
        kw = dict(vocab_size=50304, hidden_size=2048, num_layers=24,
                  num_heads=16, max_position_embeddings=2048,
                  hidden_dropout_prob=0.0, attention_dropout_prob=0.0,
                  use_flash_attention=True, recompute=True,
                  # measured round-5 sweep (tools/sweep_1p3b.sh): remat
                  # every 3rd block only — spare HBM buys back 1/3 of
                  # the recompute FLOPs (+2.7% same-session); full-remat
                  # "dots" policies OOM at b4, and no-remat at smaller
                  # batch loses more to XLA spill than remat costs
                  recompute_interval=3)
        return bench_gpt("gpt_1p3b", kw, batch=4, seq=2048, steps=5,
                         on_tpu=True,
                         opt_kw=dict(moment_dtype="bfloat16"))
    kw = dict(vocab_size=1024, hidden_size=256, num_layers=4, num_heads=4,
              max_position_embeddings=256, hidden_dropout_prob=0.0,
              attention_dropout_prob=0.0, recompute=True)
    return bench_gpt("gpt_1p3b", kw, batch=2, seq=128, steps=2,
                     on_tpu=False, opt_kw=dict(moment_dtype="bfloat16"))


def bench_resnet50(on_tpu):
    """ResNet-50 ImageNet-shape training step (BASELINE config 1)."""
    import jax
    from paddle_tpu import amp
    from paddle_tpu.jit import TrainStep
    from paddle_tpu.optimizer import Momentum
    from paddle_tpu.vision.models import resnet50
    import paddle_tpu.ops as ops

    dev = jax.devices()[0]
    batch, hw, steps = (256, 224, 10) if on_tpu else (4, 32, 2)
    # one-pass BN statistics (documented precision caveat on the flag;
    # ImageNet-normalized activations are far inside its exact range)
    import paddle_tpu as _pt
    _pt.set_flags({"FLAGS_fast_bn_stats": True})
    # NHWC end-to-end: channels stay in the lane (minor) dimension, the
    # layout the TPU vector/matrix units want (VERDICT r3 next-3);
    # space-to-depth stem turns the 3-channel 7x7/s2 conv into an
    # identical 12-channel 4x4/s1 conv (VERDICT r4 next-4)
    model = resnet50(data_format="NHWC", space_to_depth_stem=True)
    model.train()
    opt = Momentum(learning_rate=0.1, momentum=0.9,
                   parameters=model.parameters(), weight_decay=1e-4)

    def loss_fn(m, x, y):
        with amp.auto_cast(enable=True, level="O1", dtype="bfloat16"):
            logits = m(x)
        return ops.cross_entropy(logits, y)

    step = TrainStep(model, opt, loss_fn)
    rng = np.random.default_rng(0)
    x = rng.standard_normal((batch, hw, hw, 3)).astype(np.float32)
    y = rng.integers(0, 1000, (batch,)).astype(np.int32)
    dt, loss = _timed_steps(step, (x, y), steps)

    imgs_per_sec = batch * steps / dt
    # ResNet-50 fwd ~4.09 GFLOPs/image @224 (2*MACs); train ~3x fwd
    train_flops_img = 3.0 * 4.09e9 * (hw / 224.0) ** 2
    mfu = train_flops_img * imgs_per_sec / peak_flops(dev)

    # ResNet training on TPU is HBM-bound, not MXU-bound (fwd accesses
    # ~27.5 GB at bs256 vs ~10.5 ms of matmul work
    # analysis), so vs_baseline is measured against the MEMORY roofline:
    # bytes from the compiled forward's cost analysis, backward+update
    # modeled as 2x the forward's traffic (VERDICT r3 next-3).
    from paddle_tpu.jit import _collect_params, _functional_params
    import paddle_tpu.autograd.tape as _tape
    _, pts_, _, bts_ = _collect_params(model)
    tensors = pts_ + bts_

    def fwd(params, xx):
        with _tape.no_grad(), _functional_params(tensors, params):
            with amp.auto_cast(enable=True, level="O1",
                               dtype="bfloat16"):
                return model(xx)._data

    from paddle_tpu.observability import perf as _perf
    cm = _perf.read_cost_model(
        jax.jit(fwd).lower([t._data for t in tensors], x).compile())
    fwd_bytes = cm.bytes_accessed if cm else 0.0
    roofline_img_s = hbm_bw(dev) / (3.0 * fwd_bytes / batch) \
        if fwd_bytes else float("nan")
    return {
        "metric": "resnet50_train_images_per_sec_per_chip",
        "value": round(imgs_per_sec, 1),
        "unit": "images/s",
        "vs_baseline": round(imgs_per_sec / roofline_img_s, 4),
        "extra": {
            "mfu": round(mfu, 4),
            "device": str(getattr(dev, "device_kind", dev.platform)),
            "batch": batch, "image": hw, "steps": steps,
            "fwd_bytes_accessed_gb": round(fwd_bytes / 1e9, 2),
            "memory_roofline_imgs_per_sec": round(roofline_img_s, 1),
            "final_loss": round(float(loss.numpy()), 4),
        },
    }


def bench_bert_base(on_tpu):
    """BERT-base MLM with fused flash attention + layer norm
    (BASELINE config 2)."""
    import jax
    from paddle_tpu import amp
    from paddle_tpu.jit import TrainStep
    from paddle_tpu.models.bert import BertConfig, BertForMaskedLM
    from paddle_tpu.optimizer import AdamW

    dev = jax.devices()[0]
    if on_tpu:
        cfg = BertConfig(hidden_dropout_prob=0.0, attention_dropout_prob=0.0)
        batch, seq, steps = 32, 512, 10
        path = _require_pallas(batch, seq, cfg.num_heads,
                               cfg.hidden_size // cfg.num_heads)
    else:
        cfg = BertConfig(vocab_size=1024, hidden_size=128, num_layers=2,
                         num_heads=4, intermediate_size=256,
                         max_position_embeddings=128,
                         hidden_dropout_prob=0.0, attention_dropout_prob=0.0)
        batch, seq, steps, path = 2, 64, 2, "sdpa"

    model = BertForMaskedLM(cfg)
    model.train()
    opt = AdamW(learning_rate=1e-4, parameters=model.parameters())

    def loss_fn(m, ids, labels):
        with amp.auto_cast(enable=True, level="O1", dtype="bfloat16"):
            loss, _ = m(ids, labels=labels)
        return loss

    step = TrainStep(model, opt, loss_fn)
    rng = np.random.default_rng(0)
    ids = rng.integers(0, cfg.vocab_size, (batch, seq)).astype(np.int32)
    # MLM: predict on ~15% of positions, ignore the rest
    labels = np.where(rng.random((batch, seq)) < 0.15, ids, -100).astype(
        np.int32)
    dt, loss = _timed_steps(step, (ids, labels), steps)

    tokens_per_sec = batch * seq * steps / dt
    n = sum(int(np.prod(p.shape)) for p in model.parameters())
    mfu = 6.0 * n * tokens_per_sec / peak_flops(dev)
    return {
        "metric": "bert_base_mlm_train_tokens_per_sec_per_chip",
        "value": round(tokens_per_sec, 1),
        "unit": "tokens/s",
        "vs_baseline": round(mfu / 0.45, 4),
        "extra": {
            "mfu": round(mfu, 4), "params": n,
            "device": str(getattr(dev, "device_kind", dev.platform)),
            "batch": batch, "seq": seq, "steps": steps,
            "attn_path": path,
            "final_loss": round(float(loss.numpy()), 4),
        },
    }


def _dispatch_gap_summary():
    """Gap-histogram summary for the BENCH line: count, total, p50/p95
    and the top op types by attributed gap seconds — the decomposition
    of the eager-over-TrainStep ratio into named host gaps. None when
    observability is off (--no-obs) or no backward ran."""
    from paddle_tpu import observability as obs
    from paddle_tpu.observability import metrics as _m
    if not obs.enabled():
        return None
    snap = obs.snapshot()
    rec = snap.get("paddle_tpu_dispatch_gap_seconds")
    val = (rec or {}).get("series", {}).get(())
    if not val or not val["count"]:
        return None
    out = {"count": val["count"], "total_ms": round(val["sum"] * 1e3, 3)}
    for name, q in (("p50_us", 0.5), ("p95_us", 0.95)):
        est = _m.quantile_from_buckets(rec["buckets"], val["buckets"],
                                       q, lo=val["min"], hi=val["max"])
        if est is not None:
            out[name] = round(est * 1e6, 1)
    ops = snap.get("paddle_tpu_dispatch_gap_op_seconds_total", {})
    top = sorted(ops.get("series", {}).items(), key=lambda kv: -kv[1])
    out["top_ops_ms"] = {op: round(v * 1e3, 3)
                         for (op,), v in top[:5] if v}
    return out


def _dispatch_batch_summary():
    """paddle_tpu_dispatch_batch_size summary for the BENCH line:
    dispatch calls, total nodes, mean/max run length. None when the
    batched engine recorded nothing."""
    from paddle_tpu import observability as obs
    if not obs.enabled():
        return None
    rec = obs.snapshot().get("paddle_tpu_dispatch_batch_size")
    val = (rec or {}).get("series", {}).get(())
    if not val or not val["count"]:
        return None
    return {"dispatches": val["count"], "nodes": val["sum"],
            "mean": round(val["sum"] / val["count"], 2),
            "max": val["max"]}


def _graph_cache_summary():
    """paddle_tpu_backward_graph_cache_total counts for the BENCH
    line: whole-graph trace cache hits/misses/bypasses. None when the
    whole-graph engine recorded nothing."""
    from paddle_tpu import observability as obs
    if not obs.enabled():
        return None
    rec = obs.snapshot().get("paddle_tpu_backward_graph_cache_total")
    out = {k[0]: int(v)
           for k, v in (rec or {}).get("series", {}).items() if v}
    return out or None


def bench_dispatch(on_tpu):
    """Eager dispatch latency with the backward dispatch-mode A/B
    (ISSUE 10/13): whole_graph (fan-in-crossing fused runs + the
    whole-graph trace cache, the default) vs batched (PR 10
    single-consumer chains) vs per_node (the legacy walker) vs the
    compiled TrainStep — interleaved best-of-N windows in ONE session,
    so the `eager_over_trainstep <= 1.2` claim and the inter-mode
    deltas are self-verifying. Windows stop early once the ordering is
    decisive (see below). A dedicated attribution pass per mode
    captures the dispatch-gap summary (count, total, p50/p95, top ops
    — the NAMED host gaps), the fused-run length histogram, and — for
    whole_graph — the graph-cache hit/miss counts; each mode lands as
    its own record in perf_ledger.jsonl (tools/perf_ledger.py --check
    flags a dispatch-gap regression per (config, mode))."""
    import jax
    import paddle_tpu as pt
    from paddle_tpu import observability as obs
    from paddle_tpu.autograd import dispatch_queue as dq
    from paddle_tpu.jit import TrainStep
    from paddle_tpu.observability import perf
    from paddle_tpu.optimizer import SGD
    from paddle_tpu.ops.registry import exec_cache_size

    dev = jax.devices()[0]
    lin1 = pt.nn.Linear(256, 256)
    lin2 = pt.nn.Linear(256, 256)
    x = pt.to_tensor(np.random.default_rng(0).standard_normal(
        (32, 256)).astype(np.float32))
    params = lin1.parameters() + lin2.parameters()
    opt = SGD(learning_rate=1e-3, parameters=params)
    steps = 50 if on_tpu else 20
    # this CPU box swings 3x window-to-window (shared host); best-of
    # needs more samples than the quiet-chip default to converge
    windows = 3 if on_tpu else 8

    def eager_step():
        h = pt.ops.tanh(lin1(x))
        loss = (lin2(h) ** 2).mean()
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    def run_eager(mode, n):
        with dq.backward_dispatch_mode(mode):
            loss = None
            for _ in range(n):
                loss = eager_step()
            float(loss.numpy())

    run_eager("per_node", 2)      # warm per-op executables
    run_eager("batched", 2)       # warm the fused chain executable
    run_eager("whole_graph", 2)   # warm the whole-graph executable

    # the TrainStep variant gets ITS OWN modules/optimizer: the jitted
    # step donates its state, and the interleaved windows would feed
    # the eager path deleted buffers if they shared parameters
    lin3 = pt.nn.Linear(256, 256)
    lin4 = pt.nn.Linear(256, 256)

    def loss_fn(m, x):
        h = pt.ops.tanh(lin3(x))
        return (lin4(h) ** 2).mean()

    class _Pair(pt.nn.Layer):
        def __init__(self):
            super().__init__()
            self.a, self.b = lin3, lin4

    step = TrainStep(_Pair(),
                     SGD(learning_rate=1e-3,
                         parameters=lin3.parameters() + lin4.parameters()),
                     lambda m, x: loss_fn(m, x))
    step(x)
    float(step(x).numpy())

    def run_train(n):
        loss = None
        for _ in range(n):
            loss = step(x)
        float(loss.numpy())

    # interleaved best-of-N windows: every variant samples every load
    # phase of the shared box, min-reduce de-biases the contention.
    # Observability is OFF for the timed windows — per_node records
    # one gap per grad node and TrainStep records nothing, so leaving
    # it on would bias exactly the ratios this bench pins.
    # Early exit (the PR 7 deflake pattern): noise only ever INFLATES
    # a window, so once a full window improves no minimum AND the
    # mins already show the claimed orderings (both fused modes at or
    # under per_node, whole_graph within the <=1.2 TrainStep target
    # — whole_graph vs batched is NOT a claim: on this pure-chain
    # model both dispatch the identical fused call and whole_graph
    # pays its O(nodes) planning, so their ordering is noise),
    # further windows can only confirm — stop instead of always
    # burning all 8 on this noisy box. A window that still shows a
    # flipped ordering keeps sampling (it is only ever noise).
    obs_was_on = obs.enabled()
    obs.disable()
    best = {"train": float("inf"), "per_node": float("inf"),
            "batched": float("inf"), "whole_graph": float("inf")}
    windows_run = 0
    try:
        for w in range(windows):
            improved = False
            for variant in ("train", "per_node", "batched",
                            "whole_graph"):
                t0 = time.perf_counter()
                if variant == "train":
                    run_train(steps)
                else:
                    run_eager(variant, steps)
                dt = time.perf_counter() - t0
                if dt < best[variant]:
                    best[variant] = dt
                    improved = True
            windows_run = w + 1
            if (w >= 2 and not improved
                    and best["whole_graph"] <= best["per_node"]
                    and best["batched"] <= best["per_node"]
                    and best["whole_graph"] <= 1.2 * best["train"]):
                break               # decisively ordered — stop early
    finally:
        if obs_was_on:
            obs.enable()

    # attribution pass per eager mode: a fresh observability window so
    # each mode's gap/batch series and per-family ledger record are
    # its own (separate from the uninstrumented timed windows above)
    gap_by_mode = {}
    ledger_modes = []
    for mode in ("per_node", "batched", "whole_graph"):
        obs.reset()
        run_eager(mode, steps)
        summ = _dispatch_gap_summary() or {"count": 0, "total_ms": 0.0}
        if mode != "per_node":
            batch = _dispatch_batch_summary()
            if batch:
                summ["batch_size"] = batch
        rec = {
            "mode": mode,
            "families": perf.family_records(),
            "dispatch_gap": None,       # filled below
        }
        if mode == "whole_graph":
            gc = _graph_cache_summary()
            if gc:
                summ["graph_cache"] = gc
                rec["graph_cache"] = gc
        gap_by_mode[mode] = summ
        total_ms = summ.get("total_ms", 0.0) or 0.0
        rec["dispatch_gap"] = {
            "steps": steps,
            "count": summ.get("count", 0),
            "total_ms": round(total_ms, 3),
            "ms_per_step": round(total_ms / steps, 4),
        }
        ledger_modes.append(rec)

    # numerics-plane overhead A/B (ISSUE 15): a fresh 3-layer MLP in
    # whole_graph mode (the TestBackwardFamilyBudget config), plane
    # off vs on, interleaved best-of windows with observability OFF —
    # the enabled plane's real cost is the in-trace reductions + one
    # async pull per step, and that is what the timed loop pays. The
    # grad-norm headline comes from numerics.last() (readable without
    # metrics). Rides the whole_graph ledger record so
    # tools/perf_ledger.py --check baselines the overhead ratio.
    from paddle_tpu.observability import numerics as num
    nlayers = [pt.nn.Linear(256, 256) for _ in range(3)]
    nparams = [p for lyr in nlayers for p in lyr.parameters()]
    nopt = SGD(learning_rate=1e-3, parameters=nparams)

    def num_step():
        h = pt.ops.tanh(nlayers[0](x))
        h = pt.ops.tanh(nlayers[1](h))
        loss = (nlayers[2](h) ** 2).mean()
        loss.backward()
        nopt.step()
        nopt.clear_grad()
        return loss

    def run_numerics(n):
        loss = None
        for _ in range(n):
            loss = num_step()
        float(loss.numpy())

    numerics_payload = None
    steps_n = 160                       # >= 2 sampled steps per window
    obs.disable()
    try:
        with dq.backward_dispatch_mode("whole_graph"):
            run_numerics(3)             # warm the stats-off variants
            num.enable(interval=1)
            run_numerics(3)             # warm the stats-on variants
            num.disable()

            def ab_windows(n_steps, windows, **enable_kw):
                best = {"off": float("inf"), "on": float("inf")}
                for _ in range(windows):
                    num.disable()
                    t0 = time.perf_counter()
                    run_numerics(n_steps)
                    best["off"] = min(best["off"],
                                      time.perf_counter() - t0)
                    num.enable(**enable_kw)
                    t0 = time.perf_counter()
                    run_numerics(n_steps)
                    best["on"] = min(best["on"],
                                     time.perf_counter() - t0)
                num.disable()
                return best

            # headline: the DEFAULT cadence (what numerics.enable()
            # ships); diagnostic: every-step fidelity (interval=1),
            # the honest worst case this CPU box pays for full stats
            best_n = ab_windows(steps_n, 3)
            best_1 = ab_windows(steps, 3, interval=1)
            num.enable(interval=1)
            run_numerics(1)
            rec_n = num.flush()
            num.disable()
        gn = (rec_n or {}).get("grad_norm")
        numerics_payload = {
            "overhead_ratio": round(best_n["on"] / best_n["off"], 4),
            "interval": num.NumericsConfig().interval,
            "overhead_ratio_interval1": round(
                best_1["on"] / best_1["off"], 4),
            "off_steps_per_sec": round(steps_n / best_n["off"], 1),
            "on_steps_per_sec": round(steps_n / best_n["on"], 1),
            "grad_norm": round(gn, 6) if gn is not None else None,
        }
        for rec in ledger_modes:
            if rec["mode"] == "whole_graph":
                rec["numerics"] = numerics_payload
    finally:
        num.disable()
        if obs_was_on:
            obs.enable()

    dt_t, dt_p = best["train"], best["per_node"]
    dt_b, dt_w = best["batched"], best["whole_graph"]
    return {
        "metric": "eager_dispatch_steps_per_sec",
        "value": round(steps / dt_w, 1),
        "unit": "steps/s",
        "vs_baseline": round(dt_t / dt_w, 4),
        "_ledger_modes": ledger_modes,
        "extra": {
            "trainstep_steps_per_sec": round(steps / dt_t, 1),
            "per_node_steps_per_sec": round(steps / dt_p, 1),
            "batched_steps_per_sec": round(steps / dt_b, 1),
            "eager_over_trainstep_time": round(dt_w / dt_t, 2),
            "eager_over_trainstep_batched": round(dt_b / dt_t, 2),
            "eager_over_trainstep_per_node": round(dt_p / dt_t, 2),
            "whole_graph_over_batched_time": round(dt_w / dt_b, 4),
            "batched_over_per_node_time": round(dt_b / dt_p, 4),
            "exec_cache_entries": exec_cache_size(),
            "fused_chain_entries": dq.chain_cache_size(),
            "device": str(getattr(dev, "device_kind", dev.platform)),
            "steps": steps,
            "windows": windows,
            "windows_run": windows_run,
            "dispatch_gap": gap_by_mode,
            "numerics": numerics_payload,
        },
    }


def hbm_bw(device) -> float:
    return _device_peak(device, "HBM_BYTES_PER_SEC")


def bench_decode(on_tpu):
    """LLM serving decode tokens/s (VERDICT r3 missing #1c): greedy
    decode on the 1.3B config through the fused single-executable
    donated-cache scan loop (models/generation.py _build_fused_loop).
    vs_baseline is measured against the HBM roofline — bs-1 decode is
    bandwidth-bound (every step streams all weights + the KV cache), so
    roofline tok/s = b * BW / (param_bytes + b * cache_bytes)."""
    import jax
    from paddle_tpu.models import GPTForCausalLM
    from paddle_tpu.models.generation import generate
    from paddle_tpu.models.gpt import GPTConfig, num_params

    dev = jax.devices()[0]
    if on_tpu:
        kw = dict(vocab_size=50304, hidden_size=2048, num_layers=24,
                  num_heads=16, max_position_embeddings=2048,
                  hidden_dropout_prob=0.0, attention_dropout_prob=0.0)
        prompt_len, n_new, batches = 128, 128, (1, 8)
    else:
        kw = dict(vocab_size=1024, hidden_size=128, num_layers=2,
                  num_heads=4, max_position_embeddings=256,
                  hidden_dropout_prob=0.0, attention_dropout_prob=0.0)
        prompt_len, n_new, batches = 8, 8, (1, 2)
    cfg = GPTConfig(**kw)
    model = GPTForCausalLM(cfg).bfloat16()
    model.eval()
    n = num_params(cfg)
    param_bytes = 2.0 * n
    bw = hbm_bw(dev)

    rng = np.random.default_rng(0)
    results = {}
    for b in batches:
        ids = rng.integers(0, cfg.vocab_size,
                           (b, prompt_len)).astype(np.int32)
        import paddle_tpu as pt
        tids = pt.to_tensor(ids)
        # warmup compiles prefill + the fused decode loop; generate()'s
        # 128-wide cache bucketing makes every call below share the SAME
        # executables (prompt+1 .. prompt+n_new all land in one bucket)
        generate(model, tids, max_new_tokens=n_new).numpy()
        generate(model, tids, max_new_tokens=1).numpy()

        def timed(n, salt):
            # every timed call carries fresh prompt content, so no
            # layer can answer it from an identical earlier call;
            # .numpy() ends the timed region with the tokens on the
            # host, where a caller of generate() wants them
            ids2 = ids.copy()
            ids2[:, 0] = (ids2[:, 0] + salt) % cfg.vocab_size
            t2 = pt.to_tensor(ids2)
            t0 = time.perf_counter()
            generate(model, t2, max_new_tokens=n).numpy()
            return time.perf_counter() - t0

        # min-of-3 on each leg: the host's cores are shared, and a
        # stall inside either leg otherwise corrupts the prefill
        # subtraction
        t_prefill = min(timed(1, s) for s in (1, 2, 3))
        t_full = min(timed(n_new, s) for s in (4, 5, 6))
        dt = max(t_full - t_prefill, 1e-9)
        tok_s = b * (n_new - 1) / dt
        # per-step HBM traffic: all weights once + this row's KV cache
        cache_bytes = (2 * cfg.num_layers * cfg.num_heads * cfg.head_dim
                       * (prompt_len + n_new) * 2.0)
        roofline = b * bw / (param_bytes + b * cache_bytes)
        results[b] = (tok_s, roofline)

    bmain = batches[-1]
    tok_s, roofline = results[bmain]
    return {
        "metric": "gpt_1p3b_decode_tokens_per_sec",
        "value": round(tok_s, 1),
        "unit": "tokens/s",
        "vs_baseline": round(tok_s / roofline, 4),
        "extra": {
            "batch": bmain, "prompt_len": prompt_len, "new_tokens": n_new,
            "params": n, "dtype": "bfloat16",
            "device": str(getattr(dev, "device_kind", dev.platform)),
            "roofline_tokens_per_sec": round(roofline, 1),
            **{f"bs{b}_tokens_per_sec": round(r[0], 1)
               for b, r in results.items()},
            **{f"bs{b}_vs_roofline": round(r[0] / r[1], 4)
               for b, r in results.items()},
        },
    }


def _paged_workload(on_tpu):
    """Shared setup for the decode_paged bench AND the --gate window
    server: one engine + one dense baseline over the same mixed-length
    workload at equal cache HBM. Returns closures so callers control
    warmup/timing (the gate interleaves windows across processes)."""
    import jax
    import paddle_tpu as pt
    from paddle_tpu.inference import LLMEngine
    from paddle_tpu.models import GPTForCausalLM
    from paddle_tpu.models.generation import generate
    from paddle_tpu.models.gpt import GPTConfig

    if on_tpu:
        kw = dict(vocab_size=50304, hidden_size=2048, num_layers=24,
                  num_heads=16, max_position_embeddings=2048,
                  hidden_dropout_prob=0.0, attention_dropout_prob=0.0)
        n_req, max_batch, block_size, chunk = 16, 8, 64, 16
        plo, phi, glo, ghi = 64, 192, 64, 160
        quantum = 128
    else:
        kw = dict(vocab_size=1024, hidden_size=128, num_layers=2,
                  num_heads=4, max_position_embeddings=256,
                  hidden_dropout_prob=0.0, attention_dropout_prob=0.0)
        n_req, max_batch, block_size, chunk = 6, 2, 16, 4
        plo, phi, glo, ghi = 8, 24, 8, 24
        quantum = 16
    cfg = GPTConfig(**kw)
    model = GPTForCausalLM(cfg).bfloat16()
    model.eval()
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size, (int(n),)).astype(np.int32)
               for n in rng.integers(plo, phi + 1, n_req)]
    news = rng.integers(glo, ghi + 1, n_req).astype(int)
    kvH, D, L = cfg.num_heads, cfg.head_dim, cfg.num_layers
    itemsize = 2.0

    # ---- dense baseline: static groups of max_batch, padded ----
    order = np.argsort([len(p) + n for p, n in zip(prompts, news)])
    groups = [order[i:i + max_batch]
              for i in range(0, n_req, max_batch)]
    dense_bytes = 0
    for g in groups:
        pmax = max(len(prompts[i]) for i in g)
        tot = max(len(prompts[i]) + int(news[i]) for i in g)
        bucket = min(-(-tot // 128) * 128, cfg.max_position_embeddings)
        dense_bytes = max(dense_bytes,
                          2 * L * len(g) * bucket * kvH * D * itemsize)

    def run_dense():
        total = 0
        for g in groups:
            pmax = max(len(prompts[i]) for i in g)
            ids = np.full((len(g), pmax), 0, np.int32)
            for r, i in enumerate(g):
                ids[r, pmax - len(prompts[i]):] = prompts[i]  # left-pad
            n_new = int(max(news[i] for i in g))
            generate(model, pt.to_tensor(ids),
                     max_new_tokens=n_new).numpy()
            total += int(sum(news[i] for i in g))   # only requested toks
        return total

    # ---- paged engine at the same cache budget ----
    block_bytes = kvH * block_size * D * itemsize * 2 * L
    num_blocks = max(int(dense_bytes // block_bytes), 8) + 1

    # ONE engine across warmup and timing: its compiled prefill/decode
    # executables live on the instance, mirroring how generate() caches
    # its fused loops on the model — both timed runs are compile-free
    # prefix caching OFF: this config isolates paging vs dense padding;
    # the warmup/timed runs repeat identical prompts, which caching
    # would (legitimately) short-circuit — bench that with
    # --config prefix_serving instead
    eng = LLMEngine(model, max_batch=max_batch, num_blocks=num_blocks,
                    block_size=block_size, decode_chunk=chunk,
                    prompt_quantum=quantum,
                    max_model_len=cfg.max_position_embeddings,
                    enable_prefix_caching=False)

    def run_paged():
        start_tokens = eng.stats["decode_tokens"]
        for i, p in enumerate(prompts):
            eng.add_request(i, p, max_new_tokens=int(news[i]))
        done = 0
        while eng.has_unfinished:
            for r in eng.step():
                done += len(r.output_ids)
        return done, dict(eng.stats,
                          decode_tokens=eng.stats["decode_tokens"]
                          - start_tokens)

    return {
        "run_paged": run_paged, "run_dense": run_dense,
        "meta": {
            "requests": n_req, "max_batch": max_batch,
            "cache_budget_gb": round(dense_bytes / 1e9, 3),
            "num_blocks": num_blocks, "block_size": block_size,
            "decode_chunk": chunk,
        },
    }


def bench_decode_paged(on_tpu, windows=2):
    """Continuous-batching serving throughput at EQUAL cache HBM
    (VERDICT r4 next-2): a mixed-length workload through
    inference.LLMEngine (paged pool + admission/preemption) vs the
    dense static-batch generate() path given the SAME cache bytes.
    Dense must pad every sequence to the group max and run each group
    to its longest request; the paged pool shares pages across lengths,
    so more sequences decode per weight-stream pass. The two legs run
    as INTERLEAVED best-of-N windows (paged, dense, paged, dense ...)
    so a load spike on the shared box lands on both sides instead of
    corrupting the ratio — the same convention the --gate prev-rev A/B
    uses."""
    wl = _paged_workload(on_tpu)
    run_paged, run_dense = wl["run_paged"], wl["run_dense"]
    run_paged()            # compile prefill/decode executables
    run_dense()            # compile dense prefill + loop executables
    t_paged = t_dense = float("inf")
    paged_tokens = dense_tokens = 0
    stats = {}
    for _ in range(windows):
        t0 = time.perf_counter()
        ptoks, pstats = run_paged()
        dt = time.perf_counter() - t0
        if dt < t_paged:
            t_paged, paged_tokens, stats = dt, ptoks, pstats
        t0 = time.perf_counter()
        dtoks = run_dense()
        dt = time.perf_counter() - t0
        if dt < t_dense:
            t_dense, dense_tokens = dt, dtoks
    paged_tps = paged_tokens / t_paged
    dense_tps = dense_tokens / t_dense
    return {
        "metric": "gpt_1p3b_paged_serving_tokens_per_sec",
        "value": round(paged_tps, 1),
        "unit": "tokens/s",
        "vs_baseline": round(paged_tps / dense_tps, 4),
        "extra": {
            "dense_tokens_per_sec": round(dense_tps, 1),
            "windows": windows,
            **wl["meta"],
            "engine_stats": stats,
            "request_latency": _request_latency_percentiles(),
        },
    }


def bench_prefix_serving(on_tpu):
    """Automatic prefix caching on the shared-prefix serving workload
    it exists for: every request = one shared few-shot prefix + a short
    per-request tail, driven through LLMEngine with caching ON vs OFF
    at EQUAL cache HBM (same pool, same blocks — retention only parks
    pages the free list wasn't using). Both engines are warmed on the
    workload first (compiles executables; for the caching engine this
    also seeds the index — the honest steady state, since a serving
    process keeps its prefix cache across requests), then timed.
    vs_baseline = cached tokens/s over uncached; extra carries the
    headline prefill-token reduction."""
    import jax
    from paddle_tpu.inference import LLMEngine
    from paddle_tpu.models import GPTForCausalLM
    from paddle_tpu.models.gpt import GPTConfig

    if on_tpu:
        kw = dict(vocab_size=50304, hidden_size=2048, num_layers=24,
                  num_heads=16, max_position_embeddings=2048,
                  hidden_dropout_prob=0.0, attention_dropout_prob=0.0)
        n_req, max_batch, block_size, chunk = 16, 8, 64, 16
        prefix_len, tlo, thi, n_new = 512, 8, 32, 64
        quantum = 128
    else:
        kw = dict(vocab_size=1024, hidden_size=128, num_layers=2,
                  num_heads=4, max_position_embeddings=256,
                  hidden_dropout_prob=0.0, attention_dropout_prob=0.0)
        n_req, max_batch, block_size, chunk = 6, 2, 16, 4
        prefix_len, tlo, thi, n_new = 32, 2, 6, 8
        quantum = 16
    cfg = GPTConfig(**kw)
    model = GPTForCausalLM(cfg).bfloat16() if on_tpu else \
        GPTForCausalLM(cfg)
    model.eval()
    rng = np.random.default_rng(0)
    prefix = rng.integers(0, cfg.vocab_size,
                          (prefix_len,)).astype(np.int32)
    prompts = [np.concatenate([prefix, rng.integers(
        0, cfg.vocab_size, (int(t),)).astype(np.int32)])
        for t in rng.integers(tlo, thi + 1, n_req)]

    def make(enable):
        return LLMEngine(
            model, max_batch=max_batch, block_size=block_size,
            decode_chunk=chunk, prompt_quantum=quantum,
            max_model_len=cfg.max_position_embeddings,
            enable_prefix_caching=enable)

    def run(eng):
        before = dict(eng.stats)
        for i, p in enumerate(prompts):
            eng.add_request(i, p, max_new_tokens=n_new)
        done = 0
        t0 = time.perf_counter()
        while eng.has_unfinished:
            for r in eng.step():
                done += len(r.output_ids)
        dt = time.perf_counter() - t0
        delta = {k: eng.stats[k] - before.get(k, 0) for k in eng.stats}
        return done, dt, delta

    eng_on, eng_off = make(True), make(False)
    run(eng_on)                 # compile + seed the prefix index
    run(eng_off)                # compile
    tokens_on, t_on, d_on = run(eng_on)
    tokens_off, t_off, d_off = run(eng_off)
    tps_on = tokens_on / t_on
    tps_off = tokens_off / t_off
    prefill_on = d_on["prefix_cache_miss_tokens"]
    prefill_off = d_off["prefix_cache_miss_tokens"]
    return {
        "metric": "prefix_cache_serving_tokens_per_sec",
        "value": round(tps_on, 1),
        "unit": "tokens/s",
        "vs_baseline": round(tps_on / tps_off, 4),
        "extra": {
            "uncached_tokens_per_sec": round(tps_off, 1),
            "prefill_tokens_cached": prefill_on,
            "prefill_tokens_uncached": prefill_off,
            "prefill_token_reduction": round(
                1.0 - prefill_on / max(prefill_off, 1), 4),
            "prefix_hit_tokens": d_on["prefix_cache_hit_tokens"],
            "requests": n_req, "shared_prefix_len": prefix_len,
            "max_batch": max_batch, "block_size": block_size,
            "num_blocks": eng_on.cache.allocator.num_blocks,
            "new_tokens": n_new,
            "request_latency": _request_latency_percentiles(),
            "device": str(getattr(jax.devices()[0], "device_kind",
                                  jax.devices()[0].platform)),
        },
    }


def bench_spec_decode(on_tpu):
    """Speculative decoding on the workload it exists for: repetitive
    prompts (templated/few-shot-shaped traffic) decoded through
    LLMEngine with n-gram self-drafting ON vs OFF at EQUAL cache HBM
    (same pool, same blocks; per-row verify leases cover only the live
    1+drafts window, capped at each request's admission-validated
    token budget, and the reported peak is the engine's IN-STEP
    post-lease high-water — `peak_used_blocks` — not the post-rollback
    residue). Prefix caching is off for BOTH runs so the
    measurement isolates multi-token-per-step decode (the
    spec-x-prefix-cache composition is conformance-tested, and bench
    repetition would legitimately short-circuit prefill). Both engines
    are warmed first (compiles prefill/decode/verify executables),
    then timed. vs_baseline = spec tok/s over chunked tok/s; extra
    carries the headline accepted-tokens-per-step, acceptance rate,
    and per-step peak pool usage for both runs."""
    import jax
    from paddle_tpu.inference import LLMEngine, SpeculativeConfig
    from paddle_tpu.models import GPTForCausalLM
    from paddle_tpu.models.gpt import GPTConfig

    if on_tpu:
        kw = dict(vocab_size=50304, hidden_size=2048, num_layers=24,
                  num_heads=16, max_position_embeddings=2048,
                  hidden_dropout_prob=0.0, attention_dropout_prob=0.0)
        n_req, max_batch, block_size, chunk = 16, 8, 64, 16
        pat_len, reps, n_new, spec_k = 16, 8, 128, 7
        quantum = 128
    else:
        kw = dict(vocab_size=1024, hidden_size=128, num_layers=2,
                  num_heads=4, max_position_embeddings=256,
                  hidden_dropout_prob=0.0, attention_dropout_prob=0.0)
        n_req, max_batch, block_size, chunk = 6, 2, 16, 4
        pat_len, reps, n_new, spec_k = 8, 5, 32, 7
        quantum = 16
    cfg = GPTConfig(**kw)
    model = GPTForCausalLM(cfg).bfloat16() if on_tpu else \
        GPTForCausalLM(cfg)
    model.eval()
    rng = np.random.default_rng(0)
    # repetitive prompts: a per-request token pattern tiled `reps`
    # times — the n-gram proposer drafts the continuation of the last
    # match, which repetition makes an excellent guess
    prompts = [np.tile(rng.integers(0, cfg.vocab_size,
                                    (pat_len,)).astype(np.int32), reps)
               for _ in range(n_req)]

    def make(spec):
        return LLMEngine(
            model, max_batch=max_batch, block_size=block_size,
            decode_chunk=chunk, prompt_quantum=quantum,
            max_model_len=cfg.max_position_embeddings,
            enable_prefix_caching=False,
            speculative_config=SpeculativeConfig(
                proposer="ngram",
                num_speculative_tokens=spec_k) if spec else None)

    def run(eng):
        before = dict(eng.stats)
        eng.peak_used_blocks = 0
        for i, p in enumerate(prompts):
            eng.add_request(i, p, max_new_tokens=n_new)
        done = 0
        t0 = time.perf_counter()
        while eng.has_unfinished:
            for r in eng.step():
                done += len(r.output_ids)
        dt = time.perf_counter() - t0
        delta = {k: eng.stats[k] - before.get(k, 0) for k in eng.stats}
        return done, dt, delta, eng.peak_used_blocks

    def best_of(eng, windows=3):
        # best window is the honest steady state (the box is shared —
        # same convention as _timed_steps); counters are per-run
        # deltas, identical across windows by construction
        best = None
        for _ in range(windows):
            tokens, dt, delta, peak = run(eng)
            if best is None or dt < best[1]:
                best = (tokens, dt, delta, peak)
        return best

    eng_on, eng_off = make(True), make(False)
    run(eng_on)                 # compile prefill + verify executables
    run(eng_off)                # compile prefill + decode executables
    tokens_on, t_on, d_on, peak_on = best_of(eng_on)
    tokens_off, t_off, d_off, peak_off = best_of(eng_off)
    tps_on = tokens_on / t_on
    tps_off = tokens_off / t_off
    drafted = d_on["spec_drafted_tokens"]
    accepted = d_on["spec_accepted_tokens"]
    steps_on = d_on["spec_steps"]
    return {
        "metric": "spec_decode_serving_tokens_per_sec",
        "value": round(tps_on, 1),
        "unit": "tokens/s",
        "vs_baseline": round(tps_on / tps_off, 4),
        "extra": {
            "chunked_tokens_per_sec": round(tps_off, 1),
            "accepted_tokens_per_step": round(
                accepted / max(steps_on, 1), 3),
            "acceptance_rate": round(accepted / max(drafted, 1), 4),
            "drafted_tokens": int(drafted),
            "accepted_tokens": int(accepted),
            "verify_steps": int(steps_on),
            "peak_pool_blocks_spec": int(peak_on),
            "peak_pool_blocks_chunked": int(peak_off),
            "requests": n_req, "max_batch": max_batch,
            "prompt_len": pat_len * reps, "new_tokens": n_new,
            "num_speculative_tokens": spec_k,
            "decode_chunk": chunk, "block_size": block_size,
            "num_blocks": eng_on.cache.allocator.num_blocks,
            "request_latency": _request_latency_percentiles(),
            "device": str(getattr(jax.devices()[0], "device_kind",
                                  jax.devices()[0].platform)),
        },
    }


def _proc_fleet_model(**kw):
    """Module-level so the replica spawn context can pickle it by
    reference (the worker re-imports bench.py as __mp_main__)."""
    import paddle_tpu as pt
    from paddle_tpu.models import GPTForCausalLM
    from paddle_tpu.models.gpt import GPTConfig
    pt.seed(0)
    m = GPTForCausalLM(GPTConfig(**kw))
    m.eval()
    return m


def _proc_fleet_reintegration(model_kw, engine_kw, n_new):
    """Cold-vs-warm serving-fleet reintegration: two passes of an
    N=2 REAL-OS-PROCESS fleet over one shared persistent executable
    store. The cold pass starts from an empty store (spawn + XLA
    compile + serve); the warm pass spawns FRESH processes over the
    populated store under the SAME fleet names (spawn + deserialize +
    serve — and the aggregator's pid-change detection books the
    restarts). warm_over_cold is the whole-pass wall-clock ratio; a
    warm pass that hit disk for every executable reports
    warm_skipped_all_compiles=true (store misses 0, zero fresh
    compiles in any warm worker's registry)."""
    import shutil
    import tempfile
    from paddle_tpu.inference import Router
    from paddle_tpu.inference.replica_proc import process_engine_factory
    from paddle_tpu.observability import fleet as ofleet

    cache_dir = tempfile.mkdtemp(prefix="bench_exec_cache_")
    agg = ofleet.serve_aggregator(stale_after_s=60.0)
    rng = np.random.default_rng(7)
    prompts = [rng.integers(0, model_kw["vocab_size"],
                            (12,)).astype(np.int32) for _ in range(6)]

    def one_pass(tag):
        factory = process_engine_factory(
            _proc_fleet_model, model_kwargs=model_kw,
            engine_kwargs=engine_kw, exec_cache_dir=cache_dir,
            aggregator_endpoint=agg.endpoint,
            name_prefix="bench-engine")
        t0 = time.perf_counter()
        router = Router(factory, n_replicas=2, affinity=True)
        for i, p in enumerate(prompts):
            router.submit(("fleet-%s" % tag, i), p,
                          max_new_tokens=n_new)
        outs = []
        while router.has_unfinished:
            outs.extend(router.step())
        dt = time.perf_counter() - t0
        outcomes = {}
        store = {}
        for h in router.replicas:
            try:
                for k, v in h.engine.compile_outcomes().items():
                    okey = "%s/%s" % k
                    outcomes[okey] = outcomes.get(okey, 0) + int(v)
                for k, v in h.engine.exec_cache_stats().items():
                    store[k] = store.get(k, 0) + int(v)
            except Exception:
                pass
        for h in router.replicas:
            try:
                h.engine.shutdown()
            except Exception:
                pass
        outputs = sorted((str(r.request_id),
                          tuple(int(t) for t in r.output_ids))
                         for r in outs)
        return dt, outcomes, store, outputs

    try:
        cold_s, cold_out, cold_store, cold_txt = one_pass("cold")
        warm_s, warm_out, warm_store, warm_txt = one_pass("warm")
        warm_compiles = sum(v for k, v in warm_out.items()
                            if k.endswith("/compile"))
        caps = agg.capacity_records()
        health = agg.health()
        doc = json.loads(agg.to_json())
        restarts = sum(
            s.get("value", 0) for s in doc.get(
                "paddle_tpu_fleet_process_restarts_total",
                {}).get("series", ()))
        return {
            "replica_processes": 2,
            "cold_s": round(cold_s, 3),
            "warm_s": round(warm_s, 3),
            "warm_over_cold": round(warm_s / max(cold_s, 1e-9), 4),
            "warm_skipped_all_compiles": bool(
                warm_compiles == 0
                and warm_store.get("misses", 0) == 0
                and warm_store.get("hits", 0) > 0),
            "outputs_identical": bool(
                [t for _, t in cold_txt] == [t for _, t in warm_txt]),
            "cold_outcomes": cold_out, "warm_outcomes": warm_out,
            "cold_store": cold_store, "warm_store": warm_store,
            "fleet_restarts": int(restarts),
            "fleet_capacity": [
                {k: c.get(k) for k in ("process", "process_role",
                                       "requests_total",
                                       "tokens_total", "req_per_s",
                                       "tok_per_s")}
                for c in caps],
            "fleet_up": {p: bool(h["up"]) for p, h in health.items()},
        }
    finally:
        try:
            agg.close()
        except Exception:
            pass
        shutil.rmtree(cache_dir, ignore_errors=True)


def bench_router_serving(on_tpu):
    """Replicated serving through the failover Router on the workload
    prefix-cache AFFINITY exists for: S sessions, each with its own
    shared few-shot prefix, whose turns arrive interleaved across the
    fleet. N=2 in-process replicas at EQUAL TOTAL cache HBM either
    way (same two engines, same pools — the A/B flips only the
    routing policy): affinity ON routes every turn to the replica
    already holding its session's pages, affinity OFF routes blind
    least-loaded, so each session's prefix ends up recomputed on
    whichever replica the load balancer picked. Both fleets are
    warmed on the workload first (compiles + seeds the prefix
    indexes — a serving fleet keeps its caches across requests), then
    timed. vs_baseline = affinity tok/s over blind tok/s; extra
    carries the headline affinity hit-token fraction (engine-measured
    prefix hits over all prompt tokens) for both policies."""
    import jax
    from paddle_tpu.inference import LLMEngine, Router
    from paddle_tpu.models import GPTForCausalLM
    from paddle_tpu.models.gpt import GPTConfig

    if on_tpu:
        kw = dict(vocab_size=50304, hidden_size=2048, num_layers=24,
                  num_heads=16, max_position_embeddings=2048,
                  hidden_dropout_prob=0.0, attention_dropout_prob=0.0)
        n_sessions, turns, max_batch, block_size, chunk = 8, 4, 8, 64, 16
        prefix_len, tlo, thi, n_new = 512, 8, 32, 64
        quantum = 128
        # pool pressure is the point: one replica can park ~half the
        # fleet's session prefixes (8 sessions x 8 pages), not all —
        # working set 8 slots x 10 pages + trash + half the prefixes
        num_blocks = 120
    else:
        kw = dict(vocab_size=1024, hidden_size=128, num_layers=2,
                  num_heads=4, max_position_embeddings=256,
                  hidden_dropout_prob=0.0, attention_dropout_prob=0.0)
        n_sessions, turns, max_batch, block_size, chunk = 4, 3, 2, 16, 4
        prefix_len, tlo, thi, n_new = 32, 2, 6, 8
        quantum = 16
        # trash + 2 running seqs' tails + ~2 sessions' parked
        # prefixes (2 full pages each) — all 4 sessions do NOT fit,
        # so a replica can only stay warm for the sessions routed to
        # it consistently
        num_blocks = 8
    cfg = GPTConfig(**kw)
    model = GPTForCausalLM(cfg).bfloat16() if on_tpu else \
        GPTForCausalLM(cfg)
    model.eval()
    rng = np.random.default_rng(0)
    prefixes = [rng.integers(0, cfg.vocab_size,
                             (prefix_len,)).astype(np.int32)
                for _ in range(n_sessions)]
    # turn t of session s: session prefix + a fresh tail. The arrival
    # order is SHUFFLED (deterministically) — round-robin arrivals
    # would make plain least-loaded routing accidentally
    # session-sticky, and real fleet traffic interleaves sessions
    # unpredictably; the shuffle is what makes blind routing scatter
    # a session across replicas
    traffic = []
    for t in range(turns):
        for s in range(n_sessions):
            tail = rng.integers(0, cfg.vocab_size, (int(
                rng.integers(tlo, thi + 1)),)).astype(np.int32)
            traffic.append((f"s{s}", np.concatenate([prefixes[s],
                                                     tail])))
    traffic = [traffic[i] for i in rng.permutation(len(traffic))]

    def make_router(affinity):
        def factory(_i):
            return LLMEngine(
                model, max_batch=max_batch, block_size=block_size,
                num_blocks=num_blocks, decode_chunk=chunk,
                prompt_quantum=quantum,
                max_model_len=cfg.max_position_embeddings)
        return Router(factory, n_replicas=2, affinity=affinity)

    def run(router):
        hit0 = sum(h.engine.stats["prefix_cache_hit_tokens"]
                   for h in router.replicas)
        miss0 = sum(h.engine.stats["prefix_cache_miss_tokens"]
                    for h in router.replicas)
        for i, (sess, prompt) in enumerate(traffic):
            router.submit((id(router), i), prompt,
                          max_new_tokens=n_new, session_id=sess)
        done = 0
        t0 = time.perf_counter()
        while router.has_unfinished:
            for r in router.step():
                done += len(r.output_ids)
        dt = time.perf_counter() - t0
        hit = sum(h.engine.stats["prefix_cache_hit_tokens"]
                  for h in router.replicas) - hit0
        miss = sum(h.engine.stats["prefix_cache_miss_tokens"]
                   for h in router.replicas) - miss0
        return done, dt, hit, miss

    def best_of(router, windows=3):
        # best window = honest steady state on a shared box (same
        # convention as spec_decode); hit counters come from the best
        # window's delta
        best = None
        for _ in range(windows):
            tokens, dt, hit, miss = run(router)
            if best is None or dt < best[1]:
                best = (tokens, dt, hit, miss)
        return best

    r_on, r_off = make_router(True), make_router(False)
    run(r_on)                   # compile + seed both prefix indexes
    run(r_off)
    tok_on, t_on, hit_on, miss_on = best_of(r_on)
    tok_off, t_off, hit_off, miss_off = best_of(r_off)
    tps_on, tps_off = tok_on / t_on, tok_off / t_off
    # the process-fleet reintegration phase rides this config: cold
    # vs warm N=2 OS-process fleets over a shared executable store.
    # Refused on TPU: a chip belongs to one process, and this one has it.
    if on_tpu:
        reintegration = {"refused": "this process owns the chip; "
                                    "replica processes could not open it"}
    else:
        try:
            reintegration = _proc_fleet_reintegration(
                kw, dict(max_batch=max_batch, block_size=block_size,
                         num_blocks=num_blocks, decode_chunk=chunk,
                         prompt_quantum=quantum,
                         max_model_len=kw["max_position_embeddings"]),
                n_new)
        except Exception as e:
            reintegration = {"error": "%s: %s"
                             % (type(e).__name__, e)}
    return {
        "metric": "router_serving_tokens_per_sec",
        "value": round(tps_on, 1),
        "unit": "tokens/s",
        "vs_baseline": round(tps_on / tps_off, 4),
        "extra": {
            "blind_tokens_per_sec": round(tps_off, 1),
            "affinity_hit_token_fraction": round(
                hit_on / max(hit_on + miss_on, 1), 4),
            "blind_hit_token_fraction": round(
                hit_off / max(hit_off + miss_off, 1), 4),
            "affinity_hit_tokens": int(hit_on),
            "blind_hit_tokens": int(hit_off),
            "reintegration": reintegration,
            "replicas": 2, "sessions": n_sessions, "turns": turns,
            "shared_prefix_len": prefix_len, "new_tokens": n_new,
            "max_batch": max_batch, "block_size": block_size,
            "num_blocks_per_replica":
                r_on.replicas.handles[0]
                .engine.cache.allocator.num_blocks,
            "request_latency": _request_latency_percentiles(),
            "device": str(getattr(jax.devices()[0], "device_kind",
                                  jax.devices()[0].platform)),
        },
    }


def bench_traffic(on_tpu):
    """The serving SLO control plane acceptance experiment: the SAME
    heavy-tailed many-user schedule (bursty on/off Poisson arrivals,
    lognormal prompt/output tails, multi-turn shared-prefix sessions —
    inference.traffic.TrafficModel, fixed seed) driven twice against
    the Router fleet:

      A. a STATIC max-size fleet (n_replicas = the scaling ceiling);
      B. an AUTOSCALED fleet starting at 1 replica, grown/retired by
         the SLO-driven Autoscaler reading a windowed FleetSLOMonitor
         over the live registry.

    On CPU the replicas are REAL OS PROCESSES
    (inference.replica_proc.process_engine_factory): each worker
    computes in its own process and the router steps the fleet
    concurrently, so fleet size buys actual throughput and the A/B
    measures capacity, not batch slots. Worker TTFT histograms ride
    FleetAgent bundles to one aggregator; each phase uses its own
    fleet name prefix, so the bench reads any phase's fleet-wide
    TTFT distribution from the aggregator's process-merged series
    after the workers' farewell flush. The autoscaled leg grows
    through an ASYNC actuator: scan() kicks a background spawn and
    returns None (the Autoscaler journals the abort and retries on
    its streaks) until the ready client attaches through
    `add_replica(engine_factory=...)` in O(ms) — growth never stalls
    the serving loop. On TPU the replicas stay in-process (they
    share one device population), stepped sequentially over shared
    batch slots.

    Both legs share one persistent executable store (a grown replica
    reintegrates warm — growth costs process/pool setup, not XLA),
    and the SLO threshold is calibrated from an uncontended warm-up
    phase so the bench measures queueing, not box speed. Headline
    value = the capacity-planning line req/s per replica AT the SLO
    (autoscaled leg's ok-requests over its replica-seconds);
    vs_baseline = static replica-seconds over autoscaled
    replica-seconds (> 1 means the autoscaler met demand on less
    fleet). extra carries both legs' TTFT p95 / SLO attainment,
    per-cohort accounting and every committed scale decision."""
    import json
    import tempfile
    import threading

    import jax
    from paddle_tpu import observability as obs
    from paddle_tpu.observability import fleet as ofleet
    from paddle_tpu.observability import metrics as _m
    from paddle_tpu.observability import slo, slo_fleet
    from paddle_tpu.inference import (Autoscaler, LLMEngine, Router,
                                      RouterActuator, TrafficModel,
                                      run_traffic)
    from paddle_tpu.inference.replica_proc import process_engine_factory
    from paddle_tpu.models import GPTForCausalLM
    from paddle_tpu.models.gpt import GPTConfig

    if on_tpu:
        kw = dict(vocab_size=50304, hidden_size=2048, num_layers=24,
                  num_heads=16, max_position_embeddings=2048,
                  hidden_dropout_prob=0.0, attention_dropout_prob=0.0)
        max_batch, block_size, chunk, quantum = 8, 64, 16, 128
        num_blocks, max_prompt, n_new_cap = 120, 768, 64
        n_events, max_replicas = 120, 3
    else:
        kw = dict(vocab_size=1024, hidden_size=128, num_layers=2,
                  num_heads=4, max_position_embeddings=256,
                  hidden_dropout_prob=0.0, attention_dropout_prob=0.0)
        max_batch, block_size, chunk, quantum = 4, 16, 4, 16
        num_blocks, max_prompt, n_new_cap = 48, 96, 32
        n_events, max_replicas = 300, 3
    # the SLO control plane IS the observability plane: the monitor
    # reads the request histograms and the autoscaler reads the
    # monitor, so this config forces recording on even under --no-obs
    obs.enable()
    store = tempfile.mkdtemp(prefix="paddle_tpu_traffic_store_")
    proc_fleet = not on_tpu
    engine_kw = dict(max_batch=max_batch, block_size=block_size,
                     num_blocks=num_blocks, decode_chunk=chunk,
                     prompt_quantum=quantum,
                     max_model_len=kw["max_position_embeddings"])

    tm = TrafficModel(seed=7, base_rate=3.0, burst_rate=30.0,
                      off_s=2.0, on_s=1.5, max_body=max_prompt,
                      max_out=n_new_cap)
    evs = list(tm.events(n_events))

    agg = None
    if proc_fleet:
        agg = ofleet.serve_aggregator(stale_after_s=600.0)

        def make_factory(prefix):
            return process_engine_factory(
                _proc_fleet_model, model_kwargs=kw,
                engine_kwargs=engine_kw, exec_cache_dir=store,
                aggregator_endpoint=agg.endpoint,
                name_prefix=prefix)

        def shutdown_fleet(router):
            for h in list(router.replicas):
                try:
                    if h.engine is not None:
                        h.engine.shutdown()
                except Exception:
                    pass

        def ttft_stats(prefix, threshold):
            """Fleet-wide TTFT for one phase: sum the aggregator's
            process-labeled bucket vectors over that phase's name
            prefix (the slo_fleet merge idiom, scoped)."""
            doc = json.loads(agg.registry.to_json())
            rec = doc.get("paddle_tpu_request_ttft_seconds")
            buckets, lo, hi = None, None, None
            for s in (rec or {}).get("series", ()):
                pname = str(s["labels"].get("process", ""))
                if not pname.startswith(prefix):
                    continue
                v = s["value"]
                if buckets is None:
                    buckets = list(v["buckets"])
                    lo, hi = v["min"], v["max"]
                else:
                    buckets = [a + b for a, b in
                               zip(buckets, v["buckets"])]
                    if v["min"] is not None:
                        lo = v["min"] if lo is None \
                            else min(lo, v["min"])
                    if v["max"] is not None:
                        hi = v["max"] if hi is None \
                            else max(hi, v["max"])
            if not buckets or not sum(buckets):
                return {"p50_s": None, "p95_s": None,
                        "attained": None, "count": 0}
            return {
                "p50_s": round(_m.quantile_from_buckets(
                    rec["buckets"], buckets, 0.5, lo=lo, hi=hi), 4),
                "p95_s": round(_m.quantile_from_buckets(
                    rec["buckets"], buckets, 0.95, lo=lo, hi=hi), 4),
                "attained": round(_m.fraction_le(
                    rec["buckets"], buckets, threshold, hi=hi), 4),
                "count": int(sum(buckets)),
            }
    else:
        cfg = GPTConfig(**kw)
        model = GPTForCausalLM(cfg).bfloat16()
        model.eval()

        def make_factory(prefix):
            def factory(_i):
                return LLMEngine(model, exec_cache_dir=store,
                                 **engine_kw)
            return factory

        def shutdown_fleet(router):
            pass

        def ttft_stats(prefix, threshold):
            h = _m.registry().get("paddle_tpu_request_ttft_seconds")
            child = h._children.get(()) if h is not None else None
            if child is None or not child._count:
                return {"p50_s": None, "p95_s": None,
                        "attained": None, "count": 0}
            return {
                "p50_s": round(child.quantile(0.5), 4),
                "p95_s": round(child.quantile(0.95), 4),
                "attained": round(_m.fraction_le(
                    child._bounds, child._buckets, threshold,
                    hi=child._max), 4),
                "count": child._count,
            }

    class _AsyncGrowActuator(RouterActuator):
        """grow() never blocks the serving loop: the first call kicks
        a background worker spawn and returns None — the Autoscaler
        journals the abort WITHOUT resetting its breach streak and
        retries next scan — until the ready client attaches through
        the router's engine_factory override in O(ms)."""

        def __init__(self, router, factory):
            super().__init__(router)
            self._factory = factory
            self._lock = threading.Lock()
            self.ready = []
            self._spawning = False
            self._next_idx = 100     # grown replicas' index namespace

        def grow(self):
            with self._lock:
                if self.ready:
                    client = self.ready.pop()
                    return self.router.add_replica(
                        engine_factory=lambda _i, c=client: c)
                if not self._spawning:
                    self._spawning = True
                    idx = self._next_idx
                    self._next_idx += 1
                    threading.Thread(target=self._spawn, args=(idx,),
                                     daemon=True).start()
            return None

        def _spawn(self, idx):
            try:
                client = self._factory(idx)
            except Exception:
                client = None
            with self._lock:
                if client is not None:
                    self.ready.append(client)
                self._spawning = False

    # warm-up, two phases: (1) a throwaway replica floods the
    # schedule's head so every executable shape lands in the shared
    # store; (2) a FRESH warm-store replica serves a few SERIAL
    # requests whose uncontended TTFT calibrates the SLO threshold —
    # the bench then measures queueing under load, not this box's
    # absolute speed. (Separate fleet prefixes: the flood's
    # compile-stalled TTFTs must not pollute the calibration read.)
    obs.reset()
    warm_router = Router(make_factory("traffic-warm"), n_replicas=1,
                         max_inflight=64)
    run_traffic(warm_router, evs[:20], time_scale=0.0,
                max_prompt=max_prompt)
    shutdown_fleet(warm_router)
    obs.reset()
    cal_router = Router(make_factory("traffic-cal"), n_replicas=1,
                        max_inflight=64)
    for j, ev in enumerate(evs[20:28]):
        cal_router.submit(("warm", j), ev.prompt[:max_prompt],
                          max_new_tokens=4)
        while cal_router.has_unfinished:
            cal_router.step()
    shutdown_fleet(cal_router)
    warm = ttft_stats("traffic-cal", 1.0)
    # threshold off the warm MEDIAN (the p95 is one first-touch
    # executable deserialize, not steady state): a request whose
    # first token took this many times the uncontended median sat
    # in a queue
    thr = max(0.3, 10.0 * (warm["p50_s"] or 0.05))
    objective = 0.9
    # compress the schedule: the burst phases must exceed one
    # replica's capacity (or the controller has nothing to do) while
    # staying inside the max-size fleet's
    time_scale = 1.0 if proc_fleet else 0.5

    def leg(tag, autoscaled):
        obs.reset()
        prefix = "traffic-%s" % tag
        factory = make_factory(prefix)
        router = Router(
            factory, n_replicas=1 if autoscaled else max_replicas,
            max_inflight=64)
        if not proc_fleet:
            # in-process replicas share the parent registry: warm each
            # leg's STARTING replicas off the clock so first-touch
            # executable loads don't masquerade as queueing in the
            # static baseline, then zero the local series (the proc
            # fleet doesn't need this — workers load warm from the
            # store and each leg reads its own fleet prefix)
            for h in router.replicas:
                h.engine.generate([ev.prompt[:max_prompt]
                                   for ev in evs[:6]],
                                  max_new_tokens=2)
            obs.reset()
        asc = None
        actu = None
        if autoscaled:
            mon = slo_fleet.FleetSLOMonitor(
                agg=agg, min_count=3,
                flight_on_breach=False, rules=[
                    slo.SLO("ttft_p95",
                            "paddle_tpu_request_ttft_seconds",
                            threshold_s=thr, objective=objective)])
            # prime the window so earlier phases' cumulative series
            # don't read as this leg's first delta
            mon.evaluate()
            actu = (_AsyncGrowActuator(router, factory) if proc_fleet
                    else RouterActuator(router))
            asc = Autoscaler(actu, mon,
                             min_replicas=1, max_replicas=max_replicas,
                             grow_after=2, retire_after=16,
                             cooldown_scans=8)
        rep = run_traffic(router, evs, autoscaler=asc,
                          scan_every_s=0.25 if proc_fleet else 0.1,
                          time_scale=time_scale,
                          max_prompt=max_prompt)
        shutdown_fleet(router)
        if actu is not None and getattr(actu, "ready", None):
            for client in actu.ready:    # spawned but never attached
                try:
                    client.shutdown()
                except Exception:
                    pass
        rep["ttft"] = ttft_stats(prefix, thr)
        rep["slo_met"] = (rep["ttft"]["attained"] is not None
                          and rep["ttft"]["attained"] >= objective)
        return rep

    try:
        rep_static = leg("static", autoscaled=False)
        rep_auto = leg("auto", autoscaled=True)
    finally:
        if agg is not None:
            agg.close()
    cap = rep_auto["ok"] / max(rep_auto["replica_seconds"], 1e-9)
    return {
        "metric": "traffic_req_per_replica_s_at_slo",
        "value": round(cap, 4),
        "unit": "req/s/replica",
        "vs_baseline": round(
            rep_static["replica_seconds"]
            / max(rep_auto["replica_seconds"], 1e-9), 4),
        "extra": {
            "slo": {"metric": "paddle_tpu_request_ttft_seconds",
                    "threshold_s": round(thr, 4),
                    "objective": objective,
                    "calibration_warm_p95_s": warm["p95_s"]},
            "static": {
                "replicas": max_replicas,
                "replica_seconds": round(
                    rep_static["replica_seconds"], 2),
                "ttft": rep_static["ttft"],
                "slo_met": rep_static["slo_met"],
                "req_per_s": round(rep_static["req_per_s"], 3),
                "shed_rate": round(rep_static["shed_rate"], 4),
                "cohorts": rep_static["cohorts"],
            },
            "autoscaled": {
                "max_replicas": max_replicas,
                "replica_seconds": round(
                    rep_auto["replica_seconds"], 2),
                "ttft": rep_auto["ttft"],
                "slo_met": rep_auto["slo_met"],
                "req_per_s": round(rep_auto["req_per_s"], 3),
                "shed_rate": round(rep_auto["shed_rate"], 4),
                "cohorts": rep_auto["cohorts"],
                "decisions": rep_auto.get("decisions", []),
            },
            "events": n_events,
            "device": str(getattr(jax.devices()[0], "device_kind",
                                  jax.devices()[0].platform)),
        },
    }


def bench_disagg(on_tpu):
    """Prefill/decode disaggregation A/B at EQUAL total pool HBM: the
    same heavy-tailed traffic schedule (inference.traffic.TrafficModel,
    fixed seed) driven against

      A. a role-less Router fleet of N replicas (every replica serves
         both halves of the workload);
      B. a DisaggRouter over the SAME N replicas — same engine config,
         same per-replica page pool, so equal total HBM — split into
         role pools (1 prefill + N-1 decode): every multi-token
         request prefills on the prefill pool, then its committed
         prefix pages migrate over the replica RPC to a decode
         replica that re-admits it with `prefix_hashes=` (see README
         "Prefill/decode disaggregation").

    On CPU the replicas are real OS processes with per-role fleet
    names and process_role=engine_prefill/engine_decode, so the
    aggregator's process-merged request histograms split TTFT/TPOT
    per role and the extra carries per-role capacity lines
    (sessions-per-replica-second for the prefill pool, completions
    for the decode pool — static pools, so replica-seconds per role
    is exactly pool_size x leg wall).

    The CPU gate is NOT the latency ratio — one time-sliced box
    cannot measure a disaggregation win (both legs share the same
    cores, so the A/B ratio reflects scheduler noise; it is reported
    under extra with exactly that caveat). The gate is:
      (1) bit-exactness — a fixed greedy prompt set served through
          the disaggregated fleet matches a role-less single-engine
          oracle token for token, and
      (2) handoff-path accounting — handoffs == completed multi-token
          sessions, with the migrated path > 0 under the default
          config (migration on, no chaos).
    Headline value = the disaggregated leg's capacity line (ok
    requests per replica-second); vs_baseline = that capacity over
    the role-less leg's."""
    import json
    import tempfile

    import jax
    from paddle_tpu import observability as obs
    from paddle_tpu.observability import fleet as ofleet
    from paddle_tpu.observability import metrics as _m
    from paddle_tpu.inference import (DisaggRouter, LLMEngine, Router,
                                      TrafficModel, run_traffic)
    from paddle_tpu.inference.disagg import PROCESS_ROLES
    from paddle_tpu.inference.replica_proc import process_engine_factory
    from paddle_tpu.models import GPTForCausalLM
    from paddle_tpu.models.gpt import GPTConfig

    if on_tpu:
        kw = dict(vocab_size=50304, hidden_size=2048, num_layers=24,
                  num_heads=16, max_position_embeddings=2048,
                  hidden_dropout_prob=0.0, attention_dropout_prob=0.0)
        max_batch, block_size, chunk, quantum = 8, 64, 16, 128
        num_blocks, max_prompt, n_new_cap = 120, 768, 64
        n_events = 80
    else:
        kw = dict(vocab_size=1024, hidden_size=128, num_layers=2,
                  num_heads=4, max_position_embeddings=256,
                  hidden_dropout_prob=0.0, attention_dropout_prob=0.0)
        max_batch, block_size, chunk, quantum = 4, 16, 4, 16
        num_blocks, max_prompt, n_new_cap = 48, 96, 32
        n_events = 200
    n_total, n_prefill = 3, 1           # equal pool size in both legs
    n_decode = n_total - n_prefill
    obs.enable()
    store = tempfile.mkdtemp(prefix="paddle_tpu_disagg_store_")
    proc_fleet = not on_tpu
    engine_kw = dict(max_batch=max_batch, block_size=block_size,
                     num_blocks=num_blocks, decode_chunk=chunk,
                     prompt_quantum=quantum,
                     max_model_len=kw["max_position_embeddings"])

    tm = TrafficModel(seed=7, base_rate=3.0, burst_rate=30.0,
                      off_s=2.0, on_s=1.5, max_body=max_prompt,
                      max_out=n_new_cap)
    evs = list(tm.events(n_events))

    agg = None
    if proc_fleet:
        agg = ofleet.serve_aggregator(stale_after_s=600.0)
        oracle_model = _proc_fleet_model(**kw)

        def make_factory(prefix, role=None):
            return process_engine_factory(
                _proc_fleet_model, model_kwargs=kw,
                engine_kwargs=engine_kw, exec_cache_dir=store,
                aggregator_endpoint=agg.endpoint,
                name_prefix=prefix, role=role)

        def shutdown_fleet(router):
            for h in list(router.replicas):
                try:
                    if h.engine is not None:
                        h.engine.shutdown()
                except Exception:
                    pass

        def tail_stats(prefix, metric):
            """Fleet-wide request-latency tail for one leg (or one
            role pool): sum the aggregator's process-labeled bucket
            vectors over the fleet name prefix."""
            doc = json.loads(agg.registry.to_json())
            rec = doc.get(metric)
            buckets, lo, hi = None, None, None
            for s in (rec or {}).get("series", ()):
                pname = str(s["labels"].get("process", ""))
                if not pname.startswith(prefix):
                    continue
                v = s["value"]
                if buckets is None:
                    buckets = list(v["buckets"])
                    lo, hi = v["min"], v["max"]
                else:
                    buckets = [a + b for a, b in
                               zip(buckets, v["buckets"])]
                    if v["min"] is not None:
                        lo = v["min"] if lo is None \
                            else min(lo, v["min"])
                    if v["max"] is not None:
                        hi = v["max"] if hi is None \
                            else max(hi, v["max"])
            if not buckets or not sum(buckets):
                return {"p50_s": None, "p95_s": None, "count": 0}
            return {
                "p50_s": round(_m.quantile_from_buckets(
                    rec["buckets"], buckets, 0.5, lo=lo, hi=hi), 4),
                "p95_s": round(_m.quantile_from_buckets(
                    rec["buckets"], buckets, 0.95, lo=lo, hi=hi), 4),
                "count": int(sum(buckets)),
            }
    else:
        cfg = GPTConfig(**kw)
        oracle_model = GPTForCausalLM(cfg).bfloat16()
        oracle_model.eval()

        def make_factory(prefix, role=None):
            def factory(_i):
                return LLMEngine(oracle_model, exec_cache_dir=store,
                                 **engine_kw)
            return factory

        def shutdown_fleet(router):
            pass

        def tail_stats(prefix, metric):
            # in-process replicas share one registry with no process
            # labels: whole-leg tails only (obs.reset() between legs
            # scopes them); per-role splits need the proc fleet
            h = _m.registry().get(metric)
            child = h._children.get(()) if h is not None else None
            if child is None or not child._count:
                return {"p50_s": None, "p95_s": None, "count": 0}
            return {"p50_s": round(child.quantile(0.5), 4),
                    "p95_s": round(child.quantile(0.95), 4),
                    "count": child._count}

    def make_disagg(prefix):
        return DisaggRouter(
            make_factory(prefix + "-prefill", role=PROCESS_ROLES[0]),
            make_factory(prefix + "-decode", role=PROCESS_ROLES[1]),
            n_prefill=n_prefill, n_decode=n_decode, max_inflight=64)

    def warm_inproc(router):
        if proc_fleet:
            return
        for h in router.replicas:
            h.engine.generate([ev.prompt[:max_prompt]
                               for ev in evs[:6]], max_new_tokens=2)
        obs.reset()

    # phase 1 — warm the shared executable store off the clock (proc
    # workers then deserialize every shape instead of compiling it)
    obs.reset()
    warm_router = Router(make_factory("disagg-warm"), n_replicas=1,
                         max_inflight=64)
    run_traffic(warm_router, evs[:20], time_scale=0.0,
                max_prompt=max_prompt)
    shutdown_fleet(warm_router)

    # phase 2 — the CPU gate: fixed greedy prompts through a
    # disaggregated fleet vs a role-less single-engine oracle
    rng = np.random.default_rng(11)
    gate_prompts = [rng.integers(0, kw["vocab_size"],
                                 (int(n),)).astype(np.int32)
                    for n in (37, 53, 41, 29, 64, 47)]
    gate_new = 12
    oracle = LLMEngine(oracle_model, exec_cache_dir=store, **engine_kw)
    want = {}
    for i, p in enumerate(gate_prompts):
        oracle.add_request(i, p, gate_new)
    while oracle.has_unfinished:
        for r in oracle.step():
            if not r.ok:
                raise RuntimeError("gate oracle failed: %s" % r.error)
            want[r.request_id] = tuple(int(t) for t in r.output_ids)

    obs.reset()
    gate_router = make_disagg("disagg-gate")
    got = {}
    for i, p in enumerate(gate_prompts):
        gate_router.submit(i, p, max_new_tokens=gate_new)
    t0 = time.perf_counter()
    while gate_router.has_unfinished:
        if time.perf_counter() - t0 > 300:
            raise RuntimeError("disagg gate fleet wedged")
        for r in gate_router.step():
            if not r.ok:
                raise RuntimeError(
                    "gate request %r failed: %s %s"
                    % (r.request_id, r.finish_reason, r.error))
            got[r.request_id] = tuple(int(t) for t in r.output_ids)
    gstats = dict(gate_router.stats)
    shutdown_fleet(gate_router)
    bit_exact = got == want
    accounted = (gstats["handoffs"] == len(gate_prompts)
                 and gstats["handoff_migrated"] > 0
                 and gstats["handoff_fallback"] == 0
                 and gstats["migrated_bytes"] > 0)
    if not (bit_exact and accounted):
        raise RuntimeError(
            "disagg gate failed: bit_exact=%s handoffs=%s/%s "
            "migrated=%s fallback=%s migrated_bytes=%s"
            % (bit_exact, gstats["handoffs"], len(gate_prompts),
               gstats["handoff_migrated"], gstats["handoff_fallback"],
               gstats["migrated_bytes"]))

    # phase 3 — the equal-pool traffic A/B
    time_scale = 1.0 if proc_fleet else 0.5

    def leg(tag):
        obs.reset()
        prefix = "disagg-%s" % tag
        if tag == "split":
            router = make_disagg(prefix)
        else:
            router = Router(make_factory(prefix), n_replicas=n_total,
                            max_inflight=64)
        warm_inproc(router)
        rep = run_traffic(router, evs, time_scale=time_scale,
                          max_prompt=max_prompt)
        rep["router_stats"] = dict(router.stats)
        shutdown_fleet(router)
        rep["ttft"] = tail_stats(prefix,
                                 "paddle_tpu_request_ttft_seconds")
        rep["tpot"] = tail_stats(prefix,
                                 "paddle_tpu_request_tpot_seconds")
        if tag == "split" and proc_fleet:
            rep["per_role"] = {
                "prefill": {
                    "replicas": n_prefill,
                    "ttft": tail_stats(
                        prefix + "-prefill",
                        "paddle_tpu_request_ttft_seconds"),
                },
                "decode": {
                    "replicas": n_decode,
                    "ttft": tail_stats(
                        prefix + "-decode",
                        "paddle_tpu_request_ttft_seconds"),
                    "tpot": tail_stats(
                        prefix + "-decode",
                        "paddle_tpu_request_tpot_seconds"),
                },
            }
        return rep

    try:
        rep_flat = leg("flat")
        rep_split = leg("split")
    finally:
        if agg is not None:
            agg.close()

    def capacity(rep):
        return rep["ok"] / max(rep.get("replica_seconds",
                                       rep["wall_s"] * n_total), 1e-9)

    cap_split = capacity(rep_split)
    cap_flat = capacity(rep_flat)
    sstats = rep_split["router_stats"]
    wall = max(rep_split["wall_s"], 1e-9)
    # per-role capacity lines: static pools, so replica-seconds per
    # role is exactly pool_size x wall
    cap_prefill = sstats["handoffs"] / (n_prefill * wall)
    cap_decode = rep_split["ok"] / (n_decode * wall)
    caveat = (
        "both legs time-slice one host's cores, so the A/B latency "
        "and capacity ratios measure scheduling on shared CPUs, not "
        "a TPU disaggregation win; the CPU gate is bit-exactness + "
        "handoff-path accounting" if proc_fleet else
        "in-process replicas share one device population; whole-leg "
        "tails only")
    return {
        "metric": "disagg_req_per_replica_s",
        "value": round(cap_split, 4),
        "unit": "req/s/replica",
        "vs_baseline": round(cap_split / max(cap_flat, 1e-9), 4),
        "extra": {
            "gate": {
                "bit_exact": bit_exact,
                "sessions": len(gate_prompts),
                "handoffs": gstats["handoffs"],
                "migrated": gstats["handoff_migrated"],
                "readmitted": gstats["handoff_readmitted"],
                "fallback": gstats["handoff_fallback"],
                "migrated_bytes": gstats["migrated_bytes"],
            },
            "roleless": {
                "replicas": n_total,
                "ttft": rep_flat["ttft"],
                "tpot": rep_flat["tpot"],
                "req_per_s": round(rep_flat["req_per_s"], 3),
                "req_per_replica_s": round(cap_flat, 4),
                "shed_rate": round(rep_flat["shed_rate"], 4),
            },
            "disaggregated": {
                "n_prefill": n_prefill,
                "n_decode": n_decode,
                # stage accounting: the request histograms count each
                # stage as its own request — a session is one
                # prefill-pool entry plus one decode-pool re-admission,
                # so user-perceived TTFT ~= prefill TTFT + handoff +
                # decode TTFT (the per_role split keeps them apart)
                "ttft": rep_split["ttft"],
                "tpot": rep_split["tpot"],
                "per_role": rep_split.get("per_role"),
                "req_per_s": round(rep_split["req_per_s"], 3),
                "shed_rate": round(rep_split["shed_rate"], 4),
                "capacity_lines": {
                    "prefill_sessions_per_replica_s":
                        round(cap_prefill, 4),
                    "decode_completions_per_replica_s":
                        round(cap_decode, 4),
                },
                "handoffs": sstats["handoffs"],
                "handoff_migrated": sstats["handoff_migrated"],
                "handoff_readmitted": sstats["handoff_readmitted"],
                "handoff_fallback": sstats["handoff_fallback"],
                "migrated_bytes": sstats["migrated_bytes"],
            },
            "events": n_events,
            "caveat": caveat,
            "device": str(getattr(jax.devices()[0], "device_kind",
                                  jax.devices()[0].platform)),
        },
    }


def bench_comms(on_tpu):
    """Collective microbench sweep (op x payload size) over the full
    device mesh (main() forces the 8-device CPU mesh when the config is
    requested on a CPU box). Eager collectives run with observability
    ON, so every timed window carries a real completion edge
    (observability.comms blocks on the result inside the timing span)
    — the achieved bytes/s per op is launch→completion algorithmic
    bandwidth, not dispatch fiction. The per-op windows land in the
    perf ledger as `comms_<op>` families, so `tools/perf_ledger.py
    --check` baselines achieved comms bandwidth per (config, op) via
    the existing per-family bytes/s rule."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu import observability as obs
    from paddle_tpu.observability import comms
    import paddle_tpu.distributed as dist

    g = dist.new_group()        # the default (world) group
    n = g.nranks
    iters = 20 if on_tpu else 6
    # per-rank payload bytes; dim1 stays divisible by n for
    # reduce_scatter/all_to_all chunking
    sizes = (1 << 14, 1 << 18, 1 << 20) if on_tpu \
        else (1 << 14, 1 << 18)

    def make(nbytes):
        elems = max(nbytes // 4 // n * n, n)
        return jnp.zeros((n, elems), jnp.float32)

    # op runners take a fresh rank-major Tensor each call so in-place
    # mutation (_set_data) can't alias across iterations
    import paddle_tpu as pt
    ops = {
        "all_reduce": lambda x: dist.all_reduce(pt.to_tensor(x)),
        "all_gather": lambda x: dist.all_gather(pt.to_tensor(x)),
        "reduce_scatter": lambda x: dist.reduce_scatter(
            pt.to_tensor(x)),
        "broadcast": lambda x: dist.broadcast(pt.to_tensor(x), src=0),
        "all_to_all": lambda x: dist.all_to_all(pt.to_tensor(x)),
    }
    payloads = {nb: make(nb) for nb in sizes}
    # warm every (op, payload) executable OUTSIDE the measured window,
    # then reset so the ledger families cover only steady-state calls
    for fn in ops.values():
        for x in payloads.values():
            fn(x)
    obs.reset()
    per_op = {}
    for name, fn in ops.items():
        t0 = time.perf_counter()
        for x in payloads.values():
            for _ in range(iters):
                fn(x)
        per_op[name] = {"wall_s": round(time.perf_counter() - t0, 4)}
    fams = comms.family_records()
    total_bytes = total_s = 0.0
    for name in ops:
        rec = fams.get("comms_" + name) or {}
        bps = rec.get("achieved_bytes_per_s")
        per_op[name]["bytes_per_s"] = bps
        per_op[name]["runs"] = rec.get("runs", 0)
        if bps and rec.get("seconds"):
            total_bytes += bps * rec["seconds"]
            total_s += rec["seconds"]
    agg = total_bytes / total_s if total_s > 0 else 0.0
    dev = jax.devices()[0]
    return {
        "metric": "comms_bytes_per_sec",
        "value": round(agg, 1),
        "unit": "bytes/s",
        "vs_baseline": 1.0,     # baselined by the perf ledger per op
        "extra": {
            "per_op": per_op,
            "devices": n,
            "iters": iters,
            "payload_bytes": list(sizes),
            "device": str(getattr(dev, "device_kind", dev.platform)),
        },
    }


def bench_lint(on_tpu):
    """Static-analysis trajectory: run graftlint over paddle_tpu/ +
    tools/ against the checked-in baseline, write the full machine
    report to graftlint_report.json, and put the finding counts on the
    BENCH line — so the baselined burn-down count (and any new-finding
    regression) is tracked round over round exactly like a perf
    number. Pure host work: no device, no jax tracing."""
    from tools.graftlint import core as gl

    t0 = time.perf_counter()
    baseline = gl.Baseline.load(gl.default_baseline_path())
    root = gl.repo_root()
    report = gl.run_paths([os.path.join(root, "paddle_tpu"),
                           os.path.join(root, "tools")],
                          root=root, baseline=baseline)
    dur = time.perf_counter() - t0
    out = os.path.abspath("graftlint_report.json")
    with open(out, "w", encoding="utf-8") as f:
        json.dump(report.to_dict(), f, indent=1)
    per_rule = {rid: dict(c) for rid, c in
                sorted(report.per_rule().items())}
    return {
        "metric": "graftlint_new_findings",
        "value": len(report.new),
        "unit": "findings",
        # clean = 1.0; any new finding (or parse error) fails the gate
        "vs_baseline": 1.0 if not (report.new or report.parse_errors)
                       else 0.0,
        "extra": {
            "files": report.files,
            "baselined": len(report.baselined),
            "total": len(report.findings),
            "per_rule": per_rule,
            "parse_errors": len(report.parse_errors),
            "report": out,
            "lint_seconds": round(dur, 3),
        },
    }


def bench_autopilot(on_tpu):
    """Self-healing reaction time: an in-process mini fleet (served
    aggregator + attached supervisor + one polling trainer) burns
    through repeated injected NaN episodes — PoisonGradient at a known
    step, divergence event shipped, rollback commanded over the real
    RPC loopback, checkpoint restored, outcome reported — and the
    BENCH line carries the autopilot's two latencies: detection
    (divergence emission -> supervisor episode open) and MTTR
    (detection -> training resumed). Host + loopback-socket work; the
    toy training is incidental."""
    import shutil
    import tempfile

    import paddle_tpu as pt
    from paddle_tpu.observability import fleet, numerics as num
    from paddle_tpu.distributed import checkpoint as ckpt
    from paddle_tpu.resilience import faults
    from paddle_tpu.resilience import supervisor as sv

    episodes = 5
    steps_per_episode = 4
    root = tempfile.mkdtemp(prefix="bench_autopilot_")
    from paddle_tpu import observability as obs
    obs.enable()        # detection rides the trace stream: obs is
    num.enable(interval=1)      # the workload here, not overhead
    agg = fleet.serve_aggregator()
    sup = sv.attach(sv.Supervisor(
        agg, ckpt_root=root,
        policy=sv.Policy(max_rollbacks=episodes + 1)))
    saved_ident = fleet.identity()
    fleet.set_identity(process="bench_trainer", role="trainer")
    try:
        agent = fleet.FleetAgent(agg.endpoint, interval_s=3600.0,
                                 timeout_s=30.0)
        ctl = sv.TrainControl(agg.endpoint, "bench_trainer",
                              timeout_s=30.0, retries=2)
        rng = np.random.default_rng(0)
        lin = pt.nn.Linear(16, 16)
        params = lin.parameters()
        for p in params:
            p.set_value(pt.to_tensor(
                rng.standard_normal(p.shape).astype(np.float32)))
        opt = pt.optimizer.SGD(learning_rate=1e-2, parameters=params)
        sd = {p.name: p for p in params}
        x = pt.to_tensor(
            rng.standard_normal((8, 16)).astype(np.float32))

        def train_step():
            loss = (lin(x) ** 2).mean()
            loss.backward()
            opt.step()
            opt.clear_grad()

        remediations = 0
        step = 0
        t0 = time.perf_counter()
        for _ in range(episodes):
            for k in range(steps_per_episode):
                cmd = ctl.poll(step=step)
                if cmd is not None:
                    out = ctl.apply(cmd, state_dict=sd, root=root)
                    ctl.report(cmd["episode"], **out)
                    remediations += 1
                    step = out["resumed_step"] + 1
                    continue
                if k == steps_per_episode - 1:
                    faults.inject(
                        "numerics.check",
                        exc=num.PoisonGradient(param=params[0].name),
                        times=1, match={"where": "step"})
                train_step()
                num.flush()
                import numpy as _np
                if all(_np.isfinite(_np.asarray(p._data)).all()
                       for p in params):
                    ckpt.save_state_dict(
                        sd, os.path.join(root, f"step_{step}"))
                agent.ship()
                step += 1
            # drain the rollback the poisoned step triggered
            cmd = ctl.poll(step=step)
            if cmd is not None:
                out = ctl.apply(cmd, state_dict=sd, root=root)
                ctl.report(cmd["episode"], **out)
                remediations += 1
                step = out["resumed_step"] + 1
        wall = time.perf_counter() - t0

        snap = agg.registry.snapshot()

        def _hist_stats(name):
            series = snap.get(name, {}).get("series", {})
            for v in series.values():
                if v.get("count"):
                    return {"mean_ms": round(
                                v["sum"] / v["count"] * 1e3, 3),
                            "max_ms": round(v["max"] * 1e3, 3),
                            "count": v["count"]}
            return {"mean_ms": None, "max_ms": None, "count": 0}

        detect = _hist_stats(
            "paddle_tpu_autopilot_detection_latency_seconds")
        mttr = _hist_stats("paddle_tpu_autopilot_mttr_seconds")
        autopilot = {
            "episodes": remediations,
            "detection_latency": detect,
            "mttr": mttr,
            "wall_seconds": round(wall, 3),
        }
        from paddle_tpu.observability import perf
        return {
            "metric": "autopilot_mttr_ms",
            "value": mttr["mean_ms"],
            "unit": "ms",
            # healthy = every injected episode remediated, zero stuck
            "vs_baseline": 1.0 if remediations == episodes
                           and sup.failure is None else 0.0,
            "extra": {"detection_latency_ms": detect["mean_ms"],
                      "episodes_injected": episodes,
                      "episodes_remediated": remediations,
                      "policy": sup.policy.to_dict()},
            "_ledger_modes": [{
                "mode": "autopilot",
                "families": perf.family_records(),
                "dispatch_gap": None,
                "autopilot": autopilot,
            }],
        }
    finally:
        faults.clear("numerics.check")
        fleet.set_identity(process=saved_ident[0],
                           role=saved_ident[1])
        sup.close()
        agg.close()
        num.disable()
        shutil.rmtree(root, ignore_errors=True)


def bench_embedding(on_tpu):
    """Terabyte-embedding subsystem bench over the 8-device mesh
    (main() forces the CPU host-platform mesh like the comms config):
    sharded lookup + sparse-update throughput through the real
    unique-id all_to_all exchange, the mmap tier's hit rate under a
    skewed id distribution, and achieved exchange bytes/s. The
    exchange's collectives are instrumented by observability.comms, so
    the windows also ride the perf ledger as the `comms_all_to_all`
    family (baselined by tools/perf_ledger.py --check per config)."""
    import shutil
    import tempfile

    import numpy as np
    import paddle_tpu as pt
    from paddle_tpu.embedding import HostEmbedding, ShardedHostEmbedding
    from paddle_tpu.observability import metrics as _m

    rng = np.random.RandomState(0)
    n_rows, dim = (1 << 22, 128) if on_tpu else (1 << 18, 64)
    G, per, steps = 8, (1024 if on_tpu else 512), (30 if on_tpu else 12)
    emb = ShardedHostEmbedding(n_rows, dim, seed=0,
                               optimizer="adagrad")
    ids = rng.randint(0, n_rows, size=(steps, G, per))
    # warm (compile the gather executables + first-touch init)
    out = emb(ids[0])
    pt.ops.sum(out * out).backward()
    emb.apply_updates()

    def _ctr(name, **labels):
        m = _m.registry().get(name)
        if m is None:
            return 0.0
        try:
            return m.labels(**labels).value if labels else m.value
        except ValueError:
            return 0.0

    x0 = sum(_ctr("paddle_tpu_embedding_exchange_bytes_total",
                  payload=p) for p in ("ids", "rows", "grads"))
    t0 = time.perf_counter()
    rows = 0
    for s in range(1, steps):
        out = emb(ids[s])
        loss = pt.ops.sum(out * out)
        loss.backward()
        emb.apply_updates()
        rows += emb.stats["device_bytes_last"] // (
            dim * np.dtype("float32").itemsize)
    wall = time.perf_counter() - t0
    x1 = sum(_ctr("paddle_tpu_embedding_exchange_bytes_total",
                  payload=p) for p in ("ids", "rows", "grads"))
    lookup_rps = rows / wall if wall > 0 else 0.0
    xbps = (x1 - x0) / wall if wall > 0 else 0.0

    # mmap tier hit rate under a skewed (80/20) id distribution
    tier_dir = tempfile.mkdtemp(prefix="bench_emb_")
    try:
        hm = HostEmbedding(n_rows, dim, seed=0,
                           mmap_path=os.path.join(tier_dir, "t.bin"),
                           hot_rows=n_rows // 32, rows_per_page=64)
        hot_pool = rng.randint(0, n_rows // 64, size=(4096,))
        # steady state first: materialize every row (lazy-init writes
        # promote pages, which would count first-touch reads as hot),
        # then fault the hot pool's pages resident before measuring
        for lo in range(0, n_rows, 1 << 14):
            hm.read_rows(np.arange(lo, min(lo + (1 << 14), n_rows)))
        hm(np.arange(0, n_rows // 64, 64))
        h0 = _ctr("paddle_tpu_embedding_tier_rows_total", tier="hot")
        c0 = _ctr("paddle_tpu_embedding_tier_rows_total", tier="cold")
        # 95/5 skew: the hot pool's pages fit the LRU capacity with
        # room for the uniform tail's transient promotions (a working
        # set larger than the LRU degenerates to sequential-scan
        # thrash — real, but not the steady state being priced here)
        for _ in range(8 if on_tpu else 4):
            skew = np.where(rng.rand(per) < 0.95,
                            hot_pool[rng.randint(0, 4096, size=per)],
                            rng.randint(0, n_rows, size=per))
            hm(skew)
        h1 = _ctr("paddle_tpu_embedding_tier_rows_total", tier="hot")
        c1 = _ctr("paddle_tpu_embedding_tier_rows_total", tier="cold")
        served = (h1 - h0) + (c1 - c0)
        hit = (h1 - h0) / served if served else None
        resident = hm.resident_bytes()
        logical = hm.host_bytes()
    finally:
        shutil.rmtree(tier_dir, ignore_errors=True)

    return {
        "metric": "embedding_lookup_rows_per_sec",
        "value": round(lookup_rps, 1),
        "unit": "rows/s",
        "vs_baseline": 1.0,     # baselined by the perf ledger
        "extra": {
            "exchange_bytes_per_s": round(xbps, 1),
            "tier_hit_rate": None if hit is None else round(hit, 4),
            "exchange_pad_last": round(
                emb.stats["exchange_pad_last"], 4),
            "steps": steps - 1,
            "devices": G,
            "rows": n_rows,
            "dim": dim,
            "batch_per_rank": per,
            "mmap_resident_bytes": resident,
            "mmap_logical_bytes": logical,
        },
    }


CONFIGS = {
    "gpt2s": bench_gpt2_small,
    "lint": bench_lint,
    "comms": bench_comms,
    "embedding": bench_embedding,
    "gpt1p3b": bench_gpt_1p3b,
    "resnet50": bench_resnet50,
    "bert": bench_bert_base,
    "dispatch": bench_dispatch,
    "decode": bench_decode,
    "decode_paged": bench_decode_paged,
    "prefix_serving": bench_prefix_serving,
    "spec_decode": bench_spec_decode,
    "router_serving": bench_router_serving,
    "traffic": bench_traffic,
    "disagg": bench_disagg,
    "autopilot": bench_autopilot,
}


# ---------------------------------------------------------------------------
# round-over-round perf gate (VERDICT item 9 / ROADMAP item 4 prereq):
# prev-rev vs current-rev INTERLEAVED best-of-N windows per decode
# config, pass/fail JSON on the BENCH line — so every perf claim this
# round and after is self-verifying instead of compared across
# sessions with different box load.
# ---------------------------------------------------------------------------
def _gate_window_paged(on_tpu):
    """One gate window = one full serve of the decode_paged workload
    through the engine (setup+compile happen once, before READY)."""
    wl = _paged_workload(on_tpu)
    wl["run_paged"]()          # compile + settle

    def window():
        t0 = time.perf_counter()
        tokens, _stats = wl["run_paged"]()
        return tokens, time.perf_counter() - t0

    return window


def _gate_window_dense(on_tpu):
    """One gate window = one dense fused-loop generate() leg on the
    decode config's main batch."""
    import jax
    import paddle_tpu as pt
    from paddle_tpu.models import GPTForCausalLM
    from paddle_tpu.models.generation import generate
    from paddle_tpu.models.gpt import GPTConfig

    if on_tpu:
        kw = dict(vocab_size=50304, hidden_size=2048, num_layers=24,
                  num_heads=16, max_position_embeddings=2048,
                  hidden_dropout_prob=0.0, attention_dropout_prob=0.0)
        prompt_len, n_new, b = 128, 128, 8
    else:
        kw = dict(vocab_size=1024, hidden_size=128, num_layers=2,
                  num_heads=4, max_position_embeddings=256,
                  hidden_dropout_prob=0.0, attention_dropout_prob=0.0)
        prompt_len, n_new, b = 8, 8, 2
    cfg = GPTConfig(**kw)
    model = GPTForCausalLM(cfg).bfloat16()
    model.eval()
    rng = np.random.default_rng(0)
    ids = rng.integers(0, cfg.vocab_size,
                       (b, prompt_len)).astype(np.int32)
    generate(model, pt.to_tensor(ids), max_new_tokens=n_new).numpy()
    salt = [0]

    def window():
        # fresh prompt content per window (see bench_decode)
        salt[0] += 1
        ids2 = ids.copy()
        ids2[:, 0] = (ids2[:, 0] + salt[0]) % cfg.vocab_size
        t0 = time.perf_counter()
        generate(model, pt.to_tensor(ids2),
                 max_new_tokens=n_new).numpy()
        return b * n_new, time.perf_counter() - t0

    return window


GATE_WINDOWS = {
    "decode_paged": _gate_window_paged,
    "decode": _gate_window_dense,
}


def _serve_windows(config, on_tpu):
    """Hidden --window-server mode: set up the config's gate workload
    once (compiles included), print READY, then run one timed window
    per 'go' line on stdin. Run with cwd = the revision to measure —
    the cwd is pushed to sys.path FIRST, so `import paddle_tpu`
    resolves against that tree even though this bench.py (which both
    revisions share, so the protocol exists on both sides) lives in
    the current one."""
    import sys
    sys.path.insert(0, os.getcwd())
    window = GATE_WINDOWS[config](on_tpu)
    print("READY", flush=True)
    for line in sys.stdin:
        cmd = line.strip()
        if cmd == "go":
            tokens, dt = window()
            print(json.dumps({"tokens": tokens, "dt": dt}), flush=True)
        else:
            break


_GATE_SETUP_TIMEOUT_S = 1800.0   # window-server setup incl. compiles
_GATE_WINDOW_TIMEOUT_S = 600.0   # one timed window


# ---------------------------------------------------------------------------
# perf ledger: per-family expected/achieved records appended per config
# run, so a regression the --gate machinery DETECTS gets ATTRIBUTED to
# an executable family by tools/perf_ledger.py (which diffs the latest
# record against the ledger history).
# ---------------------------------------------------------------------------
def _git_rev():
    import subprocess
    root = os.path.dirname(os.path.abspath(__file__))
    try:
        sha = subprocess.run(
            ["git", "rev-parse", "--short=12", "HEAD"], cwd=root,
            capture_output=True, text=True, check=True).stdout.strip()
        dirty = subprocess.run(
            ["git", "diff", "--quiet", "HEAD"], cwd=root,
            capture_output=True).returncode != 0
        return sha + ("+dirty" if dirty else "")
    except Exception:
        return "unknown"


def _append_perf_ledger(path, name, result, modes=None):
    """JSONL records: this config window's per-family
    expected/achieved summary (observability.perf.family_records —
    reset per config by obs.reset()) plus the headline number it rode
    with. `modes` (the dispatch config's per-mode payloads) writes ONE
    record per backward dispatch mode, each carrying its own families
    and dispatch-gap totals so tools/perf_ledger.py --check can
    baseline per (config, mode). Pallas autotune sweeps recorded since
    the last append ride on the first record (so a TPU run's candidate
    timings land next to the configs they tuned under). Configs that
    compiled/ran no instrumented family (lint, --no-obs runs) append
    nothing."""
    import jax
    from paddle_tpu.observability import perf
    dev = jax.devices()[0]
    base = {
        "rev": _git_rev(), "config": name,
        "ts": round(time.time(), 3),
        "device": str(getattr(dev, "device_kind", dev.platform)),
        "metric": result.get("metric"), "value": result.get("value"),
        "vs_baseline": result.get("vs_baseline"),
    }
    records = []
    if modes:
        for m in modes:
            rec = dict(base)
            rec["mode"] = m["mode"]
            rec["families"] = m["families"]
            rec["dispatch_gap"] = m["dispatch_gap"]
            if m.get("graph_cache"):
                rec["graph_cache"] = m["graph_cache"]
            if m.get("numerics"):
                rec["numerics"] = m["numerics"]
            if m.get("autopilot"):
                rec["autopilot"] = m["autopilot"]
            records.append(rec)
    else:
        from paddle_tpu.observability import comms as _comms
        fams = perf.family_records()
        # collective windows ride as comms_<op> pseudo-families, so
        # tools/perf_ledger.py --check's per-family bytes/s rule
        # baselines comms bandwidth per (config, op) with no new rule
        fams.update(_comms.family_records())
        if fams:
            rec = dict(base)
            rec["families"] = fams
            records.append(rec)
    try:
        from paddle_tpu.kernels.pallas import autotune as _autotune
        sweeps = _autotune.drain_sweeps()
    except Exception:
        sweeps = []
    # the traffic config's capacity-planning summary rides the ledger
    # (req/s per replica at SLO history for tools/perf_ledger.py);
    # its engines compile inside worker processes, so the parent has
    # no perf families for it to ride on — carry it explicitly
    extra = result.get("extra") or {}
    traffic = None
    if name == "traffic" and "slo" in extra:
        def _leg(d):
            return {k: d.get(k) for k in
                    ("replica_seconds", "slo_met", "req_per_s",
                     "shed_rate", "ttft")}
        traffic = {
            "slo": extra["slo"],
            "autoscaled": _leg(extra.get("autoscaled") or {}),
            "static": _leg(extra.get("static") or {}),
            "decisions": len((extra.get("autoscaled") or {})
                             .get("decisions") or []),
        }
    if not records:
        if not sweeps and traffic is None:
            return None
        rec = dict(base)
        rec["families"] = {}
        records.append(rec)
    if sweeps:
        records[0]["autotune_sweeps"] = sweeps
    if traffic is not None:
        records[0]["traffic"] = traffic
    # fleet warm-reintegration summary (router_serving's process-
    # fleet phase) rides the record so tools/perf_ledger.py --check
    # can baseline the warm/cold ratio like the other cost mirrors
    reint = (result.get("extra") or {}).get("reintegration") or {}
    if "warm_over_cold" in reint:
        records[0]["reintegration"] = {
            k: reint.get(k) for k in (
                "cold_s", "warm_s", "warm_over_cold",
                "warm_skipped_all_compiles")}
    with open(path, "a", encoding="utf-8") as f:
        for rec in records:
            f.write(json.dumps(rec, sort_keys=True) + "\n")
    return path


def _run_gate(config, rev, windows, tol):
    """Interleaved prev-rev vs current-rev A/B: two persistent window
    servers (one per revision, each with its own compiled state), N
    'go' commands alternating between them, best-of-N tok/s per side.
    Returns the pass/fail dict that rides the BENCH line."""
    import queue
    import subprocess
    import sys
    import tempfile
    import threading

    root = os.path.dirname(os.path.abspath(__file__))

    def _git(*a):
        return subprocess.run(
            ["git", *a], cwd=root, capture_output=True, text=True,
            check=True).stdout.strip()

    try:
        if rev is None:
            dirty = subprocess.run(
                ["git", "diff", "--quiet", "HEAD"], cwd=root
            ).returncode != 0
            # dirty tree: the working tree IS the candidate, HEAD the
            # baseline; clean tree: this commit vs its parent
            rev = "HEAD" if dirty else "HEAD^"
        sha = _git("rev-parse", rev)
    except Exception as e:
        return {"config": config, "pass": None,
                "error": f"cannot resolve prev rev: {e}"}
    wt = tempfile.mkdtemp(prefix="bench_gate_")
    os.rmdir(wt)
    procs = {}
    outq = {}
    best = {}

    def _pump(stream, q):
        # reader thread: readline() on a live-but-wedged child blocks
        # with no timeout, which would skip the finally (leaked
        # worktree + orphan servers). Deadline-guarded queue reads
        # raise instead, and the except/finally path cleans up.
        for line in stream:
            q.put(line)
        q.put("")                            # EOF marker

    def _readline(tag, timeout, what):
        try:
            line = outq[tag].get(timeout=timeout)
        except queue.Empty:
            raise RuntimeError(
                f"{tag} window server wedged during {what} "
                f"(no output in {timeout:.0f}s)")
        if not line:
            raise RuntimeError(
                f"{tag} window server died during {what}")
        return line

    try:
        _git("worktree", "add", "--detach", wt, sha)
        for tag, cwd in (("cur", root), ("prev", wt)):
            procs[tag] = subprocess.Popen(
                [sys.executable, os.path.join(root, "bench.py"),
                 "--window-server", "--cpu-smoke", "--config", config],
                cwd=cwd, stdin=subprocess.PIPE,
                stdout=subprocess.PIPE, text=True, bufsize=1)
            outq[tag] = queue.Queue()
            threading.Thread(target=_pump,
                             args=(procs[tag].stdout, outq[tag]),
                             daemon=True).start()
        for tag in procs:
            while True:
                line = _readline(tag, _GATE_SETUP_TIMEOUT_S, "setup")
                if line.strip() == "READY":
                    break
        for _ in range(windows):
            for tag in ("cur", "prev"):     # interleaved
                p = procs[tag]
                p.stdin.write("go\n")
                p.stdin.flush()
                r = json.loads(
                    _readline(tag, _GATE_WINDOW_TIMEOUT_S, "a window"))
                tps = r["tokens"] / max(r["dt"], 1e-9)
                best[tag] = max(best.get(tag, 0.0), tps)
        ratio = best["cur"] / max(best["prev"], 1e-9)
        return {
            "config": config, "prev_rev": sha[:12],
            "windows": windows,
            "prev_tokens_per_sec": round(best["prev"], 1),
            "cur_tokens_per_sec": round(best["cur"], 1),
            "ratio": round(ratio, 4), "tol": tol,
            "pass": bool(ratio >= 1.0 - tol),
        }
    except Exception as e:
        return {"config": config, "prev_rev": sha[:12], "pass": None,
                "error": f"{type(e).__name__}: {e}",
                **({"partial_best": best} if best else {})}
    finally:
        for p in procs.values():
            try:
                p.stdin.close()
            except Exception:
                pass
            p.kill()
            p.wait()
        subprocess.run(["git", "worktree", "remove", "--force", wt],
                       cwd=root, capture_output=True)


# mechanism checks that want an 8-device mesh; on the CPU that is the
# forced host-platform device count
_HOST_MESH_CONFIGS = ("comms", "embedding", "traffic", "disagg")


def _finite_or_none(x):
    """NaN/inf -> None through a result tree (a --cpu-smoke run has no
    device peaks, so its utilizations are NaN), keeping the line JSON."""
    if isinstance(x, float):
        return x if np.isfinite(x) else None
    if isinstance(x, dict):
        return {k: _finite_or_none(v) for k, v in x.items()}
    if isinstance(x, (list, tuple)):
        return [_finite_or_none(v) for v in x]
    return x


def main():
    global _CPU_SMOKE
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", choices=sorted(CONFIGS), default="gpt2s")
    ap.add_argument("--all", action="store_true",
                    help="run every config, one JSON line each")
    ap.add_argument("--no-obs", action="store_true",
                    help="skip the observability snapshot in the output")
    ap.add_argument("--gate", action="store_true",
                    help="append the round-over-round perf gate to the "
                         "BENCH line: prev-rev vs current-rev "
                         "interleaved best-of-N windows (decode "
                         "configs only)")
    ap.add_argument("--gate-rev", default=None,
                    help="baseline revision for --gate (default: HEAD "
                         "when the tree is dirty, else HEAD^)")
    ap.add_argument("--gate-windows", type=int, default=3,
                    help="interleaved windows per side for --gate")
    ap.add_argument("--gate-tol", type=float, default=0.08,
                    help="--gate fails when cur/prev < 1 - tol")
    ap.add_argument("--ledger",
                    default=os.path.join(
                        os.path.dirname(os.path.abspath(__file__)),
                        "perf_ledger.jsonl"),
                    help="perf-ledger JSONL to append per-family "
                         "expected/achieved records to (see "
                         "tools/perf_ledger.py)")
    ap.add_argument("--no-ledger", action="store_true",
                    help="skip the perf-ledger append")
    ap.add_argument("--window-server", action="store_true",
                    help=argparse.SUPPRESS)   # internal: --gate child
    ap.add_argument("--cpu-smoke", action="store_true",
                    help="run the tiny CPU shapes as a control-flow "
                         "check (JAX_PLATFORMS=cpu). Every metric is "
                         "renamed cpu_smoke/..., nothing derived from "
                         "a device peak is a number, and no ledger "
                         "record is written. Without this flag "
                         "bench.py needs a TPU and fails without one.")
    args = ap.parse_args()

    if args.cpu_smoke and args.config in _HOST_MESH_CONFIGS \
            and not args.all:
        # the comms sweep and the sharded-embedding exchange want the
        # 8-device mesh; on a CPU box that
        # means the forced host-platform device count, and it must be
        # in the env BEFORE the first backend query (jax is imported
        # below; XLA flags are read at backend init). Scoped to a
        # comms-only invocation: the flag is process-global, and
        # forcing it under --all would silently re-topology every
        # OTHER config's ledger baseline — --all runs comms in a
        # child process instead (see the main loop).
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=8"
            ).strip()

    import jax
    dev = jax.devices()[0]
    on_tpu = dev.platform == "tpu"
    if not ((dev.platform == "cpu") if args.cpu_smoke else on_tpu):
        raise SystemExit(
            f"bench.py measures on a TPU and found platform "
            f"{dev.platform!r}"
            + (" with --cpu-smoke" if args.cpu_smoke else "")
            + ": run it through the chip tool, or check control flow "
              "with JAX_PLATFORMS=cpu python bench.py --cpu-smoke")
    if args.gate and on_tpu:
        raise SystemExit(
            "--gate starts two child processes that each need the chip "
            "and checks the previous revision out into a git worktree; "
            "one process owns the chip and the chip's copy is not a "
            "repository. Compare revisions by running both in one "
            "chip call (ROADMAP C5).")
    _CPU_SMOKE = args.cpu_smoke
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    if args.window_server:
        # IMPORTANT: no paddle_tpu import may happen before this call —
        # it re-points sys.path at the cwd so the serving revision's
        # tree wins over the one this bench.py file lives in
        _serve_windows(args.config, on_tpu)
        return

    from paddle_tpu import observability as obs
    from paddle_tpu.utils.runtime_env import use_compile_cache
    use_compile_cache()
    names = list(CONFIGS) if args.all else [args.config]
    for name in names:
        if name in _HOST_MESH_CONFIGS and args.all and args.cpu_smoke:
            # the forced host-platform device count is process-global:
            # these configs' 8-device CPU mesh must not re-topology the
            # other configs of an --all smoke, so each gets its own
            # process. On a TPU the flag means nothing and the parent
            # owns the chip, so they run in this process like the rest.
            import subprocess
            import sys
            cmd = [sys.executable, os.path.abspath(__file__),
                   "--cpu-smoke", "--config", name,
                   "--ledger", args.ledger]
            if args.no_obs:
                cmd.append("--no-obs")
            if args.no_ledger:
                cmd.append("--no-ledger")
            child = subprocess.run(cmd, capture_output=True, text=True)
            line = (child.stdout.strip().splitlines() or [""])[-1]
            if child.returncode == 0 and line:
                print(line, flush=True)
            else:
                print(json.dumps({
                    "metric": {
                        "comms": "comms_bytes_per_sec",
                        "embedding": "embedding_lookup_rows_per_sec",
                        "traffic": "traffic_req_per_replica_s_at_slo",
                        "disagg": "disagg_req_per_replica_s",
                    }[name],
                    "value": None,
                    "unit": {"comms": "bytes/s",
                             "embedding": "rows/s",
                             "traffic": "req/s/replica",
                             "disagg": "req/s/replica"}[name],
                    "vs_baseline": 0.0,
                    "extra": {"error": f"{name} child failed",
                              "rc": child.returncode,
                              "stderr": child.stderr[-500:]}}),
                    flush=True)
            continue
        if not args.no_obs:
            # per-config window so each BENCH line carries ITS series
            # (step-latency histogram summary, preemption / fused-step
            # recompile counters — see observability.summary())
            obs.enable()
            obs.reset()
        result = CONFIGS[name](on_tpu)
        ledger_modes = result.pop("_ledger_modes", None)
        if args.gate and name in GATE_WINDOWS:
            result["gate"] = _run_gate(name, args.gate_rev,
                                       args.gate_windows, args.gate_tol)
        result["device"] = device
        if args.cpu_smoke:
            result["metric"] = "cpu_smoke/" + result["metric"]
        if not args.no_obs:
            result["obs"] = obs.summary()
            if not args.no_ledger and not args.cpu_smoke:
                _append_perf_ledger(args.ledger, name, result,
                                    modes=ledger_modes)
            obs.disable()
        print(json.dumps(_finite_or_none(result)), flush=True)


if __name__ == "__main__":
    main()
