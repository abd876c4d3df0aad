"""chip_smoke.py — the quickest proof that the system still starts on the chip.

    python chip_smoke.py            one TPU chip: train phase, then serve phase
    python chip_smoke.py --chips 4  one four-chip host: the sharded train
                                    step against the unsharded one, and
                                    nothing else

Drives the two halves of the main path once, through the entry points a
user calls, at the full published width AND depth of the model the repo
sizes for one chip — GPT-3 XL / 1.3B (vocab 50304, hidden 2048, 24
layers, 16 heads x 128, seq 2048) — with random weights made from
`--seed`:

* train: `paddle_tpu.jit.TrainStep` over `GPTForCausalLM` +
  `GPTPretrainingCriterion` + `AdamW(moment_dtype="bfloat16")` under
  `amp.auto_cast(O1, bfloat16)`, flash attention, recompute every third
  block, batch 4 x 2048. Every loss finite, the last below the first, the
  attention path `pallas`.
* serve: `paddle_tpu.inference.LLMEngine` over the same architecture in
  bf16 with prefix caching on: twelve seeded requests of mixed prompt and
  generation length in three waves, the later ones sharing a prefix the
  first request has committed, so the ragged kernel really reads the
  pool. Every request finishes, none failed, every ragged executable on
  `pallas`, and the outputs are judged against a dense forward of the
  model and against `models.generation.generate()` on the same prompts
  by the rule `AGREEMENT_RULE` states — one that holds however bf16
  near-ties fall.
* --chips 4: the same TrainStep over a 2x2 ("dp", "mp") mesh of
  `jax.devices()` with `gpt_tp_rules`, against the same steps unsharded
  on one of the four devices: losses agree within `SHARDED_RTOL`, every
  parameter spans the mesh as its rule says, every device holds its
  share of bytes, the compiled step contains collectives.

One process, the only one that touches JAX; it starts none that would.
Earlier output lines are one JSON object each and are observations, not
benchmark numbers. The LAST line is the contract's

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

and is printed only when every check passed on a `tpu` platform; any
failure raises, the exit code is non-zero and that line is absent.
"""
from __future__ import annotations

import argparse
import gc
import json
import shutil
import time

import numpy as np

GPT_1P3B = dict(vocab_size=50304, hidden_size=2048, num_layers=24,
                num_heads=16, max_position_embeddings=2048,
                hidden_dropout_prob=0.0, attention_dropout_prob=0.0)
TRAIN = dict(batch=4, seq=2048, steps=4)     # 1 compiling + 3
ENGINE = dict(max_batch=8, block_size=64, decode_chunk=16,
              prompt_quantum=128, num_blocks=256)   # pool: 3.2 GB bf16
# (prompt length, generation length, shares the prefix) per wave. Prompt
# lengths stay within three values so the dense reference needs three
# prefill and three decode programs, not one per request.
PREFIX_LEN = 128
WAVES = (
    ((192, 96, True), (64, 64, False), (128, 128, False),
     (128, 160, False)),
    ((192, 64, True), (192, 128, True), (64, 96, False),
     (64, 64, False)),
    ((192, 64, True), (192, 96, True), (192, 64, True),
     (192, 128, True)),
)
# four steps as in TRAIN: at this learning rate a fresh model's loss
# falls, overshoots at the third step and falls again
SHARDED = dict(batch=4, seq=2048, steps=4)

# How the served tokens are judged. Greedy decoding in bf16 is chaotic:
# two implementations of one model (ragged Pallas attention over pages
# and a whole-pool decode scan, against dense cached attention; other
# matmul shapes, so another summation order; block sizes a timing sweep
# chose) round differently, a near-tie between the two best logits can
# fall either way, and from that token on the two continuations have
# different contexts and nothing in common. Token-for-token equality
# with `generate()` is therefore reported, not required. What is
# required holds however the ties fall: one dense forward of the model
# over each request's prompt + served tokens gives, at every generated
# position, the logit of the best token and of the served one, and
#  (1) every served token is the dense forward's best token or within
#      LOGIT_TIE of it, and
#  (2) where a request first differs from `generate()` — same context
#      up to there — generate()'s token is within LOGIT_TIE of the best
#      as well: the two parted at a tie, not at an error.
# LOGIT_TIE is in logit units; bf16 logits of this model around their
# maximum (~4 to 8) are 1/32 apart, so it allows eight such steps.
LOGIT_TIE = 0.25
AGREEMENT_RULE = {
    "served_token_within_logit_tie_of_dense_best": "every token",
    "first_difference_from_generate_is_a_logit_tie": "every request",
    "logit_tie": LOGIT_TIE}
# sharded against unsharded loss, each step: |a - b| <= SHARDED_RTOL * |b|
# (bf16 matmuls whose contraction is split over "mp" sum in another order)
SHARDED_RTOL = 2e-2


def emit(**record):
    print(json.dumps(record), flush=True)


def check(cond, message):
    if not cond:
        raise RuntimeError(f"chip_smoke: {message}")


def peak_bytes(device):
    return (device.memory_stats() or {}).get("peak_bytes_in_use")


def _sweep_summary():
    from paddle_tpu.kernels.pallas import autotune
    sweeps = autotune.drain_sweeps()
    errors = sorted({e for s in sweeps for e in s["errors"].values()})
    return {"tuning_sweeps": len(sweeps),
            "tuning_seconds": round(sum(s["seconds"] for s in sweeps), 3),
            "tuning_persisted": sum(bool(s["persisted"]) for s in sweeps),
            "tuning_winners": {"|".join(map(str, s["key"])): s["winner"]
                               for s in sweeps},
            "tuning_candidate_errors": errors[:4]}


def _build_train_step(model_kw, seed, **step_kw):
    import paddle_tpu as pt
    from paddle_tpu import amp
    from paddle_tpu.jit import TrainStep
    from paddle_tpu.models import GPTForCausalLM, GPTPretrainingCriterion
    from paddle_tpu.models.gpt import GPTConfig
    from paddle_tpu.optimizer import AdamW

    pt.seed(seed)
    cfg = GPTConfig(**model_kw, use_flash_attention=True, recompute=True,
                    recompute_interval=3)
    model = GPTForCausalLM(cfg)
    model.train()
    opt = AdamW(learning_rate=1e-4, parameters=model.parameters(),
                weight_decay=0.01, moment_dtype="bfloat16")
    crit = GPTPretrainingCriterion()

    def loss_fn(m, ids, labels):
        with amp.auto_cast(enable=True, level="O1", dtype="bfloat16"):
            logits = m(ids)
        return crit(logits, labels)

    return cfg, TrainStep(model, opt, loss_fn, **step_kw)


def _run_steps(step, cfg, batch, seq, steps, seed):
    """`steps` calls on one seeded batch; (losses, seconds per call).
    Each reading ends with the loss on the host."""
    rng = np.random.default_rng(seed)
    ids = rng.integers(0, cfg.vocab_size, (batch, seq)).astype(np.int32)
    labels = np.roll(ids, -1, axis=1)
    losses, seconds = [], []
    for _ in range(steps):
        t0 = time.perf_counter()
        losses.append(float(step(ids, labels).numpy()))
        seconds.append(time.perf_counter() - t0)
    check(all(np.isfinite(losses)), f"non-finite loss in {losses}")
    check(losses[-1] < losses[0],
          f"loss did not fall over {steps} steps: {losses}")
    return losses, seconds


def train_phase(model_kw, batch, seq, steps, seed, expect_path):
    import jax
    from paddle_tpu.kernels.pallas.flash_attention import attention_path
    from paddle_tpu.models.gpt import num_params

    cfg, step = _build_train_step(model_kw, seed)
    shape = (batch, seq, cfg.num_heads, cfg.head_dim)
    path, why = attention_path(shape, shape)
    check(path == expect_path,
          f"attention path for {shape} is {path!r} ({why}), "
          f"not {expect_path!r}")
    losses, seconds = _run_steps(step, cfg, batch, seq, steps, seed)
    emit(phase="train", params=num_params(cfg), batch=batch, seq=seq,
         steps=steps, attention_path=path,
         losses=[round(x, 4) for x in losses],
         first_call_seconds=round(seconds[0], 3),
         later_step_seconds=[round(s, 4) for s in seconds[1:]],
         peak_bytes_in_use=peak_bytes(jax.devices()[0]),
         **_sweep_summary())
    return losses


def _make_requests(waves, prefix_len, vocab, seed):
    """[[(rid, prompt, n_new)] per wave]; prompts marked as sharing start
    with the same `prefix_len` tokens."""
    rng = np.random.default_rng(seed)
    prefix = rng.integers(0, vocab, (prefix_len,)).astype(np.int32)
    out, rid = [], 0
    for wave in waves:
        reqs = []
        for plen, n_new, shares in wave:
            prompt = rng.integers(0, vocab, (plen,)).astype(np.int32)
            if shares:
                prompt[:prefix_len] = prefix
            reqs.append((rid, prompt, n_new))
            rid += 1
        out.append(reqs)
    return out


def _reference_outputs(model, requests):
    """{rid: tokens} from the dense `generate()`: one call per prompt
    length (a batch of equal-length prompts), each row cut to its own
    generation length — greedy, so a prefix of a longer run."""
    import paddle_tpu as pt
    from paddle_tpu.models.generation import generate
    by_len = {}
    for rid, prompt, n_new in requests:
        by_len.setdefault(len(prompt), []).append((rid, prompt, n_new))
    ref = {}
    for plen, group in sorted(by_len.items()):
        ids = np.stack([p for _rid, p, _n in group])
        out = generate(model, pt.to_tensor(ids),
                       max_new_tokens=max(n for _r, _p, n in group)).numpy()
        for row, (rid, _p, n_new) in zip(out, group):
            ref[rid] = row[plen:plen + n_new]
    return ref


def _dense_logits(model, sequences, starts, served, generated):
    """One dense forward (no cache, no pages) over `sequences` (prompt +
    served tokens each, right-padded: causal, so padding changes nothing
    before it). For every generated position j of row r — predicted from
    position starts[r] + j - 1 — the best logit and the logits of
    served[r][j] and generated[r][j], as float32 [rows, max generated]
    arrays (generated's only mean something up to the first difference:
    after it the context is the served one, not generate()'s)."""
    import jax
    import jax.numpy as jnp
    import paddle_tpu as pt
    from paddle_tpu.autograd import tape
    from paddle_tpu.jit import _collect_params, _functional_params

    rows, width = len(sequences), max(len(t) for t in served)
    length = -(-max(len(q) for q in sequences) // 128) * 128
    ids = np.zeros((rows, length), np.int32)
    pos = np.zeros((rows, width), np.int32)
    tok = np.zeros((2, rows, width), np.int32)
    for r, seq in enumerate(sequences):
        ids[r, :len(seq)] = seq
        n = len(served[r])
        pos[r, :n] = starts[r] + np.arange(n) - 1
        tok[0, r, :n], tok[1, r, :n] = served[r], generated[r]
    _, ptensors, _, btensors = _collect_params(model)
    tensors = ptensors + btensors

    @jax.jit
    def forward(params, ids, pos, tok):
        with tape.no_grad(), _functional_params(tensors, params):
            logits = model(pt.Tensor._wrap(ids))._data
        at = jnp.take_along_axis(logits, pos[:, :, None], axis=1).astype(
            jnp.float32)                        # [rows, width, vocab]
        pick = jnp.take_along_axis(
            at[None], tok[:, :, :, None], axis=3)[..., 0]
        return at.max(-1), pick[0], pick[1]

    return [np.asarray(a) for a in forward(
        [t._data for t in tensors], ids, pos, tok)]


def serve_phase(model_kw, engine_kw, waves, prefix_len, seed, expect_path):
    import jax
    import paddle_tpu as pt
    from paddle_tpu.inference import LLMEngine
    from paddle_tpu.models import GPTForCausalLM
    from paddle_tpu.models.gpt import GPTConfig

    cxx = shutil.which("g++")
    check(cxx is not None,
          "g++ not found: the paged KV cache compiles its block allocator "
          "(inference/_block_allocator.cpp) on first use and has no other "
          "implementation")
    pt.seed(seed + 1)
    cfg = GPTConfig(**model_kw)
    model = GPTForCausalLM(cfg).bfloat16()
    model.eval()
    engine = LLMEngine(model, max_model_len=cfg.max_position_embeddings,
                       enable_prefix_caching=True, **engine_kw)
    wave_reqs = _make_requests(waves, prefix_len, cfg.vocab_size, seed)
    requests = [r for wave in wave_reqs for r in wave]

    results, building, steady = {}, 0.0, []
    t_serve = time.perf_counter()
    pending = list(wave_reqs)
    while pending or engine.has_unfinished:
        if pending:
            # one wave before each of the first steps: a later wave is
            # admitted after the step that committed the earlier wave's
            # prefix pages
            for rid, prompt, n_new in pending.pop(0):
                engine.add_request(rid, prompt, max_new_tokens=n_new)
        n_exec = len(engine._fns)
        t0 = time.perf_counter()
        for r in engine.step():
            results[r.request_id] = r
        dt = time.perf_counter() - t0
        if len(engine._fns) > n_exec:
            building += dt      # a step that compiled (and tuned)
        else:
            steady.append(dt)
    serve_seconds = time.perf_counter() - t_serve

    failed = {rid: (r.finish_reason, r.error)
              for rid, r in results.items() if not r.ok}
    check(not failed, f"requests failed: {failed}")
    check(len(results) == len(requests),
          f"{len(results)} of {len(requests)} requests finished")
    for rid, _p, n_new in requests:
        check(len(results[rid].output_ids) == n_new,
              f"request {rid} produced {len(results[rid].output_ids)} "
              f"tokens, not {n_new}")
    paths = {str(k): v[0] for k, v in engine._ragged_paths.items()}
    check(paths and all(p == expect_path for p in paths.values()),
          f"ragged executables not all on {expect_path!r}: "
          f"{engine._ragged_paths}")
    check(any(k[2] for k in engine._ragged_paths),
          f"no with_pool=True ragged executable was built: {paths}")
    check(engine.stats["prefix_cache_hit_tokens"] > 0,
          "no request was served from cached prefix pages")
    sweeps = _sweep_summary()

    t0 = time.perf_counter()
    ref = _reference_outputs(model, requests)
    served = [results[rid].output_ids for rid, _p, _n in requests]
    generated = [ref[rid] for rid, _p, _n in requests]
    best, l_served, l_generated = _dense_logits(
        model, [np.concatenate([p, out]) for (_r, p, _n), out
                in zip(requests, served)],
        [len(p) for _r, p, _n in requests], served, generated)
    reference_seconds = time.perf_counter() - t0

    first_diff, served_gap, parted_gap = [], 0.0, 0.0
    for r, (out, gen) in enumerate(zip(served, generated)):
        n = len(out)
        served_gap = max(served_gap, float((best[r, :n]
                                            - l_served[r, :n]).max()))
        differs = np.flatnonzero(out != gen)
        first_diff.append(int(differs[0]) if len(differs) else None)
        if len(differs):
            d = differs[0]
            parted_gap = max(parted_gap, float(best[r, d]
                                               - l_generated[r, d]))
    equal = sum(int((o == g).sum()) for o, g in zip(served, generated))
    total = sum(n for _r, _p, n in requests)
    emit(phase="serve", cxx=cxx, requests=len(requests),
         finished=len(results), failed=len(failed),
         tokens_generated=total, ragged_paths=paths,
         ragged_launches=engine.stats["ragged_launches"],
         decode_chunks=engine.stats["decode_chunks"],
         prefix_cache_hit_tokens=engine.stats["prefix_cache_hit_tokens"],
         serve_seconds=round(serve_seconds, 3),
         steps_that_compiled_seconds=round(building, 3),
         steady_steps=len(steady),
         steady_step_seconds_median=(round(float(np.median(steady)), 4)
                                     if steady else None),
         reference_seconds=round(reference_seconds, 3),
         agreement_rule=AGREEMENT_RULE,
         served_tokens_not_dense_best=int(sum(
             (best[r, :len(o)] > l_served[r, :len(o)]).sum()
             for r, o in enumerate(served))),
         max_gap_served_to_dense_best=round(served_gap, 4),
         max_gap_generate_to_dense_best_where_parted=round(parted_gap, 4),
         dense_best_logit_range=[round(float(best.min()), 3),
                                 round(float(best.max()), 3)],
         first_difference_from_generate_at=first_diff,
         share_of_tokens_equal_to_generate=round(equal / total, 4),
         peak_bytes_in_use=peak_bytes(jax.devices()[0]), **sweeps)
    check(served_gap <= LOGIT_TIE,
          f"a served token is {served_gap:.3f} below the dense forward's "
          f"best logit (tie: {LOGIT_TIE})")
    check(parted_gap <= LOGIT_TIE,
          f"a request parts from generate() where generate()'s token is "
          f"{parted_gap:.3f} below the dense best (tie: {LOGIT_TIE})")
    return equal / total


def sharded_phase(model_kw, batch, seq, steps, seed, devices):
    """The TrainStep over a 2x2 ("dp", "mp") mesh of `devices` against
    the same steps unsharded on devices[0], one after the other: the
    unsharded 1.3B step fills most of one chip, so its model and
    optimizer state are released before the sharded one is built."""
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from paddle_tpu.models.shard_plans import gpt_tp_rules

    check(len(devices) == 4, f"need 4 devices, have {len(devices)}")
    cfg, step = _build_train_step(model_kw, seed)
    ref_losses, ref_seconds = _run_steps(step, cfg, batch, seq, steps, seed)
    ref_sweeps = _sweep_summary()
    del step
    gc.collect()

    mesh = Mesh(np.array(devices).reshape(2, 2), ("dp", "mp"))
    cfg, step = _build_train_step(
        model_kw, seed, mesh=mesh, shard_param=gpt_tp_rules,
        shard_data=P("dp", None))
    losses, seconds = _run_steps(step, cfg, batch, seq, steps, seed)
    for a, b in zip(losses, ref_losses):
        check(abs(a - b) <= SHARDED_RTOL * abs(b),
              f"sharded losses {losses} against unsharded {ref_losses}: "
              f"beyond rtol {SHARDED_RTOL}")

    # what one chip cannot show
    mesh_devices = set(devices)
    n_split = 0
    for name, p in zip(step._pnames, step.params):
        spec = gpt_tp_rules(name, tuple(p.shape))
        check(p.sharding.device_set == mesh_devices,
              f"{name} lives on {p.sharding.device_set}, not on the mesh")
        check(p.sharding.is_equivalent_to(NamedSharding(mesh, spec), p.ndim),
              f"{name} is sharded {p.sharding}, its rule says {spec}")
        want = tuple(d // (2 if ax == "mp" else 1) for d, ax in
                     zip(p.shape, tuple(spec) + (None,) * p.ndim))
        check(p.addressable_shards[0].data.shape == want,
              f"{name}: shard shape {p.addressable_shards[0].data.shape}, "
              f"expected {want}")
        n_split += any(ax is not None for ax in spec)
    check(n_split > 0, "no parameter is split over the mesh")
    # bytes of parameters and optimizer state each device holds, as the
    # arrays' own shards say, and as the runtime counts where it does
    held = dict.fromkeys(devices, 0)
    for arr in step.params + [v for st in step.opt_states
                              for v in st.values()]:
        for shard in arr.addressable_shards:
            held[shard.device] += shard.data.nbytes
    held = [held[d] for d in devices]
    in_use = [(d.memory_stats() or {}).get("bytes_in_use") for d in devices]
    for counts in (held, in_use):
        check(None in counts or min(counts) >= 0.1 * sum(counts),
              f"a device holds no real share of the bytes: {counts}")
    text = step._step_fn.fn.as_text()
    collectives = {op: text.count(op + "(") + text.count(op + "-start(")
                   for op in ("all-reduce", "all-gather", "reduce-scatter",
                              "all-to-all", "collective-permute")}
    check(sum(collectives.values()) > 0,
          "the compiled sharded step contains no collective")
    check("tpu_custom_call" in text or jax.default_backend() != "tpu",
          "the compiled sharded step contains no Pallas kernel")
    emit(phase="sharded_train", mesh={"dp": 2, "mp": 2}, batch=batch,
         seq=seq, steps=steps,
         losses_sharded=[round(x, 4) for x in losses],
         losses_unsharded=[round(x, 4) for x in ref_losses],
         rtol=SHARDED_RTOL,
         max_rel_diff=round(max(abs(a - b) / abs(b) for a, b
                                in zip(losses, ref_losses)), 6),
         params_split=n_split, params_total=len(step.params),
         state_bytes_per_device=held, bytes_in_use_per_device=in_use,
         collectives=collectives,
         first_call_seconds={"unsharded": round(ref_seconds[0], 3),
                             "sharded": round(seconds[0], 3)},
         later_step_seconds={
             "unsharded": [round(s, 4) for s in ref_seconds[1:]],
             "sharded": [round(s, 4) for s in seconds[1:]]},
         peak_bytes_in_use=[peak_bytes(d) for d in devices],
         tuning_unsharded=ref_sweeps, tuning_sharded=_sweep_summary())


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: the sharded train step against the "
                         "unsharded one, and no one-chip phase")
    ap.add_argument("--seed", type=int, default=0,
                    help="weights, data and requests are made from it")
    args = ap.parse_args(argv)

    import jax
    import jaxlib
    from paddle_tpu.utils.runtime_env import use_compile_cache

    cache_dir = use_compile_cache()
    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    if device["platform"] != "tpu":
        raise SystemExit(
            f"chip_smoke: needs a TPU, found {device}; run it through the "
            "chip tool")
    check(len(devices) == args.chips,
          f"--chips {args.chips} but jax reports {len(devices)} devices")
    try:
        from importlib.metadata import version
        libtpu = version("libtpu")
    except Exception:       # the distribution's name is not jax's to keep
        libtpu = None
    emit(phase="env", jax=jax.__version__, jaxlib=jaxlib.__version__,
         libtpu=libtpu, device=device, compile_cache_dir=cache_dir,
         seed=args.seed)

    if args.chips == 4:
        sharded_phase(GPT_1P3B, seed=args.seed, devices=devices, **SHARDED)
    else:
        train_phase(GPT_1P3B, seed=args.seed, expect_path="pallas", **TRAIN)
        gc.collect()        # the train state leaves before the server comes
        serve_phase(GPT_1P3B, ENGINE, WAVES, PREFIX_LEN, seed=args.seed,
                    expect_path="pallas")
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()
