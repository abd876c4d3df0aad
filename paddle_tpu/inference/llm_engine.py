"""Continuous-batching LLM serving engine over the paged KV cache.

This is THE serving path (VERDICT r4 next-2): the runtime the reference
builds around `block_multihead_attention` + `fused_multi_transformer`
(ref: python/paddle/incubate/nn/functional/block_multihead_attention.py:19
— its block tables / seq_lens operands exist exactly to drive a loop like
this one; paddle's inference serving stack wires them the same way).

TPU-native design — the scheduler is host Python + the native block
allocator; every device step is ONE cached XLA executable:

  * Paged pool: `PagedKVCache` (native C++ free-list allocator) holds one
    fixed [num_blocks, kvH, block_size, D] pool per layer. Sequences
    lease pages on admission, grow by chunks, free at EOS — HBM is
    shared across sequences of different lengths instead of padded to a
    uniform max (the entire point of paging).
  * Admission / preemption: requests queue up; a request is admitted
    when a batch slot and its prompt's pages are available. If the pool
    runs dry mid-decode, the most-recently admitted sequence is
    preempted (pages freed, request re-queued for re-prefill with its
    generated tokens carried along) — the vLLM-style recompute policy,
    matching the reference scheduler's behavior under cache pressure.
  * Ragged packed prefill/verify: every token-computing launch — a
    fresh prompt's suffix, a prefix-resume tail, a speculative verify
    window — packs its rows into ONE [total_tokens] stream with
    per-token (row, position) metadata and runs the
    `kernels.pallas.ragged_paged_attention` family (`engine_ragged`):
    mixed rows of arbitrary per-row lengths in one launch, bucketed
    ONLY on total-token count. Decode runs the WHOLE batch one chunk
    (`decode_chunk` tokens) per executable call as a `lax.scan` with
    every layer's paged attention inside — caches donated, so XLA
    updates the pool in place; k/v writes stage in a small
    [L, B, chunk] side buffer and merge with ONE flat token-major
    scatter per cache at chunk end, so the pool is never both
    scattered-into and read in the same scan body (the aliasing
    hazard that used to cost a full pool copy per step). Between
    chunks the host syncs only [B, chunk] int32 tokens.
  * Step shapes are bucketed (ragged total-token buckets, power-of-two
    chunk buckets) so the number of compiled executables stays O(log +
    linear/quantum) while attention reads scale with the CURRENT
    longest sequence, not the model maximum.
  * Automatic prefix caching (enable_prefix_caching, default on): full
    prompt blocks are content-hashed in the PagedKVCache; a request
    sharing a page-aligned prefix with earlier traffic (system prompt,
    few-shot template, its own pre-preemption context) leases the
    already-computed pages at +1 refcount and prefills only its
    uncached tail through a prefix-resume executable that reads the
    cached prefix from the pool. Pages of finished sequences park in
    an LRU, evicted only when an alloc would otherwise fail — greedy
    outputs are bit-identical with caching on or off.
"""
from __future__ import annotations

import collections
import dataclasses
import time
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..core.tensor import Tensor
from ..observability import flight as _fl
from ..observability import metrics as _om
from ..observability import perf as _pf
from ..observability import tracing as _ot
from ..resilience import faults
from .paged_cache import PagedKVCache
from .speculative import accept_drafts

__all__ = ["LLMEngine", "GenerationResult"]


# ---------------------------------------------------------------------------
# observability (process-global series; per-engine exact counts live on
# engine.stats). Handles are created once and cached — the disabled
# path through any of them is a single module-flag check.
# ---------------------------------------------------------------------------
_METRICS = None


def _metrics():
    global _METRICS
    if _METRICS is None:
        r = _om.registry()
        _METRICS = {
            "step": r.histogram(
                "paddle_tpu_engine_step_seconds",
                "LLMEngine.step() wall time (admission + prefills + one "
                "decode chunk + retirement)"),
            "prefill": r.histogram(
                "paddle_tpu_engine_prefill_seconds",
                "one batched prefill executable call incl. host prep"),
            "decode": r.histogram(
                "paddle_tpu_engine_decode_chunk_seconds",
                "one decode-chunk executable call incl. host prep"),
            "queue": r.gauge(
                "paddle_tpu_engine_queue_depth",
                "requests per scheduler queue after a step",
                ("queue",)),
            "pool": r.gauge(
                "paddle_tpu_engine_page_pool_blocks",
                "paged KV cache pool occupancy after a step",
                ("state",)),
            "events": r.counter(
                "paddle_tpu_engine_events_total",
                "engine.stats counters (preemptions, prefills, "
                "decode_chunks, decode_tokens, failed/rejected "
                "requests, deadline_expired) aggregated across engines",
                ("event",)),
            "prefix": r.counter(
                "paddle_tpu_engine_prefix_cache_tokens_total",
                "prompt tokens served from the prefix cache (hit) vs "
                "prefilled from scratch (miss), counted at admission",
                ("outcome",)),
            "spec": r.counter(
                "paddle_tpu_engine_spec_tokens_total",
                "speculative draft tokens by verification outcome: "
                "accepted = matched the target model's greedy pick "
                "and committed in bulk, rejected = rolled back (KV "
                "truncated, pages unref'd)",
                ("outcome",)),
            "spec_rate": r.gauge(
                "paddle_tpu_engine_spec_acceptance_ratio",
                "cumulative fraction of drafted tokens accepted by "
                "verification (accepted / drafted), updated after "
                "every verify step"),
            "verify": r.histogram(
                "paddle_tpu_engine_verify_seconds",
                "one speculative verify executable call (k+1 "
                "positions per row) incl. host prep"),
            "ragged": r.histogram(
                "paddle_tpu_engine_ragged_seconds",
                "one ragged packed-batch executable call (mixed "
                "prefill/prefix-resume/verify rows in a single "
                "launch) incl. host prep"),
            "prefix_pages": r.gauge(
                "paddle_tpu_engine_prefix_cache_pages",
                "prefix-cache page index occupancy after a step: "
                "indexed = hash-addressable pages (leased or parked), "
                "lru = parked cached-but-unreferenced pages",
                ("state",)),
            # -- request-scoped SLO series (one observation per
            # request-lifecycle event; request identity stays in trace
            # spans, never in labels) --
            "ttft": r.histogram(
                "paddle_tpu_request_ttft_seconds",
                "per-request time to first token: enqueue -> first "
                "sampled token (includes queue wait and prefill)"),
            "ttft_budget": r.histogram(
                "paddle_tpu_request_ttft_budget_seconds",
                "per-request TTFT latency-budget decomposition, one "
                "observation per component when the first token lands:"
                " queue_wait = (re)enqueue -> admission, summed across"
                " requeues; prefill_compute = first-build prefill wall"
                " the request rode; affinity_miss = re-prefill wall "
                "spent REBUILDING context the fleet had already "
                "computed (preemption resume, or a router re-serve/"
                "failover landing off the request's warm replica); "
                "compile_stall = ragged-executable compile wall the "
                "request waited behind; other = the remainder "
                "(scheduler overhead + time burned by a failed-over "
                "life). Components sum to the request's "
                "paddle_tpu_request_ttft_seconds observation",
                ("component",)),
            "tpot": r.histogram(
                "paddle_tpu_request_tpot_seconds",
                "per-request mean inter-token latency over the decode "
                "phase, observed once per finished request"),
            "queue_wait": r.histogram(
                "paddle_tpu_request_queue_wait_seconds",
                "time from (re)enqueue to admission into a batch slot "
                "(observed per admission, incl. post-preemption "
                "resumes)"),
            "e2e": r.histogram(
                "paddle_tpu_request_e2e_seconds",
                "end-to-end latency of successfully finished requests "
                "(enqueue -> eos/length)"),
            "req_finished": r.counter(
                "paddle_tpu_request_finished_total",
                "terminal request outcomes by finish_reason",
                ("reason",)),
            # -- HBM telemetry (compile telemetry: the shared
            # _om.compile_metrics() registration) --
            "hbm_pool": r.gauge(
                "paddle_tpu_hbm_page_pool_bytes",
                "paged KV pool HBM after a step: reserved = the whole "
                "pool allocation, used = currently leased pages",
                ("state",)),
            "hbm_live": r.gauge(
                "paddle_tpu_hbm_live_array_bytes",
                "total bytes of live jax arrays in the process, "
                "sampled at engine step boundaries (throttled to at "
                "most one walk per second)"),
        }
        _METRICS["compiles"], _METRICS["compile_time"] = \
            _om.compile_metrics()
    return _METRICS


# first-call compile shim: timing + cost-model telemetry by executable
# family. Grown from the engine-local PR 4 class into the shared
# observability.perf.CompileTimed (TrainStep uses the same shim) —
# the first call goes through the AOT path so the compiled executable
# yields its cost_analysis()/memory_analysis() expectation, carried on
# `.expected` for the roofline accounting at the launch sites.
_CompileTimed = _pf.CompileTimed


class _EngineStats(dict):
    """The ad-hoc stats dict, migrated onto the registry while staying
    a real dict: every increment site (`stats[k] += n`) keeps its exact
    per-engine semantics (tests and chip_smoke.py read those), and the write
    mirrors the delta onto the process-global
    `paddle_tpu_engine_events_total{event=k}` counter. Mirroring is a
    no-op while observability is disabled — per-engine counts keep
    working regardless. The prefix-cache token tallies are NOT mirrored:
    they already land on the dedicated
    `paddle_tpu_engine_prefix_cache_tokens_total{outcome=}` counter, and
    double-exporting them would let token volumes swamp the event
    series. The speculative-decoding token tallies are unmirrored for
    the same reason (dedicated
    `paddle_tpu_engine_spec_tokens_total{outcome=}` counter)."""

    _UNMIRRORED = frozenset(
        ("prefix_cache_hit_tokens", "prefix_cache_miss_tokens",
         "spec_drafted_tokens", "spec_accepted_tokens"))

    def __setitem__(self, key, value):
        if _om._ENABLED and key not in self._UNMIRRORED:
            delta = value - self.get(key, 0)
            if delta > 0:
                _metrics()["events"].labels(event=key).inc(delta)
        super().__setitem__(key, value)


@dataclasses.dataclass
class GenerationResult:
    request_id: object
    prompt_ids: np.ndarray
    output_ids: np.ndarray          # generated tokens (no prompt)
    finish_reason: str   # "eos" | "length" | "error" | "deadline" |
                         # "rejected" | "aborted"
    error: Optional[str] = None     # failure detail when not ok

    @property
    def ok(self) -> bool:
        return self.finish_reason in ("eos", "length")


@dataclasses.dataclass(eq=False)        # identity eq: field-comparing
class _Request:                         # ndarray prompts would make
                                        # waiting.remove() ambiguous
    rid: object
    prompt: np.ndarray                       # int32 [prompt_len]
    max_new_tokens: int                      # TOTAL generation budget
    resume_out: List[int] = dataclasses.field(default_factory=list)
    deadline: Optional[float] = None         # absolute monotonic seconds
    hash_chain: Optional[list] = None        # memoized block_hashes()
    # request-scoped observability: one trace per request lifetime —
    # the ids and timestamps survive preemption/requeue so the resumed
    # spans join the ORIGINAL trace and TTFT/e2e stay anchored at the
    # first enqueue
    trace_id: Optional[str] = None
    root_span: Optional[str] = None
    t_enq: float = 0.0                       # first enqueue (perf_counter)
    t_queued: float = 0.0                    # latest (re)enqueue
    t_first: Optional[float] = None          # first token landed
    # TTFT latency-budget accumulators (seconds; see the
    # paddle_tpu_request_ttft_budget_seconds registration). They ride
    # preemption requeues like the trace identity does, so the final
    # observation covers every life of the request in THIS engine.
    # recompute: this life re-builds context a replica had already
    # computed (preempt resume / router re-serve) — its prefill wall
    # charges to affinity_miss instead of prefill_compute.
    bud_queue: float = 0.0
    bud_prefill: float = 0.0
    bud_miss: float = 0.0
    bud_compile: float = 0.0
    recompute: bool = False

    @property
    def context_len(self) -> int:
        """Tokens the prefill must (re)build: prompt + resumed output."""
        return len(self.prompt) + len(self.resume_out)


class _Seq:
    __slots__ = ("rid", "prompt", "max_new", "slot", "length", "out",
                 "admit_seq", "deadline", "cached_len", "trace_id",
                 "root_span", "t_enq", "t_first", "bud_queue",
                 "bud_prefill", "bud_miss", "bud_compile", "recompute")

    def __init__(self, req: _Request, slot: int, admit_seq: int):
        self.rid = req.rid
        self.prompt = req.prompt
        self.max_new = req.max_new_tokens
        self.slot = slot
        self.length = 0                 # tokens currently in the cache
        self.out: List[int] = list(req.resume_out)
        self.admit_seq = admit_seq      # monotonic admission order
        self.deadline = req.deadline
        self.cached_len = 0             # prefix tokens leased from cache
        self.trace_id = req.trace_id    # request trace (see _Request)
        self.root_span = req.root_span
        self.t_enq = req.t_enq
        self.t_first = req.t_first
        self.bud_queue = req.bud_queue  # TTFT budget (see _Request)
        self.bud_prefill = req.bud_prefill
        self.bud_miss = req.bud_miss
        self.bud_compile = req.bud_compile
        self.recompute = req.recompute or bool(req.resume_out)

    @property
    def token_budget(self) -> int:
        """Max cache tokens this sequence can ever occupy — the bound
        add_request validated against the pool."""
        return len(self.prompt) + self.max_new


def _bucket(n: int, quantum: int) -> int:
    return max(quantum, ((n + quantum - 1) // quantum) * quantum)


def _pow2_ceil(n: int) -> int:
    b = 1
    while b < n:
        b *= 2
    return b


def _pow2_floor(n: int) -> int:
    b = 1
    while b * 2 <= n:
        b *= 2
    return b


# ---------------------------------------------------------------------------
# family adapters: per-model packed-qkv / attention-output plumbing
# ---------------------------------------------------------------------------
class _GPTFamily:
    """GPT: fused qkv projection, learned position embeddings, no rope."""

    needs_rope = False

    def __init__(self, model):
        self.model = model
        cfg = model.config
        self.kv_heads = cfg.num_heads
        self.head_dim = cfg.head_dim

    def embed(self, ids, pos):
        """ids/pos int32 [...] -> [..., hidden] (dropout-free: serving)."""
        emb = self.model.gpt.embeddings
        we = emb.word_embeddings.weight._data
        pe = emb.position_embeddings.weight._data
        return we[ids] + pe[pos]

    def layers(self):
        return list(self.model.gpt.layers)

    def qkv(self, layer, x):
        """x: Tensor [T, hidden] -> packed [T, (H+2kvH)*D] array (the
        fused projection already emits q∥k∥v blocks in order)."""
        h = layer.ln1(x)
        return layer.attn.qkv_proj(h)._data

    def attn_out(self, layer, x, o):
        return x + layer.attn.out_proj(Tensor._wrap(o))

    def mlp(self, layer, x):
        return x + layer.mlp(layer.ln2(x))

    def final(self, x):
        return self.model.gpt.final_norm(x)

    def logits(self, x):
        return self.model.lm_logits(x)


class _LlamaFamily:
    """LLaMA: split q/k/v (GQA cache un-repeated), RMSNorm, rotary via
    the attention op's rope_emb operand (neox/half-split layout)."""

    needs_rope = True

    def __init__(self, model):
        self.model = model
        cfg = model.config
        self.kv_heads = cfg.num_kv_heads
        self.head_dim = cfg.head_dim

    def rope_tables(self, max_len):
        from ..models.llama import _rope_cos_sin
        cfg = self.model.config
        cos, sin = _rope_cos_sin(max_len, cfg.head_dim, cfg.rope_theta,
                                 jnp.float32)
        d2 = cfg.head_dim // 2
        return jnp.stack([cos[:, :d2], sin[:, :d2]])   # [2, L, D//2]

    def embed(self, ids, pos):
        return self.model.llama.embed_tokens.weight._data[ids]

    def layers(self):
        return list(self.model.llama.layers)

    def qkv(self, layer, x):
        h = layer.input_layernorm(x)
        a = layer.self_attn
        return jnp.concatenate(
            [a.q_proj(h)._data, a.k_proj(h)._data, a.v_proj(h)._data],
            axis=-1)

    def attn_out(self, layer, x, o):
        return x + layer.self_attn.o_proj(Tensor._wrap(o))

    def mlp(self, layer, x):
        return x + layer.mlp(layer.post_attention_layernorm(x))

    def final(self, x):
        return self.model.llama.norm(x)

    def logits(self, x):
        return self.model.lm_head(x)


def _family_for(model):
    if hasattr(model, "gpt"):
        return _GPTFamily(model)
    if hasattr(model, "llama"):
        return _LlamaFamily(model)
    raise NotImplementedError(
        "LLMEngine supports the GPT and LLaMA families; add a family "
        "adapter in inference/llm_engine.py for other models")


def calibrate_kv_scales(model, sample_ids):
    """Per-layer, per-kv-head int8 quant scales (127/amax) from one
    dense forward over a representative prompt — the static-scale
    calibration the reference's cache_k/v_quant_scales operands expect
    (ref: block_multihead_attention.py:19 signature).

    sample_ids: int array [b, s]. Returns (k_scales, v_scales), each
    [num_layers, kv_heads] float32."""
    from ..models.generation import _family
    cache_builder, fwd_fn, emb_dtype = _family(model)
    ids = np.asarray(
        sample_ids.numpy() if isinstance(sample_ids, Tensor)
        else sample_ids, dtype=np.int32)
    b, s = ids.shape
    caches = cache_builder(model, b, s, emb_dtype)
    was_training = model.training
    model.eval()
    try:
        _, caches = fwd_fn(model, Tensor._wrap(jnp.asarray(ids)), caches,
                           0)
    finally:
        if was_training:
            model.train()
    ks, vs = [], []
    for c in caches:
        # cache layout [b, max_len, kv_heads, head_dim]
        amax_k = jnp.max(jnp.abs(c["k"].astype(jnp.float32)),
                         axis=(0, 1, 3))
        amax_v = jnp.max(jnp.abs(c["v"].astype(jnp.float32)),
                         axis=(0, 1, 3))
        ks.append(127.0 / jnp.maximum(amax_k, 1e-6))
        vs.append(127.0 / jnp.maximum(amax_v, 1e-6))
    return (np.asarray(jnp.stack(ks), np.float32),
            np.asarray(jnp.stack(vs), np.float32))


def _pool_decode_attention(q, kpool, vpool, block_off, lens, scale,
                           block_size, kdq=None, vdq=None):
    """One-token-per-row attention against the ENTIRE paged pool.

    TPU-native paged decode: instead of gathering each row's pages into
    a per-row [B, C, ...] context (a big materialised copy whose reads
    scale with B x padded-length), the query batch einsums against the
    token-major pool ONCE — [NB*bs, kvH, D] streams from HBM straight
    into the MXU, so cache traffic per step is the POOL size (== sum of
    live context at full occupancy, the same bytes a dense batch reads)
    and the scores against non-owned pool rows are masked out. Decode
    is HBM-bound with the MXU idle, so the wasted FLOPs are free.

    q: [B, H, D] (current token per row, already written to the pool);
    kpool/vpool: [NB*bs, kvH, D] token-major; block_off: [B, NB] int32
    — block's start position within row b's sequence, or -1 when not
    owned by row b; lens: [B] int32, attend to positions <= lens[b].
    Int8 pools: per-kv-head dequant scales fold into the (tiny)
    score/output tensors — the pool is read as int8."""
    B, H, D = q.shape
    T, kvH, _ = kpool.shape
    rep = H // kvH
    q4 = (q.astype(jnp.float32) * scale).reshape(B, kvH, rep, D)
    if kpool.dtype == jnp.int8:
        # int8 pools: correctness-first upcast (the capacity win — 2x
        # sequences per pool — is the point; see test_kv_int8)
        s = jnp.einsum("bkrd,tkd->bkrt", q4,
                       kpool.astype(jnp.float32),
                       preferred_element_type=jnp.float32)
    else:
        s = jnp.einsum("bkrd,tkd->bkrt", q4.astype(kpool.dtype),
                       kpool, preferred_element_type=jnp.float32)
    if kdq is not None:
        s = s * kdq[None, :, None, None]
    # pool row t belongs to block t//bs at slot t%bs
    toff = jnp.repeat(block_off, block_size, axis=1)       # [B, T]
    gpos = toff + jnp.tile(jnp.arange(block_size, dtype=jnp.int32),
                           T // block_size)[None, :]
    valid = (toff >= 0) & (gpos <= lens[:, None])          # [B, T]
    s = jnp.where(valid[:, None, None, :], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    if vpool.dtype == jnp.int8:
        out = jnp.einsum("bkrt,tkd->bkrd", p,
                         vpool.astype(jnp.float32),
                         preferred_element_type=jnp.float32)
    else:
        out = jnp.einsum("bkrt,tkd->bkrd", p.astype(vpool.dtype),
                         vpool, preferred_element_type=jnp.float32)
    if vdq is not None:
        out = out * vdq[None, :, None, None]
    return out.reshape(B, H * D)


# ---------------------------------------------------------------------------
# engine
# ---------------------------------------------------------------------------
class LLMEngine:
    """Continuous-batching serving engine (paged KV cache runtime).

    Usage:
        engine = LLMEngine(model, max_batch=8, num_blocks=256)
        engine.add_request("a", prompt_ids, max_new_tokens=64)
        while engine.has_unfinished:
            for r in engine.step():
                ... r.output_ids ...
    or simply `results = engine.generate(prompts, max_new_tokens=64)`.
    """

    def __init__(self, model, max_batch: int = 8,
                 num_blocks: Optional[int] = None, block_size: int = 64,
                 max_model_len: Optional[int] = None,
                 decode_chunk: int = 8, prompt_quantum: int = 128,
                 do_sample: bool = False, temperature: float = 1.0,
                 top_p: float = 1.0, top_k: int = 0,
                 eos_token_id: Optional[int] = None,
                 seed: int = 0, kv_quant_scales=None,
                 shed_load: bool = False,
                 max_waiting: Optional[int] = None,
                 step_timeout_s: Optional[float] = None,
                 enable_prefix_caching: bool = True,
                 speculative_config=None,
                 mesh=None, shard_param=None,
                 exec_cache_dir: Optional[str] = None):
        """enable_prefix_caching (default on): full prompt blocks are
        hash-indexed so requests sharing a page-aligned prefix (system
        prompts, few-shot templates, multi-turn history) lease the
        already-computed KV pages and prefill only their tail; pages of
        finished sequences are retained in an LRU evicted only under
        pool pressure. Greedy outputs are unchanged either way — set
        False to force every request to prefill from scratch.

        speculative_config: an `inference.SpeculativeConfig` turns on
        speculative decoding — a draft proposer guesses up to k tokens
        per sequence per step, one batched verify executable scores all
        k+1 positions, the matching prefix commits in bulk, and the
        first mismatch rolls the KV lease back. Greedy outputs stay
        bit-identical with speculation on or off (greedy decoding
        only: do_sample=True is refused).

        mesh/shard_param: tensor-parallel placement — a
        `jax.sharding.Mesh` (typically a sub-mesh, so one logical
        replica spans several devices) plus a
        `(name, shape) -> PartitionSpec` rule table (e.g.
        `models.shard_plans.gpt_tp_rules`). Params are device_put per
        rule; the paged pool, rope tables and quant scales replicate
        over the same mesh so every executable sees mesh-consistent
        operands. Greedy outputs are unchanged up to XLA reduction
        order for the same mesh shape.

        exec_cache_dir (default: $PADDLE_TPU_EXEC_CACHE, unset = off):
        persistent AOT executable store (`inference.exec_cache`).
        Every `_fns` entry is keyed by a sha256 over the engine's
        structural configuration + device/topology/jax fingerprint +
        package source hash; first calls consult the store before
        lowering and park fresh compiles back, so a crash-restarted
        replica reintegrates WARM (outcome=disk_hit on
        `paddle_tpu_compile_total`) instead of recompiling the zoo."""
        # fleet identity plumbing: a bare engine process ships its
        # series as process_role="engine" (weak suggestion — an
        # enclosing Router or an explicit set_identity outranks it)
        from ..observability import fleet as _ofleet
        _ofleet.suggest_role("engine")
        cfg = model.config
        self.model = model
        self.fam = _family_for(model)
        self.max_batch = int(max_batch)
        self.block_size = int(block_size)
        self.max_model_len = int(max_model_len
                                 or cfg.max_position_embeddings)
        self.npb_full = -(-self.max_model_len // self.block_size)
        if num_blocks is None:
            # enough for every slot at full length, plus the trash page
            num_blocks = self.max_batch * self.npb_full + 1
        self.decode_chunk = int(decode_chunk)
        self.prompt_quantum = int(prompt_quantum)
        self.do_sample = bool(do_sample)
        self.temperature = float(temperature)
        self.top_p = float(top_p)
        self.top_k = int(top_k)
        self.eos_token_id = eos_token_id
        self._key = jax.random.PRNGKey(seed)

        model.eval()
        emb_dtype = self.fam.embed(jnp.zeros((1,), jnp.int32),
                                   jnp.zeros((1,), jnp.int32)).dtype
        # int8 paged pool: per-layer per-kv-head static scales (see
        # calibrate_kv_scales) halve cache HBM -> ~2x sequences per pool
        self._kq = self._vq = None
        cache_dtype = emb_dtype
        if kv_quant_scales is not None:
            kq, vq = kv_quant_scales
            self._kq = jnp.asarray(kq, jnp.float32)
            self._vq = jnp.asarray(vq, jnp.float32)
            if self._kq.shape != (cfg.num_layers, self.fam.kv_heads):
                raise ValueError(
                    f"kv_quant_scales must be [{cfg.num_layers}, "
                    f"{self.fam.kv_heads}]; got {self._kq.shape}")
            cache_dtype = jnp.int8
        self.cache = PagedKVCache(
            num_layers=cfg.num_layers, num_blocks=int(num_blocks),
            kv_heads=self.fam.kv_heads, block_size=self.block_size,
            head_dim=self.fam.head_dim, dtype=cache_dtype,
            layout="token",
            enable_prefix_caching=bool(enable_prefix_caching))
        self.enable_prefix_caching = self.cache.enable_prefix_caching
        # the trash page: inactive batch rows point their whole block
        # table here so their (ignored) writes never touch live pages
        self._trash_page = self.cache.allocator.alloc(1)[0]
        # pool HBM is fixed at construction (update() swaps buffers of
        # identical shape/dtype) — computed once for the step gauges
        self._pool_bytes = \
            sum(k.nbytes for k in self.cache.key_caches) \
            + sum(v.nbytes for v in self.cache.value_caches)
        self._hbm_sampled_at = -1.0
        # wall seconds the LAST ragged launch spent on a compiling
        # first call (0.0 when it hit a warm executable) — the TTFT
        # budget's compile_stall attribution read by _run_prefills
        self._last_ragged_compile_s = 0.0
        self._rope = (self.fam.rope_tables(self.max_model_len)
                      if self.fam.needs_rope else None)

        from ..jit import _collect_params
        pnames, ptensors, bnames, btensors = _collect_params(model)
        self._tensors = ptensors + btensors
        self._param_names = pnames + bnames
        self.mesh = mesh
        if mesh is not None:
            self._shard_params(mesh, shard_param)

        self.waiting: collections.deque = collections.deque()
        self.slots: List[Optional[_Seq]] = [None] * self.max_batch
        # unified executable cache: ("ragged", token_bucket, with_pool)
        # -> the packed mixed prefill/prefix-resume/verify executable
        # ("engine_ragged" compile family), ("decode", chunk) -> the
        # chunked decode scan ("engine_decode"). The old
        # (bucket, pages)-keyed prefill / prefix-resume / verify zoo
        # collapsed into the ragged family (ISSUE 7).
        self._fns: Dict = {}
        # per-ragged-executable implementation record: fkey ->
        # ("pallas"|"jnp", reason) so launches can surface which path
        # they took (a TPU deployment silently riding the O(T^2)
        # reference because a shape gate rejected the kernel is a
        # throughput cliff that must be visible in observability)
        self._ragged_paths: Dict = {}
        # load shedding / deadlines / watchdog (resilience layer)
        self.shed_load = bool(shed_load)
        self.max_waiting = max_waiting
        self.step_timeout_s = step_timeout_s
        self._failed: List[GenerationResult] = []   # drained by step()
        self._now = time.monotonic                  # stubbable clock
        # speculative decoding (inference/speculative.py): drafts are
        # verified by a batched greedy pass, so sampling must be off —
        # greedy verification preserves outputs bit-exactly, while
        # sampled verification would change the output distribution
        self.speculative_config = speculative_config
        self._proposer = None
        self._spec_k = 0
        if speculative_config is not None:
            if self.do_sample:
                raise ValueError(
                    "speculative_config requires greedy decoding "
                    "(do_sample=False); sampled verification is not "
                    "supported")
            self._proposer = speculative_config.build_proposer()
            self._spec_k = int(
                speculative_config.num_speculative_tokens)
        # backward-compatible per-engine view; writes mirror onto the
        # observability registry (see _EngineStats)
        self.stats = _EngineStats(
            preemptions=0, prefills=0, decode_chunks=0,
            decode_tokens=0, failed_requests=0, rejected_requests=0,
            aborted_requests=0,
            deadline_expired=0, prefix_cache_hit_tokens=0,
            prefix_cache_miss_tokens=0, spec_steps=0,
            spec_drafted_tokens=0, spec_accepted_tokens=0,
            spec_proposer_errors=0, spec_step_errors=0,
            ragged_launches=0)
        # in-step pool-occupancy high-water (pages off the free list
        # at the post-lease peak); plain attribute, reset at will
        self.peak_used_blocks = 0

        # persistent executable store (inference.exec_cache): resolved
        # once, consulted by every _fns entry's CompileTimed shim
        # before lowering. Last in __init__ — the key parts read the
        # full resolved configuration above.
        from . import exec_cache as _exec_cache
        self._exec_cache = None
        self._exec_device_fp = None
        self._exec_key_base = None
        exec_cache_dir = exec_cache_dir or _exec_cache.default_dir()
        if exec_cache_dir:
            self._exec_cache = _exec_cache.ExecCache(exec_cache_dir)
            self._exec_device_fp = _exec_cache.device_fingerprint(mesh)
            self._exec_key_base = self._exec_cache_key_parts()

    def _shard_params(self, mesh, shard_param) -> None:
        """Tensor-parallel placement over `mesh`: every param lands per
        its PartitionSpec rule (default: replicated), and every other
        array the executables close over or take as operands — paged
        pool, rope tables, kv quant scales — replicates over the SAME
        mesh, so no executable ever sees operands committed to
        disagreeing device sets."""
        from jax.sharding import NamedSharding, PartitionSpec
        repl = NamedSharding(mesh, PartitionSpec())
        for name, t in zip(self._param_names, self._tensors):
            spec = None
            if shard_param is not None:
                spec = shard_param(name, tuple(t._data.shape))
            if spec is None:
                spec = PartitionSpec()
            t._data = jax.device_put(t._data,
                                     NamedSharding(mesh, spec))
        self.cache.key_caches = [jax.device_put(k, repl)
                                 for k in self.cache.key_caches]
        self.cache.value_caches = [jax.device_put(v, repl)
                                   for v in self.cache.value_caches]
        if self._rope is not None:
            self._rope = jax.tree_util.tree_map(
                lambda a: jax.device_put(a, repl), self._rope)
        if self._kq is not None:
            self._kq = jax.device_put(self._kq, repl)
            self._vq = jax.device_put(self._vq, repl)

    def _exec_cache_key_parts(self) -> dict:
        """Structural identity of this engine's executables — the
        graftlint-audited base of every persistent-store key. Built
        exclusively from plain value-comparable data (shapes, dtypes as
        strings, config scalars, content hashes): exec_cache.fingerprint
        raises on anything unstable rather than falling back to repr."""
        from . import exec_cache as _exec_cache
        params = [[n, list(t._data.shape), str(t._data.dtype)]
                  for n, t in zip(self._param_names, self._tensors)]
        pool = self.cache
        return {
            "schema": _exec_cache.SCHEMA_VERSION,
            "code": _exec_cache.code_fingerprint(),
            "device": self._exec_device_fp,
            "model": type(self.model).__name__,
            "family": type(self.fam).__name__,
            "params": params,
            "pool": {
                "num_blocks": int(pool.allocator.num_blocks),
                "block_size": int(self.block_size),
                "kv_heads": int(self.fam.kv_heads),
                "head_dim": int(self.fam.head_dim),
                "cache_dtype": str(pool.key_caches[0].dtype),
                "num_layers": len(pool.key_caches),
            },
            "engine": {
                "max_batch": self.max_batch,
                "decode_chunk": self.decode_chunk,
                "prompt_quantum": self.prompt_quantum,
                "max_model_len": self.max_model_len,
                "do_sample": self.do_sample,
                "temperature": self.temperature,
                "top_p": self.top_p,
                "top_k": self.top_k,
                "spec_k": self._spec_k,
                "kv_quant": self._kq is not None,
            },
        }

    def _exec_store_opts(self, fkey) -> dict:
        """CompileTimed kwargs binding `fkey`'s executable to its
        persistent-store slot (empty when no store is configured)."""
        if self._exec_cache is None:
            return {}
        from . import exec_cache as _exec_cache
        parts = dict(self._exec_key_base)
        parts["fkey"] = list(fkey)
        return {"store": self._exec_cache,
                "store_key": _exec_cache.fingerprint(parts),
                "store_device": self._exec_device_fp}

    # -- request lifecycle -------------------------------------------------
    def _finish_obs(self, rid, reason: str, trace_id, root_span,
                    t_enq: float, t_first, n_out: int) -> None:
        """Terminal accounting every finish path funnels through:
        outcome counter, e2e / TPOT observations (successful requests
        only — failures would poison the latency SLOs), and the
        request's ROOT span covering enqueue -> finish, which parents
        every lifecycle event recorded along the way."""
        if not (_om._ENABLED or _ot._ENABLED):
            return
        t_fin = time.perf_counter()
        if _om._ENABLED:
            m = _metrics()
            m["req_finished"].labels(reason=reason).inc()
            if reason in ("eos", "length"):
                m["e2e"].observe(t_fin - t_enq)
                if t_first is not None and n_out > 1:
                    m["tpot"].observe((t_fin - t_first) / (n_out - 1))
        if _ot._ENABLED and trace_id is not None:
            _ot.add_event(
                "request", t_enq * 1e6, (t_fin - t_enq) * 1e6,
                trace=(trace_id, root_span, None),
                args={"request_id": str(rid), "finish_reason": reason})

    def _reject(self, request_id, prompt, reason: str, exc_type=None):
        """Load-shedding admission: record a rejected result instead of
        crashing the caller (shed_load=True), or raise (legacy)."""
        if not self.shed_load:
            raise (exc_type or RuntimeError)(reason)
        self.stats["rejected_requests"] += 1
        trace_id = _ot.new_trace_id() if _ot._ENABLED else None
        root = _ot.new_span_id() if _ot._ENABLED else None
        self._finish_obs(request_id, "rejected", trace_id, root,
                         time.perf_counter(), None, 0)
        self._failed.append(GenerationResult(
            request_id=request_id, prompt_ids=prompt,
            output_ids=np.zeros((0,), np.int32),
            finish_reason="rejected", error=reason))

    def add_request(self, request_id, prompt_ids, max_new_tokens: int = 32,
                    deadline_s: Optional[float] = None,
                    obs_carry: Optional[tuple] = None,
                    prefix_hashes: Optional[list] = None):
        """Queue a request. deadline_s: wall-clock TTL from now — when
        it expires before the request finishes, the request is failed
        with finish_reason="deadline" (evicted mid-decode if running)
        while other requests keep serving.

        obs_carry: a (trace_id, root_span, t_enq[, reserve]) tuple
        from an EARLIER life of this request — the serving router
        re-serves a failed-over request from its original prompt on a
        surviving replica and passes the original trace identity and
        first enqueue timestamp here, so the request stays ONE
        connected trace tree and TTFT/queue-wait/e2e SLO accounting
        keeps charging the time the dead replica burned. The optional
        4th element marks a RE-serve (a prior replica already prefilled
        this context): the new life's prefill wall then charges to the
        affinity_miss component of the TTFT budget instead of
        prefill_compute.

        prefix_hashes: a precomputed `cache.block_hashes(prompt)`
        chain for THIS prompt — the router's affinity peek already
        hashed it once per request, and admission reuses the chain
        instead of re-hashing (the chain is a pure function of the
        tokens and the block size, so it is valid on any identically-
        provisioned replica)."""
        prompt = np.asarray(
            prompt_ids.numpy() if isinstance(prompt_ids, Tensor)
            else prompt_ids, dtype=np.int32).reshape(-1)
        total = len(prompt) + max_new_tokens
        if total > self.max_model_len:
            return self._reject(
                request_id, prompt,
                f"request {request_id!r}: prompt ({len(prompt)}) + "
                f"max_new_tokens ({max_new_tokens}) = {total} exceeds "
                f"max_model_len ({self.max_model_len})", ValueError)
        need = -(-total // self.block_size)
        if need > self.cache.allocator.num_blocks - 1:
            return self._reject(
                request_id, prompt,
                f"request {request_id!r} needs {need} cache blocks but "
                f"the pool only has "
                f"{self.cache.allocator.num_blocks - 1} usable",
                MemoryError)
        if self.max_waiting is not None and \
                len(self.waiting) >= self.max_waiting:
            return self._reject(
                request_id, prompt,
                f"request {request_id!r}: waiting queue is full "
                f"({self.max_waiting})", RuntimeError)
        deadline = (self._now() + deadline_s
                    if deadline_s is not None else None)
        # one trace per request lifetime (ids only when tracing is on;
        # the timestamps are two perf_counter reads either way — SLO
        # accounting needs them if metrics get enabled mid-flight)
        t_now = time.perf_counter()
        reserve = False
        if obs_carry is not None:
            trace_id, root, t_enq = obs_carry[:3]
            reserve = bool(obs_carry[3]) if len(obs_carry) > 3 else False
        else:
            trace_id = _ot.new_trace_id() if _ot._ENABLED else None
            root = _ot.new_span_id() if _ot._ENABLED else None
            t_enq = t_now
        self.waiting.append(_Request(request_id, prompt,
                                     int(max_new_tokens),
                                     deadline=deadline,
                                     hash_chain=(list(prefix_hashes)
                                                 if prefix_hashes
                                                 else None),
                                     trace_id=trace_id, root_span=root,
                                     t_enq=t_enq, t_queued=t_now,
                                     recompute=reserve))

    def abort_request(self, request_id) -> bool:
        """Cancel a queued or running request: leased pages return to
        the pool immediately (pages of any full, hash-indexed prefix
        blocks PARK in the prefix-cache LRU like a normal finish, so
        the computed KV stays shareable), and the request completes
        with finish_reason="aborted" on the next step() drain. The
        serving router uses this to drain a quarantined replica before
        re-routing its in-flight requests; callers use it for client
        disconnects. Returns False when the id is not queued or
        running here (already finished — or never arrived)."""
        for req in self.waiting:
            if req.rid == request_id:
                self.waiting.remove(req)
                self.stats["aborted_requests"] += 1
                self._finish_obs(req.rid, "aborted", req.trace_id,
                                 req.root_span, req.t_enq, req.t_first,
                                 len(req.resume_out))
                self._failed.append(GenerationResult(
                    request_id=req.rid, prompt_ids=req.prompt,
                    output_ids=np.asarray(req.resume_out, np.int32),
                    finish_reason="aborted",
                    error="aborted while queued"))
                return True
        for seq in self.slots:
            if seq is not None and seq.rid == request_id:
                self.stats["aborted_requests"] += 1
                self.cache.free_sequence(seq.rid)
                self.slots[seq.slot] = None
                self._finish_obs(seq.rid, "aborted", seq.trace_id,
                                 seq.root_span, seq.t_enq, seq.t_first,
                                 len(seq.out))
                self._failed.append(GenerationResult(
                    request_id=seq.rid, prompt_ids=seq.prompt,
                    output_ids=np.asarray(seq.out, np.int32),
                    finish_reason="aborted",
                    error="aborted mid-generation"))
                return True
        return False

    @property
    def has_unfinished(self) -> bool:
        return (bool(self.waiting) or bool(self._failed)
                or any(s is not None for s in self.slots))

    # -- KV-page migration (prefill/decode disaggregation) -----------------
    def _kv_scale_digest(self) -> Optional[str]:
        """Content digest of the int8 quant scales (None on fp pools).
        Migrated int8 page bytes are only meaningful under the SAME
        static scales, so the digest rides every migration chunk and
        the importer refuses a mismatch."""
        if self._kq is None:
            return None
        dig = getattr(self, "_kq_digest", None)
        if dig is None:
            import hashlib
            # scales are small, immutable engine config; one host read
            dig = hashlib.sha256(
                np.asarray(self._kq, np.float32).tobytes()  # graftlint: disable=host-sync
                + np.asarray(self._vq, np.float32).tobytes()  # graftlint: disable=host-sync
            ).hexdigest()
            self._kq_digest = dig
        return dig

    def export_kv_pages(self, hashes: list, start: int = 0,
                        limit: Optional[int] = None) -> dict:
        """One migration chunk: the committed pages for
        `hashes[start:start+limit]` (stopping at the first hash this
        pool does not hold) plus the pool-compatibility metadata the
        importer validates — geometry, cache dtype, int8-scale digest.
        The disagg driver ships consecutive chunks sequence-numbered;
        see README "Prefill/decode disaggregation" for the wire
        format."""
        meta = self.cache.page_meta()
        meta["kv_scale_digest"] = self._kv_scale_digest()
        return {"v": 1, "start": int(start), "meta": meta,
                "pages": self.cache.export_pages(hashes, start, limit)}

    def import_kv_pages(self, payload: dict) -> int:
        """Register one migration chunk's pages in this engine's pool
        (parked in the prefix-cache LRU, leased on the next matching
        admission). Raises ValueError on any pool-compatibility
        mismatch — migrated bytes are only valid bit-for-bit on an
        identically-provisioned pool; the disagg driver degrades to
        prefix-hash re-admission. Returns how many of the chunk's
        pages are now resident (pool exhaustion imports a valid chain
        prefix and stops)."""
        meta = dict(payload.get("meta") or {})
        mine = self.cache.page_meta()
        mine["kv_scale_digest"] = self._kv_scale_digest()
        if payload.get("v") != 1 or meta != mine:
            raise ValueError(
                "incompatible KV-page migration chunk: peer pool %r "
                "vs local %r" % (meta, mine))
        return self.cache.import_pages(payload.get("pages") or [])

    # -- scheduling --------------------------------------------------------
    def _free_slot(self) -> Optional[int]:
        for i, s in enumerate(self.slots):
            if s is None:
                return i
        return None

    @staticmethod
    def _merged_tokens(seq_or_req) -> np.ndarray:
        """prompt + carried output tokens — the context a prefill must
        (re)build, and the byte string the prefix index is keyed on."""
        out = getattr(seq_or_req, "resume_out", None)
        if out is None:
            out = seq_or_req.out
        if not out:
            return seq_or_req.prompt
        return np.concatenate([seq_or_req.prompt,
                               np.asarray(out, np.int32)])

    def _admit(self) -> List[_Seq]:
        """Admit waiting requests into free slots while context pages
        fit. With prefix caching the feasibility check and the lease
        both account for the request's longest cached page-aligned
        prefix: matched pages are taken at +1 refcount (parked ones
        leave the LRU) and only the remainder is freshly allocated.
        Returns the newly admitted (prefill-pending) sequences."""
        fresh = []
        while self.waiting:
            slot = self._free_slot()
            if slot is None:
                break
            req = self.waiting[0]
            merged = self._merged_tokens(req)
            if self.enable_prefix_caching and req.hash_chain is None:
                # hash the prompt ONCE per (re)queued request — a head
                # request blocked on pool pages re-plans every step,
                # and the chain is immutable in the tokens
                req.hash_chain = self.cache.block_hashes(merged)
            plan_cached, feasible, plan_pages = self.cache.prefix_plan(
                merged, req.context_len, hashes=req.hash_chain)
            if not feasible:
                break
            self.waiting.popleft()
            self._admit_counter = getattr(self, "_admit_counter", 0) + 1
            seq = _Seq(req, slot, self._admit_counter)
            ncached = self.cache.add_sequence(
                seq.rid, req.context_len, tokens=merged,
                match=(plan_cached, plan_pages))
            seq.cached_len = ncached
            seq.length = req.context_len
            self.slots[slot] = seq
            fresh.append(seq)
            self.stats["prefix_cache_hit_tokens"] += ncached
            self.stats["prefix_cache_miss_tokens"] += \
                req.context_len - ncached
            if _om._ENABLED:
                m = _metrics()
                pm = m["prefix"]
                if ncached:
                    pm.labels(outcome="hit").inc(ncached)
                pm.labels(outcome="miss").inc(req.context_len - ncached)
                qw = time.perf_counter() - req.t_queued
                seq.bud_queue += qw     # TTFT budget: queue segment
                m["queue_wait"].observe(qw)
            if _ot._ENABLED and req.trace_id is not None:
                now = time.perf_counter()
                _ot.add_event(
                    "request.queue_wait", req.t_queued * 1e6,
                    (now - req.t_queued) * 1e6,
                    trace=(req.trace_id, _ot.new_span_id(),
                           req.root_span),
                    args={"request_id": str(req.rid),
                          "resumed": bool(req.resume_out),
                          "cached_tokens": ncached})
        return fresh

    def _preempt_one(self, exclude=None) -> bool:
        """Free the most-recently admitted sequence's pages and requeue
        it (prompt + generated-so-far) for re-prefill — recompute-style
        preemption."""
        cands = [s for s in self.slots
                 if s is not None and s is not exclude]
        if not cands:
            return False
        # MOST-RECENTLY admitted loses (vLLM recompute policy): slots
        # get recycled, so admission order is tracked explicitly — the
        # oldest, most-completed sequences keep their pages
        victim = max(cands, key=lambda s: s.admit_seq)
        self.stats["preemptions"] += 1
        self.cache.free_sequence(victim.rid)
        self.slots[victim.slot] = None
        now = time.perf_counter()
        if _ot._ENABLED and victim.trace_id is not None:
            _ot.add_event(
                "request.preempt", now * 1e6, 0.0,
                trace=(victim.trace_id, _ot.new_span_id(),
                       victim.root_span),
                args={"request_id": str(victim.rid),
                      "generated": len(victim.out)})
        self.waiting.appendleft(_Request(
            victim.rid, victim.prompt, victim.max_new,
            resume_out=list(victim.out), deadline=victim.deadline,
            trace_id=victim.trace_id, root_span=victim.root_span,
            t_enq=victim.t_enq, t_queued=now, t_first=victim.t_first,
            bud_queue=victim.bud_queue, bud_prefill=victim.bud_prefill,
            bud_miss=victim.bud_miss, bud_compile=victim.bud_compile,
            recompute=True))
        return True

    def _grow(self, seq: _Seq, by: int) -> bool:
        """Lease pages to cover `by` more tokens; preempt others until it
        fits (or nothing is left to preempt)."""
        while True:
            try:
                self.cache.extend(seq.rid, by)
                return True
            except MemoryError:
                if not self._preempt_one(exclude=seq):
                    return False

    # -- device steps ------------------------------------------------------
    def _run_prefills(self, seqs: List[_Seq]) -> List[int]:
        """ONE ragged packed pass over every admitted sequence's
        uncached tokens: rows pack back-to-back into the total-token
        bucket (dead padding writes nothing), so the model's weights
        stream ONCE per admission wave instead of once per sequence.
        Returns each sequence's first sampled token."""
        t0 = time.perf_counter()
        with _ot.span("engine.prefill", seqs=len(seqs)):
            out = self._run_prefills_impl(seqs)
        t1 = time.perf_counter()
        _metrics()["prefill"].observe(t1 - t0)
        if _om._ENABLED:
            # TTFT budget: every sequence in the wave waited the whole
            # wall, so each is charged the full pass — the compile
            # stall (the ragged call's wall while its executable was
            # still compiling, stashed by _run_ragged) separately from
            # the compute, and a recompute life's compute to
            # affinity_miss (it is re-building context some replica
            # already held) instead of prefill_compute
            stall = self._last_ragged_compile_s
            work = max((t1 - t0) - stall, 0.0)
            for s in seqs:
                if self.slots[s.slot] is not s:
                    continue
                s.bud_compile += stall
                if s.recompute:
                    s.bud_miss += work
                else:
                    s.bud_prefill += work
        if _ot._ENABLED:
            # per-request attribution of the batched pass: each
            # sequence gets a child event in ITS trace spanning the
            # executable call it rode in
            for s in seqs:
                if s.trace_id is None or self.slots[s.slot] is not s:
                    continue
                _ot.add_event(
                    "request.prefill", t0 * 1e6, (t1 - t0) * 1e6,
                    trace=(s.trace_id, _ot.new_span_id(), s.root_span),
                    args={"request_id": str(s.rid),
                          "cached_tokens": s.cached_len,
                          "prefill_tokens": s.length - s.cached_len})
        return out

    def _run_prefills_impl(self, seqs: List[_Seq]) -> List[int]:
        entries, merged = self._prefill_entries(seqs)
        toks = self._run_ragged(entries)
        self._commit_prefill(seqs, merged)
        return [int(toks[s.slot][-1]) for s in seqs]

    def _prefill_entries(self, seqs: List[_Seq]):
        """Ragged-batch rows for a prefill wave: each sequence
        contributes its UNCACHED suffix at its per-row cached offset
        (page-aligned; 0 when nothing was cached). Applies the COW
        guard and the per-sequence accounting every prefill execution
        carries. Returns (entries, {rid: merged prompt+carried tokens})
        so the post-launch commit reuses the merged arrays instead of
        re-concatenating per sequence."""
        self.stats["prefills"] += len(seqs)
        entries = []
        merged_by_rid = {}
        for s in seqs:
            faults.fault_point("engine.prefill.seq", rid=s.rid)
            merged = self._merged_tokens(s)
            merged_by_rid[s.rid] = merged
            st = s.cached_len
            # COW guard: the suffix write range must not touch shared
            # pages (a no-op under page-aligned matching)
            self.cache.ensure_writable(s.rid, st)
            entries.append((s, np.asarray(merged[st:], np.int32), st,
                            False))
        return entries, merged_by_rid

    def _commit_prefill(self, seqs: List[_Seq],
                        merged_by_rid: Dict) -> None:
        if not self.cache.enable_prefix_caching:
            return
        for s in seqs:
            if self.slots[s.slot] is s:
                self.cache.commit_prefix(s.rid, merged_by_rid[s.rid])

    # -- ragged packed launches (prefill / prefix-resume / verify) ---------
    def _token_bucket(self, n: int) -> int:
        """Total-token bucket for the ragged executable: power-of-two
        below the prompt quantum (floored at the Pallas sublane count),
        quantum multiples above — the ONLY shape the ragged family
        compiles on, so a mixed workload reuses O(log + linear/quantum)
        executables instead of one per (kind, length, pages) triple."""
        if n >= self.prompt_quantum:
            return _bucket(n, self.prompt_quantum)
        return max(8, _pow2_ceil(max(n, 1)))

    def _ragged_fn(self, tb: int, with_pool: bool, all_pos: bool):
        """The ragged packed-batch executable ("engine_ragged" compile
        family): every token-computing launch — fresh prefill,
        prefix-resume, speculative verify — compiles down to this one
        function of the total-token bucket. Rows of arbitrary per-row
        lengths ride in a [tb] packed stream with per-token
        (row, position) metadata; attention over the paged pool plus
        the packed fresh k/v runs through
        kernels.pallas.ragged_paged_attention (flash-style Pallas
        kernel on TPU, the jnp reference on CPU — the
        float-op-structure twin of the executables it replaced, so
        greedy outputs stay bit-identical with the dense oracle).
        with_pool=False is the no-cached-context variant: nothing
        reads the pool, exactly the legacy fresh-prefill data flow.
        all_pos=True (verify waves) samples a token at EVERY packed
        position; all_pos=False (prefill waves) gathers each row's
        last hidden state through the `sel` operand before the lm
        head, so the [tb, vocab] logits tensor — ~tokens/rows times
        the lm-head FLOPs and a multi-GB HBM spike at serving shapes —
        is only ever built for the short verify windows that consume
        all of it."""
        fkey = ("ragged", tb, with_pool, all_pos)
        hit = self._fns.get(fkey)
        if hit is not None:
            return hit, self._ragged_paths[fkey][0]
        from ..jit import _functional_params
        from ..autograd import tape as _tape
        from ..models.generation import _pick_token
        from ..incubate.nn.functional.serving import _quantize_kv, \
            _apply_rotary
        from ..kernels.pallas.ragged_paged_attention import (
            ragged_attention_path, ragged_paged_attention)
        import math as _math
        fam = self.fam
        rope = self._rope
        bs = self.block_size
        kvH, H_D = self.fam.kv_heads, self.fam.head_dim
        nH = self.model.config.num_heads
        scale = 1.0 / _math.sqrt(H_D)
        tensors = self._tensors
        kq, vq = self._kq, self._vq
        kdq = None if kq is None else 1.0 / kq
        vdq = None if vq is None else 1.0 / vq
        T_pool = self.cache.allocator.num_blocks * bs
        # implementation pick is an executable-shape property: resolved
        # ONCE here, then baked into the program
        path, why = ragged_attention_path(
            tb, T_pool if with_pool else 0, nH, kvH, H_D, bs, with_pool)
        if path == "jnp" and jax.default_backend() == "tpu":
            # the reference path materializes [H, T, T] scores — fine
            # for CPU tests/oracles, not a serving path. The engine
            # made this launch shape itself, so this is its bug.
            raise RuntimeError(
                f"ragged executable {fkey} would run the jnp reference "
                f"on a TPU backend: {why}")
        self._ragged_paths[fkey] = (path, why)

        def ragged(params, kcs, vcs, ids, rows, pos, kvs, off, wf, sel,
                   key):
            # ids/rows/pos/wf [tb]: the packed token stream (rows -1 =
            # dead padding; wf = flat pool row to write, T_pool drops);
            # kvs [B]: cached tokens readable per row; off [B, NB]:
            # block -> start position ownership map; sel [B]: each
            # row's last packed position (0 for empty slots; consumed
            # only when all_pos=False)
            with _tape.no_grad(), _functional_params(tensors, params):
                x = Tensor._wrap(fam.embed(ids, pos))      # [tb, h]
                new_k, new_v = [], []
                for li, layer in enumerate(fam.layers()):
                    qkv = fam.qkv(layer, x)
                    q = qkv[:, :nH * H_D].reshape(tb, nH, H_D)
                    k = qkv[:, nH * H_D:(nH + kvH) * H_D].reshape(
                        tb, kvH, H_D)
                    v = qkv[:, (nH + kvH) * H_D:].reshape(
                        tb, kvH, H_D)
                    if rope is not None:
                        cos = rope[0][pos][:, None, :]     # [tb,1,D/2]
                        sin = rope[1][pos][:, None, :]
                        q = _apply_rotary(q, cos, sin, True).astype(
                            q.dtype)
                        k = _apply_rotary(k, cos, sin, True).astype(
                            k.dtype)
                    if kq is not None:
                        kw = _quantize_kv(k, kq[li], 1, 127., -127.)
                        vw = _quantize_kv(v, vq[li], 1, 127., -127.)
                    else:
                        kw = k.astype(kcs[li].dtype)
                        vw = v.astype(vcs[li].dtype)
                    # dead/padded tokens carry wf = T_pool: the scatter
                    # drops them (the same OOB trick every engine write
                    # path uses)
                    new_k.append(kcs[li].at[wf].set(kw))
                    new_v.append(vcs[li].at[wf].set(vw))
                    # pool attention reads kcs/vcs BEFORE this layer's
                    # scatter: cached-prefix pages and fresh writes are
                    # disjoint pool rows, packed k/v stay in registers
                    o = ragged_paged_attention(
                        q, k, v, kcs[li], vcs[li], rows, pos, kvs, off,
                        block_size=bs, scale=scale,
                        kdq=None if kdq is None else kdq[li],
                        vdq=None if vdq is None else vdq[li],
                        with_pool=with_pool, path=path)
                    x = fam.attn_out(
                        layer, x,
                        o.reshape(tb, nH * H_D).astype(x._data.dtype))
                    x = fam.mlp(layer, x)
                x = fam.final(x)
                if all_pos:
                    # verify: sampled targets at EVERY packed position
                    # (the lm head over [tb] rows is row-wise, so the
                    # per-position logits are the same values the
                    # per-kind executables computed)
                    lg = fam.logits(x)._data               # [tb, vocab]
                else:
                    # prefill: only each row's last position feeds a
                    # token — gather [B] hidden rows before the lm
                    # head (row-wise, so bit-identical to slicing the
                    # full [tb, vocab] logits at sel)
                    lg = fam.logits(
                        Tensor._wrap(x._data[sel]))._data  # [B, vocab]
                nxt, _ = _pick_token(lg.astype(jnp.float32), key,
                                     self.do_sample, self.temperature,
                                     self.top_p, self.top_k)
                return nxt, new_k, new_v

        fn = _CompileTimed(jax.jit(ragged, donate_argnums=(1, 2)),
                           "engine_ragged",
                           **self._exec_store_opts(fkey))
        self._fns[fkey] = fn
        return fn, path

    def _run_ragged(self, entries) -> Dict[int, np.ndarray]:
        """Pack mixed rows into ONE ragged launch and run it.

        entries: [(seq, tokens int32 [m], start, all_positions)] — each
        row computes its `tokens` at absolute positions
        start..start+m-1 while reading its cached context (positions
        < start) from the paged pool through the per-row ownership
        map; writes land token-major at the row's leased pages.
        Returns {slot: np.int32 [m]} — every packed position's sampled
        token for a verify wave, [1] (the row's last position) for a
        prefill wave."""
        B = self.max_batch
        NB = self.cache.allocator.num_blocks
        bs = self.block_size
        T_pool = NB * bs
        T_raw = sum(len(t) for _s, t, _st, _ap in entries)
        with_pool = any(st > 0 for _s, _t, st, _ap in entries)
        # waves are homogeneous: a prefill wave (all_pos=False
        # everywhere) or a verify wave (True everywhere)
        all_pos = entries[0][3]
        if all_pos:
            # verify waves PIN one bucket sized for the worst case
            # (every slot drafting the full k) — draft lengths vary
            # step to step, and letting them move the bucket would
            # reintroduce the unpredictable mid-serving compile the
            # old fixed-width verify executable existed to prevent
            tb = self._token_bucket(B * (self._spec_k + 1))
        else:
            tb = self._token_bucket(T_raw)
        with _ot.span("engine.pack", tokens=T_raw, bucket=tb):
            ids = np.zeros((tb,), np.int32)
            rows = np.full((tb,), -1, np.int32)
            pos = np.zeros((tb,), np.int32)
            kvs = np.zeros((B,), np.int32)
            off = np.full((B, NB), -1, np.int32)
            wf = np.full((tb,), T_pool, np.int32)
            sel = np.zeros((B,), np.int32)
            spans = {}
            c = 0
            for s, toks, st, _ap in entries:
                m = len(toks)
                b = s.slot
                ids[c:c + m] = toks
                rows[c:c + m] = b
                gpos = st + np.arange(m, dtype=np.int32)
                pos[c:c + m] = gpos
                kvs[b] = st
                pages = np.asarray(self.cache.pages(s.rid), np.int32)
                off[b, pages] = np.arange(len(pages), dtype=np.int32) * bs
                wf[c:c + m] = pages[gpos // bs] * bs + gpos % bs
                sel[b] = c + m - 1
                spans[b] = (c, m)
                c += m
        fn, impl = self._ragged_fn(tb, with_pool, all_pos)
        compiling = fn.pending          # first call pays the compile
        kcs, vcs = self.cache.key_caches, self.cache.value_caches
        self._key, sub = jax.random.split(self._key)
        t0 = time.perf_counter()
        with _ot.span("engine.ragged", rows=len(entries),
                      tokens=T_raw, bucket=tb, path=impl):
            with self._step_watchdog("engine ragged launch"):
                nxt, kcs, vcs = fn(
                    [t._data for t in self._tensors], kcs, vcs,
                    jnp.asarray(ids), jnp.asarray(rows),
                    jnp.asarray(pos), jnp.asarray(kvs),
                    jnp.asarray(off), jnp.asarray(wf),
                    jnp.asarray(sel), sub)
                nxt = jax.block_until_ready(nxt)
        t1 = time.perf_counter()
        for i in range(self.cache.num_layers):
            self.cache.update(i, kcs[i], vcs[i])
        self.stats["ragged_launches"] += 1
        if _om._ENABLED:
            self._last_ragged_compile_s = t1 - t0 if compiling else 0.0
            _metrics()["ragged"].observe(t1 - t0)
            if not compiling:
                # roofline: the launch is blocking-timed (the
                # block_until_ready above), so latency x the
                # executable's recorded cost model is an honest
                # achieved-rate read; a compiling first call is not
                _pf.observe_roofline("engine_ragged", t1 - t0,
                                     fn.expected)
        nxt = np.asarray(nxt)
        if all_pos:
            return {b: nxt[cc:cc + m] for b, (cc, m) in spans.items()}
        # prefill waves sampled one token per row (at sel)
        return {b: nxt[b:b + 1] for b in spans}

    def _decode_fn(self, chunk: int):
        """Chunked decode executable. The pool stays READ-ONLY inside
        the scan: a pool that is scattered into AND read by the
        whole-pool attention in the same scan body loses XLA's in-place
        aliasing (measured: a full pool copy per step). Each step
        writes its k/v into a small [L, B, chunk, kvH, D] staging
        buffer via dynamic-update-slice and attends over pool+staging
        jointly; the staging merges into the pool with ONE flat
        token-major scatter per cache at chunk end."""
        hit = self._fns.get(("decode", chunk))
        if hit is not None:
            return hit
        from ..jit import _functional_params
        from ..autograd import tape as _tape
        from ..models.generation import _pick_token
        from ..incubate.nn.functional.serving import _quantize_kv, \
            _apply_rotary
        import math as _math
        fam, B, bs = self.fam, self.max_batch, self.block_size
        H_D = fam.head_dim
        kvH = fam.kv_heads
        L = len(fam.layers())
        scale = 1.0 / _math.sqrt(H_D)
        rope = self._rope
        tensors = self._tensors
        kq, vq = self._kq, self._vq
        kdq = None if kq is None else 1.0 / kq
        vdq = None if vq is None else 1.0 / vq

        def decode(params, kcs, vcs, cur, start, tbl, off, key):
            with _tape.no_grad(), _functional_params(tensors, params):
                cdtype = kcs[0].dtype
                T_pool = kcs[0].shape[0]
                st_k = jnp.zeros((L, B, chunk, kvH, H_D), cdtype)
                st_v = jnp.zeros((L, B, chunk, kvH, H_D), cdtype)
                # pool ownership/position masks are FROZEN for the
                # whole chunk: every pool token precedes `start`
                toff = jnp.repeat(off, bs, axis=1)          # [B, Tp]
                gpos_pool = toff + jnp.tile(
                    jnp.arange(bs, dtype=jnp.int32),
                    T_pool // bs)[None, :]
                pool_ok = (toff >= 0) & (gpos_pool < start[:, None])
                jpos = jnp.arange(chunk, dtype=jnp.int32)

                def body(carry, i):
                    st_k, st_v, cur, key = carry
                    lens = start + i
                    x = Tensor._wrap(fam.embed(cur, lens)[:, None])
                    for li, layer in enumerate(fam.layers()):
                        qkv = fam.qkv(layer,
                                      Tensor._wrap(x._data[:, 0]))
                        nH = qkv.shape[-1] // H_D - 2 * kvH
                        rep = nH // kvH
                        q = qkv[:, :nH * H_D].reshape(B, nH, H_D)
                        k = qkv[:, nH * H_D:(nH + kvH) * H_D].reshape(
                            B, kvH, H_D)
                        v = qkv[:, (nH + kvH) * H_D:].reshape(
                            B, kvH, H_D)
                        if rope is not None:
                            cos = rope[0][lens][:, None, :]  # [B,1,D/2]
                            sin = rope[1][lens][:, None, :]
                            q = _apply_rotary(q, cos, sin, True).astype(
                                q.dtype)
                            k = _apply_rotary(k, cos, sin, True).astype(
                                k.dtype)
                        if kq is not None:
                            kw = _quantize_kv(k, kq[li], 1, 127., -127.)
                            vw = _quantize_kv(v, vq[li], 1, 127., -127.)
                        else:
                            kw = k.astype(cdtype)
                            vw = v.astype(cdtype)
                        # staged write: one (li, :, i) slice for every
                        # row -> dynamic-update-slice, stays in place
                        st_k = jax.lax.dynamic_update_slice(
                            st_k, kw[None, :, None], (li, 0, i, 0, 0))
                        st_v = jax.lax.dynamic_update_slice(
                            st_v, vw[None, :, None], (li, 0, i, 0, 0))
                        # scores: frozen pool part + staged part
                        with jax.named_scope("decode_attention"):
                            q4 = (q.astype(jnp.float32) * scale).reshape(
                                B, kvH, rep, H_D)
                            if cdtype == jnp.int8:
                                qop = q4
                                kp = kcs[li].astype(jnp.float32)
                                ks = st_k[li].astype(jnp.float32)
                            else:
                                qop = q4.astype(cdtype)
                                kp = kcs[li]
                                ks = st_k[li]
                            sp = jnp.einsum(
                                "bkrd,tkd->bkrt", qop, kp,
                                preferred_element_type=jnp.float32)
                            ss = jnp.einsum(
                                "bkrd,bjkd->bkrj", qop, ks,
                                preferred_element_type=jnp.float32)
                            if kdq is not None:
                                sp = sp * kdq[li][None, :, None, None]
                                ss = ss * kdq[li][None, :, None, None]
                            sp = jnp.where(pool_ok[:, None, None, :], sp,
                                           -jnp.inf)
                            ss = jnp.where(
                                (jpos <= i)[None, None, None, :], ss,
                                -jnp.inf)
                            s = jnp.concatenate([sp, ss], axis=-1)
                            p = jax.nn.softmax(s, axis=-1)
                            pp, ps = p[..., :T_pool], p[..., T_pool:]
                            if cdtype == jnp.int8:
                                vp = vcs[li].astype(jnp.float32)
                                vs = st_v[li].astype(jnp.float32)
                                ppo, pso = pp, ps
                            else:
                                vp, vs = vcs[li], st_v[li]
                                ppo, pso = pp.astype(cdtype), ps.astype(
                                    cdtype)
                            o = jnp.einsum(
                                "bkrt,tkd->bkrd", ppo, vp,
                                preferred_element_type=jnp.float32)
                            o = o + jnp.einsum(
                                "bkrj,bjkd->bkrd", pso, vs,
                                preferred_element_type=jnp.float32)
                            if vdq is not None:
                                o = o * vdq[li][None, :, None, None]
                        o = o.reshape(B, nH * H_D)
                        x = fam.attn_out(layer, x, o.astype(
                            x._data.dtype)[:, None, :])
                        x = fam.mlp(layer, x)
                    x = fam.final(x)
                    lg = fam.logits(x)._data[:, -1]
                    key, sub = jax.random.split(key)
                    nxt, _ = _pick_token(lg.astype(jnp.float32), sub,
                                         self.do_sample,
                                         self.temperature, self.top_p,
                                         self.top_k)
                    return (st_k, st_v, nxt, key), nxt

                carry = (st_k, st_v, cur, key)
                carry, toks = jax.lax.scan(body, carry, jpos)
                st_k, st_v, cur, key = carry
                # merge the chunk into the pool: ONE flat scatter per
                # cache (indices [B*chunk], token-major rows)
                gpos = start[:, None] + jpos[None, :]       # [B,chunk]
                page = jnp.clip(gpos // bs, 0, tbl.shape[1] - 1)
                phys = jnp.maximum(
                    jnp.take_along_axis(tbl, page, axis=1), 0)
                flat = (phys * bs + gpos % bs).reshape(-1)
                new_k = [kcs[li].at[flat].set(
                    st_k[li].reshape(B * chunk, kvH, H_D))
                    for li in range(L)]
                new_v = [vcs[li].at[flat].set(
                    st_v[li].reshape(B * chunk, kvH, H_D))
                    for li in range(L)]
                return new_k, new_v, jnp.transpose(toks)   # [B, chunk]

        fn = _CompileTimed(jax.jit(decode, donate_argnums=(1, 2)),
                           "engine_decode",
                           **self._exec_store_opts(("decode", chunk)))
        self._fns[("decode", chunk)] = fn
        return fn

    def _run_decode_chunk(self, only: Optional[_Seq] = None
                          ) -> Dict[int, np.ndarray]:
        """One chunk of decode steps for every active slot (or for
        `only`, with every other row rendered inactive — the
        poisoned-request isolation retry). Returns {slot: np tokens
        [chunk]}."""
        t0 = time.perf_counter()
        with _ot.span("engine.decode_chunk"):
            out = self._run_decode_chunk_impl(only)
        if out:     # skip empty calls (no active slots)
            _metrics()["decode"].observe(time.perf_counter() - t0)
        return out

    def _run_decode_chunk_impl(self, only: Optional[_Seq] = None
                               ) -> Dict[int, np.ndarray]:
        active = [s for s in self.slots
                  if s is not None and (only is None or s is only)]
        if not active:
            return {}
        # chunk size: power-of-two bucket, never past the model cap
        headroom = min(self.max_model_len - s.length for s in active)
        chunk = _pow2_floor(max(1, min(self.decode_chunk, headroom)))
        # lease pages for the chunk up front (preempting if needed),
        # capped at each sequence's remaining token budget: decode
        # never needs more blocks than add_request validated against
        # the pool (the excess in-chunk writes past the budget fall
        # through to the trash page via the table padding). Leasing is
        # delta-based off the cache's leased length, so a retry after a
        # failed executable call never double-leases.
        with _ot.span("engine.schedule", of="decode_leases"):
            for s in list(active):
                if self.slots[s.slot] is not s:     # got preempted meanwhile
                    continue
                faults.fault_point("engine.decode.seq", rid=s.rid)
                want = min(s.length + chunk, max(s.token_budget, s.length))
                by = want - self.cache.length(s.rid)
                if by > 0 and not self._grow(s, by):
                    raise MemoryError(
                        "paged pool too small for even one sequence's "
                        "decode chunk — enlarge num_blocks")
                # COW guard: the chunk's write range must not touch pages
                # other sequences still reference (no-op by construction
                # under page-aligned prefix matching)
                self.cache.ensure_writable(s.rid, s.length)
        active = [s for s in self.slots
                  if s is not None and (only is None or s is only)]
        if not active:
            return {}
        self._note_pool_highwater()
        B = self.max_batch
        NB = self.cache.allocator.num_blocks
        active_slots = {s.slot for s in active}
        with _ot.span("engine.pack", rows=len(active)):
            cur = np.zeros((B,), np.int32)
            lens = np.zeros((B,), np.int32)
            # write table (page index -> physical block; full static width)
            tbl = np.full((B, self.npb_full), self._trash_page, np.int32)
            # ownership map (physical block -> start position in row b, or
            # -1) for the whole-pool attention; inactive rows own only the
            # trash page so their softmax has one (ignored) valid position
            off = np.full((B, NB), -1, np.int32)
            off[:, self._trash_page] = 0
            for b in range(B):
                s = self.slots[b]
                if s is None or b not in active_slots:
                    continue
                cur[b] = self._last_token(s)
                lens[b] = s.length
                pages = self.cache.pages(s.rid)
                tbl[b, :len(pages)] = pages
                off[b, self._trash_page] = -1
                off[b, pages] = np.arange(len(pages), dtype=np.int32) \
                    * self.block_size
        fn = self._decode_fn(chunk)
        compiling = fn.pending          # first call pays the compile
        kcs, vcs = self.cache.key_caches, self.cache.value_caches
        self._key, sub = jax.random.split(self._key)
        t0 = time.perf_counter()
        with self._step_watchdog("engine decode chunk"):
            kcs, vcs, toks = fn([t._data for t in self._tensors], kcs, vcs,
                                jnp.asarray(cur), jnp.asarray(lens),
                                jnp.asarray(tbl), jnp.asarray(off), sub)
            toks = jax.block_until_ready(toks)
        if _om._ENABLED and not compiling:
            # blocking-timed executable call (host prep excluded):
            # latency x the recorded cost model -> achieved-vs-peak
            _pf.observe_roofline("engine_decode",
                                 time.perf_counter() - t0, fn.expected)
        for i in range(self.cache.num_layers):
            self.cache.update(i, kcs[i], vcs[i])
        toks = np.asarray(toks)
        self.stats["decode_chunks"] += 1
        out = {}
        for s in active:
            out[s.slot] = toks[s.slot]
            s.length += chunk
        return out

    def _last_token(self, seq: _Seq) -> int:
        return int(seq.out[-1]) if seq.out else int(seq.prompt[-1])

    def _note_pool_highwater(self) -> None:
        """Track the pool's true in-step occupancy high-water (pages
        off the free list right after a lease, BEFORE any rollback
        releases them) — `available_blocks` after a step can't see the
        transient verify/decode lease, and peak usage is exactly what
        the spec-vs-chunked equal-HBM comparison is about."""
        used = self.cache.allocator.num_blocks \
            - self.cache.allocator.num_free
        if used > self.peak_used_blocks:
            self.peak_used_blocks = used

    # -- speculative decoding ---------------------------------------------
    def _propose_drafts(self, active: List[_Seq]):
        """Host-side drafting: {slot: int32 drafts} plus the step's
        verify width k. Each row's draft budget is clamped so drafted
        tokens stay inside the accounting the scheduler already
        enforces — the model-length headroom (the verify window writes
        k+1 positions) and the row's remaining generation budget (a
        draft the row could never commit is never verified), so
        speculation can't push a lease past what add_request validated
        or starve deadline/shed-load checks of steps."""
        drafts: Dict[int, np.ndarray] = {}
        ctxs: Dict[int, np.ndarray] = {}
        k_step = 0
        for s in active:
            kmax = min(self._spec_k,
                       self.max_model_len - s.length - 1,
                       s.max_new - len(s.out) - 1)
            d = np.zeros((0,), np.int32)
            ctx = self._merged_tokens(s)
            ctxs[s.slot] = ctx
            if kmax > 0:
                try:
                    d = np.asarray(self._proposer.propose(
                        ctx, int(kmax)),
                        np.int32).reshape(-1)[:kmax]
                except Exception:
                    # drafting is best-effort by contract: a proposer
                    # that chokes on one request's context must not
                    # take the step (or the batch) down — that row
                    # simply decodes without drafts this step
                    self.stats["spec_proposer_errors"] += 1
            drafts[s.slot] = d
            k_step = max(k_step, len(d))
        return drafts, ctxs, k_step

    def _run_spec_step(self, finished: List[GenerationResult]) -> bool:
        """One speculative decode step for every active slot: propose
        drafts, lease the k+1-token verify window (preempting under
        pressure, capped at each row's token budget), run ONE batched
        verify executable over all k+1 positions, commit the longest
        matching prefix + the bonus token, and roll the KV lease back
        to the accepted length (truncate staged writes, unref pages).
        Returns False when no row drafted anything — the caller falls
        back to the chunked decode path, which amortizes host sync
        better when nothing is predictable."""
        active = [s for s in self.slots if s is not None]
        if not active:
            return False
        drafts, ctxs, k_step = self._propose_drafts(active)
        # a mostly-undrafted batch decodes faster on the chunked path:
        # a verify step advances an undrafted row by ONE token where a
        # decode chunk advances it by `decode_chunk` — only take the
        # spec path when at least half the batch is drafting (all-or-
        # nothing per step; both paths are oracle-exact, so the policy
        # only moves throughput)
        drafting = sum(1 for d in drafts.values() if len(d))
        if k_step <= 0 or 2 * drafting < len(active):
            return False
        # verify rides the ragged family: each row packs only its LIVE
        # 1+len(drafts) window into a bucket PINNED at the worst-case
        # B*(k+1) tokens (_run_ragged), so varying draft lengths
        # (n-gram hits are as long as the matched continuation) can
        # never compile a new shape — the same one-executable property
        # the old fixed-width verify had, without the per-row padding
        try:
            tgt, active = self._spec_device_phase(active, drafts,
                                                  k_step)
        except Exception:
            # a failure raised by the donated verify call itself is
            # fatal (the cache buffers are consumed — same rule as
            # the decode path); anything else — a fault injection, a
            # watchdog trip, a lease MemoryError, a host-prep bug —
            # degrades THIS step to the chunked decode path, which
            # carries the per-sequence poisoned-request isolation.
            # Any pages the verify lease took stay delta-accounted
            # and return at finish/preemption. Nothing has been
            # committed yet, so the fallback re-decodes from exactly
            # the pre-step state.
            if any(getattr(k, "is_deleted", lambda: False)()
                   for k in self.cache.key_caches):
                raise
            self.stats["spec_step_errors"] += 1
            return False
        if active is None:
            return True                 # everything preempted mid-lease
        # ---- point of no return: device results are in host hands.
        # Host-side failures below (truncate invariants, prefix
        # commits) would leave s.out extended without matching KV —
        # falling back to chunked decode from that state would
        # silently diverge from the greedy oracle, so they surface
        # loudly instead.
        self.stats["spec_steps"] += 1
        step_drafted = step_accepted = 0
        for s in active:
            b = s.slot
            d = drafts[b]
            t_row = tgt[b]                  # [1+len(d)] greedy targets
            a = accept_drafts(d, t_row)
            committed = t_row[:a + 1]       # accepted drafts + bonus
            n_before = len(s.out)
            for t in committed:
                if len(s.out) >= s.max_new:
                    break
                s.out.append(int(t))
                self.stats["decode_tokens"] += 1
                if (self.eos_token_id is not None
                        and int(t) == self.eos_token_id):
                    break
            n_app = len(s.out) - n_before
            # KV rollback: the cache holds valid KV exactly for the
            # committed tokens (positions start..start+n_app-1 were
            # written from the last committed token + accepted
            # drafts); rejected positions' staged writes fall past the
            # truncated lease — pages unref'd, never hash-indexed
            new_len = s.length + n_app
            self.cache.truncate(s.rid, new_len)
            s.length = new_len
            # accepted = drafts that COMMITTED (the counter's
            # contract): a draft that matched the target but fell past
            # an eos/max_new clamp was rolled back like a mismatch,
            # and counts as rejected
            a = min(a, n_app)
            step_drafted += len(d)
            step_accepted += a
            self.stats["spec_drafted_tokens"] += len(d)
            self.stats["spec_accepted_tokens"] += a
            if _ot._ENABLED and s.trace_id is not None:
                _ot.add_event(
                    "request.verify", self._t_verify0 * 1e6,
                    (self._t_verify1 - self._t_verify0) * 1e6,
                    trace=(s.trace_id, _ot.new_span_id(), s.root_span),
                    args={"request_id": str(s.rid),
                          "drafted": int(len(d)),
                          "accepted": int(a),
                          "committed": int(n_app)})
            if self.cache.enable_prefix_caching:
                # identical to the decode-chunk path: only fully
                # ACCEPTED full blocks can reach the hash index (the
                # lease was truncated first, and commit_prefix caps at
                # the leased length). The pre-step context + this
                # step's commits IS _merged_tokens(s), rebuilt-free
                ntok = min(s.length, len(s.prompt) + len(s.out))
                if self.cache.cached_prefix_len(s.rid) \
                        + self.block_size <= ntok:
                    merged = np.concatenate(
                        [ctxs[b], np.asarray(s.out[n_before:],
                                             np.int32)])
                    self.cache.commit_prefix(s.rid, merged, upto=ntok)
            self._maybe_finish(s, finished)
        if _om._ENABLED:
            m = _metrics()
            if step_accepted:
                m["spec"].labels(outcome="accepted").inc(step_accepted)
            if step_drafted - step_accepted:
                m["spec"].labels(outcome="rejected").inc(
                    step_drafted - step_accepted)
            if self.stats["spec_drafted_tokens"]:
                m["spec_rate"].set(self.stats["spec_accepted_tokens"]
                                   / self.stats["spec_drafted_tokens"])
        return True

    def _spec_device_phase(self, active, drafts, k_step):
        """Lease + batched verify call for `_run_spec_step`. Returns
        ({slot: np.int32 [1+len(drafts)] greedy targets}, surviving
        active list) — or (None, None) when preemption during leasing
        emptied the batch. Everything in here may fail WITHOUT having
        mutated host-side sequence state, which is what makes the
        caller's degrade-to-chunked-decode fallback safe."""
        # lease each row's LIVE verify window up front (preempting if
        # needed): only the row's own 1+len(drafts) positions ever
        # write (dead padding scatters out of bounds), and the lease
        # is capped at the sequence's remaining token budget exactly
        # like the chunked decode path — a rejected draft can never
        # hold pages past the budget add_request validated, and the
        # delta-based lease never double-leases on retry
        for s in list(active):
            if self.slots[s.slot] is not s:     # got preempted meanwhile
                continue
            faults.fault_point("engine.verify.seq", rid=s.rid)
            live = 1 + len(drafts.get(s.slot, ()))
            want = min(s.length + live, max(s.token_budget, s.length))
            by = want - self.cache.length(s.rid)
            if by > 0 and not self._grow(s, by):
                raise MemoryError(
                    "paged pool too small for even one sequence's "
                    "verify window — enlarge num_blocks")
            self.cache.ensure_writable(s.rid, s.length)
        active = [s for s in self.slots if s is not None]
        if not active:
            return None, None
        self._note_pool_highwater()
        # each row's ragged entry is its verify window [last committed
        # token, drafts...] at absolute positions length..length+k —
        # the cached context reads from the pool through the ownership
        # map, and the packed launch scores every window position in
        # one pass. Row widths are the LIVE 1+len(drafts) (no per-row
        # padding); the launch bucket is pinned at B*(k+1) so draft
        # length variation never compiles a new shape.
        entries = []
        for s in active:
            b = s.slot
            d = drafts.get(b, np.zeros((0,), np.int32))
            drafts[b] = d
            window = np.concatenate(
                [np.asarray([self._last_token(s)], np.int32), d])
            entries.append((s, window, s.length, True))
        t0 = time.perf_counter()
        with _ot.span("engine.verify", rows=len(active), k=k_step):
            tgt = self._run_ragged(entries)
        t1 = time.perf_counter()
        self._t_verify0, self._t_verify1 = t0, t1
        if _om._ENABLED:
            _metrics()["verify"].observe(t1 - t0)
        return tgt, active      # {slot: greedy targets}

    def _step_watchdog(self, what: str):
        """Hang detector around a device step (step_timeout_s)."""
        from ..utils.watchdog import watchdog
        if not self.step_timeout_s:
            import contextlib
            return contextlib.nullcontext()
        return watchdog(self.step_timeout_s, what=what)

    def _fail_seq(self, seq: _Seq, reason: str, finish_reason: str,
                  finished: List[GenerationResult]) -> None:
        """Evict a running sequence as failed; the engine keeps serving
        every other admitted request."""
        self.stats["failed_requests"] += 1
        self.cache.free_sequence(seq.rid)
        self.slots[seq.slot] = None
        self._finish_obs(seq.rid, finish_reason, seq.trace_id,
                         seq.root_span, seq.t_enq, seq.t_first,
                         len(seq.out))
        finished.append(GenerationResult(
            request_id=seq.rid, prompt_ids=seq.prompt,
            output_ids=np.asarray(seq.out, np.int32),
            finish_reason=finish_reason, error=reason))

    def _expire_deadlines(self, finished: List[GenerationResult]) -> None:
        """Fail requests whose TTL elapsed: waiting ones are dropped,
        running ones evicted (their pages return to the pool)."""
        now = self._now()
        expired = [r for r in self.waiting
                   if r.deadline is not None and now >= r.deadline]
        for req in expired:
            self.waiting.remove(req)
            self.stats["deadline_expired"] += 1
            self.stats["failed_requests"] += 1
            self._finish_obs(req.rid, "deadline", req.trace_id,
                             req.root_span, req.t_enq, req.t_first,
                             len(req.resume_out))
            if _fl._ARMED:
                _fl.trigger("deadline_miss", detail={
                    "request_id": str(req.rid), "where": "queued",
                    "overrun_s": now - req.deadline})
            finished.append(GenerationResult(
                request_id=req.rid, prompt_ids=req.prompt,
                output_ids=np.asarray(req.resume_out, np.int32),
                finish_reason="deadline",
                error="deadline exceeded by "
                      f"{now - req.deadline:.3f}s while queued"))
        for seq in [s for s in self.slots if s is not None]:
            if seq.deadline is not None and now >= seq.deadline:
                self.stats["deadline_expired"] += 1
                if _fl._ARMED:
                    _fl.trigger("deadline_miss", detail={
                        "request_id": str(seq.rid), "where": "running",
                        "overrun_s": now - seq.deadline})
                self._fail_seq(seq, "deadline expired mid-generation",
                               "deadline", finished)

    def _safe_prefills(self, seqs: List[_Seq],
                       finished: List[GenerationResult]):
        """Batched prefill with poisoned-request isolation: if the
        packed batch raises, each sequence is retried alone (a smaller
        total-token bucket of the same ragged family) and only the
        one(s) that still raise are failed and evicted."""
        try:
            return list(zip(seqs, self._run_prefills(seqs)))
        except Exception:
            # see step(): a failure from the donated jit call itself
            # leaves no caches to retry against — fatal, not poison
            if any(getattr(k, "is_deleted", lambda: False)()
                   for k in self.cache.key_caches):
                raise
            pairs = []
            for s in seqs:
                if self.slots[s.slot] is not s:  # preempted meanwhile
                    continue
                try:
                    (first,) = self._run_prefills([s])
                    pairs.append((s, first))
                except Exception as e:
                    self._fail_seq(
                        s, f"prefill raised {type(e).__name__}: {e}",
                        "error", finished)
            return pairs

    # -- main loop ---------------------------------------------------------
    def step(self) -> List[GenerationResult]:
        """Admit + prefill new sequences, run one decode chunk, retire
        finished sequences. Returns results finished this step —
        including failed/rejected/expired ones (check `.ok`)."""
        t0 = time.perf_counter()
        pre0 = self.stats["preemptions"] if _fl._ARMED else 0
        with _ot.span("engine.step") as sp:
            finished = self._step_impl()
        dt = time.perf_counter() - t0
        if _om._ENABLED:
            m = _metrics()
            m["step"].observe(dt)
            m["queue"].labels(queue="waiting").set(len(self.waiting))
            m["queue"].labels(queue="running").set(
                sum(s is not None for s in self.slots))
            free = self.cache.allocator.num_free
            nb = self.cache.allocator.num_blocks
            m["pool"].labels(state="free").set(free)
            m["pool"].labels(state="used").set(nb - free)
            m["prefix_pages"].labels(state="indexed").set(
                self.cache.cached_pages)
            m["prefix_pages"].labels(state="lru").set(
                self.cache.lru_pages)
            # HBM telemetry at the step boundary: the pool allocation
            # is the engine's dominant persistent HBM, live-array bytes
            # the whole process footprint (weights + pool + staging).
            # The live-array walk is O(all buffers in the process), so
            # it is throttled to one walk per second — the footprint
            # moves far slower than the step cadence, and an every-step
            # walk would skew the step-latency histogram it sits next to
            m["hbm_pool"].labels(state="reserved").set(self._pool_bytes)
            m["hbm_pool"].labels(state="used").set(
                self._pool_bytes * (nb - free) // max(nb, 1))
            now = time.perf_counter()
            if now - self._hbm_sampled_at >= 1.0:
                m["hbm_live"].set(
                    sum(a.nbytes for a in jax.live_arrays()))
                self._hbm_sampled_at = now
        if _fl._ARMED:
            cfg = _fl.config()
            thr = cfg.step_latency_threshold_s if cfg else None
            storm = cfg.preempt_storm if cfg else None
            if thr is not None and dt > thr:
                _fl.trigger("step_latency", detail={
                    "step_seconds": dt, "threshold_s": thr,
                    "trace_id": sp.trace_id, "span_id": sp.span_id},
                    extra={"engine_stats": dict(self.stats)})
            elif storm and \
                    self.stats["preemptions"] - pre0 >= storm:
                _fl.trigger("preempt_storm", detail={
                    "preemptions_in_step":
                        self.stats["preemptions"] - pre0,
                    "threshold": storm,
                    "trace_id": sp.trace_id, "span_id": sp.span_id},
                    extra={"engine_stats": dict(self.stats)})
        return finished

    def _step_impl(self) -> List[GenerationResult]:
        finished: List[GenerationResult] = []
        if self._failed:                    # load-shed rejections
            finished.extend(self._failed)
            self._failed.clear()
        faults.fault_point("engine.step")
        with _ot.span("engine.schedule"):
            self._expire_deadlines(finished)
            fresh = self._admit()
        if fresh:
            pairs = self._safe_prefills(fresh, finished)
            with _ot.span("engine.commit", of="prefill"):
                for seq, first in pairs:
                    seq.out.append(first)
                    self.stats["decode_tokens"] += 1
                    if seq.t_first is None:     # resumed seqs keep theirs
                        seq.t_first = time.perf_counter()
                        if _om._ENABLED:
                            m = _metrics()
                            ttft = seq.t_first - seq.t_enq
                            m["ttft"].observe(ttft)
                            # latency-budget attribution: the accumulated
                            # components, plus a residual so the five
                            # observations sum to the TTFT observation
                            # exactly — "other" is scheduler overhead plus
                            # anything a failed-over life burned on a
                            # replica this engine never saw
                            known = (seq.bud_queue + seq.bud_prefill
                                     + seq.bud_miss + seq.bud_compile)
                            bh = m["ttft_budget"]
                            bh.labels(component="queue_wait").observe(
                                seq.bud_queue)
                            bh.labels(component="prefill_compute").observe(
                                seq.bud_prefill)
                            bh.labels(component="affinity_miss").observe(
                                seq.bud_miss)
                            bh.labels(component="compile_stall").observe(
                                seq.bud_compile)
                            bh.labels(component="other").observe(
                                max(ttft - known, 0.0))
                    self._maybe_finish(seq, finished)
        if self._proposer is not None and self._run_spec_step(finished):
            # speculative step committed tokens, rolled back the KV
            # lease, and retired finished sequences itself (its device
            # phase degrades to the chunked path below on failure; see
            # _run_spec_step)
            return finished
        try:
            chunk_out = self._run_decode_chunk()
        except Exception:
            # poisoned-request isolation: one request's failure must
            # not take down the batch — rerun each sequence alone and
            # evict only the ones that still fail. If NO sequence
            # survives alone the failure is systemic (undersized pool,
            # device OOM), not a poisoned request: re-raise so the
            # operator sees one loud engine error, not N quiet
            # per-request ones — unless shed_load says degrade anyway.
            # A failure raised by the jitted call ITSELF is always
            # fatal: donation has already consumed the cache buffers,
            # so no retry can run against them — surface the real
            # error instead of N 'Array has been deleted' ones.
            if any(getattr(k, "is_deleted", lambda: False)()
                   for k in self.cache.key_caches):
                raise
            chunk_out = {}
            survivors = 0
            casualties = []
            for s in [s for s in self.slots if s is not None]:
                if self.slots[s.slot] is not s:  # preempted meanwhile
                    continue
                try:
                    chunk_out.update(self._run_decode_chunk(only=s))
                    survivors += 1
                except Exception as e:
                    casualties.append((s, e))
            if casualties and not survivors and not self.shed_load:
                raise
            for s, e in casualties:
                self._fail_seq(
                    s, f"decode raised {type(e).__name__}: {e}",
                    "error", finished)
        with _ot.span("engine.commit", of="decode"):
            for slot, toks in chunk_out.items():
                seq = self.slots[slot]
                if seq is None:
                    continue
                for t in toks:
                    if len(seq.out) >= seq.max_new:
                        break
                    seq.out.append(int(t))
                    self.stats["decode_tokens"] += 1
                    if (self.eos_token_id is not None
                            and int(t) == self.eos_token_id):
                        break
                if self.cache.enable_prefix_caching:
                    # register newly FILLED full blocks before the sequence
                    # can retire (so its pages park hash-indexed): valid KV
                    # covers prompt + appended tokens, capped at what the
                    # chunk actually wrote. Skip the token-array rebuild
                    # entirely when no block boundary was crossed.
                    ntok = min(seq.length, len(seq.prompt) + len(seq.out))
                    if self.cache.cached_prefix_len(seq.rid) \
                            + self.block_size <= ntok:
                        self.cache.commit_prefix(
                            seq.rid, self._merged_tokens(seq), upto=ntok)
                self._maybe_finish(seq, finished)
        return finished

    def _maybe_finish(self, seq: _Seq, finished: List[GenerationResult]):
        done_eos = (self.eos_token_id is not None and seq.out
                    and seq.out[-1] == self.eos_token_id)
        done_len = len(seq.out) >= seq.max_new
        if not (done_eos or done_len):
            return
        reason = "eos" if done_eos else "length"
        self._finish_obs(seq.rid, reason, seq.trace_id, seq.root_span,
                         seq.t_enq, seq.t_first, len(seq.out))
        finished.append(GenerationResult(
            request_id=seq.rid, prompt_ids=seq.prompt,
            output_ids=np.asarray(seq.out, np.int32),
            finish_reason=reason))
        self.cache.free_sequence(seq.rid)
        self.slots[seq.slot] = None

    def generate(self, prompts, max_new_tokens: int = 32
                 ) -> List[GenerationResult]:
        """Convenience driver: submit all prompts, run to completion,
        return results in submission order."""
        for i, p in enumerate(prompts):
            self.add_request(i, p, max_new_tokens)
        done: Dict[object, GenerationResult] = {}
        while self.has_unfinished:
            for r in self.step():
                done[r.request_id] = r
        return [done[i] for i in range(len(prompts))]
