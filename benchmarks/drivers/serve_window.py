"""The open-loop serving window: `LLMEngine.add_request` / `LLMEngine.step`
under a schedule of arrivals that does not wait for the engine.

What counts as an operation. A request that reached a terminal
`finish_reason` inside the window is one attempted operation; it has
failed if that reason is not `eos` or `length` (`GenerationResult.ok`) or
if the engine raised for it. A request still waiting or decoding when
the window closes is neither attempted nor failed: it is the backlog.
A request that finished late is a finished request. The engine runs as a
deployment that sheds nothing: no shedding, no waiting cap, no deadline.

Clocks are the harness's own, taken around `add_request` and `step`: a
request is due at its scheduled arrival; its tokens land when the
`step()` that produced them returns.

The front door. Arrivals that are due wait in the harness's queue and are
handed to the engine, first come first served, while the contexts waiting
inside the engine sum to at most `front_door_tokens`. `LLMEngine` puts no
bound on the tokens of one admission wave, and a wave's packed length
picks the `engine_ragged` program; with the door a wave is at most that
many tokens, so the set of programs the engine can ask for is closed and
set-up warms all of it: nothing compiles inside the window. The door is
the harness's policy and not the engine's: above capacity it, not the
engine, decides how full the batch runs, and its wait is part of a
request's queue wait and time to first token. A row the engine preempts
re-enters the engine's list past the door. A wave budget inside the
engine would replace it (PERF.md, section 7).

No cell of `BENCHMARK.json` uses this driver yet: PR 24 measured two
serving cells on the chip with it and withdrew them in review (PERF.md,
sections 6 and 7). Until a later `benchmark` PR brings serving cells, its
accounting, its readers and its comparison run on the CPU at tiny size in
`benchmarks/tests/`.

Traffic runs in real time from `-ramp_s`: the ramp, part of set-up,
serves the sessions' earlier turns and brings the engine to its steady
state; the window opens at a step boundary after it and closes at the
end of the step in flight when `--seconds` have passed.
"""
from __future__ import annotations

import collections
import gc
import time

import numpy as np

from harness import gpt_program, runlib
from harness.runlib import annotate, clock
from harness.traffic_model import TrafficModel


def build_engine(cfg: dict, seed: int, ref):
    from paddle_tpu.inference import LLMEngine

    sv = cfg["serving"]
    model = gpt_program.build_model(cfg, seed, ref, sv["dtype"])
    model.eval()
    return LLMEngine(
        model, max_batch=sv["max_batch"], num_blocks=sv["num_blocks"],
        block_size=sv["block_size"],
        max_model_len=cfg["max_position_embeddings"],
        decode_chunk=sv["decode_chunk"],
        prompt_quantum=sv["prompt_quantum"], do_sample=False,
        eos_token_id=None, shed_load=False, max_waiting=None,
        step_timeout_s=None, enable_prefix_caching=sv["prefix_caching"])


def schedule(cfg: dict, mix: dict, seed: int, seconds: float):
    """The arrivals from -ramp_s to the window's end and a little past
    it, as (due offset from the start of traffic, event)."""
    model = TrafficModel.from_mix(mix, seed, cfg["real_vocab_size"])
    g = mix["generator"]
    horizon = mix["ramp_s"] + seconds + mix["tail_s"]
    mean = (g["base_rate"] * g["off_s"] + g["burst_rate"] * g["on_s"]) \
        / (g["off_s"] + g["on_s"])
    events = []
    for ev in model.events(int(mean * horizon * 2) + 64):
        if ev.t > horizon:
            break
        events.append(ev)
    return events


def buckets(engine, mix: dict):
    """Every total-token bucket a wave of at most `front_door_tokens`
    can fall into."""
    out, n = [], 1
    while n <= mix["front_door_tokens"]:
        b = engine._token_bucket(n)
        if b not in out:
            out.append(b)
        n = b + 1
    return out


def warm_up(engine, cfg: dict, mix: dict):
    """Runs every program the window can ask for, once: each ragged
    bucket without and with a cached prefix, and the decode chunk."""
    rng = np.random.default_rng(0)
    vocab = cfg["real_vocab_size"]
    bs = engine.block_size

    def serve(rid, prompt, new):
        engine.add_request(rid, prompt, max_new_tokens=new)
        while engine.has_unfinished:
            for r in engine.step():
                if not r.ok:
                    raise RuntimeError(f"warm-up request {rid} ended in "
                                       f"{r.finish_reason}: {r.error}")

    prefix = rng.integers(0, vocab, (bs,)).astype(np.int32)
    # commits the shared block and builds the decode program
    serve("warm.prefix", np.concatenate(
        [prefix, rng.integers(0, vocab, (8,)).astype(np.int32)]),
        engine.decode_chunk + 1)
    for tb in buckets(engine, mix):
        fresh = rng.integers(0, vocab, (tb,)).astype(np.int32)
        serve(f"warm.{tb}.fresh", fresh, 1)
        serve(f"warm.{tb}.cached", np.concatenate([prefix, fresh]), 1)
    want = {("ragged", tb, pool, False) for tb in buckets(engine, mix)
            for pool in (False, True)} | {("decode", engine.decode_chunk)}
    missing = want - set(engine._fns)
    if missing:
        raise RuntimeError(f"warm-up did not build {sorted(missing)}")


class Req:
    __slots__ = ("ev", "due", "seen", "admit", "first", "n_first", "last",
                 "n", "reason", "ok", "cached")

    def __init__(self, ev, due):
        self.ev, self.due = ev, due
        self.seen = self.admit = self.first = self.last = None
        self.n_first = self.n = 0
        self.reason = self.ok = self.cached = None


class Window:
    """The loop and its books. Times are `clock()` readings."""

    def __init__(self, engine, events, mix, tracer=None):
        self.engine, self.mix, self.tracer = engine, mix, tracer
        self.events = events
        self.reqs = {}                  # rid -> Req, in arrival order
        self.door = collections.deque()  # due, not yet in the engine
        self.steps = []   # (begin, end, rows, tokens landed, new [(m, st)])
        self.i = 0
        self.t_traffic = None
        self.outputs = {}               # rid -> served tokens

    # -- arrivals -----------------------------------------------------------
    def _arrive(self, now):
        while self.i < len(self.events) and \
                self.t_traffic + self.events[self.i].t <= now:
            ev = self.events[self.i]
            self.i += 1
            r = self.reqs[ev.rid] = Req(ev, self.t_traffic + ev.t)
            r.seen = now
            self.door.append(r)

    def _release(self):
        eng, cap = self.engine, self.mix["front_door_tokens"]
        held = sum(q.context_len for q in eng.waiting)
        while self.door:
            r = self.door[0]
            if eng.waiting and held + len(r.ev.prompt) > cap:
                break
            self.door.popleft()
            with annotate("harness.serve.add_request"):
                eng.add_request(r.ev.rid, r.ev.prompt,
                                max_new_tokens=r.ev.max_new)
            held += len(r.ev.prompt)

    # -- one step -------------------------------------------------------------
    def _step(self):
        eng = self.engine
        with annotate("harness.serve.engine_step"):
            t_s = clock()
            results = eng.step()
            t_e = clock()
        landed, new = 0, []
        rows = 0
        for seq in eng.slots:
            if seq is not None:
                rows += 1
                landed += self._saw(self.reqs.get(seq.rid), len(seq.out),
                                    t_s, t_e, seq.cached_len, new)
        for res in results:
            r = self.reqs.get(res.request_id)
            if r is None:
                continue
            rows += 1
            landed += self._saw(r, len(res.output_ids), t_s, t_e, None, new)
            r.last, r.reason, r.ok = t_e, res.finish_reason, res.ok
            self.outputs[res.request_id] = np.asarray(res.output_ids)
        self.steps.append((t_s, t_e, rows, landed, new))
        return t_e

    @staticmethod
    def _saw(r, n_out, t_s, t_e, cached, new):
        if r is None:
            return 0
        if r.first is None and n_out > 0:
            r.admit, r.first, r.n_first = t_s, t_e, n_out
            r.cached = cached
            new.append((len(r.ev.prompt) - (cached or 0), cached or 0))
        delta = n_out - r.n
        r.n = n_out
        return max(delta, 0)

    # -- the loop ---------------------------------------------------------------
    def run_until(self, t_stop, poll_from=None):
        """Serve until a step ends at or after `t_stop`; returns that
        step's end."""
        eng = self.engine
        while True:
            now = clock()
            self._arrive(now)
            self._release()
            if eng.has_unfinished:
                now = self._step()
            else:
                nxt = (self.t_traffic + self.events[self.i].t
                       if self.i < len(self.events) else t_stop)
                with annotate("harness.serve.wait_arrival"):
                    time.sleep(max(0.0, min(nxt, t_stop) - clock()))
                now = clock()
            if poll_from is not None and self.tracer is not None:
                self.tracer.poll(now - poll_from)
            if now >= t_stop:
                return now


def sample_requests(finished, seed: int, k: int):
    """k of the window's finished requests, drawn from the seed, with
    the longest in it and one that started past a cached prefix."""
    if not finished:
        return []
    rng = np.random.default_rng([int(seed) & 0xFFFFFFFF, int(seed) >> 32])
    longest = max(finished, key=lambda r: len(r.ev.prompt) + r.n)
    picked = [longest]
    cached = [r for r in finished if r.cached and r is not longest]
    if cached:
        picked.append(cached[int(rng.integers(len(cached)))])
    rest = [r for r in finished if r not in picked]
    order = rng.permutation(len(rest))
    picked += [rest[int(j)] for j in order[:max(0, k - len(picked))]]
    return picked


def pad_len(mix: dict) -> int:
    """The reference's sequence length: the mix's longest request, up to
    a multiple of 128, so that it compiles one program."""
    return -(-mix["max_total_tokens"] // 128) * 128


def logit_gaps(model, samples, pad_to: int, chooser=None):
    """For each sampled request, one reference forward over its prompt
    with its served tokens: at every served position, how far a token's
    logit lies below the reference's best. The token is the served one,
    or — for the control — the one `chooser` (the reference computed in
    lower precision) puts first at that position. Returns the widest gap
    and the number of tokens compared."""
    import jax.numpy as jnp
    widest, count = 0.0, 0
    for prompt, served in samples:
        seq = np.concatenate([prompt, served]).astype(np.int32)
        ids = np.zeros((pad_to,), np.int32)
        ids[:len(seq)] = seq        # causal: padding after changes nothing
        at = len(prompt) - 1 + np.arange(len(served))
        logits = model.logits_at(ids, at)
        if chooser is None:
            tokens = jnp.asarray(served, jnp.int32)
        else:
            tokens = jnp.argmax(chooser.logits_at(ids, at), axis=-1)
        mine = jnp.take_along_axis(logits, tokens[:, None], axis=-1)[:, 0]
        widest = max(widest, float(jnp.max(jnp.max(logits, -1) - mine)))
        count += len(served)
    return widest, count


def measure(engine, events, mix, seconds, tracer=None, on_open=None):
    """Ramp, then the window. Returns the window's books: what the
    end-to-end metrics and the per-layer readers are taken from."""
    win = Window(engine, events, mix, tracer)
    win.t_traffic = clock()
    t0 = win.run_until(win.t_traffic + mix["ramp_s"])   # the ramp: set-up
    if on_open is not None:
        on_open(t0)
    stats0 = dict(engine.stats)
    n_steps0 = len(win.steps)
    t1 = win.run_until(t0 + seconds, poll_from=t0)
    if tracer is not None:
        tracer.finish()
    stats1 = dict(engine.stats)
    reqs = list(win.reqs.values())
    done = [r for r in reqs if r.last is not None and t0 < r.last <= t1]
    first_in = [r for r in reqs if r.first is not None and t0 < r.first <= t1]
    steps = win.steps[n_steps0:]
    return {
        "kind": "serve", "t0": t0, "t1": t1, "steps": steps, "reqs": reqs,
        "done": done, "first_in": first_in, "outputs": win.outputs,
        "ttft_ms": [1e3 * (r.first - r.due) for r in first_in],
        # per output token after the first delivery: the tokens that
        # landed after `first` over the time after it
        "tpot_ms": [1e3 * (r.last - r.first) / (r.n - r.n_first)
                    for r in first_in if r.last is not None
                    and r.last <= t1 and r.n > r.n_first],
        "backlog": sum(1 for r in reqs if r.due <= t1 and
                       (r.last is None or r.last > t1)),
        "backlog_start": sum(1 for r in reqs if r.due <= t0 and
                             (r.last is None or r.last > t0)),
        "offered": sum(1 for r in reqs if t0 < r.due <= t1),
        "tokens": sum(s[3] for s in steps),
        "stats": {k: stats1[k] - stats0.get(k, 0) for k in stats1},
        "max_batch": engine.max_batch,
        "decode_chunk": engine.decode_chunk,
        "trace_span": ((tracer.t_start, tracer.t_stop) if tracer
                       else (None, None))}


def check_schedule(events, mix):
    longest = max(len(ev.prompt) for ev in events)
    total = max(len(ev.prompt) + ev.max_new for ev in events)
    if longest > mix["front_door_tokens"] or total > mix["max_total_tokens"]:
        raise RuntimeError(
            f"the mix makes a prompt of {longest} tokens (front door "
            f"{mix['front_door_tokens']}) or a request of {total} "
            f"(max_total_tokens {mix['max_total_tokens']})")


def run(ctx) -> dict:
    cfg, mix, cell, ref = ctx.cfg, ctx.mix, ctx.cell, ctx.ref
    engine = build_engine(cfg, ctx.seed, ref)
    events = schedule(cfg, mix, ctx.seed, ctx.seconds)
    check_schedule(events, mix)
    warm_up(engine, cfg, mix)

    opened = {}

    def on_open(t0):
        ctx.watch.arm()
        opened["setup_s"] = t0 - ctx.t_process

    w = ctx.window = measure(engine, events, mix, ctx.seconds, ctx.tracer,
                             on_open)
    seen = ctx.watch.disarm()
    peak = runlib.memory_peak_bytes([f.fn for f in engine._fns.values()])
    span = w["t1"] - w["t0"]
    done = w["done"]
    # every end-to-end number the window can give: a cell's entries in
    # BENCHMARK.json choose which of them it reports
    e2e = {"ttft_p50_ms": runlib.percentile(w["ttft_ms"], 50),
           "ttft_p95_ms": runlib.percentile(w["ttft_ms"], 95),
           "tpot_p95_ms": runlib.percentile(w["tpot_ms"], 95),
           "serve_tok_s": w["tokens"] / span, "setup_s": opened["setup_s"]}

    picked = sample_requests([r for r in done if r.ok], ctx.seed,
                             mix["check_requests"])
    samples = [(r.ev.prompt, w["outputs"][r.ev.rid]) for r in picked]
    notes = {"window_s": span, "offered": w["offered"],
             "finished": len(done), "first_tokens": len(w["first_in"]),
             "tpot_samples": len(w["tpot_ms"]),
             "backlog_start": w["backlog_start"],
             "backlog_end": w["backlog"], "tokens": w["tokens"],
             "steps": len(w["steps"]),
             "tpot_p50_ms": runlib.percentile(w["tpot_ms"], 50),
             "preemptions": w["stats"]["preemptions"],
             "checked_requests": len(samples),
             "checked_past_cached_prefix": sum(1 for r in picked
                                               if r.cached), **seen}

    # the engine's state leaves before the reference comes
    del engine
    gc.collect()
    t_ref = clock()
    model = ref.Model(cfg, ctx.seed, dtype=cfg["serving"]["dtype"])
    gap, n_tok = logit_gaps(model, samples, pad_len(mix))
    notes.update(reference_s=clock() - t_ref, checked_tokens=n_tok)
    compared = {"served_logit_gap_max": {
        "value": gap if samples else None,
        "limit": cell["limits"]["served_logit_gap_max"]}}
    correct = runlib.judge(compared) and not any(seen.values())
    return {"correct": correct, "attempted": len(done),
            "failed": sum(1 for r in done if not r.ok), "e2e": e2e,
            "peak": peak, "compared": compared, "notes": notes}
