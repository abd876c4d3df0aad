"""The traffic generator: a copy of `paddle_tpu/inference/traffic.py`'s
`TrafficModel` (on/off modulated Poisson arrivals, cohorts that share a
system prompt, multi-turn sessions whose prompts grow, lognormal body and
output lengths), kept here so that no later PR can change the yardstick.

It differs from the original only in where its parameters come from:

* every parameter is read from a traffic mix file (`from_mix`); there are
  no default cohorts and no sizes for tiny CPU models;
* `run_traffic` is not copied: it drives a `Router`, times from enqueue
  and not from when a request was due.
"""
from __future__ import annotations

import dataclasses
import hashlib
from collections import OrderedDict
from typing import Iterator

import numpy as np


@dataclasses.dataclass(frozen=True)
class Cohort:
    """One user population in the mix."""
    name: str
    weight: float           # share of arrivals
    prefix_len: int         # shared cohort prefix (system prompt) tokens
    body_mu: float          # lognormal(log-mean) of per-session body len
    body_sigma: float       # lognormal log-std — the heavy tail
    out_mu: float           # lognormal(log-mean) of output tokens
    out_sigma: float
    mean_turns: float       # geometric mean turns before churn


@dataclasses.dataclass(frozen=True)
class TrafficEvent:
    t: float                # arrival offset from run start (seconds)
    rid: object
    session: int
    cohort: str
    turn: int
    prompt: np.ndarray      # int32 tokens
    max_new: int


class TrafficModel:
    """Deterministic event-stream generator (same seed -> identical
    schedule, the property the A/B bench comparison rests on).

    Arrivals are an on/off modulated Poisson process: `base_rate`
    req/s during off (calm) phases, `burst_rate` during on phases,
    phases alternating every `off_s`/`on_s` seconds — the load shape
    that makes elastic scaling pay. `n_sessions` bounds the session
    id space; `reuse` is the probability an arrival continues a
    recent session (next turn, shared prefix grows) instead of
    starting a fresh one."""

    def __init__(self, *, cohorts, seed: int, n_sessions: int, vocab: int,
                 base_rate: float, burst_rate: float, off_s: float,
                 on_s: float, reuse: float, min_body: int, max_body: int,
                 min_out: int, max_out: int, active_window: int):
        self.cohorts = tuple(cohorts)
        self.seed = int(seed)
        self.n_sessions = int(n_sessions)
        self.vocab = int(vocab)
        self.base_rate = float(base_rate)
        self.burst_rate = float(burst_rate)
        self.off_s = float(off_s)
        self.on_s = float(on_s)
        self.reuse = float(reuse)
        self.min_body, self.max_body = int(min_body), int(max_body)
        self.min_out, self.max_out = int(min_out), int(max_out)
        self._active_cap = int(active_window)
        # host-side scheduling math, no device tensors involved
        w = np.asarray([c.weight for c in self.cohorts], np.float64)
        self._cum_w = np.cumsum(w / w.sum())
        # cohort prefixes: derived once, shared by every member
        self._prefixes = [
            self._rng("prefix", i).integers(
                0, self.vocab, (c.prefix_len,)).astype(np.int32)
            for i, c in enumerate(self.cohorts)]

    def _rng(self, *key) -> np.random.Generator:
        # a distinct, deterministic stream per derivation key — the
        # stateless-session trick: nothing per-session is ever stored.
        # blake2s, NOT hash(): builtin string hashing is randomized
        # per process, and the A/B bench comparison needs the same
        # seed to mean the same schedule in every process
        digest = hashlib.blake2s(
            repr((self.seed,) + key).encode(), digest_size=8).digest()
        return np.random.default_rng(int.from_bytes(digest, "little"))

    @classmethod
    def from_mix(cls, mix: dict, seed: int, vocab: int):
        """The model a traffic mix file describes, at the run's seed."""
        g = mix["generator"]
        return cls(cohorts=[Cohort(**c) for c in g["cohorts"]],
                   seed=seed, vocab=vocab, **{k: g[k] for k in (
                       "n_sessions", "base_rate", "burst_rate", "off_s",
                       "on_s", "reuse", "min_body", "max_body", "min_out",
                       "max_out", "active_window")})

    def _lengths(self, ci: int, session: int, turn: int):
        c = self.cohorts[ci]
        r = self._rng("len", ci, session, turn)
        body = int(np.clip(r.lognormal(c.body_mu, c.body_sigma),
                           self.min_body, self.max_body))
        out = int(np.clip(r.lognormal(c.out_mu, c.out_sigma),
                          self.min_out, self.max_out))
        return body, out

    def prompt(self, ci: int, session: int, turn: int) -> np.ndarray:
        """The session's turn-`turn` prompt: cohort shared prefix +
        the session's stable context + per-turn tails of every turn
        so far — so turn t+1 extends turn t's tokens exactly, and
        affinity routing re-hits the whole conversation."""
        body, _out = self._lengths(ci, session, 0)
        stable = self._rng("body", ci, session).integers(
            0, self.vocab, (body,)).astype(np.int32)
        parts = [self._prefixes[ci], stable]
        for t in range(1, turn + 1):
            tb, _o = self._lengths(ci, session, t)
            parts.append(self._rng("turn", ci, session, t).integers(
                0, self.vocab, (max(2, tb // 4),)).astype(np.int32))
        return np.concatenate(parts)

    def events(self, n: int) -> Iterator[TrafficEvent]:
        """Yield `n` arrivals in time order."""
        rng = self._rng("arrivals")
        # active multi-turn sessions, LRU-bounded: session -> (ci, turn)
        active: "OrderedDict[int, tuple]" = OrderedDict()
        t = 0.0
        period = self.off_s + self.on_s
        for i in range(n):
            in_burst = (t % period) >= self.off_s
            rate = self.burst_rate if in_burst else self.base_rate
            t += rng.exponential(1.0 / rate)
            if active and rng.random() < self.reuse:
                # continue a recent conversation (most recent first —
                # the recency bias real session traffic has)
                k = min(len(active) - 1,
                        int(rng.geometric(0.5)) - 1)
                session = list(active)[-1 - k]
                ci, turn = active[session]
                turn += 1
                # churn: the conversation ends after ~mean_turns
                if turn + 1 >= self.cohorts[ci].mean_turns * 2 or \
                        rng.random() < 1.0 / max(
                            self.cohorts[ci].mean_turns, 1.0):
                    active.pop(session, None)
                else:
                    active[session] = (ci, turn)
                    active.move_to_end(session)
            else:
                ci = int(np.searchsorted(self._cum_w, rng.random(),
                                         side="left"))
                session = int(rng.integers(self.n_sessions))
                turn = 0
                if self.cohorts[ci].mean_turns > 1.0:
                    active[session] = (ci, turn)
                    while len(active) > self._active_cap:
                        active.popitem(last=False)
            _body, out = self._lengths(ci, session, turn)
            yield TrafficEvent(
                t=t, rid=f"r{i}", session=session,
                cohort=self.cohorts[ci].name, turn=turn,
                prompt=self.prompt(ci, session, turn), max_new=out)
