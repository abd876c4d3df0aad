"""Attention-bias classes for memory_efficient_attention (ref:
python/paddle/incubate/nn/attn_bias.py — the xformers-style family of
bias classes). Each class can MATERIALIZE itself as an additive float mask;
memory_efficient_attention also pattern-matches the causal/block
classes to stay on the masked-flash path without materializing."""
from __future__ import annotations

from abc import ABC, abstractmethod
from dataclasses import dataclass
from typing import List, Optional

import jax.numpy as jnp

NEG = -1e30


class AttentionBias(ABC):
    @abstractmethod
    def materialize(self, shape, dtype=jnp.float32):
        """Additive bias broadcastable to [b, h, sq, sk]."""


class LowerTriangularMask(AttentionBias):
    """Causal mask (q row i sees k cols <= i)."""

    def materialize(self, shape, dtype=jnp.float32):
        sq, sk = shape[-2], shape[-1]
        keep = jnp.tril(jnp.ones((sq, sk), bool), k=sk - sq)
        return jnp.where(keep, 0.0, NEG).astype(dtype)


class LowerTriangularMaskWithTensorBias(LowerTriangularMask):
    """Causal + an additive tensor bias (e.g. ALiBi slopes)."""

    def __init__(self, bias):
        self._bias = bias

    def materialize(self, shape, dtype=jnp.float32):
        base = super().materialize(shape, dtype)
        b = self._bias._data if hasattr(self._bias, "_data") else \
            jnp.asarray(self._bias)
        return base + b.astype(dtype)


@dataclass
class SeqLenInfo:
    """Cumulative packing offsets for block-diagonal masks."""
    seqstart: List[int]

    @classmethod
    def from_seqlens(cls, seqlens):
        starts = [0]
        for s in seqlens:
            starts.append(starts[-1] + int(s))
        return cls(seqstart=starts)

    @property
    def seqlens(self):
        return [b - a for a, b in zip(self.seqstart, self.seqstart[1:])]


def segment_ids(starts, total):
    """int32 [total] segment id per packed position. Validates the
    packing covers the tensor exactly — a short seqlens list would
    otherwise silently give tail tokens segment 0 (cross-sequence
    attention leakage, the xformers reference asserts the same)."""
    import numpy as np
    if starts[-1] != total:
        raise ValueError(
            f"seqlens sum to {starts[-1]} but the packed sequence "
            f"length is {total}")
    seg = np.zeros((total,), np.int32)
    for i, (a, b) in enumerate(zip(starts, starts[1:])):
        seg[a:b] = i
    return jnp.asarray(seg)


class BlockDiagonalMask(AttentionBias):
    """Packed-varlen block-diagonal mask: token i attends within its
    own sequence only."""

    def __init__(self, q_seqinfo: SeqLenInfo,
                 k_seqinfo: Optional[SeqLenInfo] = None):
        self.q_seqinfo = q_seqinfo
        self.k_seqinfo = k_seqinfo or q_seqinfo

    @classmethod
    def from_seqlens(cls, q_seqlen, kv_seqlen=None):
        qs = SeqLenInfo.from_seqlens(q_seqlen)
        ks = SeqLenInfo.from_seqlens(kv_seqlen) if kv_seqlen else None
        return cls(qs, ks)

    def _block_keep(self, sq, sk):
        qseg = segment_ids(self.q_seqinfo.seqstart, sq)
        kseg = segment_ids(self.k_seqinfo.seqstart, sk)
        return qseg[:, None] == kseg[None, :]

    def materialize(self, shape, dtype=jnp.float32):
        sq, sk = shape[-2], shape[-1]
        return jnp.where(self._block_keep(sq, sk), 0.0, NEG).astype(
            dtype)

    def make_causal(self):
        return BlockDiagonalCausalMask(self.q_seqinfo, self.k_seqinfo)


class BlockDiagonalCausalMask(BlockDiagonalMask):
    """Block-diagonal AND causal WITHIN each sequence: q local position
    i of block b sees kv local positions <= i of the SAME block (the
    reference/xformers semantics — a global diagonal is only equivalent
    when q and kv packings coincide)."""

    def materialize(self, shape, dtype=jnp.float32):
        sq, sk = shape[-2], shape[-1]
        keep = self._block_keep(sq, sk)
        qstart = jnp.asarray(self.q_seqinfo.seqstart)
        kstart = jnp.asarray(self.k_seqinfo.seqstart)
        qseg = segment_ids(self.q_seqinfo.seqstart, sq)
        kseg = segment_ids(self.k_seqinfo.seqstart, sk)
        qlocal = jnp.arange(sq) - qstart[qseg]
        klocal = jnp.arange(sk) - kstart[kseg]
        causal = klocal[None, :] <= qlocal[:, None]
        return jnp.where(keep & causal, 0.0, NEG).astype(dtype)
