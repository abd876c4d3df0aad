"""The mesh a program being traced will be partitioned over: a fact of
whoever traces it (`jit.TrainStep` under a mesh), read by what cannot
leave the split to the compiler (the Pallas flash kernels, which split
themselves with `shard_map`; the LM head, whose promise of logits is for
one device)."""
from __future__ import annotations

import contextlib
import contextvars

# (mesh, batch_axes) while a program that GSPMD will partition over
# `mesh` is being traced; None otherwise
_PLAN: contextvars.ContextVar = contextvars.ContextVar(
    "mesh_plan", default=None)


@contextlib.contextmanager
def mesh_plan(mesh, batch_axes=()):
    """Tell what is traced inside this block that the program will be
    partitioned over `mesh`, with the batch dimension of its data split
    over `batch_axes`. The compiler cannot partition a Mosaic kernel by
    itself, so under a plan `flash_attention` splits its call with
    `shard_map`: batch over `batch_axes`, heads over the mesh's other
    axes (the Megatron layout) where the per-device head count still
    fits the kernel, whole on every device of an axis where it does
    not."""
    token = _PLAN.set((mesh, tuple(batch_axes)))
    try:
        yield
    finally:
        _PLAN.reset(token)


def current_mesh_plan():
    """(mesh, batch_axes) of the `mesh_plan` being traced under, or None."""
    return _PLAN.get()
