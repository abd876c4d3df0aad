"""The mesh a program being traced will be partitioned over: a fact of
whoever traces it (`jit.TrainStep` under a mesh), read by what cannot
leave the split to the compiler (the Pallas flash and rotary kernels,
which split themselves with `shard_map`; the expert layer, which holds
its axis's share of the experts and runs their exchange; the LM head,
whose fused loss takes the vocabulary in that axis's slices and whose
promise of logits is otherwise for one device)."""
from __future__ import annotations

import contextlib
import contextvars

# (mesh, batch_axes, expert_axis) while a program that GSPMD will
# partition over `mesh` is being traced; None otherwise
_PLAN: contextvars.ContextVar = contextvars.ContextVar(
    "mesh_plan", default=None)


@contextlib.contextmanager
def mesh_plan(mesh, batch_axes=(), expert_axis=None):
    """Tell what is traced inside this block that the program will be
    partitioned over `mesh`, with the batch dimension of its data split
    over `batch_axes`. The compiler cannot partition a Mosaic kernel by
    itself, so under a plan `flash_attention` splits its call with
    `shard_map`: batch over `batch_axes`, heads over the mesh's other
    axes (the Megatron layout) where the per-device head count still
    fits the kernel, whole on every device of an axis where it does
    not.

    `expert_axis`: the mesh axis the experts lie on (the stacked experts'
    leading dimension, and with them the embedding's and the head's
    vocabulary rows: `models.shard_plans.expert_parallel_rules`). Under
    it `ops.moe_experts` holds `num_experts / size` experts a device and
    exchanges the tokens' rows, and the fused head loss takes its slice
    of the vocabulary."""
    if expert_axis is not None and expert_axis not in mesh.axis_names:
        raise ValueError(f"mesh_plan: no axis {expert_axis!r} in a mesh "
                         f"over {mesh.axis_names}")
    token = _PLAN.set((mesh, tuple(batch_axes), expert_axis))
    try:
        yield
    finally:
        _PLAN.reset(token)


def current_mesh_plan():
    """(mesh, batch_axes, expert_axis) of the `mesh_plan` being traced
    under, or None."""
    return _PLAN.get()


def expert_axis_plan():
    """(mesh, axis, size) where the plan being traced under lays the
    experts over an axis of more than one device, else None."""
    plan = _PLAN.get()
    if plan is None or plan[2] is None or plan[0].shape[plan[2]] < 2:
        return None
    return plan[0], plan[2], plan[0].shape[plan[2]]
