"""The vocabulary projection of a causal LM, shared by the model
families: the tied or untied product under the `lm_head` scope, and the
logits as a promise that the pretraining criterion can settle from the
head's operands without ever holding them whole."""
from __future__ import annotations

from typing import NamedTuple

from .. import ops
from ..amp import auto_cast
from ..amp.state import maybe_cast_inputs
from ..autograd import tape
from ..core.mesh_plan import current_mesh_plan, expert_axis_plan
from ..core.tensor import DeferredTensor, Tensor
from ..nn.layer import traced_scope, _TRACING
from ..observability import perf
from ..ops import nn_ops


class _Head(NamedTuple):
    """What deferred logits are the product of: `hidden` [..., hidden]
    (any leading axes: [batch, seq], or [passes, batch, seq] where a
    model reads its stream several times) and `weight` as amp cast them
    for the projection when the model ran, `weight` [vocab, hidden] if
    `transpose_y` (the tied embedding) else [hidden, vocab] (an untied
    Linear's)."""
    hidden: Tensor
    weight: Tensor
    transpose_y: bool


def lm_logits(hidden, tied_weight, head=None):
    """hidden -> vocab logits through `head` (an untied Linear, named by
    its parent) or, without one, the tied embedding `tied_weight`."""
    if head is None:
        # the tied head is no Layer: it names itself in a traced
        # program as the untied one is named by its parent
        with traced_scope("lm_head"):
            return ops.matmul(hidden, tied_weight, transpose_y=True)
    return head(hidden)


def deferred_logits(training, hidden, tied_weight, head=None):
    """The logits [*hidden.shape[:-1], vocab] as a promise, where the
    program is such that the criterion can do without them: traced for
    training with jax's own autodiff (no tape), on one device or under a
    mesh plan whose expert axis the vocabulary lies over (under any other
    mesh the whole product is the path that is tested), and nothing
    hooked onto an untied head. Else
    None. `hidden` may carry a leading axis of passes: the promise is
    then one for all of them, and a criterion settles every pass's rows
    in one `head_cross_entropy`."""
    if not (_TRACING.depth and training
            and not tape.is_grad_enabled()
            and (head is None or not (head._forward_pre_hooks
                                      or head._forward_post_hooks))):
        return None
    # TrainStep's, under a mesh: the fused loss takes a vocabulary that
    # lies over the plan's expert axis in slices, and no other layout
    if current_mesh_plan() is not None and expert_axis_plan() is None:
        perf.trace_note("head_loss", "whole")
        return None
    w = tied_weight if head is None else head.weight
    # the casts `matmul` (and `linear`: both on amp's white list)
    # would have made now; whoever computes from these later may
    # stand outside `auto_cast`
    cast = maybe_cast_inputs(ops.matmul.op_def, {"x": hidden, "y": w})
    made = _Head(cast["x"], cast["y"], head is None)

    def whole():
        perf.trace_note("head_loss", "whole")
        with auto_cast(enable=False), traced_scope("lm_head"):
            return ops.matmul(made.hidden, made.weight,
                              transpose_y=made.transpose_y)._data

    vocab = w.shape[0] if head is None else w.shape[1]
    return DeferredTensor(whole, list(hidden.shape[:-1]) + [vocab],
                          made.hidden._data.dtype, producer=made)


def causal_lm_logits(training, hidden, tied_weight, head=None):
    """What a causal LM's training forward returns: the promise where
    it can be made, else the product."""
    logits = deferred_logits(training, hidden, tied_weight, head)
    return lm_logits(hidden, tied_weight, head) if logits is None \
        else logits


def head_cross_entropy(head: _Head, labels, weight=None, with_rows=False):
    """sum_i weight[i] * cross_entropy(head.hidden[i] . W, labels[i])
    over all of the head's rows (every leading axis of `hidden`
    flattened; `labels` and `weight` of as many elements; `weight` None:
    1/rows each, the mean), the logits never whole
    (`ops.linear_cross_entropy`): one call, so the head's float32 `dW`
    is made and held once however many passes the rows come from. With
    `with_rows`: (the sum, every row's cross-entropy as a reading).
    Operations keep the `lm_head` scope beside the caller's."""
    hidden = ops.reshape(head.hidden, (-1, head.hidden.shape[-1]))
    n = hidden.shape[0]
    vocab = head.weight.shape[0 if head.transpose_y else 1]
    chunk = nn_ops.lce_chunk(vocab)
    chunks = nn_ops._lce_plan(n, chunk)[0]
    note = f"fused, chunks {chunks}"
    if head.hidden.ndim > 3:    # the rows of several passes
        note += f", rows {n}"
    plan = expert_axis_plan()
    if plan is not None:
        mesh, axis, slices = plan
        if vocab % slices or n % slices:
            raise ValueError(
                f"head_cross_entropy: {vocab} vocabulary rows and {n} rows "
                f"do not divide over the {slices} devices of {axis!r}")
        note += f", vocabulary in {slices} slices of {vocab // slices}"
    perf.trace_note("head_loss", note)
    if weight is not None:
        weight = ops.reshape(weight, (n,))
    with auto_cast(enable=False), traced_scope("lm_head"):
        return ops.linear_cross_entropy(
            hidden, head.weight, ops.reshape(labels, (n,)), weight,
            transpose_y=head.transpose_y, chunk=chunk, with_rows=with_rows,
            over=plan and plan[:2])
