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
from ..core.tensor import DeferredTensor, Tensor
from ..nn.layer import traced_scope, _TRACING
from ..observability import perf


class _Head(NamedTuple):
    """What deferred logits are the product of: `hidden` and `weight` as
    amp cast them for the projection when the model ran, `weight`
    [vocab, hidden] if `transpose_y` (the tied embedding) else
    [hidden, vocab] (an untied Linear's)."""
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
    """The logits as a promise, where the program is such that the
    criterion can do without them: traced for training with jax's own
    autodiff (no tape), on one device (under a mesh the tied embedding
    is sharded and the whole product is the path that is tested there),
    and nothing hooked onto an untied head. Else None."""
    if not (_TRACING.depth and training
            and not tape.is_grad_enabled()
            and (head is None or not (head._forward_pre_hooks
                                      or head._forward_post_hooks))):
        return None
    from ..kernels.pallas.flash_attention import _MESH_PLAN
    if _MESH_PLAN.get() is not None:    # TrainStep's, under a mesh
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
    return DeferredTensor(whole, hidden.shape[:-1] + [vocab],
                          made.hidden._data.dtype, producer=made)


def causal_lm_logits(training, hidden, tied_weight, head=None):
    """What a causal LM's training forward returns: the promise where
    it can be made, else the product."""
    logits = deferred_logits(training, hidden, tied_weight, head)
    return lm_logits(hidden, tied_weight, head) if logits is None \
        else logits
