"""Operations and bytes the grouped-matmul kernels' algorithm needs
(`paddle_tpu/kernels/pallas/grouped_matmul.py`), for the expert products
of one sparse feed-forward on `rows` assignments to `groups` held
experts of `hidden` x `width` SwiGLU experts, bf16.

`moe_gmm` is four products a layer: rows [R, hidden] x gate_up
[G, hidden, 2 width], rows [R, width] x down [G, width, hidden], and
their gradients to the rows, which are the same two shapes with the
weight's other axis contracted. The kernel's name does not say which of
the two shapes a call is; both occur equally often in every phase
(forward, run again by `jax.checkpoint`, backward), so a call costs the
mean of the two (`variants`). `moe_gmm_dw` is the two weight gradients,
as often each. Each product reads its two operands once and writes its
result once; the padding rows of the kernels' layout are an
implementation's choice and are not counted."""

KERNELS = {"moe_gmm": "gmm", "moe_gmm_dw": "dw"}


def classify(component: str):
    """Which grouped-matmul kernel an operation is, by the scope path
    the program gave it (`…/layers/2/moe/experts/moe_gmm`: a
    `pallas_call`'s name is its innermost `jax.named_scope`), or None."""
    return KERNELS.get(component.rsplit("/", 1)[-1])


def variants(kind: str, rows: float, groups: int, hidden: int, width: int):
    """[(operations, bytes)] of the shapes a call of `kind` can be."""
    if kind not in ("gmm", "dw"):
        raise KeyError(kind)
    return [(2.0 * rows * k * n,
             2.0 * (rows * k + rows * n + groups * k * n))      # bf16
            for k, n in ((hidden, 2 * width), (width, hidden))]
