"""The part of `moe_ffn_ms.train` that is no product of an expert:
device time per step under a `moe` layer's `router` (scores, top-k,
weights), `permute` (the sorts, the index arithmetic, the gather of the
rows) and `combine` (the weighted sum back in the tokens' order, a
gather over every slot), all phases."""
from harness import trace_scopes

PARTS = {"router", "permute", "combine"}


def in_route(component: str) -> bool:
    path = component.split("/")
    return "moe" in path and bool(PARTS & set(path[path.index("moe"):]))


def read(run):
    scoped = trace_scopes.of(run)
    return scoped and scoped.step_ms(r"jit_step", in_route)
