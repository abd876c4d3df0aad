"""Device time per step of the operations that add a pass's weight
cotangents into the sums the backward loop carries: the transposed
scan's own `add_any` (the operation whose path ends
`ut_loop/while/body/.../add_any` outside every layer's scope), as its
own operation or as the root of a fusion XLA made of it. A matrix's sum
that XLA folds into the product which makes the cotangent (an output
fusion rooted in the layer's `dot_general`) is that product's time and
not read here: this metric is what is LEFT of the sums outside the
products, 20 GB of float32 traffic a step (24 ms at 819 GB/s) if nothing
is folded, next to nothing if all of it is. Nothing to read in a program
without the `ut_loop` scope."""
import bisect

from harness import trace_scopes

OUTSIDE_A_LAYER = {"ut_loop", "while", "body", "closed_call"}


def is_grad_sum(op_name: str) -> bool:
    """The path of a sum the loop itself adds: under `ut_loop`, in no
    scope below the loop's own body, ending in `add_any`."""
    elements = trace_scopes.split_path(op_name)
    names = [trace_scopes.unwrap(e)[0] for e in elements[:-1]]
    if elements[-1] != "add_any" or "ut_loop" not in names:
        return False
    return set(names[names.index("ut_loop"):]) <= OUTSIDE_A_LAYER


def read(run):
    scoped = trace_scopes.of(run)
    runs = scoped.runs(r"jit_step") if scoped else []
    if not runs:
        return None
    los = [lo for lo, _hi in runs]
    total, looped = 0.0, False
    for mid, start, seconds in scoped.ops():
        i = bisect.bisect_right(los, start) - 1
        if i < 0 or start > runs[i][1]:
            continue
        looped = looped or "ut_loop" in scoped.scope(mid)[1].split("/")
        name = trace_scopes.op_name_of(
            scoped.plane.event_stats.get(mid, {}).get("tf_op", ""))
        if is_grad_sum(name):
            total += seconds
    return 1e3 * total / len(runs) if looped else None
