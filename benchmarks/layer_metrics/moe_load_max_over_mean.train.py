"""The window's mean, over steps and sparse layers, of the busiest held
expert's assignments over the mean held expert's: the program's own
counter (`observe_expert_load` on the counts every step hands out with
its loss). 1 is a perfectly even load; the grouped-matmul kernels'
padding and a deployment's slowest chip grow with it."""


def read(run):
    return (run.window or {}).get("moe", {}).get("moe.load_max_over_mean")
