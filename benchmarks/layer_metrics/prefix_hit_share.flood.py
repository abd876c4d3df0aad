"""The engine's count of prompt tokens served from cached prefix pages
over all prompt tokens it admitted in the window."""


def read(run):
    st = run.window["stats"]
    total = st["prefix_cache_hit_tokens"] + st["prefix_cache_miss_tokens"]
    return 100.0 * st["prefix_cache_hit_tokens"] / total if total else None
