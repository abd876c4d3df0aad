"""Rows that took part in a step (running after it, or finished by it)
over `max_batch`, averaged over the window's steps. Above capacity the
harness's front door (`front_door_tokens`) and the engine's batching set
it together."""


def read(run):
    w = run.window
    if not w["steps"]:
        return None
    rows = sum(s[2] for s in w["steps"]) / len(w["steps"])
    return 100.0 * rows / w["max_batch"]
