"""Scheduler: jit dispatches the engine's loop issued in the window, per
thousand output tokens completed in it."""


def read(facts, trace):
    b, a = facts.get("before"), facts.get("after")
    if not b or not a or not facts.get("tokens_completed"):
        return None
    return 1000.0 * (a["n_host_dispatches"] - b["n_host_dispatches"]) \
        / facts["tokens_completed"]
