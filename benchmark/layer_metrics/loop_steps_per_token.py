"""Engine programs: passes of the looped stack run for the tokens the engine
delivered in the window, a token (``llm_loop_steps_total`` over the tokens
emitted, both as the window's delta). ``total_ut_steps`` (4.0) at the
published exit threshold; anything else says a pass was left out or run
twice. None for a program without the counter (its stack runs once)."""


def compute(before, after):
    tokens = after["n_tokens"] - before["n_tokens"]
    if tokens <= 0:
        return None
    return (after["loop_steps"] - before["loop_steps"]) / tokens


def read(facts, trace):
    b, a = facts.get("before") or {}, facts.get("after") or {}
    if "loop_steps" not in a or "loop_steps" not in b:
        return None
    return compute(b, a)
