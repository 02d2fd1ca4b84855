"""Scheduler: prompt tokens served from cached prefix pages, as a share of
the prompt tokens admitted in the window."""


def read(facts, trace):
    b, a = facts.get("before"), facts.get("after")
    if not b or not a:
        return None
    prompt = a["n_prompt_tokens"] - b["n_prompt_tokens"]
    if prompt <= 0:
        return None
    return 100.0 * (a["n_cached_tokens"] - b["n_cached_tokens"]) / prompt
