"""Trainer: median device time of one execution of the step program over the
optimizer steps it holds."""
from benchmark.layer_metrics import _programs


def read(facts, trace):
    ms = _programs.median_ms(trace, (facts.get("step_program", "step"),))
    return None if ms is None else ms / facts["steps_per_execution"]
