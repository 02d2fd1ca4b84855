"""Engine programs: median device time of one execution of the mixed-tick
program (``mixed_fn``: a prefill chunk and the decode rows in one batch) over
the ticks a dispatch holds."""
from benchmark.layer_metrics import _programs


def read(facts, trace):
    ms = _programs.median_ms(trace, ("mixed_fn",))
    return None if ms is None else ms / facts["decode_ticks_per_dispatch"]
