"""Engine programs: median device time of one execution of the decode
program (``decode_fn`` at one tick a dispatch, ``slab_fn`` above that) over
the ticks it holds."""
from benchmark.layer_metrics import _programs


def read(facts, trace):
    ms = _programs.median_ms(trace, ("decode_fn", "slab_fn"))
    return None if ms is None else ms / facts["decode_ticks_per_dispatch"]
