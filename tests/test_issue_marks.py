"""The marks inside ``llm.issue.mixed`` and ``llm.issue.decode`` (ISSUE 37):
``packed``, ``staged``, ``launched``, ``booked`` as events of the issue phase,
once each and in that order, every dispatch; the attrs of each phase are what
the parent's ordering wrote (the stamps moved to the phase's end); a dispatch
finishes the spans it finished before and puts no new name on the profiler's
clock; with tracing off nothing is recorded and the tokens are the same. The
models with state, a loop or two cache groups pin their attrs in their own
files (``tests/test_granite_hybrid.py``, ``test_ouro.py``,
``test_laguna.py``)."""
import collections
import glob
import os

import jax
import numpy as np

import paddle_tpu as pt
from paddle_tpu.inference.llm import LLMEngine
from paddle_tpu.observability import tracing


def _tiny_gpt():
    from paddle_tpu.models.gpt import GPTForCausalLM, gpt_config
    pt.seed(0)
    return GPTForCausalLM(gpt_config(
        "gpt2-small", num_layers=2, hidden_size=64, num_heads=4,
        vocab_size=97, max_position_embeddings=96, hidden_dropout=0.0,
        attention_dropout=0.0))


def _jobs():
    rng = np.random.RandomState(0)
    return [(rng.randint(0, 97, n).tolist(), m)
            for n, m in ((5, 9), (23, 4), (3, 12), (20, 6), (9, 3))]


def _engine(**kw):
    return LLMEngine(_tiny_gpt(), max_seqs=4, page_size=4, num_pages=128,
                     prefill_chunk=8, prefix_cache=False, **kw)


def _phase_counts(spans, prefixes):
    return collections.Counter(s["name"] for s in spans
                               if s["name"].startswith(prefixes))


def test_every_dispatch_carries_the_four_marks_and_the_parents_attrs(
        issue_phases):
    tracing.enable()
    with _engine() as eng:
        traced = issue_phases.serve(eng, _jobs())
    spans = tracing.finished_spans()
    issue_phases.check_marks(spans)
    # the attrs of the same run on the parent's ordering (PR 36's tree)
    assert issue_phases.digest(spans) == "bd0ba7d8808adada"
    # what a dispatch finishes: its issue phase, one wait, one emit
    assert _phase_counts(spans, ("llm.issue.", "llm.drain.")) == {
        "llm.issue.mixed": 9, "llm.issue.decode": 6,
        "llm.drain.wait": 15, "llm.drain.emit": 15}
    assert tracing.dropped_spans() == 0
    # tracing off: the phase is the no-op span, nothing is recorded, and
    # the same jobs get the same tokens
    tracing.disable()
    tracing.clear()
    assert tracing.phase("llm.issue.mixed") is tracing.NOOP_SPAN
    with _engine() as eng:
        plain = issue_phases.serve(eng, _jobs())
    assert tracing.finished_spans() == []
    assert [o["output_ids"] for o in plain] \
        == [o["output_ids"] for o in traced]


def test_a_decode_dispatch_sends_its_host_arrays_in_one_transfer(
        issue_phases):
    """ISSUE 44, a pool of one group: from the entry of ``_issue`` to the
    jitted call ONE host array goes to the device (positions, lens, the
    block table, nonces and temperatures were five), the program finds no
    numpy argument, and the spans say so: ``h2d_transfers`` 1 on every
    decode dispatch, on a mixed one the thirteen it sends (the carry's two,
    the schedule's eight, the table, temperatures, nonces). Greedy and
    sampled rows get the tokens of an engine nobody spies on."""
    jobs = _jobs()
    tracing.enable()
    with _engine() as eng, issue_phases.decode_staging(eng) as seen:
        futs = [eng.submit(p, max_new_tokens=n, temperature=t)
                for (p, n), t in zip(jobs, (0.0, 0.9, 0.0, 0.7, 0.0))]
        outs = [f.result(600)["output_ids"] for f in futs]
        layout = eng._decode_layout
    spans = tracing.finished_spans()
    assert len(seen) >= 6 and {n for n, _, _ in seen} == {1}
    assert {staged.shape for _, staged, _ in seen} == {(layout.size,)}
    assert layout.size == 4 * 4 + 4 * eng.pages_per_seq
    assert issue_phases.transfers(spans) == {"llm.issue.decode": {1},
                                             "llm.issue.mixed": {13}}
    tracing.disable()
    with _engine() as eng:
        plain = [eng.submit(p, max_new_tokens=n, temperature=t)
                 for (p, n), t in zip(jobs, (0.0, 0.9, 0.0, 0.7, 0.0))]
        assert [f.result(600)["output_ids"] for f in plain] == outs


def test_through_the_kernel_the_tiles_pages_are_the_parents(issue_phases):
    """``attention_impl="pallas"``: READ cuts the chunk's rows into the
    kernel's tiles (``PagePool.pages_touched``), at the phase's end now."""
    tracing.enable()
    with _engine(attention_impl="pallas") as eng:
        issue_phases.serve(eng, _jobs())
    spans = tracing.finished_spans()
    issue_phases.check_marks(spans)
    assert issue_phases.digest(spans) == "c07287643656dd08"


def test_the_marks_put_no_new_name_on_the_profilers_clock(
        tmp_path, issue_phases):
    with _engine() as eng:
        issue_phases.serve(eng, _jobs())        # compile every shape
        assert tracing.finished_spans() == []
        jax.profiler.start_trace(str(tmp_path))
        try:
            issue_phases.serve(eng, _jobs())
        finally:
            jax.profiler.stop_trace()
    spans = tracing.finished_spans()
    issue_phases.check_marks(spans)
    path = sorted(glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                            recursive=True))[-1]
    pd = jax.profiler.ProfileData.from_file(path)
    on_clock = collections.Counter(
        e.name for plane in pd.planes if plane.name.startswith("/host:")
        for line in plane.lines for e in line.events
        if e.name.startswith("llm."))
    # one annotation a phase, none a mark
    assert on_clock == _phase_counts(
        spans, ("llm.loop.", "llm.issue.", "llm.drain."))
    assert not set(issue_phases.MARKS) & set(on_clock)


def test_the_ring_counts_what_it_drops():
    tracing.enable(capacity=4)
    try:
        for i in range(6):
            with tracing.phase(f"p{i}"):
                pass
        assert [s["name"] for s in tracing.finished_spans()] \
            == ["p2", "p3", "p4", "p5"]
        assert tracing.dropped_spans() == 2
        tracing.set_capacity(3)         # a smaller ring evicts as well
        assert tracing.dropped_spans() == 3
        tracing.clear()
        assert tracing.dropped_spans() == 0
        with tracing.phase("q"):
            pass
        assert tracing.dropped_spans() == 0
    finally:
        tracing.disable()
        tracing.set_capacity(tracing.DEFAULT_TABLE_CAP)
        tracing.clear()
