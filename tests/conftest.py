"""Test configuration: force an 8-device virtual CPU mesh so sharding /
collective tests run without TPU hardware (SURVEY.md §4: the reference's
analog is gloo-CPU collective tests + fake devices; here
xla_force_host_platform_device_count gives us N host 'chips').

The driver's command sets JAX_PLATFORMS=cpu; the config update below
makes a bare ``pytest`` do the same.

The Pallas kernels compile for the TPU or raise; the CPU suite runs
them through the Pallas interpreter by the one switch below
(``ops.flash_attention.INTERPRET``). tests/test_chip_compile.py is the
file that asks the TPU compiler.
"""

import contextlib
import os

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()

# children a test spawns (replicas, dist workers) take what jax finds:
# hold them to the CPU the same way the driver's command holds us
os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
# Model.prepare() turns the persistent compile cache on (core/
# compile_cache.py); the CPU suite stays hermetic and runs without it
jax.config.update("jax_enable_compilation_cache", False)

import numpy as np  # noqa: E402
import pytest  # noqa: E402

import importlib  # noqa: E402

# (the package re-exports a function of the same name: ask for the module)
importlib.import_module("paddle_tpu.ops.flash_attention").INTERPRET = True

assert jax.devices()[0].platform == "cpu", (
    "tests must run on the virtual CPU mesh; got "
    f"{jax.devices()}")
assert jax.device_count() == 8, (
    f"expected 8 virtual CPU devices, got {jax.device_count()}")


@pytest.fixture(autouse=True)
def _seed_everything():
    import paddle_tpu
    paddle_tpu.seed(42)
    np.random.seed(42)
    yield


# -- BENCHMARK.json as an earlier PR left it ---------------------------------
#
# A PR may only APPEND to BENCHMARK.json's lists (the builder's instructions,
# word for word: "Put new entries at the end of their lists: one put first or
# in the middle reads as a change to what was there", and a PR that changes
# what the benchmark had is refused before any run). A test that pins a LAST
# place to its own PR's entries therefore holds only until the next PR
# appends. ``tests/benchmark`` belongs to the benchmark (``paths``), so such a
# test is a ``benchmark`` PR's to rewrite (ROADMAP A0b(g)); until then it
# runs, whole, over the file with what later PRs appended cut off: every
# assertion it makes still has to hold, and "last" means "last when its PR was
# accepted", which is what it pinned.

def benchmark_as_left_by(bench: dict, cell: str) -> dict:
    """``bench`` (BENCHMARK.json) without what was appended behind ``cell``:
    the cells after it, the configurations only they run, their names on the
    metrics' ``workloads`` lists and the metrics that list only them."""
    names = [w["name"] for w in bench["workloads"]]
    kept = set(names[:names.index(cell) + 1])
    cells = [w for w in bench["workloads"] if w["name"] in kept]

    def cut(metrics):
        out = []
        for m in metrics:
            if "workloads" in m:
                listed = [n for n in m["workloads"] if n in kept]
                if not listed:
                    continue
                m = dict(m, workloads=listed)
            out.append(m)
        return out

    return dict(bench, workloads=cells,
                configs=[c for c in bench["configs"]
                         if c["name"] in {w["config"] for w in cells}],
                end_to_end=cut(bench["end_to_end"]),
                per_layer=cut(bench["per_layer"]))


# test -> the cell its PR added
PINS_A_LAST_PLACE = {
    "tests/benchmark/test_kda.py::"
    "test_the_benchmark_gains_one_configuration_one_cell_and_one_reader":
    "reason_closed_kda",
    "tests/benchmark/test_mimo.py::"
    "test_the_benchmark_gains_one_configuration_one_cell_and_one_reader":
    "mixed_len_closed_sink",
    "tests/benchmark/test_mimo.py::"
    "test_an_earlier_prs_last_places_are_read_with_later_entries_cut_off":
    "mixed_len_closed_sink",
}


@pytest.fixture(autouse=True)
def _last_places_as_their_pr_left_them(request, monkeypatch):
    cell = PINS_A_LAST_PLACE.get(request.node.nodeid)
    if cell:
        monkeypatch.setattr(request.module, "BENCH", benchmark_as_left_by(
            request.module.BENCH, cell))
    yield


@pytest.fixture
def as_left_by():
    return benchmark_as_left_by


# -- the dispatches of an engine run, for the tests of their span attrs ------

ISSUE_MARKS = ("packed", "staged", "launched", "booked")


class _Dispatches:
    """What ``issue_phases`` hands a test."""
    MARKS = ISSUE_MARKS

    @staticmethod
    def serve(eng, jobs, timeout=600):
        """``jobs`` (``(prompt, max_new_tokens)`` pairs) through ``eng`` so
        that its dispatches repeat run for run: a control op holds the
        engine thread while every job is submitted, the loop then admits
        them all in ONE iteration, and nothing outside feeds it again
        (plain ``submit`` calls race the loop). Returns the outputs."""
        import threading
        gate = threading.Event()
        held = eng._post_ctl(lambda: gate.wait(timeout))
        try:
            futs = [eng.submit(p, max_new_tokens=n) for p, n in jobs]
        finally:
            gate.set()
        assert held.result(timeout)
        return [f.result(timeout) for f in futs]

    @staticmethod
    def launched(spans):
        """The issue phases that launched a program, in issue order."""
        out = [s for s in spans if s["name"].startswith("llm.issue.")
               and "issue_seq" in s["attrs"]]
        return sorted(out, key=lambda s: s["attrs"]["issue_seq"])

    @classmethod
    def digest(cls, spans):
        """Of every dispatch's name and attrs (``issue_seq``, ``live_rows``,
        ``chunk_rows``, ``chunk_tokens``, ``ticks``, ``kv_pages_read``,
        ``kv_pages_live``, ``state_rows``, ``state_bytes`` and, where the
        model has them, ``kv_groups``, ``context_tokens``,
        ``window_pages_released``, ``loop_steps``, ``kv_cache_layers``).
        ``h2d_transfers`` (ISSUE 44) is left out, so that the pins keep
        saying the other attrs are what they were before it;
        :meth:`transfers` reads it."""
        import hashlib
        import json
        rows = [[s["name"], {k: v for k, v in s["attrs"].items()
                             if k != "h2d_transfers"}]
                for s in cls.launched(spans)]
        text = json.dumps(rows, sort_keys=True, default=int)
        return hashlib.sha256(text.encode()).hexdigest()[:16]

    @staticmethod
    @contextlib.contextmanager
    def decode_staging(eng):
        """While it is open, every decode dispatch of ``eng`` appends to the
        list it yields ``(n, staged, copy)``: ``n`` host arrays handed to
        ``jnp.asarray`` / ``jax.device_put`` from the entry of ``_issue`` to
        the jitted call (which must find device arrays alone: a numpy
        argument would be one more transfer, inside the call), the staged
        vector the program was given and a copy of it taken at the launch."""
        import jax.numpy as jnp
        seen, counting = [], {"n": None}

        def counted(real):
            def call(x, *a, **kw):
                if counting["n"] is not None and isinstance(x, np.ndarray):
                    counting["n"] += 1
                return real(x, *a, **kw)
            return call

        issue, decode_fn = eng._issue, eng._decode_fn

        def issue_counted(live):
            counting["n"] = 0
            try:
                return issue(live)
            finally:
                counting["n"] = None

        def decode_spied(*args):
            n, counting["n"] = counting["n"], None
            assert all(isinstance(a, jax.Array)
                       for a in jax.tree_util.tree_leaves(args))
            seen.append((n, args[3], np.array(args[3])))
            return decode_fn(*args)

        mp = pytest.MonkeyPatch()
        mp.setattr(jnp, "asarray", counted(jnp.asarray))
        mp.setattr(jax, "device_put", counted(jax.device_put))
        eng._issue, eng._decode_fn = issue_counted, decode_spied
        try:
            yield seen
        finally:
            mp.undo()
            eng._issue, eng._decode_fn = issue, decode_fn

    @staticmethod
    def tokens_beside_chunks(spans, prompt_tokens):
        """``{issue_seq: tokens}`` of the dispatches that carried a chunk of
        the request whose prompt has ``prompt_tokens`` tokens (its
        ``llm.prefill`` span's ``chunk`` events) AND delivered tokens to
        ANOTHER request already in decode (that one's ``slab`` events):
        the witness that a long prompt's chunks ride beside live decode
        rows and do not stall them."""
        long = [s for s in spans if s["name"] == "llm.prefill"
                and s["attrs"]["prompt_tokens"] == prompt_tokens]
        assert len(long) == 1, long
        chunks = {e["attrs"]["issue_seq"] for e in long[0]["events"]
                  if e["name"] == "chunk"}
        out = {}
        for s in spans:
            if s["name"] == "llm.decode" \
                    and s["parent_id"] != long[0]["parent_id"]:
                for e in s["events"]:
                    if e["name"] == "slab" and e["attrs"]["tokens"] \
                            and e["attrs"]["issue_seq"] in chunks:
                        out[e["attrs"]["issue_seq"]] = e["attrs"]["tokens"]
        return out

    @classmethod
    def transfers(cls, spans):
        """``{phase name: the set of h2d_transfers its dispatches carry}``."""
        out = {}
        for s in cls.launched(spans):
            out.setdefault(s["name"], set()).add(s["attrs"]["h2d_transfers"])
        return out

    @classmethod
    def check_marks(cls, spans,
                    kinds=("llm.issue.mixed", "llm.issue.decode")):
        """Every dispatch carries the four marks once each, in order,
        without attrs, between its phase's start and end."""
        found = cls.launched(spans)
        assert {s["name"] for s in found} == set(kinds)
        for s in found:
            marks = [e for e in s["events"] if e["name"] in ISSUE_MARKS]
            assert [e["name"] for e in marks] == list(ISSUE_MARKS), s
            assert all("attrs" not in e for e in marks)
            times = [s["ts"]] + [e["ts"] for e in marks] \
                + [s["ts"] + s["dur"]]
            assert times == sorted(times)
        # a phase that found nothing to launch carries no mark and no attr
        for s in spans:
            if s["name"].startswith("llm.issue.") and s not in found:
                assert s["attrs"] == {} and s["events"] == []


@pytest.fixture
def issue_phases():
    """Helpers for a test of the ``llm.issue.*`` phases: ``serve`` (a
    repeatable run), ``launched``, ``digest``, ``check_marks``,
    ``transfers``, ``tokens_beside_chunks``, ``decode_staging``; tracing is off and the table empty
    before and after."""
    from paddle_tpu.observability import tracing
    tracing.disable()
    tracing.clear()
    yield _Dispatches
    tracing.disable()
    tracing.clear()
