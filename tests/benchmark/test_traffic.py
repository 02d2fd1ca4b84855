"""Seeded traffic: reproducible, the stated distributions, the same work for
every seed, and the open loop's due times."""
import itertools
import json
import os
import statistics

import pytest

from benchmark.traffic import closed_loop, open_loop, shapes, train_steps

HERE = os.path.dirname(os.path.abspath(__file__))
MIXES = os.path.join(os.path.dirname(os.path.dirname(HERE)), "benchmark",
                     "workloads")


def mix(name):
    return json.load(open(os.path.join(MIXES, name + ".json")))


def take(gen, n):
    return list(itertools.islice(gen, n))


@pytest.mark.parametrize("seed", [0, 7, 2**31 + 11, 2**32 + 5])
def test_closed_loop_is_reproducible_for_any_whole_number(seed):
    spec = mix("chat_closed")
    a = take(closed_loop.requests(spec, seed, 50304), 70)
    b = take(closed_loop.requests(spec, seed, 50304), 70)
    assert a == b
    assert all(0 <= t < 50304 for r in a for t in r["prompt_ids"])


def test_two_seeds_carry_the_same_sizes_in_another_order():
    spec = mix("chat_closed")
    n = spec["cycle"]
    a = take(closed_loop.requests(spec, 1, 50304), n)
    b = take(closed_loop.requests(spec, 2, 50304), n)
    size = lambda r: (len(r["prompt_ids"]), r["max_new_tokens"])  # noqa: E731
    assert sorted(map(size, a)) == sorted(map(size, b))
    assert list(map(size, a)) != list(map(size, b))
    assert a[0]["prompt_ids"] != b[0]["prompt_ids"]


def test_chat_lengths_follow_the_stated_lognormals():
    spec = mix("chat_closed")
    reqs = take(closed_loop.requests(spec, 3, 50304), spec["cycle"])
    plens = [len(r["prompt_ids"]) for r in reqs]
    olens = [r["max_new_tokens"] for r in reqs]
    assert 115 <= statistics.median(plens) <= 140
    assert 115 <= statistics.median(olens) <= 140
    assert min(plens) >= 16 and max(plens) <= 512
    assert min(olens) >= 16 and max(olens) <= 384
    assert max(p + o for p, o in zip(plens, olens)) <= 896
    assert len(plens) == 16
    # sigma 0.7: the 84th percentile of a lognormal is median * e^sigma
    assert 220 <= sorted(plens)[int(0.84 * len(plens))] <= 290


@pytest.mark.parametrize("kind,spec,expect", [
    ("uniform", {"dist": "uniform", "min": 16, "max": 64}, (40, 16, 64)),
    ("fixed", {"dist": "fixed", "value": 9}, (9, 9, 9)),
    ("exponential", {"dist": "exponential", "mean": 2.0}, (2.0, 0.0, 20.0)),
])
def test_cycles_have_the_mean_and_range_of_their_distribution(kind, spec,
                                                              expect):
    xs = shapes.cycle(spec, 64)
    mean, lo, hi = expect
    assert abs(statistics.mean(xs) - mean) <= 0.05 * mean + 0.1
    assert lo <= min(xs) and max(xs) <= hi


def test_open_loop_schedule_shares_prefixes_and_keeps_its_rate():
    spec = mix("docqa_open")
    n = spec["cycle"] * spec["asks"]
    sched = take(open_loop.schedule(spec, 5, 50304), n)
    assert sched == take(open_loop.schedule(spec, 5, 50304), n)
    dues = [d for d, _ in sched]
    assert dues == sorted(dues)
    rate = len(dues) / (dues[-1] - dues[0])
    assert 0.7 * spec["rate_rps"] <= rate <= 1.4 * spec["rate_rps"]
    groups = {}
    for due, req in sched:
        groups.setdefault(req["group"], []).append((due, req))
    full = [g for g in groups.values() if len(g) == spec["asks"]]
    assert full
    for g in full:
        prompts = [r["prompt_ids"] for _, r in g]
        shared = os.path.commonprefix(prompts)
        assert 1024 <= len(shared) <= 1536 + 1
        assert all(16 <= len(p) - len(shared) + 1 <= 65 for p in prompts)
        assert max(d for d, _ in g) - min(d for d, _ in g) \
            <= spec["spread_s"] + 1e-9
        assert all(16 <= r["max_new_tokens"] <= 64 for _, r in g)
    found = open_loop.prompts(spec, 5, 50304, [3, 17])
    by_index = {r["index"]: r["prompt_ids"] for _, r in sched}
    assert found == {3: by_index[3], 17: by_index[17]}


def test_train_batches_are_seeded_and_all_rows_differ():
    spec = {"batch": 2, "seq": 64}
    a = train_steps.batches(spec, 2**31 + 3, 512, 4)
    assert a.shape == (4, 2, 64) and a.dtype.name == "int32"
    assert (a == train_steps.batches(spec, 2**31 + 3, 512, 4)).all()
    assert (train_steps.batches(spec, 2**31 + 3, 512, 2, first=2)
            == a[2:]).all()
    rows = {tuple(r) for r in a.reshape(-1, 64)}
    assert len(rows) == 8


def test_closed_loop_primes_alone_then_ramps_its_callers_in():
    import threading
    import time

    class Ctl:
        def __init__(self, seconds):
            self.t_end = time.monotonic() + seconds

        def closed(self):
            return time.monotonic() >= self.t_end

        def wait_closed(self):
            time.sleep(max(0.0, self.t_end - time.monotonic()))

    sent, lock = [], threading.Lock()

    def post(req, due):
        with lock:
            sent.append((time.monotonic(), req["index"],
                         threading.get_ident()))
        time.sleep(0.02)
        return {"index": req["index"], "status": "ok"}

    spec = dict(mix("chat_closed"), clients=4, ramp_s=0.4, drain_s=2,
                prime={"prompt_len": 8, "max_new_tokens": 2})
    records, unanswered = closed_loop.run(spec, 3, 512, post, Ctl(0.6))
    assert unanswered == 0
    assert sent[0][1] == -1                     # the prime request, alone
    assert sent[1][0] - sent[0][0] >= 0.02
    first = {}
    for t, index, ident in sent[1:]:
        first.setdefault(ident, t)
    starts = sorted(first.values())
    assert len(starts) == 4
    gaps = [b - a for a, b in zip(starts, starts[1:])]
    assert all(0.05 <= g <= 0.2 for g in gaps)  # 0.4 s / 4 callers apart
    assert sorted(r["index"] for r in records) == list(range(len(records)))
