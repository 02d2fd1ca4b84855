"""Closed loop: ``clients`` callers, each sends its next request when the
reply arrives, no think time. Nothing is shared between prompts. The callers
start one by one over ``ramp_s``, after one short ``prime`` request sent alone."""

from __future__ import annotations

import itertools
import threading
import time

from . import shapes

# which time of a request's record decides whether it belongs to the window
WINDOW_BY = "done"


def requests(spec: dict, seed: int, vocab: int):
    """Endless stream of ``{"index", "prompt_ids", "max_new_tokens"}``.
    Prompt and output lengths are stratified cycles of ``spec["cycle"]``
    shapes, paired by a fixed shuffle, permuted by the seed each repetition."""
    n = int(spec["cycle"])
    plens = shapes.cycle(spec["prompt_len"], n)
    olens = shapes.cycle(spec["output_len"], n)
    pair = shapes.rng(int(spec.get("pairing_seed", 0)), 7).permutation(n)
    pairs = [(plens[i], olens[int(pair[i])]) for i in range(n)]
    index = 0
    for epoch in itertools.count():
        for plen, olen in shapes.permuted(pairs, seed, 1, epoch):
            yield {"index": index, "group": index,
                   "prompt_ids": shapes.tokens(seed, 2, index, plen, vocab),
                   "max_new_tokens": olen}
            index += 1


def prompts(spec: dict, seed: int, vocab: int, indices) -> dict:
    """The prompts of the requests with these indices, made again."""
    want, out = set(indices), {}
    for req in requests(spec, seed, vocab):
        if req["index"] in want:
            out[req["index"]] = req["prompt_ids"]
            if len(out) == len(want):
                return out


def run(spec: dict, seed: int, vocab: int, post, ctl):
    """Drive the loop until ``ctl`` says the window has closed, then wait for
    the requests in flight; returns every request's record and how many are
    still unanswered after ``drain_s``. ``post(request, due)`` sends one and
    returns its record."""
    stream = requests(spec, seed, vocab)
    lock = threading.Lock()
    records = []

    def client(start_after: float):
        time.sleep(start_after)
        while not ctl.closed():
            with lock:
                req = next(stream)
            rec = post(req, time.monotonic())
            with lock:
                records.append(rec)

    # one short request alone first (in a checkout's first run it waits for
    # the programs to compile), then the callers join one by one over
    # ``ramp_s``: callers that all start at once leave a backlog of prompts
    # that the window would still be draining
    prime = spec.get("prime")
    if prime:
        post({"index": -1, "group": -1, "prompt_ids": shapes.tokens(
            seed, 8, 0, int(prime["prompt_len"]), vocab),
            "max_new_tokens": int(prime["max_new_tokens"])}, time.monotonic())
    n = int(spec["clients"])
    step = float(spec.get("ramp_s", 0.0)) / n
    threads = [threading.Thread(target=client, args=(i * step,), daemon=True)
               for i in range(n)]
    for t in threads:
        t.start()
    ctl.wait_closed()
    # no new request is sent; those in flight are waited for, so that the
    # tokens they produced inside the window can be counted
    deadline = time.monotonic() + float(spec["drain_s"])
    for t in threads:
        t.join(max(0.0, deadline - time.monotonic()))
    with lock:
        return list(records), sum(t.is_alive() for t in threads)
