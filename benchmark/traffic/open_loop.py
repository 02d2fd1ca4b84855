"""Open loop: requests are due on a schedule whatever the server does.

Groups (documents) arrive with exponential gaps at ``rate_rps / asks`` a
second; each group has a shared prefix and is asked ``asks`` times, the first
ask at arrival and the others spread over ``spread_s``. ``asks`` 1 and no
``prefix_len`` gives plain Poisson arrivals of unshared prompts. Each request
is timed from when it was DUE; how late the generator sent it is recorded.
"""

from __future__ import annotations

import heapq
import itertools
import queue
import threading
import time

from . import shapes

# which time of a request's record decides whether it belongs to the window
WINDOW_BY = "due"


def schedule(spec: dict, seed: int, vocab: int):
    """Endless stream of ``(due_offset_s, request)`` in due order."""
    n = int(spec["cycle"])
    asks = int(spec.get("asks", 1))
    gaps = shapes.cycle({"dist": "exponential",
                         "mean": asks / float(spec["rate_rps"])}, n)
    pre = shapes.cycle(spec["prefix_len"], n) if "prefix_len" in spec \
        else [0] * n
    tails = shapes.cycle(spec["tail_len"], n * asks)
    outs = shapes.cycle(spec["output_len"], n * asks)
    offs = shapes.cycle({"dist": "uniform", "min": 0.0,
                         "max": float(spec.get("spread_s", 0.0)),
                         "int": False}, n * max(asks - 1, 1))
    fix = shapes.rng(int(spec.get("pairing_seed", 0)), 7)
    tails = [tails[i] for i in fix.permutation(len(tails))]
    outs = [outs[i] for i in fix.permutation(len(outs))]
    offs = [offs[i] for i in fix.permutation(len(offs))]
    heap, index, now, tie = [], 0, 0.0, itertools.count()
    for epoch in itertools.count():
        order = shapes.rng(seed, 1, epoch).permutation(n)
        egaps = shapes.permuted(gaps, seed, 3, epoch)
        for j, g in zip(order, egaps):
            now += g
            group = epoch * n + int(j)
            while heap and heap[0][0] <= now:
                due, _, req = heapq.heappop(heap)
                yield due, req
            prefix = shapes.tokens(seed, 4, group, pre[j], vocab)
            for a in range(asks):
                k = int(j) * asks + a
                off = 0.0 if a == 0 else offs[int(j) * (asks - 1) + a - 1]
                req = {"index": index, "group": group,
                       "prompt_ids": prefix + shapes.tokens(
                           seed, 2, index, tails[k], vocab),
                       "max_new_tokens": outs[k]}
                index += 1
                heapq.heappush(heap, (now + off, next(tie), req))


def prompts(spec: dict, seed: int, vocab: int, indices) -> dict:
    """The prompts of the requests with these indices, made again."""
    want, out = set(indices), {}
    for _, req in schedule(spec, seed, vocab):
        if req["index"] in want:
            out[req["index"]] = req["prompt_ids"]
            if len(out) == len(want):
                return out


def run(spec: dict, seed: int, vocab: int, post, ctl):
    """Send each request at its due time from a pool of ``max_in_flight``
    workers, until ``ctl`` says the window has closed."""
    work: queue.Queue = queue.Queue()
    lock = threading.Lock()
    records = []

    def worker():
        while True:
            item = work.get()
            if item is None:
                return
            rec = post(*item)
            with lock:
                records.append(rec)

    workers = [threading.Thread(target=worker, daemon=True)
               for _ in range(int(spec.get("max_in_flight", 256)))]
    for t in workers:
        t.start()
    t_start = time.monotonic()
    for off, req in schedule(spec, seed, vocab):
        due = t_start + off
        while True:
            wait = due - time.monotonic()
            if wait <= 0 or ctl.closed():
                break
            time.sleep(min(wait, 0.05))
        if ctl.closed():
            break
        work.put((req, due))
    for _ in workers:
        work.put(None)
    deadline = time.monotonic() + float(spec.get("drain_s", 120))
    for t in workers:
        t.join(max(0.0, deadline - time.monotonic()))
    with lock:
        return list(records), sum(t.is_alive() for t in workers)
