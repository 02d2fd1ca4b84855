#!/usr/bin/env python3
"""The load generator: a child process that never imports JAX and speaks HTTP
only, so it neither touches the chip nor shares the interpreter lock with the
engine's loop.

stdin, line 1: the spec ``{"url", "kind", "traffic", "seed", "vocab"}``; load
starts at once (that is the warm-up). Later lines: ``go <t0> <seconds>`` opens
the window at monotonic time ``t0``; ``stop`` ends without a window. stdout,
last line: ``{"records": [...], "unfinished_threads": n}`` with one record a
request sent since the start.
"""

from __future__ import annotations

import http.client
import importlib
import json
import os
import sys
import threading
import time
from urllib.parse import urlparse


class Control:
    """The window, as told by the parent."""

    def __init__(self):
        self.t_end = None
        self._stop = threading.Event()

    def listen(self, stream):
        for line in stream:
            parts = line.split()
            if parts and parts[0] == "go":
                self.t_end = float(parts[1]) + float(parts[2])
            elif parts and parts[0] == "stop":
                self._stop.set()
        self._stop.set()        # the parent went away

    def closed(self) -> bool:
        return self._stop.is_set() or (
            self.t_end is not None and time.monotonic() >= self.t_end)

    def wait_closed(self):
        while not self.closed():
            time.sleep(0.02)


def make_post(url: str, timeout: float):
    u = urlparse(url)

    def post(req: dict, due: float) -> dict:
        rec = {"index": req["index"], "group": req["group"],
               "n_prompt": len(req["prompt_ids"]),
               "asked": req["max_new_tokens"], "due": due,
               "status": "error"}
        body = json.dumps({"prompt_ids": req["prompt_ids"],
                           "max_new_tokens": req["max_new_tokens"],
                           "temperature": 0.0})
        rec["sent"] = time.monotonic()
        try:
            conn = http.client.HTTPConnection(u.hostname, u.port,
                                              timeout=timeout)
            try:
                conn.request("POST", "/generate", body,
                             {"Content-Type": "application/json"})
                resp = conn.getresponse()
                data = resp.read()
            finally:
                conn.close()
            rec["done"] = time.monotonic()
            if resp.status != 200:
                rec["status"] = f"http_{resp.status}"
                return rec
            out = json.loads(data)
            rec.update(ttft_s=out.get("ttft_s"), latency_s=out["latency_s"],
                       output_ids=out["output_ids"])
            if out.get("truncated"):
                rec["status"] = "truncated"
            elif len(out["output_ids"]) != req["max_new_tokens"] \
                    or out.get("ttft_s") is None:
                rec["status"] = "short"
            else:
                rec["status"] = "ok"
        except (OSError, http.client.HTTPException, ValueError) as e:
            rec["done"] = time.monotonic()
            rec["error"] = repr(e)[:200]
        return rec

    return post


def main() -> int:
    if "jax" in sys.modules:
        raise RuntimeError("the load generator must not import jax")
    spec = json.loads(sys.stdin.readline())
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    kind = importlib.import_module(f"traffic.{spec['kind']}")
    ctl = Control()
    threading.Thread(target=ctl.listen, args=(sys.stdin,),
                     daemon=True).start()
    post = make_post(spec["url"], float(spec["traffic"].get("timeout_s", 600)))
    records, alive = kind.run(spec["traffic"], int(spec["seed"]),
                              int(spec["vocab"]), post, ctl)
    print(json.dumps({"records": records, "unfinished_threads": alive}),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
