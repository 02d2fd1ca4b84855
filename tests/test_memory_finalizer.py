"""The memory ledger's GC finalizer (``memory.finalize_scope``) must be safe
wherever the collector happens to run it — also on a thread that holds the
ledger lock: ``_collect`` copies its rows under that lock, an allocation there
can trigger a collection, and a finalizer that took the (non-reentrant) lock
again hung a whole tier-1 run (PERF.md, PR 23)."""
import gc
import threading

from paddle_tpu.observability import memory


class _Owner:
    pass


class _CollectingLock:
    """The ledger's own lock, forcing a collection inside every section it
    guards: what an unlucky allocation does, made certain."""

    def __init__(self, inner):
        self.inner = inner

    def __enter__(self):
        self.inner.acquire()
        gc.collect()
        return self

    def __exit__(self, *exc):
        self.inner.release()


def test_finalizer_inside_the_locked_collect_does_not_deadlock():
    memory.reset()
    try:
        led = memory.instance()
        kept, scope = _Owner(), memory.next_scope()
        memory.set_entry(scope, "kept", "params", 10.0)
        memory.finalize_scope(kept, scope)
        owner, dead = _Owner(), memory.next_scope()
        memory.set_entry(dead, "dropped", "params", 1000.0)
        memory.finalize_scope(owner, dead)
        gc.disable()
        try:
            owner.me = owner        # a cycle: only the collector frees it
            del owner
            led._mu = _CollectingLock(led._mu)
            rows = []
            t = threading.Thread(target=lambda: rows.extend(led.rows()),
                                 daemon=True)
            t.start()
            t.join(timeout=30)
            assert not t.is_alive(), "finalizer deadlocked on the ledger lock"
        finally:
            gc.enable()
        # the read that the collection interrupted may still list the row;
        # the next one has dropped the finalized scope and kept the live one
        owners = {r["owner"] for r in led.rows()}
        assert owners == {"kept"}
        assert kept is not None
    finally:
        memory.reset()
