"""paddle_tpu.io — datasets and the DataLoader.

Rebuild of the reference's data pipeline
(reference: python/paddle/io/__init__.py re-exporting
python/paddle/fluid/dataloader/{dataset,batch_sampler,dataloader_iter}.py —
``Dataset``, ``IterableDataset``, ``TensorDataset``, ``BatchSampler``,
``DistributedBatchSampler``:19, multi-process ``_DataLoaderIterMultiProcess``
:342 with shared-memory queues; C++ side blocking-queue reader ops in
paddle/fluid/operators/reader/).

TPU-native design: the loader produces NumPy host batches on background
threads and *prefetches them to device* ahead of the compiled step
(double-buffering analog of the reference's use_double_buffer /
DecoratedReader), so the MXU never waits on host I/O. Per-process sharding
for data parallelism comes from ``DistributedBatchSampler``. A native C++
sample-decode path can plug in underneath via ``worker_fn`` without
changing this API.
"""

from __future__ import annotations

import itertools
import math
import queue
import threading
import time
from typing import (Any, Callable, Iterable, Iterator, List, Optional,
                    Sequence, Tuple)

import jax
import numpy as np

from ..core import rng as rng_mod
from ..observability import goodput as _goodput
from ..observability import metrics as _obs
from ..observability import tracing as _tracing
from ..reliability import faults as _faults


def _loader_metrics():
    reg = _obs.default_registry()
    return {
        "wait": reg.histogram(
            "dataloader_next_wait_seconds",
            "time the consumer blocked waiting for the next batch"),
        "batches": reg.counter(
            "dataloader_batches", "batches handed to the train loop"),
    }


def _superbatch_metrics():
    reg = _obs.default_registry()
    return {
        "wait": reg.histogram(
            "train_loop_prefetch_wait_seconds",
            "time the fused train loop blocked waiting for the next "
            "[K, ...] slab (≈0 when the double-buffered prefetch "
            "keeps up)"),
        "batches": reg.counter(
            "train_loop_slabs", "superbatch slabs handed to the fused "
            "train loop"),
    }


class Dataset:
    """Map-style dataset (ref: fluid/dataloader/dataset.py Dataset)."""

    def __getitem__(self, idx):
        raise NotImplementedError

    def __len__(self):
        raise NotImplementedError


class IterableDataset(Dataset):
    def __iter__(self):
        raise NotImplementedError

    def __getitem__(self, idx):
        raise TypeError("IterableDataset is not subscriptable")

    def __len__(self):
        raise TypeError("IterableDataset has no len()")


class TensorDataset(Dataset):
    def __init__(self, tensors: Sequence):
        arrays = [np.asarray(t) for t in tensors]
        n = arrays[0].shape[0]
        assert all(a.shape[0] == n for a in arrays)
        self.arrays = arrays

    def __getitem__(self, idx):
        return tuple(a[idx] for a in self.arrays)

    def __len__(self):
        return self.arrays[0].shape[0]


class Subset(Dataset):
    def __init__(self, dataset: Dataset, indices: Sequence[int]):
        self.dataset = dataset
        self.indices = list(indices)

    def __getitem__(self, idx):
        return self.dataset[self.indices[idx]]

    def __len__(self):
        return len(self.indices)


class ConcatDataset(Dataset):
    def __init__(self, datasets: Sequence[Dataset]):
        self.datasets = list(datasets)
        self.cum = np.cumsum([len(d) for d in self.datasets])

    def __len__(self):
        return int(self.cum[-1])

    def __getitem__(self, idx):
        ds = int(np.searchsorted(self.cum, idx, side="right"))
        prev = 0 if ds == 0 else int(self.cum[ds - 1])
        return self.datasets[ds][idx - prev]


class ChainDataset(IterableDataset):
    def __init__(self, datasets: Sequence[IterableDataset]):
        self.datasets = list(datasets)

    def __iter__(self):
        return itertools.chain(*self.datasets)


def random_split(dataset: Dataset, lengths: Sequence[int]):
    assert sum(lengths) == len(dataset)
    perm = np.random.RandomState(0).permutation(len(dataset))
    out, ofs = [], 0
    for n in lengths:
        out.append(Subset(dataset, perm[ofs:ofs + n].tolist()))
        ofs += n
    return out


# ---------------------------------------------------------------------------
# Samplers (ref: fluid/dataloader/{sampler,batch_sampler}.py)
# ---------------------------------------------------------------------------

class Sampler:
    def __init__(self, data_source=None):
        self.data_source = data_source

    def __iter__(self):
        raise NotImplementedError


class SequenceSampler(Sampler):
    def __iter__(self):
        return iter(range(len(self.data_source)))

    def __len__(self):
        return len(self.data_source)


class RandomSampler(Sampler):
    def __init__(self, data_source, replacement: bool = False,
                 num_samples: Optional[int] = None):
        super().__init__(data_source)
        self.replacement = replacement
        self.num_samples = num_samples or len(data_source)
        self._epoch = 0

    def __iter__(self):
        n = len(self.data_source)
        rs = np.random.RandomState(
            (rng_mod._tls.global_seed + self._epoch) % (2 ** 31))
        self._epoch += 1
        if self.replacement:
            return iter(rs.randint(0, n, self.num_samples).tolist())
        return iter(rs.permutation(n)[:self.num_samples].tolist())

    def __len__(self):
        return self.num_samples


class BatchSampler(Sampler):
    """ref: fluid/dataloader/batch_sampler.py BatchSampler."""

    def __init__(self, dataset=None, sampler: Optional[Sampler] = None,
                 shuffle: bool = False, batch_size: int = 1,
                 drop_last: bool = False):
        self.batch_size = batch_size
        self.drop_last = drop_last
        if sampler is not None:
            self.sampler = sampler
        elif shuffle:
            self.sampler = RandomSampler(dataset)
        else:
            self.sampler = SequenceSampler(dataset)

    def __iter__(self):
        batch = []
        for idx in self.sampler:
            batch.append(idx)
            if len(batch) == self.batch_size:
                yield batch
                batch = []
        if batch and not self.drop_last:
            yield batch

    def __len__(self):
        n = len(self.sampler)
        if self.drop_last:
            return n // self.batch_size
        return (n + self.batch_size - 1) // self.batch_size


class DistributedBatchSampler(BatchSampler):
    """Shards sample indices across data-parallel ranks
    (ref: fluid/dataloader/batch_sampler.py DistributedBatchSampler:~196).
    On TPU, rank/world come from jax.process_index/count by default."""

    def __init__(self, dataset, batch_size: int, num_replicas=None,
                 rank=None, shuffle: bool = False, drop_last: bool = False):
        self.dataset = dataset
        self.batch_size = batch_size
        self.nranks = num_replicas if num_replicas is not None else \
            jax.process_count()
        self.local_rank = rank if rank is not None else jax.process_index()
        self.shuffle = shuffle
        self.drop_last = drop_last
        self.epoch = 0
        self.num_samples = int(
            math.ceil(len(dataset) / self.nranks)) if not drop_last else \
            len(dataset) // self.nranks
        self.total_size = self.num_samples * self.nranks

    def __iter__(self):
        n = len(self.dataset)
        if self.shuffle:
            rs = np.random.RandomState(self.epoch)
            indices = rs.permutation(n).tolist()
        else:
            indices = list(range(n))
        if not self.drop_last:
            indices += indices[: self.total_size - len(indices)]
        else:
            indices = indices[: self.total_size]
        indices = indices[self.local_rank:self.total_size:self.nranks]
        batch = []
        for idx in indices:
            batch.append(idx)
            if len(batch) == self.batch_size:
                yield batch
                batch = []
        if batch and not self.drop_last:
            yield batch

    def __len__(self):
        if self.drop_last:
            return self.num_samples // self.batch_size
        return (self.num_samples + self.batch_size - 1) // self.batch_size

    def set_epoch(self, epoch: int):
        # a user-driven epoch pin (the torch/paddle sampler contract):
        # once called, the DataLoader's pass-index sync backs off and
        # shuffle order is the caller's responsibility (including on
        # resume)
        self._epoch_set_by_user = True
        self.epoch = epoch


# ---------------------------------------------------------------------------
# Collation + DataLoader
# ---------------------------------------------------------------------------

class WorkerInfo:
    """ref: fluid/dataloader/worker.py WorkerInfo — id/num_workers/seed
    visible inside a worker process so IterableDatasets can shard."""

    def __init__(self, wid: int, num_workers: int, seed: int):
        self.id = wid
        self.num_workers = num_workers
        self.seed = seed


_worker_info: Optional[WorkerInfo] = None


def get_worker_info() -> Optional[WorkerInfo]:
    """ref: paddle.io.get_worker_info — None in the main process."""
    return _worker_info


def default_collate_fn(batch: List[Any]):
    """Stack a list of samples into a batch (ref:
    fluid/dataloader/collate.py default_collate_fn)."""
    first = batch[0]
    if isinstance(first, (np.ndarray, jax.Array)):
        return np.stack([np.asarray(b) for b in batch])
    if isinstance(first, (int, np.integer)):
        return np.asarray(batch, dtype=np.int64)
    if isinstance(first, (float, np.floating)):
        return np.asarray(batch, dtype=np.float32)
    if isinstance(first, (list, tuple)):
        return type(first)(default_collate_fn(list(x)) for x in zip(*batch))
    if isinstance(first, dict):
        return {k: default_collate_fn([b[k] for b in batch]) for k in first}
    return np.asarray(batch)


class _PrefetchIterator:
    """Background-thread batch producer + device prefetch
    (replaces _DataLoaderIterMultiProcess, fluid/dataloader/
    dataloader_iter.py:342 — threads instead of fork: batches feed one
    process-local device via jax.device_put, and XLA releases the GIL
    during compute so Python threads keep the queue full)."""

    _SENTINEL = object()

    def __init__(self, produce: Callable[[], Iterator], buffer_size: int,
                 to_device: bool, instruments=None, on_item=None):
        self._q: "queue.Queue" = queue.Queue(maxsize=max(buffer_size, 1))
        self._to_device = to_device
        self._err: Optional[BaseException] = None
        self._produce = produce
        self._stop = threading.Event()
        self._obs = instruments or _loader_metrics()
        # consumption hook (DataLoader cursor tracking): fires on the
        # CONSUMER thread as each item is handed out — prefetched-but-
        # unconsumed batches never advance the resume cursor
        self._on_item = on_item
        self._thread = threading.Thread(target=self._run, daemon=True)
        self._thread.start()

    def _run(self):
        try:
            for item in self._produce():
                if self._stop.is_set():
                    return
                if self._to_device:
                    item = jax.tree_util.tree_map(
                        lambda x: jax.device_put(np.asarray(x)), item)
                self._q.put(item)
        except BaseException as e:  # propagate to consumer
            self._err = e
        finally:
            self._q.put(self._SENTINEL)

    def __iter__(self):
        return self

    def __next__(self):
        t0 = time.perf_counter()
        item = self._q.get()
        if item is self._SENTINEL:
            if self._err is not None:
                raise self._err
            raise StopIteration
        # wait ≈ how starved the train loop is for input: near zero
        # when prefetch keeps up, ≈ batch production time when not
        t1 = time.perf_counter()
        self._obs["wait"].observe(t1 - t0)
        self._obs["batches"].inc()
        if _goodput.enabled():
            # the SAME wait the histogram observes: input starvation
            # on the time ledger (input_wait badput)
            _goodput.note("input_wait", t1 - t0)
        if _tracing.active():
            # post-hoc span over the wait interval: the input-starved
            # share shows up next to dispatch/drain in span rollups
            _tracing.start_span("io.next_wait", t0=t0).end(t1)
        if self._on_item is not None:
            self._on_item(item)
        return item

    def close(self):
        self._stop.set()
        try:
            while True:
                self._q.get_nowait()
        except queue.Empty:
            pass


# -- multiprocess workers (ref: _DataLoaderIterMultiProcess,
#    fluid/dataloader/dataloader_iter.py:342) --------------------------------
#
# fork-based: the dataset is inherited by the worker processes (no
# per-batch pickling of the dataset), batches return through pipes as
# pickled numpy — the reference's shared-memory LoDTensor queue is a
# CUDA-pinned-memory optimization that doesn't apply to a PJRT host
# buffer, so plain pipes + the device-prefetch thread give the same
# overlap. Workers never touch jax/TPU state.

_mp_dataset = None
_mp_collate = None
# One fork pool at a time forks, submits or shuts down. An executor's
# submit() and shutdown() hold its wake-up lock across a pipe write, where
# the GIL changes hands; a worker that another loader's thread forks at
# that moment inherits the lock held, signals every inherited wake-up at
# its own exit (concurrent.futures.process._python_exit), and waits on it
# for ever: the pool never joins, and the worker outlives the process with
# its stdout open.
_pool_mu = threading.Lock()


def _map_worker_init(dataset, collate_fn, wid, num_workers, seed):
    global _mp_dataset, _mp_collate, _worker_info
    _mp_dataset = dataset
    _mp_collate = collate_fn
    _worker_info = WorkerInfo(wid, num_workers, seed)
    np.random.seed((seed + wid) % (2 ** 31))


def _map_worker_collate(batch_idx):
    return _mp_collate([_mp_dataset[i] for i in batch_idx])


def _iter_worker_loop(dataset, collate_fn, batch_size, drop_last,
                      wid, num_workers, seed, out_q):
    """Worker body for IterableDataset: iterate a private copy with
    worker_info set (the dataset shards itself via get_worker_info, same
    contract as the reference), collate and ship batches."""
    global _worker_info
    _worker_info = WorkerInfo(wid, num_workers, seed)
    np.random.seed((seed + wid) % (2 ** 31))
    try:
        it = iter(dataset)
        if batch_size is None:
            for item in it:
                out_q.put(("item", item))
        else:
            while True:
                batch = list(itertools.islice(it, batch_size))
                if not batch or (len(batch) < batch_size and drop_last):
                    break
                out_q.put(("item", collate_fn(batch)))
        out_q.put(("done", None))
    except BaseException as e:  # noqa: BLE001 — ship to parent
        import traceback
        out_q.put(("error", traceback.format_exc() + repr(e)))


class DataLoader:
    """ref: python/paddle/fluid/reader.py:275 DataLoader."""

    def __init__(self, dataset: Dataset, batch_size: Optional[int] = 1,
                 shuffle: bool = False, batch_sampler=None, sampler=None,
                 drop_last: bool = False, collate_fn=None,
                 num_workers: int = 0, prefetch_factor: int = 2,
                 return_list: bool = True, to_device: bool = True):
        self.dataset = dataset
        self.collate_fn = collate_fn or default_collate_fn
        if num_workers == "auto":
            # ref: incubate/autotune.py dataloader tuner
            from ..incubate.autotune import suggested_num_workers
            num_workers = suggested_num_workers()
        self.num_workers = num_workers
        self.prefetch_factor = prefetch_factor
        self.to_device = to_device
        self._iterable = isinstance(dataset, IterableDataset)
        self.batch_size = batch_size
        self.drop_last = drop_last
        if self._iterable:
            self.batch_sampler = None
        elif batch_sampler is not None:
            self.batch_sampler = batch_sampler
        else:
            self.batch_sampler = BatchSampler(
                dataset, sampler=sampler, shuffle=shuffle,
                batch_size=batch_size or 1, drop_last=drop_last)
        # resume cursor (preemption-safe training, ISSUE 8): which pass
        # (epoch) is running and how many host batches the CONSUMER has
        # taken from it — see state_dict()/load_state_dict()
        self._pass_index = 0      # passes started (next pass's index)
        self._current_pass = 0
        self._batch_cursor = 0
        self._resume_cursor: Optional[Tuple[int, int]] = None

    def _produce(self, skip: int = 0):
        if self._iterable:
            it = iter(self.dataset)
            if self.batch_size is None:
                yield from itertools.islice(it, skip, None)
                return
            n = 0
            while True:
                batch = list(itertools.islice(it, self.batch_size))
                if not batch:
                    return
                if len(batch) < self.batch_size and self.drop_last:
                    return
                n += 1
                if n > skip:  # iterables can't seek: consume and drop
                    yield self.collate_fn(batch)
        else:
            # map-style skip happens at the INDEX level — skipped
            # batches cost no __getitem__/collate work on resume
            for batch_idx in itertools.islice(
                    iter(self.batch_sampler), skip, None):
                yield self.collate_fn([self.dataset[i] for i in batch_idx])

    def _produce_multiprocess_map(self, seed, skip: int = 0):
        """Ordered pipelined map over batch indices on a fork pool —
        up to num_workers*prefetch_factor batches in flight."""
        import collections
        from concurrent.futures import ProcessPoolExecutor
        import multiprocessing as mp
        ctx = mp.get_context("fork")
        wid_counter = ctx.Value("i", 0)

        def _init(dataset, collate, nw, sd):
            with wid_counter.get_lock():
                wid = wid_counter.value
                wid_counter.value += 1
            _map_worker_init(dataset, collate, wid, nw, sd)

        pool = ProcessPoolExecutor(
            max_workers=self.num_workers, mp_context=ctx,
            initializer=_init,
            initargs=(self.dataset, self.collate_fn, self.num_workers,
                      seed))
        try:
            pending: "collections.deque" = collections.deque()
            depth = self.num_workers * max(self.prefetch_factor, 1)
            it = itertools.islice(iter(self.batch_sampler), skip, None)
            for batch_idx in it:
                with _pool_mu:      # the first submit forks the workers
                    pending.append(
                        pool.submit(_map_worker_collate, batch_idx))
                if len(pending) >= depth:
                    yield pending.popleft().result()
            while pending:
                yield pending.popleft().result()
        finally:
            # wait: the pool's manager thread closes the wake-up pipe
            # under the same lock, so it ends inside _pool_mu too
            with _pool_mu:
                pool.shutdown(wait=True, cancel_futures=True)

    def _produce_multiprocess_iter(self, seed, skip: int = 0):
        """IterableDataset workers: each process iterates its own copy
        with worker_info set (datasets shard via get_worker_info, ref
        contract); parent round-robins worker queues for a deterministic
        order (which is also what makes the resume ``skip`` exact: the
        parent drops the first ``skip`` batches of the SAME deterministic
        round-robin stream the interrupted run consumed)."""
        import multiprocessing as mp

        ctx = mp.get_context("fork")
        queues = [ctx.Queue(maxsize=max(self.prefetch_factor, 1))
                  for _ in range(self.num_workers)]
        procs = [
            ctx.Process(
                target=_iter_worker_loop,
                args=(self.dataset, self.collate_fn, self.batch_size,
                      self.drop_last, w, self.num_workers, seed, queues[w]),
                daemon=True)
            for w in range(self.num_workers)]
        for p in procs:
            p.start()
        alive = [True] * self.num_workers
        try:
            while any(alive):
                for w in range(self.num_workers):
                    if not alive[w]:
                        continue
                    while True:
                        try:
                            kind, payload = queues[w].get(timeout=5.0)
                            break
                        except queue.Empty:
                            # watchdog (ref: _DataLoaderIterMultiProcess
                            # worker-status check): a worker killed by
                            # the OS (OOM/segfault) sends nothing — fail
                            # loudly instead of hanging fit() forever
                            if not procs[w].is_alive():
                                raise RuntimeError(
                                    f"DataLoader worker {w} died "
                                    f"(exitcode {procs[w].exitcode})")
                    if kind == "error":
                        raise RuntimeError(
                            f"DataLoader worker {w} failed:\n{payload}")
                    if kind == "done":
                        alive[w] = False
                        continue
                    if skip > 0:
                        skip -= 1
                        continue
                    yield payload
        finally:
            for p in procs:
                p.terminate()
            for p in procs:  # reap — terminate alone leaks zombies
                p.join(timeout=5.0)

    def _begin_pass(self) -> Tuple[int, int]:
        """Start one pass over the data: resolve which pass index it is
        (a pending resume cursor wins), how many batches to skip, and
        sync every epoch-seeded sampler to that index — so pass ``e``
        of a resumed run shuffles EXACTLY like pass ``e`` of an
        uninterrupted one."""
        if self._resume_cursor is not None:
            pass_idx, skip = self._resume_cursor
            self._resume_cursor = None
        else:
            pass_idx, skip = self._pass_index, 0
        self._pass_index = pass_idx + 1
        self._current_pass = pass_idx
        self._batch_cursor = skip
        self._sync_shuffle_epoch(pass_idx)
        return pass_idx, skip

    def _sync_shuffle_epoch(self, epoch: int) -> None:
        for obj in (self.batch_sampler,
                    getattr(self.batch_sampler, "sampler", None)):
            if obj is None:
                continue
            if getattr(obj, "_epoch_set_by_user", False):
                # the user drives this sampler's epoch (set_epoch
                # contract) — never overwrite their pin with the
                # loader's private pass counter
                continue
            if hasattr(obj, "set_epoch"):
                obj.set_epoch(epoch)
                # a loader-managed sync must stay distinguishable from
                # a user call: un-latch the flag set_epoch just set
                try:
                    obj._epoch_set_by_user = False
                except AttributeError:
                    pass
            elif hasattr(obj, "_epoch"):
                obj._epoch = epoch

    def _note_consumed(self, n: int) -> None:
        self._batch_cursor += n

    def _select_produce(self, pass_idx: int = None, skip: int = 0):
        """Pick the host-batch producer for one pass (serial generator or
        the fork-pool pipelines), resolving the per-epoch worker seed on
        the CALLER thread (where paddle.seed's thread-local state lives —
        the produce generator body runs on the prefetch thread)."""
        if pass_idx is None:
            pass_idx, skip = self._begin_pass()
        if self.num_workers > 0:
            # worker seed keyed by the PASS INDEX (not a private
            # counter): a resumed run's pass e re-derives the exact
            # per-worker seeds the interrupted run used
            seed = (int(rng_mod._tls.global_seed) + pass_idx) % (2 ** 31)
            mp_produce = self._produce_multiprocess_iter if self._iterable \
                else self._produce_multiprocess_map
            produce = lambda: mp_produce(seed, skip)  # noqa: E731
        else:
            produce = lambda: self._produce(skip)  # noqa: E731
        if not _faults.enabled():
            # zero-overhead default: the injection wrapper only exists
            # on passes started while chaos is armed
            return produce

        def produce_with_faults():
            # injection site io.worker: one check per produced host
            # batch — models a worker dying mid-epoch (OOM/segfault);
            # the fault rides the prefetch queue to the training loop
            for b in produce():
                _faults.check("io.worker")
                yield b

        return produce_with_faults

    def __iter__(self):
        pass_idx, skip = self._begin_pass()
        return _PrefetchIterator(self._select_produce(pass_idx, skip),
                                 self.prefetch_factor, self.to_device,
                                 on_item=lambda _b: self._note_consumed(1))

    # -- resume cursor (preemption-safe training) ---------------------------
    def state_dict(self) -> dict:
        """The exact-resume cursor: the pass (epoch) currently being
        consumed and how many host batches the consumer has taken from
        it. Batches sitting in the prefetch queue (produced, never
        consumed) are NOT counted — they re-produce on resume, so the
        training loop sees each batch exactly once. Safe with
        multiprocess workers: worker seeds and the round-robin order
        derive from the pass index alone."""
        if _faults.enabled():
            _faults.check("loader.state")
        return {"pass": int(self._current_pass),
                "batch": int(self._batch_cursor)}

    def load_state_dict(self, state: dict) -> None:
        """Arm the NEXT iteration pass to resume at ``state``: it runs
        as pass ``state["pass"]`` (same shuffle permutation, same
        worker seeds) and skips the first ``state["batch"]`` batches —
        map-style datasets skip at the index level (no __getitem__
        cost), IterableDatasets consume-and-drop. A mid-superbatch
        cursor (batch not a multiple of steps_per_loop) is fine:
        ``superbatches`` restacks slabs from the resume point and the
        fused loop's per-step keys depend only on the global step."""
        if _faults.enabled():
            _faults.check("loader.state")
        pass_idx = int(state["pass"])
        skip = int(state["batch"])
        self._resume_cursor = (pass_idx, skip)
        self._current_pass = pass_idx
        self._batch_cursor = skip
        self._pass_index = pass_idx

    def superbatches(self, steps_per_loop: int):
        """Iterate ``[K, ...]``-stacked slabs for the fused train loop.

        Stacks ``steps_per_loop`` consecutive host batches into one
        superbatch (leading dim = per-slab optimizer steps) and ships it
        with the same background-thread device prefetch as ``__iter__``:
        the NEXT slab's jax.device_put overlaps the current slab's
        compute (double buffering, one queue slot ahead per
        ``prefetch_factor``). Batches whose leaf shapes differ from the
        slab being built (the ragged tail of an epoch with
        drop_last=False) flush the slab early, so every yielded slab is
        rectangular; consumers route short slabs (leading dim < K)
        through the per-step path. Prefetch wait/slab counts land in the
        ``train_loop_*`` instruments rather than the per-batch
        dataloader ones. The resume cursor counts the BATCHES inside
        each consumed slab (leading dim), so a checkpoint taken between
        slabs — or at a ragged tail — resumes mid-superbatch: the
        restarted stream restacks slabs from the skipped batch onward
        (slab boundaries may shift; per-step contents don't)."""
        k = max(int(steps_per_loop), 1)
        pass_idx, skip = self._begin_pass()
        produce = self._select_produce(pass_idx, skip)

        def gen():
            buf: List[Any] = []
            sig = None
            for b in produce():
                s = tuple(np.shape(x)
                          for x in jax.tree_util.tree_leaves(b))
                if buf and s != sig:
                    yield stack_batches(buf)
                    buf = []
                buf.append(b)
                sig = s
                if len(buf) == k:
                    yield stack_batches(buf)
                    buf = []
            if buf:
                yield stack_batches(buf)

        def consumed(slab):
            self._note_consumed(
                int(jax.tree_util.tree_leaves(slab)[0].shape[0]))

        return _PrefetchIterator(gen, max(self.prefetch_factor, 1),
                                 self.to_device,
                                 instruments=_superbatch_metrics(),
                                 on_item=consumed)

    def __len__(self):
        if self._iterable:
            raise TypeError("IterableDataset DataLoader has no len()")
        return len(self.batch_sampler)


def stack_batches(batches: List[Any]):
    """Stack same-structure host batches leaf-wise into one [K, ...]
    superbatch (the fused train loop's unit of dispatch)."""
    return jax.tree_util.tree_map(
        lambda *xs: np.stack([np.asarray(x) for x in xs]), *batches)


# variable-length sequence tools (XLA static-shape policy; SURVEY §7)
from .sequence import (LengthBucketBatchSampler, bucket_collate,  # noqa: E402
                       default_boundaries, pad_sequence)


class ComposeDataset(Dataset):
    """Zip-style composition: sample i concatenates the fields of
    sample i from every child (ref: fluid/dataloader/dataset.py
    ComposeDataset)."""

    def __init__(self, datasets):
        self.datasets = list(datasets)
        if not self.datasets:
            raise ValueError("ComposeDataset needs at least one child")
        n = len(self.datasets[0])
        for d in self.datasets[1:]:
            if len(d) != n:
                raise ValueError("ComposeDataset children must have "
                                 "equal lengths")

    def __len__(self):
        return len(self.datasets[0])

    def __getitem__(self, idx):
        out = []
        for d in self.datasets:
            s = d[idx]
            out.extend(s if isinstance(s, (tuple, list)) else (s,))
        return tuple(out)


class WeightedRandomSampler(Sampler):
    """Sample indices ∝ weights, with/without replacement (ref:
    fluid/dataloader/sampler.py WeightedRandomSampler)."""

    def __init__(self, weights, num_samples: int, replacement=True):
        import numpy as _np
        self.weights = _np.asarray(weights, _np.float64)
        if (self.weights < 0).any():
            raise ValueError("weights must be non-negative")
        self.num_samples = int(num_samples)
        self.replacement = bool(replacement)
        if not replacement and num_samples > len(self.weights):
            raise ValueError("cannot draw more samples than weights "
                             "without replacement")

    def __iter__(self):
        import numpy as _np
        p = self.weights / self.weights.sum()
        # seeded like RandomSampler: paddle.seed-reproducible, epoch-
        # advancing, independent of the global np.random state
        epoch = getattr(self, "_epoch", 0)
        self._epoch = epoch + 1
        rs = _np.random.RandomState(
            (rng_mod._tls.global_seed + epoch) % (2 ** 31))
        idx = rs.choice(len(p), size=self.num_samples,
                        replace=self.replacement, p=p)
        return iter(idx.tolist())

    def __len__(self):
        return self.num_samples
from . import checkpoint  # noqa: E402,F401  (io.checkpoint.AutoCheckpoint)
