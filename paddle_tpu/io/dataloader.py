"""DataLoader (reference: python/paddle/io/reader.py:262 DataLoader,
dataloader_iter.py:368 multiprocess iter).

TPU-native design:
- worker pool via a thread/process pool feeding an ordered prefetch queue —
  the reference's shared-memory tensor IPC is unnecessary because host numpy
  batches go straight into a PjRt host-to-device transfer;
- ``prefetch_to_device``: up to ``prefetch_factor`` batches are staged onto
  the accelerator asynchronously (jax.device_put is async) so H2D overlaps
  the previous step's compute — replacing the reference's pin-memory +
  cuda-stream copy path.
"""
from __future__ import annotations

import collections
import itertools
import queue
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Any, Callable, Iterable, List, Optional

import numpy as np
import jax

from ..core.tensor import Tensor
from .dataset import Dataset, IterableDataset
from .sampler import BatchSampler, SequenceSampler, RandomSampler

__all__ = ["DataLoader", "default_collate_fn"]


def default_collate_fn(batch):
    """Stack samples into batched arrays
    (reference: python/paddle/io/dataloader/collate.py)."""
    sample = batch[0]
    if isinstance(sample, np.ndarray):
        return np.stack(batch, axis=0)
    if isinstance(sample, Tensor):
        return Tensor(np.stack([s.numpy() for s in batch], axis=0))
    if isinstance(sample, (int, np.integer)):
        return np.asarray(batch, dtype=np.int64)
    if isinstance(sample, (float, np.floating)):
        return np.asarray(batch, dtype=np.float32)
    if isinstance(sample, (str, bytes)):
        return list(batch)
    if isinstance(sample, collections.abc.Mapping):
        return {k: default_collate_fn([d[k] for d in batch]) for k in sample}
    if isinstance(sample, collections.abc.Sequence):
        transposed = list(zip(*batch))
        return [default_collate_fn(list(s)) for s in transposed]
    raise TypeError(f"cannot collate batch of type {type(sample)}")


class _DevicePrefetchIter:
    """Double-buffered async H2D stage (reference:
    python/paddle/io/dataloader/dataloader_iter.py:368 — pin-memory +
    buffer-reader thread hiding ingest behind compute). A dedicated
    thread pulls host batches from ``src``, stages them on device
    (``jax.device_put``), and keeps up to ``depth`` staged batches
    queued ahead of the consumer, so the transfer for batch N+1 runs
    while the step consuming batch N computes. One thread serializes
    transfers — deliberate: concurrent h2d streams contend for the
    same host-link bandwidth without helping latency."""

    _END = ("end", None)

    def __init__(self, src, stage, depth=2, on_next=None):
        self.q: queue.Queue = queue.Queue(maxsize=max(1, depth))
        self._stop = threading.Event()
        self._done = False
        self._src = src
        self._stage = stage
        # observability hook: called with the staged-queue depth after
        # each consumer pull (a queue pinned at 0 = ingest-bound, at
        # depth = compute-bound); must be cheap and never raise
        self._on_next = on_next
        self._thread = threading.Thread(
            target=self._run, name="device-prefetch", daemon=True)
        self._thread.start()

    def _put(self, item):
        while not self._stop.is_set():
            try:
                self.q.put(item, timeout=0.1)
                return True
            except queue.Full:
                continue
        return False

    def _run(self):
        try:
            for host_batch in self._src:
                if self._stop.is_set():
                    return
                if not self._put(("item", self._stage(host_batch))):
                    return
            self._put(self._END)
        except BaseException as e:  # noqa: BLE001 — relayed to consumer
            self._put(("err", e))

    def __next__(self):
        # after an error was relayed (or close()), the producer thread
        # is gone and nothing will ever be enqueued again — a blocking
        # get() would deadlock a consumer that catches the error and
        # keeps iterating; terminate the iteration instead
        if self._done:
            raise StopIteration
        while True:
            try:
                kind, payload = self.q.get(timeout=0.1)
                break
            except queue.Empty:
                if self._stop.is_set():
                    self._done = True
                    raise StopIteration from None
        if kind == "item":
            if self._on_next is not None:
                self._on_next(self.q.qsize())
            return payload
        self._done = True
        self._stop.set()
        if kind == "err":
            raise payload
        raise StopIteration

    def __iter__(self):
        return self

    def close(self):
        self._stop.set()

    def __del__(self):
        self._stop.set()


class _PrefetchIter:
    def __init__(self, loader, index_iter):
        self.loader = loader
        self.index_iter = index_iter
        self.pool = (ThreadPoolExecutor(loader.num_workers)
                     if loader.num_workers > 0 else None)
        self.pending = collections.deque()
        self.prefetch = max(loader.prefetch_factor, 1) * max(
            loader.num_workers, 1)
        self._fill()

    def _load(self, indices):
        ds = self.loader.dataset
        samples = [ds[i] for i in indices]
        batch = self.loader.collate_fn(samples)
        # pooled workers stage to device in-thread (overlapped there);
        # the synchronous num_workers=0 path returns the host batch and
        # lets DataLoader.__iter__ wrap it in _DevicePrefetchIter
        if self.pool is not None:
            return self.loader._to_device(batch)
        return batch

    def _fill(self):
        while len(self.pending) < self.prefetch:
            try:
                indices = next(self.index_iter)
            except StopIteration:
                return
            if self.pool is not None:
                self.pending.append(self.pool.submit(self._load, indices))
            else:
                self.pending.append(indices)

    def __next__(self):
        if not self.pending:
            if self.pool is not None:
                self.pool.shutdown(wait=False)
            raise StopIteration
        item = self.pending.popleft()
        self._fill()
        if self.pool is not None:
            return item.result()
        return self._load(item)

    def __iter__(self):
        return self


class WorkerInfo:
    """reference: io/dataloader/worker.py WorkerInfo — id / num_workers /
    dataset of the calling worker; None in the main process."""

    def __init__(self, id, num_workers, dataset):
        self.id = id
        self.num_workers = num_workers
        self.dataset = dataset

    def __repr__(self):
        return (f"WorkerInfo(id={self.id}, "
                f"num_workers={self.num_workers})")


_WORKER_INFO = [None]


def get_worker_info():
    """reference: python/paddle/io/__init__.py get_worker_info — worker
    context inside DataLoader subprocess/thread workers, else None."""
    return _WORKER_INFO[0]


def _worker_loop(dataset, collate_fn, task_q, result_q, use_shm,
                 worker_init_fn, worker_id, num_workers=0):
    """Subprocess worker (reference: python/paddle/io/dataloader/worker.py
    _worker_loop): pulls (batch_idx, indices) tasks, pushes collated numpy
    batches back — through the native shared-memory ring queue
    (csrc/shm_queue.cc) when available, else a multiprocessing.Queue.
    Workers never touch jax; device_put happens in the parent."""
    import pickle
    import traceback
    _WORKER_INFO[0] = WorkerInfo(worker_id, num_workers, dataset)
    if worker_init_fn is not None:
        worker_init_fn(worker_id)
    while True:
        task = task_q.get()
        if task is None:
            return
        bidx, indices = task
        try:
            batch = collate_fn([dataset[i] for i in indices])
            msg = (bidx, "ok", batch)
        except Exception:  # noqa: BLE001 — propagate to parent
            msg = (bidx, "exc", traceback.format_exc())
        if use_shm:
            result_q.put(pickle.dumps(msg, protocol=pickle.HIGHEST_PROTOCOL))
        else:
            result_q.put(msg)


class _ProcessPoolIter:
    """Multiprocess prefetch iterator with batch reordering (reference:
    dataloader_iter.py _DataLoaderIterMultiProcess)."""

    def __init__(self, loader, index_iter):
        import multiprocessing as mp
        import os
        self.loader = loader
        self.index_iter = index_iter
        # forkserver by default: forking a process that already holds
        # XLA/JAX runtime threads is a known deadlock source (CPython 3.12
        # warns on it). Unpicklable worker args (e.g. closures in tests)
        # fall back to fork; PADDLE_TPU_DATALOADER_START_METHOD overrides.
        method = getattr(loader, "_mp_start_method", None)
        if method is None:
            method = os.environ.get("PADDLE_TPU_DATALOADER_START_METHOD")
        if method is None:
            import io as _io
            import pickle as _pkl
            probed = (loader.dataset, loader.collate_fn,
                      getattr(loader, "worker_init_fn", None))
            # anything living in __main__ pickles by reference but forces
            # the forkserver child to re-import (re-execute) the training
            # script — only safe under fork
            in_main = any(
                getattr(type(o), "__module__", None) == "__main__" or
                getattr(o, "__module__", None) == "__main__"
                for o in probed if o is not None)
            try:
                # probe into a null sink — no materialized copy of a
                # potentially multi-GB in-memory dataset
                class _Null(_io.RawIOBase):
                    def write(self, b):
                        return len(b)
                _pkl.Pickler(_Null(), _pkl.HIGHEST_PROTOCOL).dump(probed)
                method = "fork" if in_main else "forkserver"
            except Exception:
                method = "fork"
            loader._mp_start_method = method  # probe once per loader
        try:
            ctx = mp.get_context(method)
        except ValueError:
            ctx = mp.get_context("fork")
        self.task_q = ctx.Queue()
        self.result_shm = None
        if loader.use_shared_memory:
            try:
                from ..core.native import SharedMemoryQueue
                name = f"/ptq_dl_{os.getpid()}_{id(self) & 0xFFFFFF:x}"
                self.result_shm = SharedMemoryQueue(name,
                                                    capacity=256 << 20)
            except Exception:
                self.result_shm = None
        self.use_shm = self.result_shm is not None
        self.result_q = self.result_shm if self.use_shm else ctx.Queue()
        self.workers = [
            ctx.Process(target=_worker_loop,
                        args=(loader.dataset, loader.collate_fn,
                              self.task_q, self.result_q, self.use_shm,
                              loader.worker_init_fn, i,
                              loader.num_workers),
                        daemon=True)
            for i in range(loader.num_workers)]
        for w in self.workers:
            w.start()
        self.buffer = {}
        self.next_idx = 0
        self.sent_idx = 0
        self.exhausted = False
        self.prefetch = max(loader.prefetch_factor, 1) * loader.num_workers
        # paddle semantics: timeout=0 means no limit; worker death is
        # detected by liveness polling, not by the timeout
        self.timeout = loader.timeout if loader.timeout else None
        self._fill()

    def _fill(self):
        while not self.exhausted and \
                self.sent_idx - self.next_idx < self.prefetch:
            try:
                indices = next(self.index_iter)
            except StopIteration:
                self.exhausted = True
                return
            self.task_q.put((self.sent_idx, indices))
            self.sent_idx += 1

    def _recv(self):
        """Blocking receive in short slices, checking worker liveness each
        slice (reference: dataloader_iter.py _thread_monitor + worker
        watchdog): a worker killed mid-batch (OOM) raises a clear error
        instead of an opaque queue timeout."""
        import pickle
        import queue as _queue
        deadline = (time.time() + self.timeout) if self.timeout else None
        while True:
            try:
                if self.use_shm:
                    return pickle.loads(self.result_q.get(timeout=5.0))
                return self.result_q.get(timeout=5.0)
            except (TimeoutError, _queue.Empty):
                dead = [w for w in self.workers
                        if not w.is_alive() and w.exitcode not in (0, None)]
                if dead:
                    self._shutdown()
                    raise RuntimeError(
                        f"DataLoader worker (pid {dead[0].pid}) exited "
                        f"unexpectedly with code {dead[0].exitcode} — "
                        f"likely killed (OOM?)") from None
                if deadline and time.time() > deadline:
                    self._shutdown()
                    raise TimeoutError(
                        f"DataLoader batch not produced within "
                        f"{self.timeout}s (workers alive)") from None

    def __next__(self):
        if self.next_idx >= self.sent_idx and self.exhausted:
            self._shutdown()
            raise StopIteration
        while self.next_idx not in self.buffer:
            bidx, status, payload = self._recv()
            if status == "exc":
                self._shutdown()
                raise RuntimeError(
                    f"DataLoader worker failed for batch {bidx}:\n{payload}")
            self.buffer[bidx] = payload
        batch = self.buffer.pop(self.next_idx)
        self.next_idx += 1
        self._fill()
        return batch

    def _shutdown(self):
        for _ in self.workers:
            self.task_q.put(None)
        for w in self.workers:
            w.join(timeout=5)
            if w.is_alive():
                w.terminate()
        if self.result_shm is not None:
            self.result_shm.close()
            self.result_shm = None

    def __del__(self):
        try:
            if any(w.is_alive() for w in self.workers):
                self._shutdown()
        except Exception:  # noqa: BLE001 — interpreter teardown
            pass

    def __iter__(self):
        return self


class _IterableDatasetIter:
    def __init__(self, loader):
        self.loader = loader
        self.it = iter(loader.dataset)

    def __next__(self):
        samples = list(itertools.islice(self.it, self.loader.batch_size))
        if not samples:
            raise StopIteration
        if self.loader.drop_last and \
                len(samples) < self.loader.batch_size:
            raise StopIteration
        return self.loader.collate_fn(samples)

    def __iter__(self):
        return self


class DataLoader:
    def __init__(self, dataset, feed_list=None, places=None,
                 return_list=True, batch_sampler=None, batch_size=1,
                 shuffle=False, drop_last=False, collate_fn=None,
                 num_workers=0, use_buffer_reader=True,
                 prefetch_factor=2, use_shared_memory=True, timeout=0,
                 worker_init_fn=None, persistent_workers=False,
                 prefetch_to_device=True, worker_type="thread"):
        self.dataset = dataset
        self.batch_size = batch_size
        self.shuffle = shuffle
        self.drop_last = drop_last
        self.collate_fn = collate_fn or default_collate_fn
        self.num_workers = num_workers
        self.prefetch_factor = prefetch_factor
        self.use_shared_memory = use_shared_memory
        self.timeout = timeout
        self.worker_init_fn = worker_init_fn
        # "thread" (default: zero-copy into device_put, fine for numpy-light
        # pipelines) or "process" (reference behavior: subprocess workers +
        # shared-memory IPC, for GIL-heavy transforms)
        self.worker_type = worker_type
        self.prefetch_to_device = prefetch_to_device
        self.return_list = return_list
        self._is_iterable = isinstance(dataset, IterableDataset)
        if batch_sampler is not None:
            self.batch_sampler = batch_sampler
            self.batch_size = getattr(batch_sampler, "batch_size", batch_size)
        elif not self._is_iterable and batch_size is not None:
            self.batch_sampler = BatchSampler(dataset, shuffle=shuffle,
                                              batch_size=batch_size,
                                              drop_last=drop_last)
        else:
            self.batch_sampler = None

    def _to_device(self, batch):
        if not self.prefetch_to_device:
            return _to_tensors(batch)
        def put(x):
            if isinstance(x, np.ndarray):
                if x.dtype == np.float64:
                    x = x.astype(np.float32)
                return Tensor(jax.device_put(x))
            if isinstance(x, Tensor):
                return Tensor(jax.device_put(x._value))
            return x
        return jax.tree_util.tree_map(
            put, batch,
            is_leaf=lambda x: isinstance(x, (np.ndarray, Tensor)))

    def __iter__(self):
        if self._is_iterable:
            inner = _IterableDatasetIter(self)
        elif self.worker_type == "process" and self.num_workers > 0:
            inner = _ProcessPoolIter(self, iter(self.batch_sampler))
        else:
            inner = _PrefetchIter(self, iter(self.batch_sampler))
            if inner.pool is not None:
                # thread workers already stage to device in-pool; their
                # futures run ahead of the consumer, so h2d is overlapped
                return inner
        if not self.prefetch_to_device:
            return map(_to_tensors, inner)
        return _DevicePrefetchIter(inner, self._to_device,
                                   depth=max(self.prefetch_factor, 1))

    def __len__(self):
        if self._is_iterable:
            raise TypeError("IterableDataset has no __len__")
        return len(self.batch_sampler)


def _to_tensors(batch):
    def conv(x):
        if isinstance(x, np.ndarray):
            return Tensor(x)
        return x
    return jax.tree_util.tree_map(
        conv, batch, is_leaf=lambda x: isinstance(x, (np.ndarray, Tensor)))
