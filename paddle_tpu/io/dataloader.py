"""DataLoader (ref: python/paddle/io/reader.py:262 DataLoader;
io/dataloader/dataloader_iter.py multiprocess workers + shared-memory
transport; C++ core imperative/data_loader.cc).

TPU-first host pipeline, two worker transports:

* THREADS (default): numpy collate releases the GIL for big copies;
  worker threads pull index batches into a bounded prefetch queue with
  async device_put double buffering. Right when item loading is IO- or
  copy-bound.
* PROCESSES (``use_shared_memory=True``): fork-per-worker with
  pickle-free numpy transport over ``multiprocessing.shared_memory``
  (the reference's design: dataloader_iter.py:368 forked workers,
  worker.py:293 loop, shm tensor transport). Right when the per-item
  transform is Python-compute-bound (GIL-bound under threads). Workers
  run dataset code only — never JAX — so forking under an initialized
  JAX parent is safe.

num_workers=0 degrades to synchronous iteration.

Every ``next`` of the three iterators is a ``loader.next`` span
(``batch=``) with three children: ``loader.wait`` (blocked until the
batch is there; ``ready=`` the batches workers had already delivered
when ``next`` was called), ``loader.unpack`` (shared memory to arrays,
or collate) and ``loader.h2d`` (arrays to device Tensors). Worker threads
collate and place ahead of the consumer, so under THREADS the last two
are spans of the worker's own thread. Each iterator's construction (the
workers forked or the threads started, before the first ``next``) is one
``loader.start`` span (``workers=``).
"""
from __future__ import annotations

import itertools
import multiprocessing as _mp
import queue
import threading
import time
import traceback

import numpy as np

from ..core.tensor import Tensor
from ..observability.spans import span
from ..resilience import faults
from .dataset import Dataset, IterableDataset
from .sampler import BatchSampler

__all__ = ["DataLoader", "default_collate_fn"]


def default_collate_fn(batch):
    """Stack samples into batched arrays (ref io/dataloader/collate.py)."""
    sample = batch[0]
    if isinstance(sample, Tensor):
        return Tensor(np.stack([np.asarray(s._data) for s in batch]))
    if isinstance(sample, np.ndarray):
        return np.stack(batch)
    if isinstance(sample, (int, float, np.integer, np.floating)):
        return np.asarray(batch)
    if isinstance(sample, (str, bytes)):
        return list(batch)
    if isinstance(sample, dict):
        return {
            k: default_collate_fn([s[k] for s in batch]) for k in sample
        }
    if isinstance(sample, (list, tuple)):
        transposed = list(zip(*batch))
        return type(sample)(
            default_collate_fn(list(items)) for items in transposed
        )
    raise TypeError(f"cannot collate batch of {type(sample)}")


def _to_device(obj, place=None):
    if isinstance(obj, np.ndarray):
        return Tensor(obj)
    if isinstance(obj, Tensor):
        return obj
    if isinstance(obj, dict):
        return {k: _to_device(v, place) for k, v in obj.items()}
    if isinstance(obj, (list, tuple)):
        return type(obj)(_to_device(v, place) for v in obj)
    return obj


def _deliver(collate_fn, items):
    """Collate one batch and place it, each under its span."""
    with span("loader.unpack"):
        batch = collate_fn(items)
    with span("loader.h2d"):
        return _to_device(batch)


class _Prefetcher:
    """Bounded background producer over a batch iterator.

    Batches are tagged with their production index and re-ordered on the
    consumer side, preserving the reference DataLoader's in-order contract
    (dataloader_iter.py _rcvd_idx reordering) regardless of per-batch
    collate latency across threads.
    """

    _DONE = object()

    def __init__(self, gen_fn, depth, num_threads):
        self._q = queue.Queue(maxsize=depth)
        self._gen_fn = gen_fn
        self._threads = []
        self._lock = threading.Lock()
        self._iter = None
        self._stop = threading.Event()
        self._n = num_threads
        self._next_idx = 0

    def start(self):
        self._iter = self._gen_fn()
        for _ in range(self._n):
            t = threading.Thread(target=self._work, daemon=True)
            t.start()
            self._threads.append(t)

    def _next_job(self):
        with self._lock:
            try:
                job = next(self._iter)
            except StopIteration:
                return None, self._DONE
            except Exception as e:  # producer failure must reach consumer
                return None, e
            idx = self._next_idx
            self._next_idx += 1
            return idx, job

    def _put(self, item):
        """Queue put that stays responsive to shutdown (never blocks
        forever on a full queue after the consumer abandoned us)."""
        while not self._stop.is_set():
            try:
                self._q.put(item, timeout=0.1)
                return True
            except queue.Full:
                continue
        return False

    def _work(self):
        while not self._stop.is_set():
            idx, job = self._next_job()
            if job is self._DONE:
                self._put((None, self._DONE))
                return
            if isinstance(job, Exception):
                self._put((None, job))
                return
            try:
                self._put((idx, job()))
            except Exception as e:
                self._put((None, e))
                return

    def __iter__(self):
        self._done, pending = 0, {}
        for want in itertools.count():
            with span("loader.next", batch=want), span(
                "loader.wait", ready=len(pending) + self._q.qsize()
            ):
                # after the last worker is done nothing more comes: what
                # is pending then is all there is
                while want not in pending and self._done < self._n:
                    self._receive(pending)
            if want not in pending:
                return
            yield pending.pop(want)

    def _receive(self, pending):
        idx, payload = self._q.get()
        if payload is self._DONE:
            self._done += 1
        elif isinstance(payload, Exception):
            self.shutdown()
            raise payload
        else:
            pending[idx] = payload

    def shutdown(self):
        self._stop.set()
        # unblock any producer stuck on a full queue
        try:
            while True:
                self._q.get_nowait()
        except queue.Empty:
            pass


# -- process workers + shared-memory transport ------------------------------


def _shm_pack(tree):
    """numpy pytree -> (meta, shm handles): arrays are copied into
    SharedMemory blocks and described by (name, shape, dtype) — the
    pickle-free transport of the reference's shm tensors
    (io/dataloader/worker.py:418 _convert_to_tensor_list analogue)."""
    from multiprocessing import resource_tracker, shared_memory

    shms = []

    def pack(v):
        if isinstance(v, Tensor):
            v = np.asarray(v._data)
        if isinstance(v, np.ndarray):
            shm = shared_memory.SharedMemory(
                create=True, size=max(1, v.nbytes)
            )
            # ownership passes to the consumer, which unlinks. Left
            # registered, the block is unlinked by this worker's resource
            # tracker when the worker exits — before a slow consumer (a
            # training step that is still compiling) has opened it
            resource_tracker.unregister(shm._name, "shared_memory")
            dst = np.ndarray(v.shape, v.dtype, buffer=shm.buf)
            dst[...] = v
            shms.append(shm)
            return ("__shm__", shm.name, v.shape, str(v.dtype))
        if isinstance(v, dict):
            return {k: pack(x) for k, x in v.items()}
        if isinstance(v, (list, tuple)):
            return type(v)(pack(x) for x in v)
        return v

    return pack(tree), shms


def _shm_unpack(meta):
    """Rebuild the pytree from shm descriptors; copies out and unlinks."""
    from multiprocessing import shared_memory

    def unpack(v):
        if isinstance(v, tuple) and len(v) == 4 and v[0] == "__shm__":
            _, name, shape, dtype = v
            shm = shared_memory.SharedMemory(name=name)
            try:
                arr = np.array(
                    np.ndarray(shape, dtype, buffer=shm.buf), copy=True
                )
            finally:
                shm.close()
                shm.unlink()
            return arr
        if isinstance(v, dict):
            return {k: unpack(x) for k, x in v.items()}
        if isinstance(v, list):
            return [unpack(x) for x in v]
        if isinstance(v, tuple):
            return tuple(unpack(x) for x in v)
        return v

    return unpack(meta)


def _mp_worker_loop(dataset, collate_fn, index_q, result_q, worker_id,
                    worker_init_fn):
    """Worker process body (ref io/dataloader/worker.py:293 _worker_loop):
    pull index batches, load + collate to numpy, ship via shared memory.
    Runs dataset code only — no JAX."""
    try:
        if worker_init_fn is not None:
            worker_init_fn(worker_id)
        while True:
            job = index_q.get()
            if job is None:
                result_q.put((None, "__done__", None))
                return
            bidx, indices = job
            try:
                # fault site inherited through fork: schedules active in
                # the parent reach the worker (docs/resilience.md)
                faults.fire(
                    "dataloader.worker", worker_id=worker_id, batch=bidx,
                )
                batch = collate_fn([dataset[i] for i in indices])
                meta, shms = _shm_pack(batch)
                result_q.put((bidx, "__ok__", meta))
                for s in shms:
                    s.close()  # consumer unlinks
            except Exception:
                result_q.put((None, "__err__", traceback.format_exc()))
                return
    except KeyboardInterrupt:
        pass


class _MPLoaderIter:
    """In-order multiprocess iteration (ref dataloader_iter.py:368
    _DataLoaderIterMultiProcess: fork workers, per-batch reordering by
    _rcvd_idx, error propagation with worker traceback)."""

    def __init__(self, loader):
        ctx = _mp.get_context("fork")
        self._n = loader.num_workers
        # shutdown grace before terminate->kill escalation; a user
        # DataLoader(timeout=...) bounds it (0 keeps the 5 s default)
        self._grace = float(getattr(loader, "timeout", 0) or 5.0)
        self._index_q = ctx.Queue()
        self._result_q = ctx.Queue()
        self._batches = list(enumerate(loader._index_batches()))
        self._total = len(self._batches)
        # bounded prefetch (the reference's outstanding-batch window,
        # dataloader_iter.py _outstanding_capacity): only this many index
        # batches are in flight, so /dev/shm holds O(depth) batches, not
        # the whole epoch
        self._depth = max(
            self._n, loader.prefetch_factor * self._n
        )
        self._fed = 0
        self._sent_stop = False
        self._procs = [
            ctx.Process(
                target=_mp_worker_loop,
                args=(loader.dataset, loader.collate_fn, self._index_q,
                      self._result_q, w, loader.worker_init_fn),
                daemon=True,
            )
            for w in range(self._n)
        ]
        for p in self._procs:
            p.start()

    def _feed(self, served):
        while (self._fed < self._total
               and self._fed - served < self._depth):
            self._index_q.put(self._batches[self._fed])
            self._fed += 1
        if self._fed >= self._total and not self._sent_stop:
            for _ in range(self._n):
                self._index_q.put(None)
            self._sent_stop = True

    def __iter__(self):
        self._done, pending = 0, {}
        try:
            self._feed(0)
            for want in range(self._total):
                with span("loader.next", batch=want):
                    with span("loader.wait") as wait:
                        # what the workers delivered while the step ran
                        while self._receive(pending, block=False):
                            pass
                        wait.attrs["ready"] = len(pending)
                        while want not in pending:
                            if self._done == self._n:
                                # a worker's batches come before its
                                # leave: this one is lost
                                raise RuntimeError(
                                    "DataLoader workers exited before "
                                    "producing all batches"
                                )
                            self._receive(pending, block=True)
                    with span("loader.unpack"):
                        batch = _shm_unpack(pending.pop(want))
                    with span("loader.h2d"):
                        batch = _to_device(batch)
                yield batch
                self._feed(want + 1)
        finally:
            self.shutdown()

    def _receive(self, pending, block):
        """One message of the result queue into ``pending``; False when
        none came (right away if not ``block``, else within 5 s)."""
        try:
            bidx, tag, payload = (
                self._result_q.get(timeout=5.0) if block
                else self._result_q.get_nowait()
            )
        except queue.Empty:
            # liveness: a worker killed by the OS (OOM/segfault) posts
            # nothing; if nobody is left and the queue stayed empty
            # through the timeout, nothing will come
            if block and not any(p.is_alive() for p in self._procs):
                raise RuntimeError(
                    "DataLoader workers died before producing "
                    "all batches (killed by the OS?)"
                )
            return False
        if tag == "__done__":
            self._done += 1
        elif tag == "__err__":
            raise RuntimeError(f"DataLoader worker failed:\n{payload}")
        else:
            pending[bidx] = payload
        return True

    def shutdown(self, grace=None):
        """Stop workers with escalation: SIGTERM, wait out the grace
        period, then SIGKILL stragglers — a worker hung in native code
        (or ignoring SIGTERM) must not leak past close. Raises if any
        child survives SIGKILL (only possible for unkillable D-state
        processes, which the caller must know about)."""
        grace = self._grace if grace is None else float(grace)
        for p in self._procs:
            if p.is_alive():
                p.terminate()
        deadline = time.monotonic() + grace
        for p in self._procs:
            p.join(timeout=max(0.0, deadline - time.monotonic()))
        stragglers = [p for p in self._procs if p.is_alive()]
        if stragglers:
            # escalation to SIGKILL is a fleet-health event: record it
            # (a worker routinely ignoring SIGTERM is wedged in native
            # code or masked signals — worth a postmortem entry)
            from ..observability import flight, metrics

            metrics.counter(
                "paddle_tpu_dataloader_worker_kills_total",
                "process workers that ignored SIGTERM and were "
                "SIGKILLed at shutdown",
            ).inc(len(stragglers))
            flight.record(
                "dataloader", "worker-kill",
                pids=[p.pid for p in stragglers],
            )
        for p in stragglers:
            p.kill()
        for p in stragglers:
            p.join(timeout=5)
        # unlink any unconsumed shm blocks
        try:
            while True:
                _, tag, payload = self._result_q.get_nowait()
                if tag == "__ok__":
                    _shm_unpack(payload)
        except queue.Empty:
            pass
        leaked = [p.pid for p in self._procs if p.is_alive()]
        if leaked:
            raise RuntimeError(
                f"DataLoader workers survived SIGKILL: pids {leaked}"
            )


class DataLoader:
    """ref: io/reader.py:262. Supported: map + iterable datasets, custom
    sampler/batch_sampler/collate_fn, shuffle, drop_last, num_workers
    (threads by default, forked processes with shared-memory transport
    when use_shared_memory=True), prefetch_factor, worker_init_fn."""

    def __init__(self, dataset, feed_list=None, places=None,
                 return_list=True, batch_sampler=None, batch_size=1,
                 shuffle=False, drop_last=False, collate_fn=None,
                 num_workers=0, use_buffer_reader=True,
                 prefetch_factor=2, use_shared_memory=False, timeout=0,
                 worker_init_fn=None, persistent_workers=False):
        self.dataset = dataset
        self.return_list = return_list
        self.collate_fn = collate_fn or default_collate_fn
        self.num_workers = max(0, int(num_workers))
        self.timeout = float(timeout or 0)
        self.prefetch_factor = max(1, int(prefetch_factor))
        self.use_shared_memory = bool(use_shared_memory)
        self.worker_init_fn = worker_init_fn
        self._iterable_mode = isinstance(dataset, IterableDataset)
        if self.use_shared_memory and self._iterable_mode:
            raise ValueError(
                "use_shared_memory (process workers) requires a map-style "
                "dataset; IterableDataset pulls are sequential"
            )

        if self._iterable_mode:
            if batch_sampler is not None or shuffle:
                raise ValueError(
                    "IterableDataset does not support sampler/shuffle"
                )
            self.batch_sampler = None
            self.batch_size = batch_size
            self.drop_last = drop_last
        elif batch_sampler is not None:
            self.batch_sampler = batch_sampler
            self.batch_size = batch_sampler.batch_size
        else:
            if batch_size is None:
                raise ValueError("batch_size or batch_sampler required")
            self.batch_size = batch_size
            self.batch_sampler = BatchSampler(
                dataset, shuffle=shuffle, batch_size=batch_size,
                drop_last=drop_last,
            )
        # mid-epoch resume cursor (training resume contract,
        # docs/resilience.md): batches DELIVERED to the consumer this
        # epoch — tracked at yield time, so prefetch depth never leaks
        # into the cursor
        self._served_in_epoch = 0
        self._resume_skip = 0

    # -- training resume contract ------------------------------------------
    def state_dict(self):
        """Mid-epoch cursor: batches delivered this epoch plus the
        sampler's shuffle state (epoch-start RNG / epoch number), enough
        to regenerate the same index stream and skip forward. Assumes a
        single active iterator (the training loop's)."""
        sd = {"batches_served": self._served_in_epoch}
        if self.batch_sampler is not None and hasattr(
            self.batch_sampler, "state_dict"
        ):
            sd["sampler"] = self.batch_sampler.state_dict()
        return sd

    def load_state_dict(self, state):
        """Arm the next ``__iter__`` to skip the already-consumed
        batches. Map-style datasets skip at the INDEX level (no sample
        is loaded); iterable datasets must consume-and-drop, since the
        stream has no random access."""
        self._resume_skip = int(state.get("batches_served", 0))
        self._served_in_epoch = self._resume_skip
        if state.get("sampler") is not None and hasattr(
            self.batch_sampler, "load_state_dict"
        ):
            self.batch_sampler.load_state_dict(state["sampler"])

    def _index_batches(self):
        """Index-batch stream with the resume skip applied (consumed
        once; later epochs start at batch 0)."""
        skip, self._resume_skip = self._resume_skip, 0
        it = iter(self.batch_sampler)
        for _ in range(skip):
            if next(it, None) is None:
                break
        yield from it

    def __len__(self):
        if self._iterable_mode:
            raise TypeError("IterableDataset DataLoader has no len()")
        return len(self.batch_sampler)

    def _batches_map(self):
        for indices in self._index_batches():
            yield [self.dataset[i] for i in indices]

    def _batches_iterable(self):
        skip, self._resume_skip = self._resume_skip, 0
        batch = []
        for sample in self.dataset:
            batch.append(sample)
            if len(batch) == self.batch_size:
                if skip > 0:
                    skip -= 1  # consume-and-drop: streams can't seek
                else:
                    yield batch
                batch = []
        if batch and not getattr(self, "drop_last", False):
            if skip <= 0:
                yield batch

    def _produce(self):
        gen = (
            self._batches_iterable()
            if self._iterable_mode
            else self._batches_map()
        )
        for batch in gen:
            yield batch

    def __iter__(self):
        # always-on pipeline telemetry: one counter bump per delivered
        # batch (host-side, nanoseconds next to collate + H2D)
        from ..observability import metrics as _obs_metrics

        batches = _obs_metrics.counter(
            "paddle_tpu_dataloader_batches_total",
            "batches delivered to the training loop", ("transport",),
        )
        transport = (
            "sync" if self.num_workers == 0
            else "process" if (self.use_shared_memory
                              and not self._iterable_mode)
            else "thread"
        )
        # the armed skip (if any) counts as already-served; delivered
        # batches advance the cursor from there
        self._served_in_epoch = self._resume_skip
        for batch in self._iter_impl():
            batches.inc(transport=transport)
            self._served_in_epoch += 1
            yield batch
        # the epoch COMPLETED (we reached exhaustion, not an abandoned
        # iterator): the cursor now refers to the next epoch. Without
        # this, a checkpoint taken in the rollover window — after the
        # consumer saw StopIteration, before the next epoch's first
        # batch — records the old epoch's full count against the new
        # epoch and a resume would skip that epoch entirely. The
        # sampler's epoch-start RNG snapshot is stale in the same
        # window — roll it forward too, or the resume replays the
        # finished epoch's permutation as the next epoch's.
        self._served_in_epoch = 0
        roll = getattr(self.batch_sampler, "_roll_epoch", None)
        if roll is not None:
            roll()

    def _iter_impl(self):
        if self.num_workers == 0:
            with span("loader.start", workers=0):
                produced = self._produce()
            for want in itertools.count():
                with span("loader.next", batch=want):
                    with span("loader.wait", ready=0):
                        items = next(produced, None)
                    if items is None:
                        return
                    batch = _deliver(self.collate_fn, items)
                yield batch

        if self.use_shared_memory and not self._iterable_mode:
            # forked out of a process that may hold a model already
            with span("loader.start", workers=self.num_workers):
                forked = _MPLoaderIter(self)
            yield from forked
            return

        def job_stream():
            if self._iterable_mode:
                # iterable datasets must be pulled sequentially; workers
                # parallelize collate + H2D only
                for batch in self._batches_iterable():
                    yield (lambda b=batch: _deliver(self.collate_fn, b))
            else:
                # map-style: item loading happens INSIDE the job so worker
                # threads overlap dataset reads (the reference's
                # multiprocess worker loop, worker.py:293)
                for indices in self._index_batches():
                    yield (
                        lambda idx=indices: _deliver(
                            self.collate_fn,
                            [self.dataset[i] for i in idx],
                        )
                    )

        pf = _Prefetcher(
            job_stream,
            depth=self.prefetch_factor * self.num_workers,
            num_threads=self.num_workers,
        )
        with span("loader.start", workers=self.num_workers):
            pf.start()
        try:
            yield from pf
        finally:
            pf.shutdown()
