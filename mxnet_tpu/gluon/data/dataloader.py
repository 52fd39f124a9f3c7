"""DataLoader — mini-batch iterator over a Dataset with prefetch.

Reference: ``python/mxnet/gluon/data/dataloader.py:534`` — multiprocessing
workers passing batches through shared-memory NDArrays rebuilt via
ForkingPickler fd passing (:28-111), `_MultiWorkerIter` (:459).

TPU-native re-design: batches are assembled as host numpy and moved to device
in one `jax.device_put` per batch (a single HBM DMA — the analog of the
reference's pinned-memory copy).  Parallelism uses a thread pool with a
bounded prefetch queue: augmentation is numpy (releases the GIL), and the
double-buffering mirrors the reference's PrefetcherIter
(src/io/iter_prefetcher.h:66).  A process pool (``thread_pool=False``)
serves CPU-bound Python transforms: workers START via spawn by default
(``dataloader.start_method`` knob; fork is opt-in — forking a live
multithreaded XLA runtime risks deadlock), are pinned to the CPU backend,
and hand batches back through POSIX shared memory.
"""
from __future__ import annotations

import multiprocessing
from concurrent.futures import ThreadPoolExecutor, ProcessPoolExecutor

import numpy as _np

from ...ndarray.ndarray import NDArray, array as nd_array
from .sampler import SequentialSampler, RandomSampler, BatchSampler

__all__ = ["DataLoader", "default_batchify_fn"]


def default_batchify_fn(data):
    """Collate a list of samples into a batch (reference: dataloader.py:126)."""
    if isinstance(data[0], NDArray):
        return nd_array(_np.stack([d.asnumpy() for d in data]))
    if isinstance(data[0], tuple):
        data = zip(*data)
        return [default_batchify_fn(i) for i in data]
    data = _np.asarray(data)
    return nd_array(data, dtype=data.dtype if data.dtype != _np.float64 else _np.float32)


_worker_dataset = None


def _worker_initializer(dataset):
    global _worker_dataset
    _worker_dataset = dataset
    try:
        # workers are host-side: pin any jax use to CPU — the chip belongs
        # to one process, the parent, and a child that reached for it
        # would fail or hang
        import jax
        jax.config.update("jax_platforms", "cpu")
    except Exception:  # noqa: BLE001 — fork children inherit a live config
        pass


def _worker_fn(samples, batchify_fn, dataset=None):
    """Function for processing data in worker process."""
    ds = dataset if dataset is not None else _worker_dataset
    return batchify_fn([ds[i] for i in samples])


class _ShmDesc:
    """Descriptor of one array parked in POSIX shared memory."""

    __slots__ = ("name", "shape", "dtype")

    def __init__(self, name, shape, dtype):
        self.name = name
        self.shape = shape
        self.dtype = dtype


def _shm_export(obj):
    """Park every array of a batch in shared memory; return descriptors.

    The reference passes worker batches through shared-memory NDArrays
    rebuilt via ForkingPickler fd passing (dataloader.py:28-111); this is
    the same trick over multiprocessing.shared_memory — the batch BYTES
    never travel through the result pipe, only tiny descriptors do.
    """
    from multiprocessing import shared_memory, resource_tracker

    def conv(x):
        if isinstance(x, NDArray):
            x = x.asnumpy()
        if isinstance(x, _np.ndarray):
            shm = shared_memory.SharedMemory(create=True,
                                             size=max(1, x.nbytes))
            view = _np.ndarray(x.shape, x.dtype, buffer=shm.buf)
            view[...] = x
            name = shm.name
            shm.close()
            try:
                # ownership transfers to the consumer (which unlinks);
                # keep this process's resource tracker from double-freeing
                resource_tracker.unregister("/" + name, "shared_memory")
            except Exception:  # noqa: BLE001 — tracker API is private
                pass
            return _ShmDesc(name, x.shape, str(x.dtype))
        if isinstance(x, (list, tuple)):
            return type(x)(conv(i) for i in x)
        return x

    return conv(obj)


def _shm_import(obj):
    """Rebuild a batch from shared-memory descriptors (consumer side):
    map, one copy into the device/XLA buffer, unlink."""
    from multiprocessing import shared_memory

    def conv(x):
        if isinstance(x, _ShmDesc):
            shm = shared_memory.SharedMemory(name=x.name)
            arr = _np.ndarray(x.shape, _np.dtype(x.dtype), buffer=shm.buf)
            # own the bytes BEFORE unmapping: jax's CPU backend zero-copies
            # aligned numpy buffers, so handing `arr` over directly would
            # leave a live device array aliasing unmapped shm (segfault)
            out = nd_array(arr.copy())
            shm.close()
            shm.unlink()
            return out
        if isinstance(x, (list, tuple)):
            return type(x)(conv(i) for i in x)
        return x

    return conv(obj)


def _numpy_batchify(data):
    """default_batchify_fn's host twin: same collation, numpy output —
    forked workers must never construct device arrays (fork + live XLA
    runtime deadlocks, and the chip belongs to the parent process).  The
    parent wraps the batch once."""
    if isinstance(data[0], NDArray):
        return _np.stack([d.asnumpy() for d in data])
    if isinstance(data[0], tuple):
        return [_numpy_batchify(list(i)) for i in zip(*data)]
    out = _np.asarray(data)
    return out.astype(_np.float32) if out.dtype == _np.float64 else out


def _worker_fn_shm(samples, batchify_fn, dataset=None):
    if batchify_fn is default_batchify_fn:
        batchify_fn = _numpy_batchify
    return _shm_export(_worker_fn(samples, batchify_fn, dataset))


class DataLoader:
    """Loads data from a dataset and returns mini-batches
    (reference: dataloader.py:534)."""

    def __init__(self, dataset, batch_size=None, shuffle=False, sampler=None,
                 last_batch=None, batch_sampler=None, batchify_fn=None,
                 num_workers=0, pin_memory=False, pin_device_id=0,
                 prefetch=None, thread_pool=True, timeout=120,
                 start_method=None):
        self._dataset = dataset
        self._pin_memory = pin_memory
        self._thread_pool = thread_pool
        self._timeout = timeout

        if batch_sampler is None:
            if batch_size is None:
                raise ValueError(
                    "batch_size must be specified unless batch_sampler is "
                    "specified")
            if sampler is None:
                if shuffle:
                    sampler = RandomSampler(len(dataset))
                else:
                    sampler = SequentialSampler(len(dataset))
            elif shuffle:
                raise ValueError(
                    "shuffle must not be specified if sampler is specified")
            batch_sampler = BatchSampler(
                sampler, batch_size, last_batch if last_batch else "keep")
        elif batch_size is not None or shuffle or sampler is not None or \
                last_batch is not None:
            raise ValueError(
                "batch_size, shuffle, sampler and last_batch must not be "
                "specified if batch_sampler is specified.")

        self._batch_sampler = batch_sampler
        self._num_workers = num_workers if num_workers >= 0 else 0
        self._prefetch = max(0, int(prefetch) if prefetch is not None
                             else 2 * self._num_workers)
        if batchify_fn is None:
            self._batchify_fn = default_batchify_fn
        else:
            self._batchify_fn = batchify_fn
        self._pool = None
        if self._num_workers > 0:
            if thread_pool:
                self._pool = ThreadPoolExecutor(max_workers=self._num_workers)
            else:
                if start_method is None:
                    from ... import config as _cfg
                    start_method = _cfg.get("dataloader.start_method")
                # spawn (default): workers start from a clean interpreter —
                # no fork-of-a-multithreaded-XLA-runtime deadlock class.
                # fork stays available as an opt-in for cheap startup.
                ctx = multiprocessing.get_context(start_method)
                # snapshot to host BEFORE handing off: children index
                # numpy, never the jax runtime (see Dataset.host_view)
                host_ds = dataset.host_view() if hasattr(
                    dataset, "host_view") else dataset
                self._pool = ProcessPoolExecutor(
                    max_workers=self._num_workers, mp_context=ctx,
                    initializer=_worker_initializer, initargs=(host_ds,))

    def __iter__(self):
        if self._num_workers == 0:
            def same_process_iter():
                for batch in self._batch_sampler:
                    yield self._batchify_fn([self._dataset[i] for i in batch])
            return same_process_iter()
        return _PrefetchIter(self)

    def __len__(self):
        return len(self._batch_sampler)

    def __del__(self):
        if self._pool is not None:
            self._pool.shutdown(wait=False)


class _PrefetchIter:
    """Bounded-queue async iterator (PrefetcherIter analog,
    src/io/iter_prefetcher.h:66-142)."""

    def __init__(self, loader):
        self._loader = loader
        self._iter = iter(loader._batch_sampler)
        self._pending = []
        thread = loader._thread_pool
        ds = loader._dataset if thread else None
        self._submit_args = (loader._batchify_fn, ds)
        for _ in range(max(1, loader._prefetch)):
            self._push_next()

    def _push_next(self):
        batch = next(self._iter, None)
        if batch is None:
            return
        # process workers hand batches over via shared memory (fd-passing
        # analog, reference dataloader.py:28-111); threads share the heap
        fn = _worker_fn if self._loader._thread_pool else _worker_fn_shm
        fut = self._loader._pool.submit(fn, batch, *self._submit_args)
        self._pending.append(fut)

    def __iter__(self):
        return self

    def __next__(self):
        if not self._pending:
            raise StopIteration
        import concurrent.futures as _cf
        fut = self._pending.pop(0)
        try:
            out = fut.result(timeout=self._loader._timeout)
        except _cf.TimeoutError:
            # keep a still-running future owned WITHOUT submitting a
            # replacement (retry loops must not grow the queue): its shm
            # segments — unregistered from the worker's resource tracker —
            # must still be unlinked by close() once it completes, or they
            # leak in /dev/shm (ADVICE r4)
            self._pending.insert(0, fut)
            raise
        except Exception:
            # worker raised: no shm was exported; refill the pipeline so
            # a skip-bad-batch consumer keeps its prefetch depth
            self._push_next()
            raise
        self._push_next()
        if not self._loader._thread_pool:
            out = _shm_import(out)
        return out

    def close(self):
        """Drain abandoned prefetches: every exported shm segment must be
        unlinked even if the consumer never imported it (early `break`,
        exception) — otherwise /dev/shm leaks until reboot."""
        pending, self._pending = self._pending, []
        if self._loader._thread_pool:
            return
        from multiprocessing import shared_memory

        def unlink(obj):
            if isinstance(obj, _ShmDesc):
                try:
                    shm = shared_memory.SharedMemory(name=obj.name)
                    shm.close()
                    shm.unlink()
                except FileNotFoundError:
                    pass
            elif isinstance(obj, (list, tuple)):
                for o in obj:
                    unlink(o)

        for fut in pending:
            try:
                unlink(fut.result(timeout=self._loader._timeout))
            except Exception:  # noqa: BLE001 — worker died; nothing to free
                pass

    def __del__(self):
        try:
            self.close()
        except Exception:  # noqa: BLE001 — interpreter teardown
            pass
