"""DataLoader.

Reference: `python/mxnet/gluon/data/dataloader.py` — fork-based worker pool
moving NDArrays through CPU shared memory with a custom ForkingPickler
(:48-138).

TPU-native design: workers produce **numpy** batches (no device state in
workers at all — the fork-after-PjRt-init hazard the reference fights with
`pthread_atfork`, `src/initialize.cc:73-87`, disappears), and the parent does
ONE host→HBM upload per batch.  `num_workers` uses a thread pool by default:
the heavy lifting (decode/augment) is numpy releasing the GIL, and threads
share the process so no pickling is needed.  A multiprocessing pool
(`thread_pool=False`) is available for CPU-bound python transforms.
"""
from __future__ import annotations

import multiprocessing
from concurrent.futures import ThreadPoolExecutor
import numpy as onp

from ... import numpy as mxnp
from ...ndarray.ndarray import NDArray
from .dataset import Dataset
from .sampler import BatchSampler, RandomSampler, SequentialSampler

__all__ = ["DataLoader", "default_batchify_fn", "default_mp_batchify_fn"]


def default_batchify_fn(data):
    """Stack samples into a batch (reference dataloader.py:158)."""
    if isinstance(data[0], NDArray):
        return mxnp.stack(data)
    if isinstance(data[0], (tuple, list)):
        data = zip(*data)
        return [default_batchify_fn(list(i)) for i in data]
    arr = onp.asarray(data)
    return arr


default_mp_batchify_fn = default_batchify_fn


def _as_device_batch(batch):
    if isinstance(batch, onp.ndarray):
        return mxnp.array(batch, dtype=batch.dtype)
    if isinstance(batch, (list, tuple)):
        return type(batch)(_as_device_batch(b) for b in batch)
    return batch


def _prefetched_device_batches(host_batches, depth, sharding=None):
    """Ride ``DevicePrefetcher``: a feeder thread issues async H2D
    transfers (per-device shard puts under a ``sharding``) ``depth``
    batches ahead of the consumer, so the wire rides concurrently with
    device compute (reference role: `src/io/iter_prefetcher.h:1`,
    DataLoader ``pin_memory``).

    Host batches are arbitrary pytrees (list of data/label, nested
    tuples); each is flattened to a leaf tuple for the prefetcher and
    reassembled in FIFO order.  The ``with`` block guarantees the feeder
    thread never outlives an exception in the consuming loop — if the
    user's step raises, this generator is closed and the prefetcher's
    ``__exit__`` joins the feeder."""
    import jax
    from collections import deque

    from ...io.prefetch import DevicePrefetcher

    treedefs = deque()

    def leaves():
        for b in host_batches:
            flat, td = jax.tree_util.tree_flatten(
                b, is_leaf=lambda x: isinstance(x, NDArray))
            treedefs.append(td)
            yield tuple(f._data if isinstance(f, NDArray) else f
                        for f in flat)

    with DevicePrefetcher(leaves(), depth=depth, sharding=sharding) as pf:
        for arrs in pf:
            yield jax.tree_util.tree_unflatten(treedefs.popleft(),
                                               list(arrs))


class _Worker:
    """Top-level callable so it pickles for multiprocessing."""

    def __init__(self, dataset, batchify_fn):
        self.dataset = dataset
        self.batchify_fn = batchify_fn

    def __call__(self, indices):
        return self.batchify_fn([self.dataset[i] for i in indices])


class DataLoader:
    def __init__(self, dataset, batch_size=None, shuffle=False, sampler=None,
                 last_batch=None, batch_sampler=None, batchify_fn=None,
                 num_workers=0, pin_memory=False, pin_device_id=0,
                 prefetch=None, thread_pool=True, timeout=120,
                 try_nopython=None, device=None, prefetch_to_device=False,
                 sharding=None):
        self._dataset = dataset
        self._device = device
        # NamedSharding: the prefetcher builds dp global batches via
        # per-device shard puts (zero host-side replication); implies
        # the prefetch-to-device path even if not requested explicitly
        self._sharding = sharding
        if sharding is not None and not prefetch_to_device:
            prefetch_to_device = True
        self._pin_memory = pin_memory  # PjRt stages host transfers itself
        # int = explicit lookahead depth; True (incl. implied by
        # sharding=) defers to MXNET_PREFETCH_DEPTH via
        # DevicePrefetcher(depth=None)
        self._prefetch_to_device = prefetch_to_device

        if batch_sampler is None:
            if batch_size is None:
                raise ValueError(
                    "batch_size must be specified unless batch_sampler is")
            if sampler is None:
                sampler = RandomSampler(len(dataset)) if shuffle else \
                    SequentialSampler(len(dataset))
            elif shuffle:
                raise ValueError(
                    "shuffle must not be specified if sampler is specified")
            batch_sampler = BatchSampler(sampler, batch_size,
                                         last_batch or "keep")
        elif (batch_size is not None or shuffle or sampler is not None or
              last_batch is not None):
            raise ValueError(
                "batch_size, shuffle, sampler and last_batch must not be "
                "specified if batch_sampler is specified.")
        self._batch_sampler = batch_sampler
        self._num_workers = max(0, num_workers)
        self._thread_pool = thread_pool
        self._timeout = timeout
        self._batchify_fn = batchify_fn or default_batchify_fn
        self._prefetch = max(0, prefetch if prefetch is not None
                             else 2 * self._num_workers)
        self._worker = _Worker(dataset, self._batchify_fn)
        self._pool = None

    def _get_pool(self):
        if self._pool is None and self._num_workers > 0:
            if self._thread_pool:
                self._pool = ThreadPoolExecutor(self._num_workers)
            else:
                ctx = multiprocessing.get_context("spawn")
                self._pool = ctx.Pool(self._num_workers)
        return self._pool

    def __iter__(self):
        from ... import telemetry as _telemetry

        if self._prefetch_to_device:
            depth = (None if self._prefetch_to_device is True
                     else int(self._prefetch_to_device))
            inner = _prefetched_device_batches(self._host_batches(),
                                               depth, self._sharding)
        else:
            inner = (_as_device_batch(b) for b in self._host_batches())
        # time each batch production as the "data-wait" step phase: with
        # enough workers/prefetch it collapses toward zero; a fat span
        # here means the input pipeline, not the chip, bounds step time
        while True:
            phase = _telemetry.step_phase("data-wait")
            phase.__enter__()
            try:
                batch = next(inner)
            except StopIteration:
                phase.cancel()   # exhausted probe: not a batch wait, discard
                return
            except BaseException:
                phase.cancel()   # a failing worker: leave no span open
                raise
            phase.__exit__(None, None, None)
            yield batch

    def _host_batches(self):
        if self._num_workers == 0:
            for indices in self._batch_sampler:
                yield self._worker(indices)
            return

        pool = self._get_pool()
        pending = []
        it = iter(self._batch_sampler)
        max_inflight = self._num_workers + self._prefetch

        def submit(indices):
            if self._thread_pool:
                return pool.submit(self._worker, indices)
            return pool.apply_async(self._worker, (indices,))

        try:
            for indices in it:
                pending.append(submit(indices))
                if len(pending) >= max_inflight:
                    fut = pending.pop(0)
                    yield (fut.result(self._timeout) if self._thread_pool
                           else fut.get(self._timeout))
            while pending:
                fut = pending.pop(0)
                yield (fut.result(self._timeout) if self._thread_pool
                       else fut.get(self._timeout))
        finally:
            for fut in pending:
                if self._thread_pool:
                    fut.cancel()

    def __len__(self):
        return len(self._batch_sampler)

    def __del__(self):
        if self._pool is not None:
            if self._thread_pool:
                self._pool.shutdown(wait=False)
            else:
                self._pool.terminate()
