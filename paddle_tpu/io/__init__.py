"""paddle.io — Dataset / DataLoader.

Reference parity: python/paddle/fluid/reader.py DataLoader:149 +
dataloader/dataloader_iter.py (multiprocess worker pool, shared-mem queues)
and operators/reader/buffered_reader.cc (double-buffer device prefetch).

TPU-native: host-side loading uses a thread/process pool producing numpy
batches; device prefetch keeps `prefetch_depth` batches in flight via
non-blocking jax.device_put (the buffered_reader analog) so the TPU never
waits on host IO.
"""
from __future__ import annotations

import itertools
import math
import queue
import threading
from typing import Iterable

import numpy as np

from ..framework import random as _random
from ..tensor import Tensor


class Dataset:
    def __getitem__(self, idx):
        raise NotImplementedError

    def __len__(self):
        raise NotImplementedError


class IterableDataset(Dataset):
    def __iter__(self):
        raise NotImplementedError

    def __getitem__(self, idx):
        raise RuntimeError("IterableDataset has no __getitem__")

    def __len__(self):
        raise RuntimeError("IterableDataset has no __len__")


class TensorDataset(Dataset):
    def __init__(self, tensors):
        self.tensors = tensors

    def __getitem__(self, idx):
        return tuple(t[idx] for t in self.tensors)

    def __len__(self):
        return self.tensors[0].shape[0]

    # ship to loader workers as plain numpy: unpickling device arrays in a
    # forkserver/spawn child would import jax there (slow, and the site
    # TPU plugin must never run in a worker); samples re-wrap as Tensors
    # in the parent's collate
    def __getstate__(self):
        return {"tensors": [np.asarray(t.numpy() if isinstance(t, Tensor)
                                       else t) for t in self.tensors]}

    def __setstate__(self, state):
        self.tensors = state["tensors"]


class ComposeDataset(Dataset):
    def __init__(self, datasets):
        self.datasets = list(datasets)

    def __len__(self):
        return min(len(d) for d in self.datasets)

    def __getitem__(self, idx):
        out = []
        for d in self.datasets:
            item = d[idx]
            out.extend(item if isinstance(item, (tuple, list)) else [item])
        return tuple(out)


class ChainDataset(IterableDataset):
    def __init__(self, datasets):
        self.datasets = list(datasets)

    def __iter__(self):
        return itertools.chain(*self.datasets)


class Subset(Dataset):
    def __init__(self, dataset, indices):
        self.dataset = dataset
        self.indices = list(indices)

    def __getitem__(self, idx):
        return self.dataset[self.indices[idx]]

    def __len__(self):
        return len(self.indices)


def _host_rng():
    """numpy RandomState chained off the framework RNG: paddle.seed()
    reproduces host-side sampling/shuffling, and test order can't bleed
    through the GLOBAL np.random state (the reference seeds its sampler
    RNGs from op/program seeds the same way).  Each call advances the
    chain, so successive epochs draw different permutations."""
    from ..framework.random import np_random_state

    return np_random_state()


def random_split(dataset, lengths, generator=None):
    total = len(dataset)
    if sum(lengths) != total:
        raise ValueError("sum of lengths must equal dataset size")
    perm = _host_rng().permutation(total)
    out, start = [], 0
    for ln in lengths:
        out.append(Subset(dataset, perm[start:start + ln].tolist()))
        start += ln
    return out


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
    def __init__(self, data_source, replacement=False, num_samples=None,
                 generator=None):
        super().__init__(data_source)
        self.replacement = replacement
        self._num_samples = num_samples

    @property
    def num_samples(self):
        return self._num_samples or len(self.data_source)

    def __iter__(self):
        n = len(self.data_source)
        rng = _host_rng()
        if self.replacement:
            return iter(rng.randint(0, n, self.num_samples).tolist())
        return iter(rng.permutation(n)[:self.num_samples].tolist())

    def __len__(self):
        return self.num_samples


class WeightedRandomSampler(Sampler):
    def __init__(self, weights, num_samples, replacement=True):
        self.weights = np.asarray(weights, np.float64)
        self.num_samples = num_samples
        self.replacement = replacement

    def __iter__(self):
        p = self.weights / self.weights.sum()
        return iter(_host_rng().choice(len(self.weights), self.num_samples,
                                       replace=self.replacement,
                                       p=p).tolist())

    def __len__(self):
        return self.num_samples


class BatchSampler(Sampler):
    def __init__(self, dataset=None, sampler=None, shuffle=False, batch_size=1,
                 drop_last=False):
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
    """Reference: python/paddle/io DistributedBatchSampler — shards the
    dataset across data-parallel ranks."""

    def __init__(self, dataset, batch_size, num_replicas=None, rank=None,
                 shuffle=False, drop_last=False):
        from ..distributed import get_rank, get_world_size

        self.dataset = dataset
        self.batch_size = batch_size
        self.nranks = num_replicas if num_replicas is not None else get_world_size()
        self.local_rank = rank if rank is not None else get_rank()
        self.shuffle = shuffle
        self.drop_last = drop_last
        self.epoch = 0
        self.num_samples = int(math.ceil(len(dataset) / self.nranks))
        self.total_size = self.num_samples * self.nranks

    def set_epoch(self, epoch):
        self.epoch = epoch

    def __iter__(self):
        n = len(self.dataset)
        indices = np.arange(n)
        if self.shuffle:
            rng = np.random.RandomState(self.epoch)
            rng.shuffle(indices)
        indices = np.concatenate(
            [indices, indices[: self.total_size - n]])
        local = indices[self.local_rank::self.nranks]
        batch = []
        for idx in local.tolist():
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


def _numpy_collate(batch):
    """Worker-side collate: numpy-first (device transfer happens in the
    parent; Tensor samples are unwrapped to numpy so only plain arrays
    cross the process queue)."""
    sample = batch[0]
    if isinstance(sample, (tuple, list)):
        return [_numpy_collate([b[i] for b in batch])
                for i in range(len(sample))]
    if isinstance(sample, dict):
        return {k: _numpy_collate([b[k] for b in batch]) for k in sample}
    if isinstance(sample, Tensor):
        return np.stack([np.asarray(b.numpy()) for b in batch])
    if isinstance(sample, np.ndarray):
        return np.stack(batch)
    if isinstance(sample, (int, np.integer)):
        return np.asarray(batch, np.int64)
    if isinstance(sample, (float, np.floating)):
        return np.asarray(batch, np.float32)
    return batch


def _tensor_wrap(tree):
    """Parent-side: numpy leaves -> Tensor (device transfer boundary)."""
    if isinstance(tree, list):
        return [_tensor_wrap(t) for t in tree]
    if isinstance(tree, dict):
        return {k: _tensor_wrap(v) for k, v in tree.items()}
    if isinstance(tree, np.ndarray):
        return Tensor(tree)
    return tree


class _WorkerError:
    def __init__(self, worker_id, tb):
        self.worker_id = worker_id
        self.traceback = tb


def _worker_loop(dataset, collate_fn, index_queue, result_queue, worker_id,
                 worker_init_fn):
    """Forked worker: fetch + collate in numpy, ship via queue (reference
    dataloader_iter.py _worker_loop)."""
    import traceback
    if worker_init_fn is not None:
        worker_init_fn(worker_id)
    use_numpy = collate_fn is default_collate_fn
    while True:
        job = index_queue.get()
        if job is None:
            break
        bid, indices = job
        try:
            samples = [dataset[i] for i in indices]
            batch = (_numpy_collate(samples) if use_numpy
                     else collate_fn(samples))
            result_queue.put((bid, batch))
        except Exception:
            result_queue.put((bid, _WorkerError(worker_id,
                                                traceback.format_exc())))


def pad_ragged(seqs, buckets=None, pad_value=0, dtype=np.int64,
               truncate="tail"):
    """Ragged per-sample sequences → one dense ``[B, L]`` array.

    ``L`` is the smallest entry of ``buckets`` that fits the batch's
    longest sequence (so a handful of XLA shapes serve every batch);
    without buckets, the exact max length.  Sequences beyond the last
    bucket are truncated — ``truncate="tail"`` keeps the last elements
    (the recency convention for click logs), ``"head"`` the first.
    Returns ``(dense, lengths)`` with post-truncation int32 lengths.
    This is numpy-only on purpose: it runs inside collate_fn on the
    DataLoader's prefetch thread.
    """
    cap = None
    if buckets:
        buckets = sorted(int(b) for b in buckets)
        cap = buckets[-1]
    lens = [len(s) if cap is None else min(len(s), cap) for s in seqs]
    width = max(lens) if lens else 1
    if buckets:
        for b in buckets:
            if width <= b:
                width = b
                break
    out = np.full((len(seqs), max(width, 1)), pad_value, dtype)
    for i, s in enumerate(seqs):
        arr = np.asarray(s, dtype)
        if lens[i] < len(arr):
            arr = arr[-lens[i]:] if truncate == "tail" else arr[:lens[i]]
        out[i, :lens[i]] = arr
    return out, np.asarray(lens, np.int32)


def default_collate_fn(batch):
    sample = batch[0]
    if isinstance(sample, (tuple, list)):
        return [default_collate_fn([b[i] for b in batch])
                for i in range(len(sample))]
    if isinstance(sample, dict):
        return {k: default_collate_fn([b[k] for b in batch]) for k in sample}
    if isinstance(sample, Tensor):
        return Tensor(np.stack([np.asarray(b.numpy()) for b in batch]))
    if isinstance(sample, np.ndarray):
        return Tensor(np.stack(batch))
    if isinstance(sample, (int, np.integer)):
        return Tensor(np.asarray(batch, np.int64))
    if isinstance(sample, (float, np.floating)):
        return Tensor(np.asarray(batch, np.float32))
    return batch


class DataLoader:
    def __init__(self, dataset, feed_list=None, places=None,
                 return_list=True, batch_sampler=None, batch_size=1,
                 shuffle=False, drop_last=False, collate_fn=None,
                 num_workers=0, use_buffer_reader=True, prefetch_factor=2,
                 use_shared_memory=False, timeout=0, worker_init_fn=None,
                 persistent_workers=False):
        self.dataset = dataset
        self.return_list = return_list
        self.collate_fn = collate_fn or default_collate_fn
        self.num_workers = num_workers
        self.prefetch_factor = max(prefetch_factor, 1)
        self.use_buffer_reader = use_buffer_reader
        self.timeout = timeout
        self.worker_init_fn = worker_init_fn
        # optional per-batch placement hook (framework.transfer.
        # shard_batch partial): runs on the PREFETCH THREAD, so the
        # async device_put of the next global batch onto its target
        # sharding overlaps device compute of the current one.  Set by
        # Model.fit(mesh=...) for the duration of the fit.
        self.placement = None
        self._iterable_mode = isinstance(dataset, IterableDataset)
        if batch_sampler is not None:
            self.batch_sampler = batch_sampler
            self.batch_size = batch_sampler.batch_size
        elif not self._iterable_mode:
            self.batch_sampler = BatchSampler(dataset, shuffle=shuffle,
                                              batch_size=batch_size,
                                              drop_last=drop_last)
            self.batch_size = batch_size
        else:
            self.batch_sampler = None
            self.batch_size = batch_size
            self.drop_last = drop_last

    def __len__(self):
        if self._iterable_mode:
            raise TypeError("IterableDataset has no fixed length")
        return len(self.batch_sampler)

    @staticmethod
    def _placed(gen, place):
        """Apply the placement hook inside the producing generator so it
        executes on whichever thread drives `gen` (the prefetch thread
        when use_buffer_reader is on)."""
        try:
            for item in gen:
                yield place(item)
        finally:
            gen.close()

    def _epoch_batches(self):
        """Materialize this epoch's batch indices ON THE CALLING THREAD.

        The sampler draws its shuffle permutation from the framework RNG
        chain, which is THREAD-LOCAL (framework/random.py): iterating
        the sampler lazily inside the buffered-reader prefetch thread
        would pull the permutation from that thread's own never-seeded
        chain, so `paddle.seed()` silently stopped controlling shuffle
        order (and buffered vs unbuffered loaders shuffled differently).
        Drawing here — the consumer's thread, before the prefetch thread
        exists — restores the seeded, thread-agnostic contract.

        Only the framework's own BatchSampler (incl. subclasses) is
        materialized this way: it is the sampler that draws from the
        framework chain, and it is len-bounded by construction.  A
        user-supplied batch_sampler may be generator-backed or infinite,
        so it keeps its lazy streaming contract (see __iter__)."""
        return [list(b) for b in self.batch_sampler]

    def _produce(self, idx_batches=None):
        if self._iterable_mode:
            batch = []
            for item in self.dataset:
                batch.append(item)
                if len(batch) == self.batch_size:
                    yield self.collate_fn(batch)
                    batch = []
            if batch and not getattr(self, "drop_last", False):
                yield self.collate_fn(batch)
            return
        if self.num_workers > 0:
            # worker dispatch needs the full index list up front (round-
            # robin + reorder) — same as before the RNG fix
            yield from self._produce_multiprocess(
                idx_batches if idx_batches is not None
                else [list(b) for b in self.batch_sampler])
            return
        for idx_batch in (idx_batches if idx_batches is not None
                          else self.batch_sampler):
            yield self.collate_fn([self.dataset[i] for i in idx_batch])

    def _pick_start_method(self):
        """forkserver by default: fork() in a JAX process (multithreaded)
        is a documented deadlock risk and warns on every worker start.
        forkserver workers descend from a clean helper process that never
        imported jax. Requires a picklable dataset/collate/init_fn — a
        preflight checks this and falls back to fork with a warning
        (reference worker model pickles too: dataloader_iter.py).
        Override with PADDLE_TPU_MP_START=fork|forkserver|spawn."""
        import multiprocessing as mp
        import os
        import pickle

        env = os.environ.get("PADDLE_TPU_MP_START", "").strip().lower()
        if env:
            return env
        cached = getattr(self, "_mp_start_cache", None)
        if cached is not None:
            return cached

        class _CapHit(Exception):
            pass

        class _NullSink:
            # stream to nowhere with a byte cap: the preflight only needs
            # to know whether pickling FAILS (lambdas, locks — which fail
            # early), not the bytes.  pickle.dumps of a large in-memory
            # dataset would burn CPU and transiently hold the whole
            # serialization (round-3 advisor finding).
            def __init__(self, cap=64 << 20):
                self.n, self.cap = 0, cap

            def write(self, b):
                self.n += len(b)
                if self.n > self.cap:
                    raise _CapHit

        try:
            # fns first and UNCAPPED: they are tiny, and the usual
            # unpicklables (lambdas, bound methods) live here — a huge
            # dataset must not cap the probe before they are reached
            pickle.Pickler(_NullSink(cap=1 << 62)).dump(
                (self.collate_fn, self.worker_init_fn))
            pickle.Pickler(_NullSink()).dump(self.dataset)
        except _CapHit:
            pass  # huge but structurally picklable: forkserver is fine
        except Exception:
            import warnings
            warnings.warn(
                "DataLoader dataset/collate_fn/worker_init_fn is not "
                "picklable; falling back to fork-based workers (deadlock "
                "risk in multithreaded processes). Define them at module "
                "scope to enable forkserver workers.", RuntimeWarning)
            self._mp_start_cache = "fork"
            return "fork"
        method = ("forkserver"
                  if "forkserver" in mp.get_all_start_methods() else "spawn")
        self._mp_start_cache = method
        return method

    def _produce_multiprocess(self, idx_batches):
        """Multi-process map-style loading (reference:
        fluid/reader.py dataloader_iter.py _DataLoaderIterMultiProcess:478 —
        worker pool + result reordering).  Workers do numpy-only work
        (fetch + collate); device transfer stays in the main process, the
        process boundary for XLA."""
        import multiprocessing as mp
        import os

        ctx = mp.get_context(self._pick_start_method())
        index_queues = [ctx.Queue() for _ in range(self.num_workers)]
        result_queue = ctx.Queue()
        workers = []
        # Workers must never touch the accelerator: the chip belongs to
        # the parent, and a child that claims it fails or hangs.  Env is
        # captured at child (and forkserver-server) start, so pin the CPU
        # around the spawn window.
        prev = os.environ.get("JAX_PLATFORMS")
        os.environ["JAX_PLATFORMS"] = "cpu"
        try:
            for wid, iq in enumerate(index_queues):
                w = ctx.Process(
                    target=_worker_loop,
                    args=(self.dataset, self.collate_fn, iq, result_queue,
                          wid, self.worker_init_fn),
                    daemon=True)
                w.start()
                workers.append(w)
        finally:
            if prev is None:
                del os.environ["JAX_PLATFORMS"]
            else:
                os.environ["JAX_PLATFORMS"] = prev
        try:
            batches = idx_batches
            # dispatch round-robin, keep prefetch_factor per worker in flight
            next_send = 0
            max_inflight = self.num_workers * self.prefetch_factor
            reorder: dict[int, object] = {}
            next_yield = 0
            user_timeout = self.timeout if self.timeout > 0 else None
            import time as _time

            def send_one():
                nonlocal next_send
                if next_send < len(batches):
                    index_queues[next_send % self.num_workers].put(
                        (next_send, batches[next_send]))
                    next_send += 1

            def recv_one():
                """Poll the result queue, detecting dead workers (a
                segfaulted/OOM-killed worker would otherwise hang the
                loader forever) and honoring the user timeout."""
                deadline = (None if user_timeout is None
                            else _time.monotonic() + user_timeout)
                while True:
                    try:
                        return result_queue.get(timeout=1.0)
                    except queue.Empty:
                        pass
                    for w in workers:
                        if not w.is_alive() and w.exitcode != 0:
                            raise RuntimeError(
                                f"DataLoader worker pid={w.pid} died with "
                                f"exit code {w.exitcode}. If this "
                                "happened at startup, the launching "
                                "script probably lacks an `if __name__ "
                                "== '__main__':` guard — forkserver/"
                                "spawn workers re-import the main module "
                                "(same contract as torch DataLoader on "
                                "spawn platforms). Guard the script, or "
                                "set PADDLE_TPU_MP_START=fork to opt "
                                "back into fork workers (deadlock risk "
                                "in multithreaded/JAX processes).")
                    if deadline is not None and _time.monotonic() > deadline:
                        raise RuntimeError(
                            f"DataLoader worker timed out after "
                            f"{self.timeout}s")

            for _ in range(min(max_inflight, len(batches))):
                send_one()
            while next_yield < len(batches):
                if next_yield in reorder:
                    batch = reorder.pop(next_yield)
                    next_yield += 1
                    from .. import core as _core
                    _core.stat_add("dataloader.batches")
                    if self.collate_fn is default_collate_fn:
                        batch = _tensor_wrap(batch)
                    yield batch
                    send_one()
                    continue
                bid, payload = recv_one()
                if isinstance(payload, _WorkerError):
                    raise RuntimeError(
                        f"DataLoader worker {payload.worker_id} failed:\n"
                        f"{payload.traceback}")
                reorder[bid] = payload
        finally:
            for iq in index_queues:
                iq.put(None)
            for w in workers:
                w.join(timeout=5)
                if w.is_alive():
                    w.terminate()

    def __iter__(self):
        # sampler permutation drawn HERE (the thread CALLING iter(),
        # i.e. the seeded consumer) — never lazily on the prefetch
        # thread; see _epoch_batches.  A plain method (not a generator
        # function) so the draw happens at iter() time, not deferred to
        # the first next(), which a prefetch wrapper could run on an
        # unseeded thread.  User-supplied batch_samplers stay lazy:
        # they may be generator-backed/infinite, and they don't draw
        # from the framework chain, so eager materialization would only
        # break them without fixing anything.
        idx_batches = (self._epoch_batches()
                       if isinstance(self.batch_sampler, BatchSampler)
                       else None)
        return self._iter_impl(idx_batches)

    def _iter_impl(self, idx_batches):
        gen = self._produce(idx_batches)
        place = self.placement
        if place is not None:
            gen = self._placed(gen, place)
        if not self.use_buffer_reader:
            yield from gen
            return
        # double-buffered prefetch on a background thread
        # (operators/reader/buffered_reader.cc analog)
        q: queue.Queue = queue.Queue(maxsize=self.prefetch_factor)
        sentinel = object()
        stop = threading.Event()

        def put_or_stop(item) -> bool:
            while not stop.is_set():
                try:
                    q.put(item, timeout=0.1)
                    return True
                except queue.Full:
                    continue
            return False

        def worker():
            try:
                for item in gen:
                    if not put_or_stop(item):
                        return
                put_or_stop(sentinel)
            except BaseException as e:  # re-raised in the consumer
                put_or_stop(e)
            finally:
                # run the source generator's cleanup (worker-process
                # shutdown) in ITS OWN thread — the consumer abandoning
                # iteration early must not leak worker processes
                gen.close()

        t = threading.Thread(target=worker, daemon=True)
        t.start()
        try:
            while True:
                item = q.get()
                if item is sentinel:
                    break
                if isinstance(item, BaseException):
                    raise item
                yield item
        finally:
            stop.set()
            t.join(timeout=10)


def get_worker_info():
    return None  # single-process host loading; workers are threads
