"""Data pipeline (reference surface: python/paddle/io/ + fluid/dataloader/).

TPU-native DataLoader: worker processes (or threads) produce numpy batches,
a prefetcher overlaps host->device transfer with compute (the role the
reference's pin-memory + C++ reader queues played,
paddle/fluid/pybind/reader_py.cc, paddle/fluid/operators/reader/).
"""
from __future__ import annotations

import itertools
import math
import queue as _queue
import threading
from typing import Iterable, List, Optional, Sequence

import numpy as np

from ..core import random as _rnd
from ..core.tensor import Tensor


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
        for d in self.datasets:
            yield from d


class ConcatDataset(Dataset):
    def __init__(self, datasets):
        self.datasets = list(datasets)
        self.cum = np.cumsum([len(d) for d in self.datasets])

    def __len__(self):
        return int(self.cum[-1])

    def __getitem__(self, idx):
        di = int(np.searchsorted(self.cum, idx, side="right"))
        prev = 0 if di == 0 else int(self.cum[di - 1])
        return self.datasets[di][idx - prev]


class Subset(Dataset):
    def __init__(self, dataset, indices):
        self.dataset = dataset
        self.indices = list(indices)

    def __getitem__(self, idx):
        return self.dataset[self.indices[idx]]

    def __len__(self):
        return len(self.indices)


def random_split(dataset, lengths, generator=None):
    if all(isinstance(l, float) for l in lengths):
        n = len(dataset)
        counts = [int(math.floor(n * f)) for f in lengths]
        counts[-1] = n - sum(counts[:-1])
        lengths = counts
    perm = np.random.RandomState(
        _rnd.default_generator().initial_seed or None).permutation(
        len(dataset)).tolist()
    out, off = [], 0
    for l in lengths:
        out.append(Subset(dataset, perm[off:off + l]))
        off += l
    return out


class Sampler:
    def __init__(self, data_source=None):
        self.data_source = data_source

    def __iter__(self):
        raise NotImplementedError

    def __len__(self):
        return len(self.data_source)


class SequenceSampler(Sampler):
    def __iter__(self):
        return iter(range(len(self.data_source)))


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
        rng = np.random.default_rng()
        if self.replacement:
            return iter(rng.integers(0, n, self.num_samples).tolist())
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
        rng = np.random.default_rng()
        return iter(rng.choice(len(self.weights), self.num_samples,
                               replace=self.replacement, p=p).tolist())

    def __len__(self):
        return self.num_samples


class BatchSampler(Sampler):
    def __init__(self, dataset=None, sampler=None, shuffle=False,
                 batch_size=1, drop_last=False):
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
    """Per-rank disjoint shard of the dataset
    (reference: python/paddle/io/dataloader/batch_sampler.py
    DistributedBatchSampler) — on TPU this shards by process index for
    multi-host input pipelines."""

    def __init__(self, dataset, batch_size, num_replicas=None, rank=None,
                 shuffle=False, drop_last=False):
        self.dataset = dataset
        self.batch_size = batch_size
        self.shuffle = shuffle
        self.drop_last = drop_last
        if num_replicas is None:
            try:
                import jax
                num_replicas = jax.process_count()
            except Exception:
                num_replicas = 1
        if rank is None:
            try:
                import jax
                rank = jax.process_index()
            except Exception:
                rank = 0
        self.nranks = num_replicas
        self.local_rank = rank
        self.epoch = 0
        self.num_samples = int(math.ceil(len(dataset) / num_replicas))
        self.total_size = self.num_samples * num_replicas

    def __iter__(self):
        indices = list(range(len(self.dataset)))
        if self.shuffle:
            rng = np.random.RandomState(self.epoch)
            rng.shuffle(indices)
        indices += indices[: self.total_size - len(indices)]
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

    def set_epoch(self, epoch):
        self.epoch = epoch


def _collate_host(batch):
    """default_collate_fn's stacking, on the host: numpy in, numpy out.
    Leaves that will become Tensors come back as ``np.ndarray``."""
    sample = batch[0]
    if isinstance(sample, Tensor):
        return np.stack([np.asarray(s._array) for s in batch])
    if isinstance(sample, np.ndarray):
        return np.stack(batch)
    if isinstance(sample, (int, float, np.integer, np.floating)):
        return np.asarray(batch)
    if isinstance(sample, (list, tuple)):
        transposed = list(zip(*batch))
        return tuple(_collate_host(list(s)) for s in transposed)
    if isinstance(sample, dict):
        return {k: _collate_host([b[k] for b in batch]) for k in sample}
    return batch


def _to_tensors(tree):
    import jax.tree_util as jtu
    return jtu.tree_map(
        lambda a: Tensor(a) if isinstance(a, np.ndarray) else a, tree)


def _has_device_leaf(tree) -> bool:
    import jax
    import jax.tree_util as jtu
    return any(isinstance(leaf, (Tensor, jax.Array)) for leaf in
               jtu.tree_leaves(tree, is_leaf=lambda l: isinstance(l, Tensor)))


def default_collate_fn(batch):
    """Stack samples into batched Tensors (reference:
    fluid/dataloader/collate.py default_collate_fn)."""
    return _to_tensors(_collate_host(batch))


class DataLoader:
    """reference surface: python/paddle/io/DataLoader (fluid/reader.py:146).

    num_workers>0 uses a thread pool producing ready batches ahead of time
    (numpy work releases the GIL; the heavy lifting is in the dataset's own
    decode code), plus a device-prefetch queue.
    """

    def __init__(self, dataset, feed_list=None, places=None,
                 return_list=True, batch_sampler=None, batch_size=1,
                 shuffle=False, drop_last=False, collate_fn=None,
                 num_workers=0, use_buffer_reader=True, prefetch_factor=2,
                 use_shared_memory=True, timeout=0, worker_init_fn=None,
                 persistent_workers=False):
        self.dataset = dataset
        self.collate_fn = collate_fn or default_collate_fn
        self.num_workers = num_workers
        self.use_shared_memory = use_shared_memory
        self.worker_init_fn = worker_init_fn
        self.prefetch_factor = max(prefetch_factor, 2)
        self._iterable_mode = isinstance(dataset, IterableDataset)
        if self._iterable_mode:
            self.batch_sampler = None
            self.batch_size = batch_size
            self.drop_last = drop_last
        elif batch_sampler is not None:
            self.batch_sampler = batch_sampler
        else:
            self.batch_sampler = BatchSampler(
                dataset, shuffle=shuffle, batch_size=batch_size,
                drop_last=drop_last)

    def __len__(self):
        if self._iterable_mode:
            raise TypeError("IterableDataset DataLoader has no len()")
        return len(self.batch_sampler)

    def _iter_batches(self):
        if self._iterable_mode:
            it = iter(self.dataset)
            while True:
                batch = list(itertools.islice(it, self.batch_size))
                if not batch:
                    return
                if len(batch) < self.batch_size and self.drop_last:
                    return
                yield self.collate_fn(batch)
        else:
            for idxs in self.batch_sampler:
                yield self.collate_fn([self.dataset[i] for i in idxs])

    def __iter__(self):
        if self.num_workers == 0:
            yield from self._iter_batches()
            return
        if (self.use_shared_memory and not self._iterable_mode):
            it = self._iter_multiprocess()
            if it is not None:
                yield from it
                return
        yield from self._iter_threaded()

    def _iter_threaded(self):
        q: _queue.Queue = _queue.Queue(maxsize=self.prefetch_factor
                                       * self.num_workers)
        sentinel = object()

        def producer():
            try:
                for b in self._iter_batches():
                    q.put(b)
            finally:
                q.put(sentinel)

        t = threading.Thread(target=producer, daemon=True,
                             name="dataloader-producer")
        t.start()
        while True:
            item = q.get()
            if item is sentinel:
                break
            yield item

    def _iter_multiprocess(self):
        """Real worker processes over the native shared-memory ring queue
        (csrc/shm_queue.cpp) — the C++ data-feed path.  Returns None when
        the native transport is unavailable or the dataset yields device
        data (caller falls back to threads).

        The workers are FORKED copies of a process that may own the
        accelerator, and a forked copy must never touch jax: they fetch
        samples and stack them with numpy, nothing else.  Tensors are
        built — and a custom ``collate_fn`` runs — in the parent.
        """
        all_batches = list(self.batch_sampler)
        if not all_batches:
            return None
        # what one sample looks like decides the path: a dataset that
        # hands out Tensors (TensorDataset) would make the forked workers
        # read device arrays
        if _has_device_leaf(self.dataset[all_batches[0][0]]):
            return None
        try:
            from .shm_queue import ShmQueue
            out_q = ShmQueue(capacity=128 << 20)
        except RuntimeError:          # native library unavailable
            return None
        import multiprocessing as mp
        ctx = mp.get_context("fork")
        nw = min(self.num_workers, len(all_batches))
        dataset = self.dataset
        default_collate = self.collate_fn is default_collate_fn
        collate = self.collate_fn
        init_fn = self.worker_init_fn
        qname = out_q.name

        def worker(wid):
            from .shm_queue import ShmQueue as SQ
            q = SQ(qname, create=False)
            if init_fn is not None:
                init_fn(wid)
            for bi in range(wid, len(all_batches), nw):
                samples = [dataset[i] for i in all_batches[bi]]
                q.put((bi, _collate_host(samples) if default_collate
                       else samples))
            q.put(("done", wid))

        procs = [ctx.Process(target=worker, args=(w,), daemon=True)
                 for w in range(nw)]
        for p in procs:
            p.start()

        def gen():
            pending = {}
            done = 0
            nxt = 0
            total = len(all_batches)
            try:
                while nxt < total:
                    if nxt in pending:
                        payload = pending.pop(nxt)
                    else:
                        tag, payload_or_wid = out_q.get()
                        if tag == "done":
                            done += 1
                            if done == nw and nxt >= total:
                                break
                            continue
                        if tag != nxt:
                            pending[tag] = payload_or_wid
                            continue
                        payload = payload_or_wid
                    nxt += 1
                    yield (_to_tensors(payload) if default_collate
                           else collate(payload))
            finally:
                out_q.close()
                for p in procs:
                    p.join(timeout=2)
                    if p.is_alive():
                        p.terminate()
                out_q.destroy()

        return gen()
