"""DataSet iterators + async host-side prefetch.

Reference parity: nd4j `DataSetIterator` SPI and DL4J's iterator stack —
`ExistingDataSetIterator`, `ListDataSetIterator`, `IteratorDataSetIterator`,
`MultipleEpochsIterator`, and the async prefetch wrappers
`AsyncDataSetIterator` / `AsyncMultiDataSetIterator` (deeplearning4j-nn
datasets/iterator/AsyncDataSetIterator.java — background prefetch thread +
LinkedBlockingQueue) that every fit() transparently wraps
(MultiLayerNetwork.java:1024).

TPU-native: iterators produce host-side numpy DataSets; AsyncDataSetIterator
runs a Python producer thread with a bounded queue so host ETL overlaps with
device compute (the jit dispatch is async, so the device pipeline stays full —
the role the reference's prefetch thread plays for GPU).
"""
from __future__ import annotations

import queue
import threading
import time
from typing import Callable, Iterable, Iterator, List, Optional

import numpy as np

from ..optimize import tracing
from .dataset import DataSet, MultiDataSet


def register_metrics():
    """Pre-register the prefetcher's family at 0; returns the counter."""
    from ..optimize.metrics import registry
    return registry().counter(
        "etl_h2d_bytes_total",
        "Bytes of host batches the device prefetcher handed to the device")


class DataSetIterator:
    """Iterator SPI (reference nd4j DataSetIterator). Subclasses implement
    `reset` and `__next__`; `__iter__` restarts by default."""

    def __iter__(self) -> Iterator[DataSet]:
        self.reset()
        return self

    def __next__(self) -> DataSet:
        raise NotImplementedError

    def reset(self) -> None:
        pass

    def batch_size(self) -> int:
        raise NotImplementedError

    def total_examples(self) -> Optional[int]:
        return None

    def async_supported(self) -> bool:
        return True

    # Normalizer hook (reference DataSetIterator.setPreProcessor)
    pre_processor: Optional[Callable[[DataSet], DataSet]] = None

    def _maybe_preprocess(self, ds: DataSet) -> DataSet:
        if self.pre_processor is not None:
            out = self.pre_processor(ds)
            return ds if out is None else out
        return ds


class ListDataSetIterator(DataSetIterator):
    """Iterate a list of examples in minibatches (reference
    ListDataSetIterator)."""

    def __init__(self, data: DataSet, batch_size: int = 32, shuffle: bool = False,
                 seed: Optional[int] = None, drop_last: bool = False):
        self._data = data
        self._batch = int(batch_size)
        self._shuffle = shuffle
        self._seed = seed
        self._epoch = 0
        self._drop_last = drop_last
        self._cursor = 0
        self._view = data

    def reset(self):
        self._cursor = 0
        if self._shuffle:
            self._view = self._data.shuffle(
                None if self._seed is None else self._seed + self._epoch)
            self._epoch += 1

    def __next__(self) -> DataSet:
        n = self._view.num_examples()
        if self._cursor >= n:
            raise StopIteration
        end = min(self._cursor + self._batch, n)
        if self._drop_last and end - self._cursor < self._batch:
            raise StopIteration
        ds = DataSet(self._view.features[self._cursor:end],
                     self._view.labels[self._cursor:end],
                     None if self._view.features_mask is None
                     else self._view.features_mask[self._cursor:end],
                     None if self._view.labels_mask is None
                     else self._view.labels_mask[self._cursor:end])
        self._cursor = end
        return self._maybe_preprocess(ds)

    def batch_size(self):
        return self._batch

    def total_examples(self):
        return self._data.num_examples()


class ExistingDataSetIterator(DataSetIterator):
    """Wrap an existing iterable of DataSets (reference
    ExistingDataSetIterator)."""

    def __init__(self, datasets: Iterable[DataSet]):
        self._datasets = list(datasets)
        self._i = 0

    def reset(self):
        self._i = 0

    def __next__(self):
        if self._i >= len(self._datasets):
            raise StopIteration
        ds = self._datasets[self._i]
        self._i += 1
        return self._maybe_preprocess(ds)

    def batch_size(self):
        return self._datasets[0].num_examples() if self._datasets else 0


class MultipleEpochsIterator(DataSetIterator):
    """Replay an iterator for N epochs as one pass (reference
    MultipleEpochsIterator)."""

    def __init__(self, epochs: int, base: DataSetIterator):
        self._epochs = int(epochs)
        self._base = base
        self._epoch = 0
        self._inner: Optional[Iterator] = None

    def reset(self):
        self._epoch = 0
        self._inner = None

    def __next__(self):
        while True:
            if self._inner is None:
                if self._epoch >= self._epochs:
                    raise StopIteration
                self._base.reset()
                self._inner = iter(self._base)
                self._epoch += 1
            try:
                return next(self._inner)
            except StopIteration:
                self._inner = None

    def batch_size(self):
        return self._base.batch_size()


class _StreamEnd:
    """Queue-carried end-of-stream marker, optionally holding the
    producer's error. Shipping the error inside the queue item (instead
    of on a shared instance attribute) ties each epoch's error to its
    own queue: a stale producer that outlived its 5s join timeout can
    only write to the old queue, never poison the next epoch."""

    __slots__ = ("error",)

    def __init__(self, error: Optional[BaseException] = None):
        self.error = error


class AsyncDataSetIterator(DataSetIterator):
    """Background-thread prefetch with a bounded queue (reference
    datasets/iterator/AsyncDataSetIterator.java). `queue_size` mirrors the
    reference's buffer size (default 8)."""

    def __init__(self, base: DataSetIterator, queue_size: int = 8):
        self._base = base
        self._queue_size = max(1, int(queue_size))
        self._queue: Optional[queue.Queue] = None
        self._thread: Optional[threading.Thread] = None
        self._shutdown = threading.Event()

    def _produce_item(self, ds, host_ms: float):
        """Hook for subclasses (DevicePrefetchIterator): transform a batch
        on the producer thread before it enters the queue. `host_ms` is
        the time the producer just spent pulling the batch from the base
        iterator (host ETL)."""
        return ds

    def _next_resilient(self, it):
        """One base-iterator poll with ONE transparent retry on transient
        failure (flaky storage/network-backed iterators; the ``etl.next``
        fault point fires per attempt). A second consecutive failure
        propagates to the consumer as usual."""
        from ..utils import faults
        try:
            faults.fire("etl.next")
            return next(it)
        except StopIteration:
            raise
        except Exception as e:
            import logging
            from ..optimize import metrics as metrics_mod
            metrics_mod.registry().counter(
                "retries_total",
                "Transient-failure retries per distributed edge"
                ).labels(edge="etl.next").inc()
            logging.getLogger(__name__).warning(
                "prefetch producer: base iterator failed "
                "(%s: %s); retrying once", type(e).__name__, e)
            faults.fire("etl.next")
            return next(it)

    def _producer(self, q: queue.Queue):
        """The producer thread. Its spans, from the readings it takes
        anyway: `etl/produce` (the pull from the base iterator) and
        `etl/handoff` (blocked in `q.put`: the queue is full, so the
        consumer or the device is the slow side, not this thread)."""
        try:
            it = iter(self._base)
            while True:
                t0 = time.perf_counter()
                try:
                    ds = self._next_resilient(it)
                except StopIteration:
                    break
                host_s = time.perf_counter() - t0
                tracing.add_span("etl/produce", t0, host_s)
                if self._shutdown.is_set():
                    return
                item = self._produce_item(ds, host_s * 1000.0)
                t1 = time.perf_counter()
                q.put(item)
                tracing.add_span("etl/handoff", t1,
                                 time.perf_counter() - t1)
            q.put(_StreamEnd())
        except BaseException as e:  # propagate to consumer via the queue
            q.put(_StreamEnd(e))

    def reset(self):
        self._stop_thread()
        self._shutdown.clear()
        self._queue = queue.Queue(maxsize=self._queue_size)
        self._thread = threading.Thread(
            target=self._producer, args=(self._queue,), daemon=True)
        self._thread.start()

    def _stop_thread(self):
        if self._thread is not None and self._thread.is_alive():
            self._shutdown.set()
            try:
                while True:
                    self._queue.get_nowait()
            except queue.Empty:
                pass
            self._thread.join(timeout=5.0)
        self._thread = None

    def __iter__(self):
        self.reset()
        return self

    def __next__(self):
        if self._queue is None:
            self.reset()
        item = self._queue.get()
        if isinstance(item, _StreamEnd):
            self._thread = None
            if item.error is not None:
                raise item.error
            raise StopIteration
        return item

    def batch_size(self):
        return self._base.batch_size()

    def shutdown(self):
        self._stop_thread()


class AsyncMultiDataSetIterator(AsyncDataSetIterator):
    """Background prefetch over MultiDataSet streams (reference
    datasets/iterator/AsyncMultiDataSetIterator.java) — same bounded-queue
    machinery; a ComputationGraph's fit wraps with this (reference
    ComputationGraph.java:867)."""

    def __init__(self, base, queue_size: int = 8):
        # `base` may be any (re-)iterable of MultiDataSets, incl. a list.
        super().__init__(base, queue_size)

    def batch_size(self):
        return self._base.batch_size() if hasattr(self._base, "batch_size") \
            else None


class IteratorDataSetIterator(DataSetIterator):
    """Re-batch a stream of DataSets to a fixed minibatch size (reference
    IteratorDataSetIterator, used by the Spark worker loop)."""

    def __init__(self, base: Iterable[DataSet], batch_size: int):
        self._base_iterable = base
        self._batch = int(batch_size)
        self._iter: Optional[Iterator[DataSet]] = None
        self._buffer: List[DataSet] = []
        self._buffered = 0

    def reset(self):
        self._iter = iter(self._base_iterable)
        self._buffer = []
        self._buffered = 0

    def __next__(self) -> DataSet:
        if self._iter is None:
            self.reset()
        while self._buffered < self._batch:
            try:
                ds = next(self._iter)
            except StopIteration:
                break
            self._buffer.append(ds)
            self._buffered += ds.num_examples()
        if not self._buffer:
            raise StopIteration
        merged = DataSet.merge(self._buffer)
        out = DataSet(merged.features[:self._batch], merged.labels[:self._batch],
                      None if merged.features_mask is None
                      else merged.features_mask[:self._batch],
                      None if merged.labels_mask is None
                      else merged.labels_mask[:self._batch])
        rest = merged.features.shape[0] - self._batch
        if rest > 0:
            self._buffer = [DataSet(
                merged.features[self._batch:], merged.labels[self._batch:],
                None if merged.features_mask is None
                else merged.features_mask[self._batch:],
                None if merged.labels_mask is None
                else merged.labels_mask[self._batch:])]
            self._buffered = rest
        else:
            self._buffer = []
            self._buffered = 0
        return out

    def batch_size(self):
        return self._batch


def as_iterator(data, labels=None, batch_size: int = 32) -> DataSetIterator:
    """Coerce (features, labels) / DataSet / iterator to a DataSetIterator."""
    if isinstance(data, DataSetIterator):
        return data
    if isinstance(data, DataSet):
        return ListDataSetIterator(data, batch_size or data.num_examples())
    if labels is None:
        raise ValueError("labels required when passing a raw feature array")
    ds = DataSet(np.asarray(data), np.asarray(labels))
    return ListDataSetIterator(ds, batch_size or ds.num_examples())


class AsyncShieldDataSetIterator(DataSetIterator):
    """Opt-out wrapper: guarantees fit() will NOT wrap the underlying
    iterator in background prefetch (reference
    AsyncShieldDataSetIterator — for sources whose batches must not be
    consumed ahead of the training step, e.g. externally synchronized
    or stateful readers)."""

    def __init__(self, underlying):
        # same iterable tolerance as the async wrapper it opts OUT of:
        # plain lists/generators are accepted (materialized so repeat
        # epochs see the data)
        if not hasattr(underlying, "reset"):
            underlying = list(underlying)
        self.underlying = underlying
        self._it = None

    def __iter__(self):
        self.reset()
        return self

    def __next__(self) -> DataSet:
        if self._it is None:
            self.reset()
        return self._maybe_preprocess(next(self._it))

    def reset(self):
        if hasattr(self.underlying, "reset"):
            self.underlying.reset()
        self._it = iter(self.underlying)

    def batch_size(self):
        return self.underlying.batch_size() \
            if hasattr(self.underlying, "batch_size") else None

    def total_examples(self):
        return self.underlying.total_examples() \
            if hasattr(self.underlying, "total_examples") else None

    def async_supported(self) -> bool:
        return False  # the whole point


class AsyncShieldMultiDataSetIterator(AsyncShieldDataSetIterator):
    """Multi-dataset flavor (reference AsyncShieldMultiDataSetIterator)."""


class PadToBucketIterator(DataSetIterator):
    """Pad ragged batches up to the epoch's canonical batch shape so ONE
    compiled train step serves the whole epoch (the tf.data
    pad-to-bucket idea applied to the XLA recompile problem: a short
    final batch otherwise compiles a brand-new program per shape).

    The canonical row count is the first batch's (the full-size batches
    lead; only tails are ragged), so a dataset that fits in a single
    batch is never padded and existing single-batch behavior is
    untouched. Pad rows repeat the tail example and carry a zero-weight
    labels mask (created when absent — data/padding.py contract), so
    loss and gradients match the unpadded batch EXACTLY; score
    normalization divides by real rows. BatchNorm train-mode statistics
    and dropout draws still see pad rows (documented caveat).

    Time-axis raggedness (variable sequence tails) pads only when the
    batch already carries BOTH masks: zero-padding a rank>=2 mask leaves
    sum(mask) — the loss denominator — unchanged, so the math stays
    exact; synthesizing a time mask where none exists would flip the
    normalization semantics, so maskless ragged-time batches pass
    through unpadded (shape change, honest recompile).

    `bucket_rows="pow2"` switches the row target from the first batch's
    count to the shared power-of-two bucket rule
    (data/padding.next_pow2_bucket — the same rounding ParallelInference
    and the serving gateway use), for streams whose batch sizes vary
    throughout rather than only at the tail: at most log2(max_batch)
    distinct compiled shapes instead of one per distinct size."""

    def __init__(self, base, batch_size: Optional[int] = None,
                 bucket_rows: str = "first"):
        if bucket_rows not in ("first", "pow2"):
            raise ValueError(
                f"bucket_rows must be 'first' or 'pow2', got {bucket_rows!r}")
        self._base = base
        self._fixed_target = batch_size
        self._target: Optional[int] = batch_size
        self._target_t: Optional[int] = None
        self._bucket_rows = bucket_rows
        self._it: Optional[Iterator] = None

    def reset(self):
        self._it = iter(self._base)
        self._target = self._fixed_target
        self._target_t = None

    def __iter__(self):
        self.reset()
        return self

    @staticmethod
    def _pad_time(ds: DataSet, target_t: int) -> DataSet:
        t = ds.features.shape[1]
        pad = target_t - t
        if pad <= 0:
            return ds
        def pad_axis1(a, val=0.0):
            if a is None:
                return None
            a = np.asarray(a)
            width = [(0, 0)] * a.ndim
            width[1] = (0, pad)
            return np.pad(a, width, constant_values=val)
        return DataSet(pad_axis1(ds.features), pad_axis1(ds.labels),
                       pad_axis1(ds.features_mask), pad_axis1(ds.labels_mask))

    def _row_target(self, n: int) -> int:
        from .padding import next_pow2_bucket
        if self._bucket_rows == "pow2" and self._fixed_target is None:
            return next_pow2_bucket(n)
        if self._target is None:
            self._target = n
        return self._target

    def __next__(self) -> DataSet:
        from .padding import (pad_dataset_rows, pad_lmask_zero_weight,
                              pad_multidataset_rows)
        if self._it is None:
            self.reset()
        ds = next(self._it)
        # Uniform mask structure across the epoch: padding only the tail
        # batch would give it a labels mask the full batches lack, and
        # jit retraces on pytree structure — two compiles, defeating the
        # point. Every maskless batch gets the ones (n,1) mask, which
        # the zero-weight contract guarantees is loss-exact (the rank-2
        # mask path divides by sum(mask) = n).
        if isinstance(ds, MultiDataSet):
            if ds.labels_masks is None or any(m is None
                                              for m in ds.labels_masks):
                masks = ds.labels_masks or [None] * len(ds.labels)
                ds = MultiDataSet(
                    ds.features, ds.labels, ds.features_masks,
                    [m if m is not None
                     else pad_lmask_zero_weight(None, len(l), 0)
                     for m, l in zip(masks, ds.labels)])
            return pad_multidataset_rows(ds, self._row_target(
                ds.num_examples()))
        if ds.labels_mask is None:
            ds = DataSet(ds.features, ds.labels, ds.features_mask,
                         pad_lmask_zero_weight(None, ds.num_examples(), 0))
        # Ragged time tail: pad up to the canonical length when both
        # masks are present (exactness requires them, see class doc).
        if np.ndim(ds.features) == 3:
            t = ds.features.shape[1]
            if self._target_t is None:
                self._target_t = t
            elif t < self._target_t and ds.features_mask is not None \
                    and ds.labels_mask is not None \
                    and np.ndim(ds.labels_mask) >= 2:
                ds = self._pad_time(ds, self._target_t)
        return pad_dataset_rows(ds, self._row_target(ds.num_examples()))

    def batch_size(self):
        return self._base.batch_size() if hasattr(self._base, "batch_size") \
            else self._fixed_target

    def total_examples(self):
        return self._base.total_examples() \
            if hasattr(self._base, "total_examples") else None

    def async_supported(self) -> bool:
        base_ok = getattr(self._base, "async_supported", lambda: True)
        return base_ok()


class PackToBucketIterator(DataSetIterator):
    """Pack ragged sequences MULTIPLE-per-row instead of padding each to
    its own row (the varlen/segment-mask sibling of PadToBucketIterator;
    docs/perf_data_pipeline.md §PackToBucket): every emitted batch has
    the one canonical ``(rows, bucket_len)`` shape — ONE compiled train
    step per epoch — but the time axis is dense with real tokens, so at
    ragged length mixes the same step processes 2-3x the real tokens of
    the padded layout.

    The emitted feature mask carries SEGMENT IDS (0 = pad, 1..k = the
    k sequences sharing the row); an attention layer with
    ``packed_segments=True`` reads them through the ordinary mask
    plumbing and forbids cross-segment attention, so per-token outputs
    match the unpacked batch exactly. The labels mask is the rank-2
    zero-weight contract (data/padding.py): loss numerator AND
    denominator (sum(mask) = real tokens) are identical to training on
    the unpacked ragged batch — loss-exact, not approximately so.
    Per-segment 0-based positions ride along as ``packed_positions``
    for position-consuming consumers (attention itself needs only ids).

    `bucket_len` defaults to the pow2 bucket of the first batch's
    longest sequence (the shared next_pow2_bucket rule); `rows` defaults
    to the first batch's first-fit bin count. Later batches that need
    more bins split into several emitted packed batches (same shape);
    leftover bins pad with fully-masked all-zero rows. A sequence longer
    than `bucket_len` raises — choose the bucket for the corpus.

    Requires [batch, time, features] features and per-timestep rank-3
    labels; lengths come from the batch's features_mask row sums (a
    maskless batch packs as full-length rows). Masks must be contiguous
    from t=0 — mid-sequence holes have no packed representation."""

    def __init__(self, base, bucket_len: Optional[int] = None,
                 rows: Optional[int] = None):
        self._base = base
        self._fixed_bucket = bucket_len
        self._fixed_rows = rows
        self._bucket = bucket_len
        self._rows = rows
        self._it: Optional[Iterator] = None
        self._pending: List[DataSet] = []

    def reset(self):
        self._it = iter(self._base)
        self._bucket = self._fixed_bucket
        self._rows = self._fixed_rows
        self._pending = []

    def __iter__(self):
        self.reset()
        return self

    def _lengths(self, ds: DataSet, n: int, t: int) -> np.ndarray:
        if ds.features_mask is None:
            return np.full(n, t, dtype=np.int64)
        fm = np.asarray(ds.features_mask) > 0
        lengths = fm.sum(axis=1).astype(np.int64)
        contiguous = np.arange(t)[None, :] < lengths[:, None]
        if not np.array_equal(fm, contiguous):
            raise ValueError(
                "PackToBucketIterator needs contiguous-from-start "
                "feature masks (no mid-sequence holes)")
        return lengths

    def _pack_batch(self, ds: DataSet) -> List[DataSet]:
        from .padding import (first_fit_pack, next_pow2_bucket,
                              pack_sequences, record_packing)
        f = np.asarray(ds.features)
        if f.ndim != 3:
            raise ValueError(
                "PackToBucketIterator needs [batch, time, features] "
                f"features, got shape {f.shape}")
        lab = np.asarray(ds.labels)
        if lab.ndim != 3:
            raise ValueError(
                "PackToBucketIterator needs per-timestep (rank-3) "
                f"labels, got shape {lab.shape}")
        n, t = f.shape[0], f.shape[1]
        lengths = self._lengths(ds, n, t)
        if self._bucket is None:
            self._bucket = next_pow2_bucket(int(lengths.max()))
        lmask = None if ds.labels_mask is None \
            else np.asarray(ds.labels_mask)
        if lmask is not None and lmask.ndim != 2:
            raise ValueError(
                "PackToBucketIterator needs a per-token rank-2 labels "
                f"mask, got shape {lmask.shape}")
        bins = first_fit_pack(lengths, self._bucket)
        if self._rows is None:
            self._rows = len(bins)
        out: List[DataSet] = []
        for c0 in range(0, len(bins), self._rows):
            chunk = bins[c0:c0 + self._rows]
            pf, pl, seg, plm, pos = pack_sequences(
                f, lab, lengths, self._bucket, bins=chunk,
                rows=self._rows, labels_mask=lmask)
            packed = DataSet(pf, pl, seg, plm)
            try:
                packed.packed_positions = pos
            except AttributeError:
                pass
            out.append(packed)
            record_packing(
                "fit", items=sum(len(b) for b in chunk),
                real_tokens=int(sum(int(lengths[i])
                                    for b in chunk for i in b)),
                padded_tokens=self._rows * self._bucket)
        return out

    def __next__(self) -> DataSet:
        if self._it is None:
            self.reset()
        while not self._pending:
            self._pending = self._pack_batch(next(self._it))
        return self._maybe_preprocess(self._pending.pop(0))

    def batch_size(self):
        return self._rows

    def total_examples(self):
        return self._base.total_examples() \
            if hasattr(self._base, "total_examples") else None

    def async_supported(self) -> bool:
        base_ok = getattr(self._base, "async_supported", lambda: True)
        return base_ok()


class DevicePrefetchIterator(AsyncDataSetIterator):
    """Background prefetch that stages batches ONTO THE DEVICE: the
    producer thread runs `jax.device_put` (with an optional
    NamedSharding for ParallelWrapper's mesh path) and blocks until the
    transfer lands, so the training thread dequeues device-resident
    arrays and never pays host→device latency inside the step loop —
    the prefetch_to_device stage of tf.data (Murray et al., VLDB 2021)
    for this framework. Shutdown/reset/error semantics are inherited
    from AsyncDataSetIterator (same bounded queue + sentinel protocol).

    The producer issues TRANSFERS AND NOTHING ELSE: an array is staged
    as the host holds it, dtype and all, and no executable is dispatched
    from this thread. The device runs executables in the order they are
    enqueued, so a cast enqueued here could get in ahead of the fit
    thread's next train step and park that step behind a transfer; the
    cast to the network's dtype is the front end's (`_cast_features`,
    `_pack_inputs`), on the fit thread, on a batch that has landed.

    `depth` bounds how many staged batches may be device-resident at
    once (HBM cost: depth x batch bytes, in the host's dtype).
    `sharding` places every staged array under that sharding; batches
    whose leading dimension is not divisible by `batch_divisor` (the
    mesh's data-axis size) skip device staging and pass through as host
    arrays, letting the wrapper's zero-weight pad path handle them.

    Each staged batch carries its ETL breakdown as `_etl_host_ms` (time
    the producer spent pulling it from the base iterator) and
    `_etl_h2d_ms` (device_put + transfer wait); fit() surfaces them as
    model.last_etl_host_ms / last_etl_h2d_ms next to the consumer-side
    last_etl_ms stall clock. With tracing on, each staging is an
    `etl/stage` span on the producer's thread (args `bytes`, `rows`)
    with children `etl/stage/put` (the `device_put`s, nothing else) and
    `etl/stage/fence` (the wait for the link to deliver them; it does
    not queue behind what the device is computing);
    `etl_h2d_bytes_total` counts the host bytes handed over."""

    def __init__(self, base, depth: int = 2, sharding=None,
                 batch_divisor: int = 1):
        super().__init__(base, queue_size=depth)
        self._sharding = sharding
        self._divisor = max(1, int(batch_divisor))
        self._h2d_bytes = register_metrics()

    def _put(self, a):
        import jax
        # an absent mask (None) is an empty tree and comes back as None
        return jax.device_put(a, self._sharding)

    def _stage(self, ds):
        import jax
        t0 = time.perf_counter()
        if isinstance(ds, MultiDataSet):
            out = MultiDataSet(
                [self._put(f) for f in ds.features],
                [self._put(l) for l in ds.labels],
                None if ds.features_masks is None
                else [self._put(m) for m in ds.features_masks],
                None if ds.labels_masks is None
                else [self._put(m) for m in ds.labels_masks])
            leaves = out.features + out.labels
            sources = (list(ds.features) + list(ds.labels)
                       + list(ds.features_masks or ())
                       + list(ds.labels_masks or ()))
        elif isinstance(ds, DataSet):
            out = DataSet(self._put(ds.features), self._put(ds.labels),
                          self._put(ds.features_mask),
                          self._put(ds.labels_mask))
            leaves = [out.features, out.labels]
            sources = [ds.features, ds.labels, ds.features_mask,
                       ds.labels_mask]
        else:
            return ds
        t1 = time.perf_counter()
        # Fence on the producer thread, and on the link alone (nothing
        # above is an executable). It stays: a step launched on a batch
        # that has not landed parks the device's compute stream behind
        # the link, a whole cycle idle; and it is what keeps at most
        # `depth` staged batches resident.
        jax.block_until_ready([a for a in leaves if a is not None])
        t2 = time.perf_counter()
        # what went over the link: the arrays that were the host's
        nbytes = sum(int(getattr(a, "nbytes", 0)) for a in sources
                     if not isinstance(a, jax.Array))
        self._h2d_bytes.inc(nbytes)
        if tracing.is_enabled():
            sid = tracing.add_span("etl/stage", t0, t2 - t0, bytes=nbytes,
                                   rows=ds.num_examples())
            tracing.add_span("etl/stage/put", t0, t1 - t0, parent=sid)
            tracing.add_span("etl/stage/fence", t1, t2 - t1, parent=sid)
        return out

    def _produce_item(self, ds, host_ms: float):
        n = getattr(ds, "num_examples", lambda: 0)()
        if self._sharding is not None and n % self._divisor != 0:
            # Indivisible ragged batch: staging under the sharding would
            # fail (and a host round-trip to pad would cost MORE than
            # letting the wrapper pad host-side). Pass through.
            staged, h2d_ms = ds, 0.0
        else:
            t0 = time.perf_counter()
            staged = self._stage(ds)
            h2d_ms = (time.perf_counter() - t0) * 1000.0
        try:
            staged._etl_host_ms = host_ms
            staged._etl_h2d_ms = h2d_ms
        except AttributeError:
            pass  # foreign batch type without attribute support
        return staged

    def async_supported(self) -> bool:
        return False  # already threaded; fit() must not double-wrap
