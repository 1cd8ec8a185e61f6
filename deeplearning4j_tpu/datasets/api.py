"""DataSet containers and iterator SPI (reference: nd4j ``DataSet`` /
``MultiDataSet`` and ``datasets/iterator/DataSetIterator`` SPI,
SURVEY.md §2.1 datasets/iterator).

Host-side containers are numpy; conversion to device arrays happens
once, inside the jitted step's argument transfer (and under pjit the
transfer is sharded per device)."""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Iterator, List, Optional, Sequence

import numpy as np


@dataclass
class DataSet:
    """features/labels (+ optional masks) minibatch container."""

    features: np.ndarray
    labels: np.ndarray
    features_mask: Optional[np.ndarray] = None
    labels_mask: Optional[np.ndarray] = None

    def num_examples(self) -> int:
        return int(self.features.shape[0])

    # -- the ONE npz shard codec (export-based training + object-store
    # shards share this format; reference BatchAndExportDataSetsFunction)

    def save_npz(self, file) -> None:
        """Write this minibatch as an npz shard (``file``: path or
        file-like)."""
        arrays = {"features": np.asarray(self.features),
                  "labels": np.asarray(self.labels)}
        if self.features_mask is not None:
            arrays["features_mask"] = np.asarray(self.features_mask)
        if self.labels_mask is not None:
            arrays["labels_mask"] = np.asarray(self.labels_mask)
        np.savez(file, **arrays)

    def to_npz_bytes(self) -> bytes:
        import io

        buf = io.BytesIO()
        self.save_npz(buf)
        return buf.getvalue()

    @classmethod
    def load_npz(cls, file) -> "DataSet":
        """Read a shard written by ``save_npz`` (path or file-like)."""
        with np.load(file) as z:
            return cls(
                features=z["features"], labels=z["labels"],
                features_mask=(
                    z["features_mask"] if "features_mask" in z else None
                ),
                labels_mask=(
                    z["labels_mask"] if "labels_mask" in z else None
                ),
            )

    @classmethod
    def from_npz_bytes(cls, data: bytes) -> "DataSet":
        import io

        return cls.load_npz(io.BytesIO(data))

    def split_test_and_train(self, n_train: int):
        return (
            DataSet(
                self.features[:n_train], self.labels[:n_train],
                None if self.features_mask is None else self.features_mask[:n_train],
                None if self.labels_mask is None else self.labels_mask[:n_train],
            ),
            DataSet(
                self.features[n_train:], self.labels[n_train:],
                None if self.features_mask is None else self.features_mask[n_train:],
                None if self.labels_mask is None else self.labels_mask[n_train:],
            ),
        )

    def shuffle(self, seed: int = 0) -> "DataSet":
        rng = np.random.RandomState(seed)
        idx = rng.permutation(self.num_examples())
        return DataSet(
            self.features[idx], self.labels[idx],
            None if self.features_mask is None else self.features_mask[idx],
            None if self.labels_mask is None else self.labels_mask[idx],
        )

    def batch_by(self, batch_size: int) -> List["DataSet"]:
        out = []
        n = self.num_examples()
        for i in range(0, n, batch_size):
            out.append(DataSet(
                self.features[i:i + batch_size],
                self.labels[i:i + batch_size],
                None if self.features_mask is None
                else self.features_mask[i:i + batch_size],
                None if self.labels_mask is None
                else self.labels_mask[i:i + batch_size],
            ))
        return out


@dataclass
class ChunkedDataSet:
    """k same-shaped minibatches pre-stacked on a leading axis
    ([k, b, ...]) — the payload an input pipeline hands the engines'
    fused multi-step dispatch DIRECTLY, skipping the per-batch
    split-and-restack round trip (each split/stack is a device
    dispatch; through a high-latency link those dominated streamed
    training). Produced by ``DevicePrefetchIterator(emit_chunks=True)``
    and consumed natively by the engines' scan path."""

    features: np.ndarray      # [k, b, ...]
    labels: np.ndarray        # [k, b, ...]
    features_mask: Optional[np.ndarray] = None
    labels_mask: Optional[np.ndarray] = None

    @property
    def k(self) -> int:
        return int(np.shape(self.features)[0])

    def num_examples(self) -> int:
        s = np.shape(self.features)
        return int(s[0]) * int(s[1])

    def to_datasets(self) -> List["DataSet"]:
        """Unstack into k per-batch DataSets (the fallback for
        consumers without a fused chunk path)."""
        def at(a, i):
            return None if a is None else a[i]

        return [
            DataSet(
                features=self.features[i], labels=self.labels[i],
                features_mask=at(self.features_mask, i),
                labels_mask=at(self.labels_mask, i),
            )
            for i in range(self.k)
        ]


@dataclass
class PlacedDataSet:
    """A minibatch that has already been materialized, cast, and
    placed on device (sharded when a mesh is in play) by an input
    pipeline — the payload ``datasets.prefetch.PrefetchIterator``
    hands the engines so the host->device scatter happens on the
    prefetch thread, off the step's critical path.

    ``features``/``labels``/masks are device arrays (or, for the DAG
    engine, lists of per-slot device arrays) in exactly the layout the
    consumer's placement function produced; consumers that receive one
    skip their own placement. ``num_rows`` is the count of VALID
    examples — when a trailing partial batch was padded up to the
    data-parallel degree, ``num_rows`` is the pre-padding size (the
    honest examples/sec signal) while the arrays carry the padded
    rows, masked out of the loss. ``has_masks`` caches whether any
    mask rides along (the trainer's step choice needs it without
    re-walking graph mask lists)."""

    features: object
    labels: object
    features_mask: object = None
    labels_mask: object = None
    num_rows: Optional[int] = None
    has_masks: Optional[bool] = None

    def num_examples(self) -> int:
        if self.num_rows is not None:
            return int(self.num_rows)
        first = self.features
        if isinstance(first, (list, tuple)):
            first = first[0]
        return int(np.shape(first)[0])


@dataclass
class PlacedChunk:
    """A block of k same-shaped minibatches stacked ``[k, b, ...]``
    AND already placed on device — the double-buffered feed payload of
    the megastep executor. A ``PrefetchIterator`` in chunk-stacking
    mode assembles the next block and runs its ``chunk_placement``
    (stack + ``device_put``, e.g. ``DistributedTrainer.place_chunk``)
    on the worker thread while the device executes the current
    megastep, so the K-step dispatch never waits on a host->device
    copy. ``num_rows`` counts valid examples across all k steps (the
    examples/sec signal)."""

    features: object          # [k, b, ...] device array (or list)
    labels: object
    features_mask: object = None
    labels_mask: object = None
    num_rows: Optional[int] = None

    @property
    def k(self) -> int:
        first = self.features
        if isinstance(first, (list, tuple)):
            first = first[0]
        return int(np.shape(first)[0])

    def num_examples(self) -> int:
        if self.num_rows is not None:
            return int(self.num_rows)
        first = self.features
        if isinstance(first, (list, tuple)):
            first = first[0]
        s = np.shape(first)
        return int(s[0]) * int(s[1])

    def to_datasets(self) -> List["DataSet"]:
        """Unstack into k per-batch DataSets (device slices) — the
        per-step fallback for trailing partial blocks."""
        def at(a, i):
            return None if a is None else a[i]

        return [
            DataSet(
                features=at(self.features, i), labels=at(self.labels, i),
                features_mask=at(self.features_mask, i),
                labels_mask=at(self.labels_mask, i),
            )
            for i in range(self.k)
        ]


@dataclass
class MultiDataSet:
    """Multi-input/multi-output container (reference nd4j MultiDataSet,
    consumed by ComputationGraph)."""

    features: Sequence[np.ndarray]
    labels: Sequence[np.ndarray]
    features_masks: Optional[Sequence[Optional[np.ndarray]]] = None
    labels_masks: Optional[Sequence[Optional[np.ndarray]]] = None

    def num_examples(self) -> int:
        return int(self.features[0].shape[0])


def payload_arrays(item):
    """An item's features and labels, one array at a time (arrays, or
    lists of arrays as the DAG engine takes them)."""
    for part in (item.features, item.labels):
        yield from part if isinstance(part, (list, tuple)) else (part,)


def payload_bytes(item) -> int:
    """Bytes of an item's features and labels: the ``bytes`` attr of
    the ``prefetch.produce`` spans."""
    return sum(int(getattr(a, "nbytes", 0) or 0)
               for a in payload_arrays(item))


class DataSetIterator:
    """Iterator SPI (reference ``DataSetIterator``). Subclasses
    implement ``__next__``/``has_next``/``reset``; iteration protocol
    provided for pythonic loops."""

    def __iter__(self) -> Iterator[DataSet]:
        self.reset()
        return self

    def __next__(self) -> DataSet:
        if not self.has_next():
            raise StopIteration
        return self.next()

    def next(self) -> DataSet:
        raise NotImplementedError

    def has_next(self) -> bool:
        raise NotImplementedError

    def reset(self) -> None:
        pass

    def batch(self) -> int:
        raise NotImplementedError

    def total_examples(self) -> int:
        return -1


class ListDataSetIterator(DataSetIterator):
    """Iterate a pre-built list of minibatches (reference
    ``ListDataSetIterator``)."""

    def __init__(self, batches: Sequence[DataSet]):
        self._batches = list(batches)
        self._pos = 0

    def next(self) -> DataSet:
        ds = self._batches[self._pos]
        self._pos += 1
        return ds

    def has_next(self) -> bool:
        return self._pos < len(self._batches)

    def reset(self) -> None:
        self._pos = 0

    def batch(self) -> int:
        return self._batches[0].num_examples() if self._batches else 0

    def total_examples(self) -> int:
        return sum(b.num_examples() for b in self._batches)


class ExistingDataSetIterator(DataSetIterator):
    """Wrap any iterable of DataSets (reference
    ``ExistingDataSetIterator``)."""

    def __init__(self, iterable):
        self._iterable = iterable
        self._it = None

    def __iter__(self):
        self._it = iter(self._iterable)
        return self

    def __next__(self):
        if self._it is None:
            self._it = iter(self._iterable)
        return next(self._it)

    def reset(self):
        self._it = None


def resolve_synthetic_opt_in(
    allow_synthetic: Optional[bool], dataset: str, where: str,
) -> None:
    """Shared gate for synthetic-data fallbacks (MNIST/CIFAR): real
    data missing is an error unless the caller opted in explicitly or
    via ``DL4J_TPU_ALLOW_SYNTHETIC=1``; opting in still warns loudly.
    Returns None on opt-in; raises FileNotFoundError otherwise."""
    import os
    import warnings

    if allow_synthetic is None:
        allow_synthetic = os.environ.get(
            "DL4J_TPU_ALLOW_SYNTHETIC", ""
        ).lower() in ("1", "true", "on")
    if not allow_synthetic:
        raise FileNotFoundError(
            f"{dataset} data not found in {where}. Place the data "
            "there, or opt in to synthetic data with "
            "allow_synthetic=True / DL4J_TPU_ALLOW_SYNTHETIC=1."
        )
    warnings.warn(
        f"{dataset} data not found — using SYNTHETIC "
        f"class-conditional data (not real {dataset}).",
        RuntimeWarning, stacklevel=3,
    )
