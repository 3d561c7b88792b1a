"""Data layer: MNIST-784 parquet/npy loading with DP sharding + microbatching.

Capability parity with /root/reference/shallowspeed/dataset.py: same on-disk
format (``x_{train,val}.parquet`` + ``y_{train,val}.npy``), same drop-last to a
multiple of the global batch size (dataset.py:52), same strided DP shard
``X[rank : full : size]`` with a contiguous copy (dataset.py:57-58), same
microbatch slicing arithmetic (dataset.py:66-80), same divisibility asserts,
and deliberately NO shuffling — determinism is part of the correctness story
("distributed == sequential" is checked float-for-float).

TPU additions: ``epoch_arrays()`` materializes the whole local shard as
``(num_batches, M, mubatch, dim)`` host arrays so the training loop can feed
jitted steps (or a whole-epoch lax.scan) without per-microbatch host slicing —
the reference's per-instruction ``load_micro_batch_*`` host copies would
serialize a TPU pipeline on dispatch overhead.
"""

import os
from pathlib import Path

import numpy as np


def _read_features(save_dir: Path, suffix: str) -> np.ndarray:
    pq = save_dir / f"x_{suffix}.parquet"
    npy = save_dir / f"x_{suffix}.npy"
    if pq.exists():
        import pandas as pd

        return pd.read_parquet(pq).to_numpy(dtype=np.float32)
    if npy.exists():
        return np.load(npy).astype(np.float32)
    raise FileNotFoundError(
        f"No features found at {pq} or {npy}. Run `python prepare_data.py` first."
    )


def _read_tokens(save_dir: Path, suffix: str):
    """A packed token set: ``tokens_<suffix>.npy`` and ``segments_<suffix>
    .npy``, both int32 ``(rows, seq_len + 1)`` (what benchmarks/datasets/
    packed_tokens.py writes): a row's inputs are ``[:-1]``, its targets
    ``[1:]``, and ``segments`` numbers the row's documents from 0."""
    found = []
    for name in ("tokens", "segments"):
        path = save_dir / f"{name}_{suffix}.npy"
        if not path.exists():
            raise FileNotFoundError(
                f"No token set at {path}: a token model trains on "
                f"tokens_{suffix}.npy and segments_{suffix}.npy"
            )
        found.append(np.load(path).astype(np.int32))
    tokens, segments = found
    if tokens.shape != segments.shape or tokens.ndim != 2:
        raise ValueError(
            f"tokens {tokens.shape} and segments {segments.shape} must be one "
            f"(rows, seq_len + 1) shape"
        )
    return tokens, segments


def packed_counts(segments):
    """What a packed token set holds: ``{"tokens", "documents", "pairs"}``
    of the rows' INPUTS (``segments[:, :-1]``); ``pairs`` counts the (query,
    key) pairs a causal, same-document mask admits, the query itself
    included: a token at offset ``i`` of its document (within the row)
    admits ``i + 1``."""
    seg = np.asarray(segments)[:, :-1]
    first = np.ones(seg.shape, bool)
    first[:, 1:] = seg[:, 1:] != seg[:, :-1]
    at = np.broadcast_to(np.arange(seg.shape[1]), seg.shape)
    start = np.maximum.accumulate(np.where(first, at, 0), axis=1)
    return {
        "tokens": int(seg.size),
        "documents": int(first.sum()),
        "pairs": int((at - start + 1).sum()),
    }


class Dataset:
    """One split (train or val) of the MNIST-784-format dataset.

    Construction mirrors the reference's signature
    (dataset.py:19-31): ``mubatch_size`` is the per-DP-replica microbatch and
    must divide the local batch ``global_batch_size // DP_size``.

    ``tokens=True`` reads a packed token set instead (``_read_tokens``):
    ``input_X`` holds the rows' token ids and ``target_y`` their document
    numbers, int32, and everything below (drop-last, shard, microbatch
    slicing, ``epoch_arrays``) treats them as it treats features and labels.
    """

    def __init__(
        self, save_dir, global_batch_size, mubatch_size, validation=False,
        tokens=False,
    ):
        self.save_dir = Path(save_dir)
        if not self.save_dir.is_dir():
            raise FileNotFoundError(
                f"{self.save_dir} is not a directory — run `python prepare_data.py`"
            )
        self.global_batch_size = int(global_batch_size)
        self.mubatch_size = int(mubatch_size)
        self.local_batch_size = None
        self._val = validation
        self._tokens = bool(tokens)
        self.input_X = None
        self.target_y = None

    # -- loading ------------------------------------------------------------

    def load(self, DP_rank=0, DP_size=1):
        if not (0 <= DP_rank < DP_size):
            raise ValueError(f"DP_rank {DP_rank} out of range for DP_size {DP_size}")
        if self.global_batch_size % DP_size != 0:
            raise ValueError("global batch size must be divisible by DP size")
        self.local_batch_size = self.global_batch_size // DP_size
        if self.local_batch_size % self.mubatch_size != 0:
            raise ValueError("microbatch size must divide the local batch size")

        suffix = "val" if self._val else "train"
        if self._tokens:
            X, y = _read_tokens(self.save_dir, suffix)
        else:
            X = _read_features(self.save_dir, suffix)
            y = np.load(self.save_dir / f"y_{suffix}.npy").astype(np.float32)
        if len(X) != len(y):
            raise ValueError("feature/target length mismatch")

        # drop-last so every batch is exactly global_batch_size long — keeps
        # training equivalent across microbatch counts (dataset.py:49-52)
        self.raw_len = len(X)  # pre-drop-last size, for diagnostics
        full = len(X) - (len(X) % self.global_batch_size)
        # strided DP shard; contiguous copy for clean host->device transfers
        self.input_X = np.ascontiguousarray(X[DP_rank:full:DP_size])
        self.target_y = np.ascontiguousarray(y[DP_rank:full:DP_size])

    def _require_loaded(self):
        if self.input_X is None:
            raise RuntimeError("Dataset not loaded — call .load(DP_rank, DP_size) first")

    def __len__(self):
        self._require_loaded()
        return len(self.input_X)

    # -- reference-parity microbatch access (dataset.py:66-86) --------------

    def _mubatch_slice(self, batch_id, mubatch_id):
        self._require_loaded()
        assert batch_id < self.get_num_batches()
        assert mubatch_id < self.get_num_mubatches()
        start = batch_id * self.local_batch_size + mubatch_id * self.mubatch_size
        return slice(start, start + self.mubatch_size)

    def load_micro_batch_input(self, batch_id, mubatch_id):
        return self.input_X[self._mubatch_slice(batch_id, mubatch_id)]

    def load_micro_batch_target(self, batch_id, mubatch_id):
        return self.target_y[self._mubatch_slice(batch_id, mubatch_id)]

    def get_num_batches(self):
        return len(self) // self.local_batch_size

    def get_num_mubatches(self):
        return self.local_batch_size // self.mubatch_size

    # -- TPU-friendly bulk access -------------------------------------------

    def epoch_arrays(self):
        """Whole local shard as (num_batches, M, mubatch, dim) fp32 arrays
        (int32 ids and document numbers for a token set).

        Row order is identical to sequential microbatch iteration, so feeding
        these to a scanned step reproduces the reference's data order exactly.
        """
        self._require_loaded()
        nb, M, mb = self.get_num_batches(), self.get_num_mubatches(), self.mubatch_size
        X = self.input_X[: nb * self.local_batch_size]
        y = self.target_y[: nb * self.local_batch_size]
        return (
            X.reshape(nb, M, mb, X.shape[-1]),
            y.reshape(nb, M, mb, y.shape[-1]),
        )


def default_data_dir() -> Path:
    return Path(os.environ.get("SHALLOWSPEED_DATA_DIR", "data/mnist_784"))
