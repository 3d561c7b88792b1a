"""Deterministic, layout-independent parameter initialization.

The reference guarantees that model initialization is identical no matter how
the model is partitioned across DP replicas / PP stages, by seeding a fresh
MT19937 stream per Linear layer from its (in, out) dims
(/root/reference/shallowspeed/layers.py:103-113). We reproduce that scheme
bit-for-bit on host NumPy, then device_put — it is what makes "TPU run reaches
the NumPy reference's loss" a checkable statement, and what makes the
layout-independent model hash (utils.py) meaningful.

No leaf's values depend on any other leaf's (each has a stream of its own), so
a model's leaves are drawn concurrently (``draw_leaves``): the same functions,
the same streams, the same bits, on as many threads as the process may use.
"""

import concurrent.futures
import os
import zlib
from typing import Callable, NamedTuple

import numpy as np

from shallowspeed_tpu.observability.metrics import NullMetrics

# A list of draws with fewer elements than this is drawn by the calling
# thread. Measured once (PERF.md §6, PR 38: 16 equal draws through
# ``draw_leaves`` with the pool on and off, on the 13 cores of the chip's
# host): handing 16 draws to 13 threads costs 5 to 8 ms however small they
# are, which 2**20 elements (11 ms of ``token_leaf_init``, 26 ms of
# ``linear_init``) are the first to pay back. ``mnist-mlp`` (181,105) and the
# tests' trees lie under it, the benchmark's other models 85 times and more
# above.
POOL_MIN_ELEMENTS = 1 << 20


def linear_init(in_dim: int, out_dim: int):
    """Weights N(0,1)/sqrt(in) fp32 with per-layer seed in + 1337*out; zero bias.

    Matches reference layers.py:106-113 exactly (same bit-stream, same dtype
    ops: normal -> astype(float32) -> divide by float64 sqrt).
    """
    rs = np.random.RandomState(
        np.random.MT19937(np.random.SeedSequence(in_dim + out_dim * 1337))
    )
    w = rs.normal(0.0, 1.0, size=(out_dim, in_dim)).astype(np.float32) / np.sqrt(
        in_dim
    )
    b = np.zeros((1, out_dim), dtype=np.float32)
    return np.asarray(w, dtype=np.float32), b


def token_leaf_init(layer_index: int, name: str, shape, kind: str):
    """One leaf of a token model, float32, from a stream seeded by the
    layer's index and the leaf's name (so two layers of one shape differ, and
    no layout or size of anything else moves a leaf's values).

    ``weight``: N(0, 0.02), the family's initializer; ``ones``: a norm's
    scale; ``taps``: U(-1/sqrt(K), 1/sqrt(K)) for K taps; ``a_log``: log of
    U(1, 16) and ``dt_bias``: the inverse softplus of exp(U(log 0.001, log
    0.1)), the Gated DeltaNet's published initial decay (assumed: the
    configuration does not state them)."""
    rs = np.random.Generator(
        np.random.PCG64(zlib.crc32(f"{layer_index}/{name}".encode()))
    )
    if kind == "weight":  # drawn in float32: a billion of them, at set-up
        leaf = rs.standard_normal(shape, dtype=np.float32) * np.float32(0.02)
    elif kind == "ones":
        leaf = np.ones(shape)
    elif kind == "taps":
        bound = shape[-1] ** -0.5
        leaf = rs.uniform(-bound, bound, size=shape)
    elif kind == "a_log":
        leaf = np.log(rs.uniform(1.0, 16.0, size=shape))
    elif kind == "dt_bias":
        dt = np.exp(rs.uniform(np.log(0.001), np.log(0.1), size=shape))
        leaf = dt + np.log(-np.expm1(-dt))
    else:
        raise ValueError(f"unknown leaf kind {kind!r}")
    return np.asarray(leaf, dtype=np.float32)


class Draw(NamedTuple):
    """One independent draw: ``fn(*args)`` gives a leaf, or a tuple of
    leaves, of ``sizes`` elements each."""

    fn: Callable
    args: tuple
    sizes: tuple


def draw_leaves(draws, metrics=None):
    """``[d.fn(*d.args) for d in draws]``, drawn concurrently: one thread per
    core the process may use and never more than there are draws, the largest
    draws started first (a model's largest leaf is the floor of the whole
    draw and must not start last), the results in the order given. With one
    worker, or under ``POOL_MIN_ELEMENTS``, it is that serial loop itself.

    The pool runs under the host span ``draw``, opened and closed by the
    calling thread (no span opens in a worker: its path would not lie under
    the caller's); with a recorder (``metrics``) the span is a record of the
    stream too and one event ``weights_init`` says how far the pool engaged."""
    sizes = [n for d in draws for n in d.sizes]
    workers = 1
    if sum(sizes) >= POOL_MIN_ELEMENTS:
        workers = min(len(os.sched_getaffinity(0)), len(draws))
    if metrics is None:
        metrics = NullMetrics()
    with metrics.span("draw") as drawn:
        if workers == 1:
            leaves = [d.fn(*d.args) for d in draws]
        else:
            pool = concurrent.futures.ThreadPoolExecutor(workers, "draw")
            try:
                largest_first = sorted(
                    range(len(draws)), key=lambda i: -sum(draws[i].sizes)
                )
                futures = [None] * len(draws)
                for i in largest_first:
                    futures[i] = pool.submit(draws[i].fn, *draws[i].args)
                leaves = [future.result() for future in futures]
            finally:
                # after an error the draws not yet started are dropped
                pool.shutdown(cancel_futures=True)
    metrics.event(
        "weights_init", workers=workers, leaves=len(sizes), elements=sum(sizes),
        largest_leaf_elements=max(sizes, default=0), draw_s=drawn.seconds,
    )
    return leaves
