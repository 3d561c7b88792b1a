"""Deterministic, layout-independent parameter initialization.

The reference guarantees that model initialization is identical no matter how
the model is partitioned across DP replicas / PP stages, by seeding a fresh
MT19937 stream per Linear layer from its (in, out) dims
(/root/reference/shallowspeed/layers.py:103-113). We reproduce that scheme
bit-for-bit on host NumPy, then device_put — it is what makes "TPU run reaches
the NumPy reference's loss" a checkable statement, and what makes the
layout-independent model hash (utils.py) meaningful.
"""

import zlib

import numpy as np


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
