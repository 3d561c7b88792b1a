"""Stateless op kernels: forward and hand-derived backward, in jax.numpy.

Capability parity with the reference's NumPy kernels
(/root/reference/shallowspeed/functional.py:4-44): relu, linear, softmax and
MSE-after-softmax loss, each with an explicit hand-written VJP. The backward
functions are part of the framework surface (we do NOT rely on jax.grad in the
training path; jax.grad serves as a test oracle instead — strictly stronger
than the reference's finite-difference tests).

TPU notes:
- everything is fp32; matmuls default to ``precision=HIGHEST`` so the loss
  trajectory is comparable float-for-float with a NumPy oracle. Callers that
  want raw MXU throughput can pass ``precision='default'`` to use bf16-input
  passes on the systolic array.
- ops are shape-polymorphic and padding-safe: zero-padded rows/columns stay
  exactly zero through linear/relu, and the softmax head takes an explicit
  validity mask so padded logits contribute nothing. This is what lets the
  SPMD pipeline executor run unequal stages as fixed-shape stacked params.
"""

import os
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

from shallowspeed_tpu.observability.scopes import scope, scoped

# Opt-in Pallas kernel path for the fused linear+relu hot op (pallas_ops.py);
# default is plain XLA, which already fuses well for this model class.
_PALLAS = os.environ.get("SHALLOWSPEED_PALLAS", "0") == "1"


def set_pallas(enabled: bool) -> None:
    """Select the kernel backend for functions built AFTER this call.

    The flag is read at TRACE time: step/predict functions that are already
    jitted keep whichever backend they were traced with (their compiled
    executables are cached). Rebuild the function (e.g. construct a new
    TrainingSession / call make_train_epoch again) after toggling.
    """
    global _PALLAS
    _PALLAS = bool(enabled)


def pallas_enabled() -> bool:
    return _PALLAS

# Matmul precision used across the framework. HIGHEST = fp32 accumulate with
# full-precision inputs (required for NumPy-trajectory parity tests); callers
# may override per-call.
DEFAULT_PRECISION = lax.Precision.HIGHEST

# Large-negative used to mask invalid logits. Not -inf: exp(-inf - -inf) would
# produce NaN when a fully-masked row meets the global max subtraction.
_NEG_MASK = -1e30


@scoped("act")
def relu(x):
    """max(x, 0). Reference: functional.py:4-5."""
    return jnp.maximum(x, 0.0)


@scoped("act")
def relu_grad(g, bitmask):
    """VJP of relu given the cached activation bitmask (out > 0).

    Reference: functional.py:8-10 (bitmask of the *input*; identical since the
    reference computes the mask on the relu input and we compute it on the
    pre-activation — same tensor).
    """
    return g * bitmask


_INV_SQRT2 = 0.7071067811865476
_INV_SQRT_2PI = 0.3989422804014327


@scoped("act")
def gelu(x):
    """Exact (erf) GELU: x * Phi(x) — the transformer-block activation of
    the model zoo. gelu(0) == 0, so zero-padded rows and columns stay
    exactly zero — the same padding invariant linear/relu keep, which the
    stacked SPMD executor relies on."""
    return 0.5 * x * (1.0 + lax.erf(x * _INV_SQRT2))


@scoped("act")
def gelu_grad_mult(z):
    """d gelu(z)/dz = Phi(z) + z * phi(z), from the pre-activation ``z``.

    The gelu analogue of relu's cached bitmask: the backward multiplies the
    incoming grad elementwise, ``g_eff = g * gelu_grad_mult(z)``. The value
    at z == 0 is 0.5 (not 0), but padded positions carry g == 0, so nothing
    leaks into padding.
    """
    phi = _INV_SQRT_2PI * jnp.exp(-0.5 * z * z)
    return 0.5 * (1.0 + lax.erf(z * _INV_SQRT2)) + z * phi


@scoped("linear/fwd")
def linear(x, w, b, precision=DEFAULT_PRECISION):
    """y = x @ w.T + b with w: (out, in), b: (1, out) or (out,), or ``None``
    for a product without a bias (the token model's).

    Reference: functional.py:13-17.
    """
    y = jnp.matmul(x, w.T, precision=precision)
    return y if b is None else y + jnp.reshape(b, (1, -1))


@scoped("linear/dgrad")
def linear_grad_input(g, w, precision=DEFAULT_PRECISION):
    """The relay-critical half of linear's VJP: dx = g @ w.

    This is the ONLY product the upstream pipeline stage needs — it sits on
    the inter-stage backward relay critical path (PipeDream, arxiv
    1806.03377), which is why the split-backward schedules run it at the
    tick the combined backward would have and defer the weight half.
    """
    return jnp.matmul(g, w, precision=precision)


@scoped("linear/wgrad")
def linear_grad_weight(g, x, precision=DEFAULT_PRECISION):
    """The deferrable half of linear's VJP: (dw, db) = (g.T @ x, sum_rows(g)).

    Consumes only the stashed activation ``x`` and the (stashed) output-grad
    ``g`` — nothing downstream of it relays anywhere, so a split schedule
    (2BP, arxiv 2405.18047) may pack it into otherwise-idle bubble ticks.
    """
    dw = jnp.matmul(g.T, x, precision=precision)
    db = g.sum(axis=0)
    return dw, db


def linear_grad(g, x, w, precision=DEFAULT_PRECISION):
    """VJP of linear: returns (dx, dw, db) = (g @ w, g.T @ x, sum_rows(g)).

    Reference: functional.py:20-21. Expressed as the composition of the
    split halves (``linear_grad_input`` + ``linear_grad_weight``) so the
    combined and two-stage backward paths can never disagree: they are the
    same expressions, executed at different ticks.
    """
    dx = linear_grad_input(g, w, precision=precision)
    dw, db = linear_grad_weight(g, x, precision=precision)
    return dx, dw, db


def linear_relu_grad_input(g, bitmask, w, precision=DEFAULT_PRECISION):
    """Split B-input of the linear+relu unit: dx from W and the relu mask
    (the stashed activation is NOT needed — only B-weight reads it)."""
    return linear_grad_input(relu_grad(g, bitmask), w, precision=precision)


def linear_relu_grad_weight(g, bitmask, x, precision=DEFAULT_PRECISION):
    """Split B-weight of the linear+relu unit: (dw, db) from the stashed
    activation and the stashed output-grad."""
    return linear_grad_weight(relu_grad(g, bitmask), x, precision=precision)


def linear_relu_fused(x, w, b, precision=DEFAULT_PRECISION):
    """Fused y = relu(x @ w.T + b); returns (y, pre-activation bitmask).

    XLA path by default; the Pallas kernel (pallas_ops.py) when enabled —
    same contract either way, so the model layer is backend-agnostic.
    """
    if _PALLAS:
        from shallowspeed_tpu import pallas_ops

        with scope("linear/fwd"):
            y, mask = pallas_ops.linear_relu_fwd(x, w, b, precision=precision)
        return y, mask > 0
    y = linear(x, w, b, precision=precision)
    with scope("act"):
        mask = y > 0
    return relu(y), mask


def linear_relu_grad_fused(g, bitmask, x, w, precision=DEFAULT_PRECISION):
    """Backward of linear_relu_fused: (dx, dw, db) in one fused unit."""
    if _PALLAS:
        from shallowspeed_tpu import pallas_ops

        with scope("linear/dgrad"):  # one kernel for dx, dw and db
            dx, dw, db = pallas_ops.linear_relu_bwd(
                g, bitmask.astype(jnp.float32), x, w, precision=precision
            )
        return dx, dw, jnp.reshape(db, (-1,))
    return linear_grad(relu_grad(g, bitmask), x, w, precision=precision)


def _stability_max(z, group_rows):
    """The max subtracted for stability: over the WHOLE array (the
    reference's quirk), or — with ``group_rows`` — over each consecutive
    group of that many rows, reproducing exactly what a per-microbatch loop
    would have computed. Grouping matters because the ``+1e-7`` denominator
    breaks exact shift-invariance."""
    if group_rows is None:
        return jnp.max(z)
    g = z.reshape(-1, group_rows, z.shape[-1])
    m = jnp.max(g, axis=(1, 2), keepdims=True)
    return jnp.broadcast_to(m, g.shape).reshape(z.shape)


@scoped("softmax")
def softmax(z, valid_mask=None, group_rows=None):
    """Row softmax with the reference's exact quirks (functional.py:24-27):

    - the max subtracted for stability is the *global* max over the whole
      array (not per-row) — or per consecutive ``group_rows``-row group, for
      callers that fuse several microbatches into one call and need the
      per-microbatch semantics float-for-float,
    - the denominator gets ``+ 1e-7``.

    ``valid_mask`` (broadcastable to z, True = real logit) supports the padded
    SPMD layout: masked positions get probability exactly 0 and do not affect
    the max or the row sums.
    """
    if valid_mask is not None:
        z = jnp.where(valid_mask, z, _NEG_MASK)
    z_exp = jnp.exp(z - _stability_max(z, group_rows))
    return z_exp / (z_exp.sum(axis=1, keepdims=True) + 1e-7)


@scoped("softmax")
def softmax_grad(g, z, valid_mask=None, group_rows=None):
    """VJP of softmax, recomputing the forward from the cached *input* z.

    Recomputation instead of stashing the output is deliberate: on TPU the
    extra exp/sum fuses into the backward and saves HBM traffic — and it is
    also exactly what the reference does (functional.py:30-35).
    """
    out = softmax(z, valid_mask, group_rows)
    gz = out * g
    return gz - out * gz.sum(axis=-1, keepdims=True)


@scoped("loss")
def mse_loss(p, t, batch_size):
    """sum((t - p)^2) / batch_size. Reference: functional.py:38-40.

    ``batch_size`` is the GLOBAL batch size: this single scaling is what makes
    microbatch gradient accumulation + DP SUM-reduction reproduce the serial
    full-batch gradient with no averaging anywhere (reference layers.py:160).
    """
    return ((t - p) ** 2).sum() / batch_size


@scoped("loss")
def mse_loss_grad(p, t, batch_size):
    """dL/dp = -2 (t - p) / batch_size. Reference: functional.py:43-44."""
    return -2.0 * (t - p) / batch_size


@partial(jax.jit, static_argnames=("batch_size", "group_rows"))
@scoped("head_grad")
def softmax_mse_head_grad(z, t, batch_size, valid_mask=None, group_rows=None):
    """Fused loss-head backward: d(MSE(softmax(z), t))/dz.

    The reference implements this as two chained Module backwards
    (MSELoss layers.py:157-163 then Softmax layers.py:89-93); fused here so
    XLA emits a single elementwise pipeline over the logits.
    """
    p = softmax(z, valid_mask, group_rows)
    g = mse_loss_grad(p, t, batch_size)
    return softmax_grad(g, z, valid_mask, group_rows)


# ---------------------------------------------------------------------------
# Token-model ops (model.py's ``token_*``): an embedding, RMSNorm, SwiGLU,
# cross-entropy over the (sliced) vocabulary, blocked attention under a causal
# and same-document mask, the short causal convolution, the gated delta rule
# as a chunked scan. Matrix products with a weight go through ``linear`` and
# its two gradient halves above. Each op hands back its output and the
# function that pulls a cotangent back; both trace under the op's scope, so
# the class table attributes forward and backward alike. Pointwise ops
# recompute their forward inside the backward (``jax.vjp`` at the time the
# cotangent arrives): what is kept between the passes is the op's inputs.
# Attention's backward is written out (its blocking IS the algorithm). The
# scan has two forms and ``scan_path`` between them, by shape alone: where
# the shapes tile, two Pallas kernels (``pallas_ops.gdn_scan_fwd`` / ``_bwd``,
# the state in VMEM across the chunks, the backward written out, the state
# entering each chunk and the chunk's inverse kept between the passes);
# everywhere else the chunked form in ``jax.numpy`` with ``jax.vjp`` for its
# backward, which keeps one state per block of chunks.
# ---------------------------------------------------------------------------

ATTN_BLOCK = 512  # queries and keys per block of the attention core
SCAN_CHUNK = 128  # tokens per chunk of the gated delta rule
SCAN_BLOCK = 4  # chunks whose matrices are made (and, backward, rebuilt) at once
# The scan's own matrix products, whatever the session's precision: float32
# passes. The gradient that reaches W_q and W_k through the normalised q and k
# is about ten times as sensitive to rounding as the rest of the model
# (PERF.md section 6: moving the plain reference from bfloat16 operands to
# float32 moves their update by 11% and most other tensors' by 1 to 3%); with
# bfloat16 operands in the scan the system's update of them stood 10% from
# the reference's, with float32 passes 4%. (The attention core, given the
# same, moved nothing and cost 48 ms a step: it keeps the session's.)
SCAN_PRECISION = lax.Precision.HIGHEST
INVERSE_LEAF = 32  # rows of the diagonal blocks ``_unit_lower_inverse`` starts from
L2_EPS = 1e-6


def _pointwise(name, fn, *args):
    """``fn(*args)`` under ``scope(name)`` and the function that pulls a
    cotangent back to ``args`` (a tuple), which recomputes ``fn``."""
    with scope(name):
        out = fn(*args)

    def back(dout):
        with scope(name):
            return jax.vjp(fn, *args)[1](dout)

    return out, back


def fan_in(*cotangents):
    """The sum of the cotangents that reach one value from the ops that
    read it (a residual stream's branches, a projection's inputs)."""
    with scope("fanin"):
        total = cotangents[0]
        for more in cotangents[1:]:
            total = total + more
        return total


def _as_bfloat16(a):
    """``a`` rounded to bfloat16, still float32."""
    return a.astype(jnp.bfloat16).astype(a.dtype)


def dense(x, w, precision=DEFAULT_PRECISION):
    """``x @ w.T`` for ``x``: (..., in), ``w``: (out, in), through ``linear``;
    ``back(dy) -> (dx, dw)`` through ``linear_grad_input`` / ``_weight``.

    Under ``Precision.DEFAULT`` the operands of all three products are
    rounded to bfloat16 HERE, with float32 accumulation left to the product:
    the policy stated outright, the same on a CPU as on the chip. On the
    chip the compiler rounds the same operands (one layer's gradients agree
    to the last digit printed with and without this, PERF.md section 6), but
    it then keeps the operand it was handed: rounded here, the activation
    kept for the weight gradient is the rounded one, and the cell's step fell
    from 1,798 to 1,521 ms. The compiler folds the second conversion away."""
    lead, x2 = x.shape[:-1], x.reshape(-1, x.shape[-1])
    rounds = precision == lax.Precision.DEFAULT
    if rounds:
        with scope("linear/fwd"):
            x2, w = _as_bfloat16(x2), _as_bfloat16(w)
    y = linear(x2, w, None, precision=precision)

    def back(dy):
        g = dy.reshape(-1, dy.shape[-1])
        if rounds:
            with scope("linear/dgrad"):
                g = _as_bfloat16(g)
        dx = linear_grad_input(g, w, precision=precision)
        dw, _ = linear_grad_weight(g, x2, precision=precision)
        return dx.reshape(*lead, -1), dw

    return y.reshape(*lead, -1), back


def embed(table, tokens):
    """Rows of ``table`` (vocab, hidden) for ``tokens``; the gradient is a
    scatter-add of the rows' cotangents."""
    with scope("embed"):
        x = jnp.take(table, tokens, axis=0)

    def back(dx):
        with scope("embed"):
            return jnp.zeros_like(table).at[tokens.reshape(-1)].add(
                dx.reshape(-1, dx.shape[-1])
            )

    return x, back


def _rms(x, w, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _silu(x):
    return x * jax.nn.sigmoid(x)


def rms_norm(x, w, eps):
    """``x / rms(x) * w`` over the last axis."""
    return _pointwise("norm", lambda x, w: _rms(x, w, eps), x, w)


def residual_norm(x, branch, w, eps):
    """``x + rms_norm(branch)``: the family's norm sits on the branch."""
    return _pointwise("norm", lambda x, b, w: x + _rms(b, w, eps), x, branch, w)


def swiglu(gate, up):
    """``silu(gate) * up``, the SwiGLU's pointwise part."""
    return _pointwise("swiglu", lambda g, u: _silu(g) * u, gate, up)


def cross_entropy(logits, targets, step_tokens):
    """Sum over positions of ``logsumexp(logits) - logits[target]`` over
    ``step_tokens`` (the STEP's tokens, so microbatches add up to the step's
    mean, as ``mse_loss``'s global batch size does)."""

    def fn(z):
        picked = jnp.take_along_axis(z, targets[..., None], axis=-1)[..., 0]
        return jnp.sum(jax.nn.logsumexp(z, axis=-1) - picked) / step_tokens

    return _pointwise("head/xent", fn, logits)


def conv_silu(x, taps, seg):
    """``silu(conv(x))``: a causal depthwise convolution along axis 1 of
    ``x`` (rows, seq, channels) with ``taps`` (channels, K), tap ``j`` on the
    token ``j`` back, reading zero for a token of another document."""

    def fn(x, taps):
        y = jnp.zeros_like(x)
        for j in range(taps.shape[1]):
            back_x = jnp.pad(x, ((0, 0), (j, 0), (0, 0)))[:, : x.shape[1]]
            back_seg = jnp.pad(seg, ((0, 0), (j, 0)), constant_values=-1)[
                :, : x.shape[1]
            ]
            y = y + jnp.where((back_seg == seg)[..., None], back_x, 0.0) * taps[:, j]
        return _silu(y)

    return _pointwise("gdn/conv", fn, x, taps)


def qk_l2norm(q, k):
    """Per head (last axis): ``q / |q| / sqrt(d_k)``, ``k / |k|``."""

    def fn(q, k):
        unit = lambda a: a * lax.rsqrt(jnp.sum(a * a, -1, keepdims=True) + L2_EPS)  # noqa: E731
        return unit(q) * q.shape[-1] ** -0.5, unit(k)

    return _pointwise("gdn/gate", fn, q, k)


def delta_gates(b, a, a_log, dt_bias, neg_eigval):
    """``beta = sigmoid(b)`` (doubled under ``neg_eigval``) and the LOG of
    the decay, ``-exp(A_log) softplus(a + dt_bias)``."""

    def fn(b, a, a_log, dt_bias):
        beta = jax.nn.sigmoid(b) * (2.0 if neg_eigval else 1.0)
        return beta, -jnp.exp(a_log) * jax.nn.softplus(a + dt_bias)

    return _pointwise("gdn/gate", fn, b, a, a_log, dt_bias)


def gated_head_norm(o, gate, w, eps):
    """``rms_norm(o) * silu(gate)`` per head (last axis)."""
    return _pointwise(
        "gdn/gate", lambda o, g, w: _rms(o, w, eps) * _silu(g), o, gate, w
    )


def _block_len(length, want):
    """The largest divisor of ``length`` that is at most ``want``."""
    return next(c for c in range(min(want, length), 0, -1) if length % c == 0)


def _first_key_block(segb):
    """For each block of queries, the first block of keys that holds a token
    of a document some query of it belongs to. ``segb``: (rows, n, c),
    documents numbered upwards along a row. Over all rows: the earliest."""
    first_doc = segb[:, :, 0]  # of the queries of block i
    last_doc = segb[:, :, -1]  # of the keys of block j
    return jnp.min(
        jnp.sum(last_doc[:, None, :] < first_doc[:, :, None], axis=-1), axis=0
    ).astype(jnp.int32)


def _attn_mask(seg_q, seg_k, at_q, at_k):
    """(rows, 1, c, c): same document and key not after query."""
    return ((seg_q[:, :, None] == seg_k[:, None, :])
            & (at_q[:, None] >= at_k[None, :])[None])[:, None]


def attention(q, k, v, seg, precision=DEFAULT_PRECISION, block=ATTN_BLOCK):
    """``softmax(q k^T / sqrt(d) + M) v`` with ``M`` = causal and same
    document, by blocks: ``q``: (rows, heads, seq, d), ``k, v``: (rows,
    kv_heads, seq, d) with ``kv_heads`` dividing ``heads`` (grouped-query
    attention: query head ``h`` reads key/value head ``h // (heads //
    kv_heads)``; the group is one more axis of the products, no key or value
    is repeated), ``seg``: (rows, seq). For each block of queries a loop over
    the blocks of keys from the first that holds one of its documents up to
    its own (an online softmax), so no score matrix wider than a block
    exists and blocks the mask empties are not visited. The backward
    recomputes each visited block's scores from the saved log-sum-exp. ->
    ``o, back``; ``back(do) -> (dq, dk, dv)``."""
    rows, heads, seq, d = q.shape
    kv_heads = k.shape[1]
    if heads % kv_heads:
        raise ValueError(f"{kv_heads} key/value heads do not divide {heads} query heads")
    group = heads // kv_heads
    c = _block_len(seq, block)
    n = seq // c
    scale = d**-0.5
    # the axes of a block of queries: with one query head a key/value head
    # the products are the ungrouped ones, letter for letter
    Q = "bhq" if group == 1 else "bhgq"
    lead = (rows, heads) if group == 1 else (rows, kv_heads, group)

    def blocks(a):  # (rows, heads, seq, d) -> (n, *lead, c, d)
        shape = lead if a.shape[1] == heads else (rows, kv_heads)
        return jnp.moveaxis(a.reshape(*shape, n, c, d), len(shape), 0)

    def unblocks(a):  # (n, rows, ..., c, d) -> (rows, heads, seq, d)
        return jnp.moveaxis(a, 0, a.ndim - 3).reshape(rows, -1, seq, d)

    def dot(spec, a, b):
        return jnp.einsum(spec, a, b, precision=precision)

    def masked(seg_q, seg_k, at_q, at_k):
        mask = _attn_mask(seg_q, seg_k, at_q, at_k)
        return mask if group == 1 else mask[:, :, None]

    with scope("attn/core"):
        qb, kb, vb = blocks(q), blocks(k), blocks(v)
        segb = seg.reshape(rows, n, c)
        seg_n = jnp.moveaxis(segb, 1, 0)  # (n, rows, c)
        at = jnp.arange(seq).reshape(n, c)
        first_key = _first_key_block(segb)

        def query_block(i):
            q_i = qb[i]

            def key_block(j, carry):
                m, l, acc = carry
                s = dot(f"{Q}d,bhkd->{Q}k", q_i, kb[j]) * scale
                mask = masked(seg_n[i], seg_n[j], at[i], at[j])
                m_new = jnp.maximum(m, jnp.max(jnp.where(mask, s, _NEG_MASK), -1))
                p = jnp.where(mask, jnp.exp(s - m_new[..., None]), 0.0)
                fix = jnp.exp(m - m_new)
                acc = acc * fix[..., None] + dot(f"{Q}k,bhkd->{Q}d", p, vb[j])
                return m_new, l * fix + p.sum(-1), acc

            m, l, acc = lax.fori_loop(
                first_key[i], i + 1, key_block,
                (
                    jnp.full((*lead, c), _NEG_MASK, q.dtype),
                    jnp.zeros((*lead, c), q.dtype),
                    jnp.zeros((*lead, c, d), q.dtype),
                ),
            )
            return acc / l[..., None], m + jnp.log(l)

        ob, lse = lax.map(query_block, jnp.arange(n))
        o = unblocks(ob)

    def back(do):
        with scope("attn/core"):
            dob = blocks(do)
            delta = jnp.sum(dob * ob, axis=-1)  # (n, *lead, c)

            def query_block(i, grads):
                q_i, do_i = qb[i], dob[i]

                def key_block(j, carry):
                    dq_i, dk, dv = carry
                    s = dot(f"{Q}d,bhkd->{Q}k", q_i, kb[j]) * scale
                    mask = masked(seg_n[i], seg_n[j], at[i], at[j])
                    p = jnp.where(mask, jnp.exp(s - lse[i][..., None]), 0.0)
                    dp = dot(f"{Q}d,bhkd->{Q}k", do_i, vb[j])
                    ds = p * (dp - delta[i][..., None]) * scale
                    dq_i = dq_i + dot(f"{Q}k,bhkd->{Q}d", ds, kb[j])
                    dk = dk.at[j].add(dot(f"{Q}k,{Q}d->bhkd", ds, q_i))
                    dv = dv.at[j].add(dot(f"{Q}k,{Q}d->bhkd", p, do_i))
                    return dq_i, dk, dv

                dq, dk, dv = grads
                dq_i, dk, dv = lax.fori_loop(
                    first_key[i], i + 1, key_block, (jnp.zeros_like(q_i), dk, dv)
                )
                return dq.at[i].set(dq_i), dk, dv

            zeros = jnp.zeros_like(qb)
            zeros_kv = zeros if group == 1 else jnp.zeros_like(kb)
            dq, dk, dv = lax.fori_loop(0, n, query_block, (zeros, zeros_kv, zeros_kv))
            return unblocks(dq), unblocks(dk), unblocks(dv)

    return o, back


def _diagonal_blocks(a, b):
    """(..., c, c) -> (..., c // b, b, b): the blocks on the diagonal."""
    return jnp.stack(
        [a[..., i : i + b, i : i + b] for i in range(0, a.shape[-1], b)], axis=-3
    )


def _unit_lower_inverse(a):
    """``(I + a)^-1`` for strictly lower-triangular ``a`` (..., c, c), in
    matrix products only (the chip's own triangular solve goes row by row).
    The diagonal blocks of at most ``INVERSE_LEAF`` rows (on the chip 32
    took 0.63 of the time of 16, and 64 and 128 the time of 32) are inverted
    together by
    the finite series ``sum_k (-a)^k`` (``a`` is nilpotent), as the product
    ``(I - a)(I + a^2)(I + a^4)...``; then neighbouring blocks are merged,
    all pairs of a level at once, ``[[T11, 0], [-T22 a21 T11, T22]]``, until
    one is left. Float32 passes throughout: the products are tiny and
    everything after them inherits their error."""
    c = a.shape[-1]

    def dot(x, y):
        return jnp.matmul(x, y, precision=lax.Precision.HIGHEST)

    b = c
    while b > INVERSE_LEAF and b % 2 == 0:
        b //= 2
    power = -_diagonal_blocks(a, b)
    inverse = jnp.eye(b, dtype=a.dtype) + power
    done = 2  # powers 0 .. done - 1 are in ``inverse``
    while done < b:
        power = dot(power, power)
        inverse = inverse + dot(inverse, power)
        done *= 2
    while b < c:
        t11, t22 = inverse[..., 0::2, :, :], inverse[..., 1::2, :, :]
        a21 = _diagonal_blocks(a, 2 * b)[..., b:, :b]
        t21 = -dot(dot(t22, a21), t11)
        inverse = jnp.concatenate(
            [
                jnp.concatenate([t11, jnp.zeros_like(t11)], axis=-1),
                jnp.concatenate([t21, t22], axis=-1),
            ],
            axis=-2,
        )
        b *= 2
    return inverse[..., 0, :, :]


def _kernels_lower(dtype, precision):
    """What both pairs of scan kernels take: float32, and a precision Mosaic
    lowers (``HIGHEST`` and ``DEFAULT``; it refuses ``HIGH``)."""
    return jnp.dtype(dtype) == jnp.float32 and precision in (
        lax.Precision.HIGHEST, lax.Precision.DEFAULT
    )


def scan_path(seq, chunk, d_k, d_v, dtype, precision=SCAN_PRECISION):
    """Which form ``gated_delta_scan`` runs for these shapes: ``"pallas"``
    (the kernels of ``pallas_ops.gdn_scan_fwd`` / ``_bwd``) where they tile,
    ``"xla"`` (the chunked form in ``jax.numpy``) everywhere else. The
    kernels take float32, chunks of exactly 128 tokens (a (128 x 128)
    matrix is the MXU's own tile, and the inverse's block masks want a
    power of two), head sizes that are multiples of 8 (a sublane tile; the
    state's rows) up to 256, and a precision Mosaic lowers (``HIGHEST`` and
    ``DEFAULT``; it refuses ``HIGH``). Compiled and run on the chip against
    the XLA form at nine shapes from (8, 8) to (256, 256), the cell's (96,
    192) among them (PERF.md section 6, PR 33)."""
    tiles = (
        _block_len(seq, chunk) == 128
        and all(d % 8 == 0 and 8 <= d <= 256 for d in (d_k, d_v))
        and _kernels_lower(dtype, precision)
    )
    return "pallas" if tiles else "xla"


def kda_scan_path(seq, heads, d_k, d_v, dtype, precision=SCAN_PRECISION):
    """``scan_path`` for ``kda_scan``: the kernels of
    ``pallas_ops.kda_scan_fwd`` / ``_bwd`` where the shapes tile. They choose
    their own chunk, ``KDA_KERNEL_CHUNK`` tokens whatever chunk the XLA form
    would take, so a row is whole chunks of that; they read ``q, k, v`` and
    the log decay where the model keeps them, (rows, seq, heads, d), a block
    8 heads of a chunk (a head is one sublane of a token's tile, a channel a
    lane), so the heads come in eights, a key head is the 128 lanes and a
    value head 128 or 256 (compiled for the chip at both, run on it at the
    cell's 128; with 256 key channels Mosaic took 166 s over the pair:
    PERF.md section 6, PR 36); float32 and the precisions Mosaic lowers, as
    the scalar rule's."""
    tiles = (
        seq % KDA_KERNEL_CHUNK == 0
        and heads % 8 == 0
        and d_k == 128
        and d_v in (128, 256)
        and _kernels_lower(dtype, precision)
    )
    return "pallas" if tiles else "xla"


def _document_masks(seg, n, c):
    """-> ``first`` (rows, seq), ``segc`` (n, rows, c), ``carried`` and
    ``to_last`` (n, rows, c). ``carried``: the token belongs to the document
    of the token before its chunk, so the state entering the chunk reaches
    it (the first chunk's is zero); ``to_last``: to the document of the
    chunk's last token, so it reaches the state that leaves."""
    rows = seg.shape[0]
    segc = jnp.moveaxis(seg.reshape(rows, n, c), 1, 0)
    first = jnp.concatenate(
        [jnp.ones((rows, 1), bool), seg[:, 1:] != seg[:, :-1]], axis=1
    )
    entering = jnp.concatenate([seg[:, :1], seg[:, c - 1 : -1 : c]], axis=1).T
    return first, segc, segc == entering[:, :, None], segc == segc[:, :, -1:]


def _gated_delta_scan_pallas(q, k, v, beta, log_decay, seg, precision):
    """``gated_delta_scan`` through the kernels. Left in XLA, under the same
    scope: the layout (heads before tokens, so that a block is a (128, d)
    tile of one head), the per-token rows a chunk needs (the running sum of
    the log decay, the decay from the entering state and to the leaving one
    under the document masks) packed 8 to a block, and their pull-back."""
    from shallowspeed_tpu import pallas_ops as K

    rows, seq, heads, _ = q.shape
    c = 128  # what ``scan_path`` admits
    n = seq // c

    def heads_first(a):  # (rows, seq, heads, d) -> (rows * heads, seq, d)
        return jnp.moveaxis(a, 2, 1).reshape(rows * heads, seq, a.shape[-1])

    def rows_first(a):
        return jnp.moveaxis(a.reshape(rows, heads, seq, a.shape[-1]), 1, 2)

    def per_head(a):  # (n, rows, c) -> (rows, 1, n, c)
        return jnp.moveaxis(a, 0, 1)[:, None]

    with scope("gdn/scan"):
        first, segc, carried, to_last = _document_masks(seg, n, c)

        def packed(beta, log_decay):  # (rows, seq, heads) -> (rows * heads, n, 8, c)
            chunks = lambda a: jnp.moveaxis(a, 2, 1).reshape(rows, heads, n, c)  # noqa: E731
            # a document's first token takes no decay: its state starts from zero
            g = jnp.cumsum(chunks(jnp.where(first[..., None], 0.0, log_decay)), -1)
            g_in = jnp.where(per_head(carried), jnp.exp(g), 0.0)
            g_out = jnp.where(per_head(to_last), jnp.exp(g[..., -1:] - g), 0.0)
            keep = jnp.pad(g_in[..., -1:], ((0, 0),) * 3 + ((0, c - 1),))
            by_row = {
                K.GDN_G: g, K.GDN_BETA: chunks(beta),
                K.GDN_SEG: jnp.broadcast_to(per_head(segc).astype(g.dtype), g.shape),
                K.GDN_G_IN: g_in, K.GDN_G_OUT: g_out, K.GDN_KEEP: keep,
            }
            zero = jnp.zeros_like(g)
            return jnp.stack(
                [by_row.get(r, zero) for r in range(K.GDN_ROWS)], axis=-2
            ).reshape(rows * heads, n, K.GDN_ROWS, c)

        p, pull_rows = jax.vjp(packed, beta, log_decay)
        qh, kh, vh = heads_first(q), heads_first(k), heads_first(v)
        o, states, inverses = K.gdn_scan_fwd(
            qh, kh, vh, p, leaf=INVERSE_LEAF, precision=precision
        )
        o = rows_first(o)

    def back(do):
        with scope("gdn/scan"):
            dq, dk, dv, dp = K.gdn_scan_bwd(
                qh, kh, vh, p, states, inverses, heads_first(do), precision=precision
            )
            dbeta, dlog_decay = pull_rows(dp)
            return rows_first(dq), rows_first(dk), rows_first(dv), dbeta, dlog_decay

    return o, back


def gated_delta_scan(
    q, k, v, beta, log_decay, seg, precision=None, chunk=SCAN_CHUNK,
    block=SCAN_BLOCK,
):
    """The gated delta rule ``S_t = a_t S_{t-1} (I - b_t k_t k_t^T) + b_t v_t
    k_t^T``, ``o_t = S_t q_t``, ``S`` zero at a document's first token, in
    its chunked (WY) form: within a chunk of ``chunk`` tokens everything is
    matrix products (the inverse of one unit-triangular matrix gives every
    token's corrected value from the chunk's entering state), and only the
    (d_k x d_v) state goes from chunk to chunk. ``q, k``: (rows, seq, heads,
    d_k), ``v``: (rows, seq, heads, d_v), ``beta, log_decay``: (rows, seq,
    heads), ``seg``: (rows, seq). A document that starts inside a chunk masks
    the chunk's decay matrix (no pair across the start) and cuts the entering
    state off from the tokens after it. -> ``o, back``; ``back(do) -> (dq, dk,
    dv, dbeta, dlog_decay)``. Every product is ``precision`` (float32 passes
    by default, ``SCAN_PRECISION``) in either form.

    Two forms, the same mathematics, and ``scan_path`` between them from the
    shapes alone (no flag, no environment variable): the kernels of
    ``_gated_delta_scan_pallas`` where the shapes tile, the ``jax.numpy``
    form of ``_gated_delta_scan_xla`` (which ``block`` belongs to) everywhere
    else. The second is the first's oracle (tests/test_token_ops.py)."""
    if precision is None:
        precision = SCAN_PRECISION
    shapes = (q.shape[1], chunk, q.shape[-1], v.shape[-1], q.dtype)
    if scan_path(*shapes, precision) == "pallas":
        return _gated_delta_scan_pallas(q, k, v, beta, log_decay, seg, precision)
    return _gated_delta_scan_xla(q, k, v, beta, log_decay, seg, precision, chunk, block)


def _gated_delta_scan_xla(q, k, v, beta, log_decay, seg, precision, chunk, block):
    """``gated_delta_scan`` in ``jax.numpy``, for any chunk and head size.
    The chunks are taken ``block`` at a time: a block's matrices are made in
    one batch (the work is matrix products over all its chunks at once), its
    chunks' states follow one another in an inner scan, and the blocks in an
    outer scan whose body is a ``jax.checkpoint``. -> ``o, back``; ``back(do)
    -> (dq, dk, dv, dbeta, dlog_decay)``: ``jax.vjp`` of that, which keeps
    the state entering each BLOCK between the passes, rebuilds one block's
    matrices at a time in the backward, and holds nothing per token."""
    rows, seq, heads, dk = q.shape
    dv = v.shape[-1]
    c = _block_len(seq, chunk)
    n = seq // c
    per = _block_len(n, block)  # chunks per block

    def dot(spec, a, b):
        return jnp.einsum(spec, a, b, precision=precision)

    def chunks(a):  # (rows, seq, heads, ...) -> (blocks, per, rows, heads, c, ...)
        a = a.reshape(rows, n, c, heads, *a.shape[3:])
        a = jnp.moveaxis(jnp.moveaxis(a, 3, 2), 1, 0)
        return a.reshape(n // per, per, *a.shape[1:])

    def blocks(a):  # (n, ...) -> (blocks, per, ...)
        return a.reshape(n // per, per, *a.shape[1:])

    with scope("gdn/scan"):
        first, segc, carried, to_last = _document_masks(seg, n, c)
        same = blocks((segc[:, :, :, None] == segc[:, :, None, :])[:, :, None])
        carried = blocks(carried[:, :, None, :])  # (.., rows, 1, c)
        to_last = blocks(to_last[:, :, None, :])
        lower = jnp.tril(jnp.ones((c, c), bool))
        strict = jnp.tril(jnp.ones((c, c), bool), -1)

    @jax.checkpoint
    def block_of_chunks(state, xs):
        qc, kc, vc, bc, gc, same, carried, to_last = xs  # (per, rows, heads, c, ..)
        g = jnp.cumsum(gc, axis=-1)
        pair = same & lower
        decay = jnp.where(
            pair, jnp.exp(jnp.where(pair, g[..., :, None] - g[..., None, :], 0.0)), 0.0
        )
        a = jnp.where(strict, bc[..., None] * dot("nbhid,nbhjd->nbhij", kc, kc) * decay, 0.0)
        g_in = jnp.where(carried, jnp.exp(g), 0.0)  # decay from the entering state
        solved = dot(
            "nbhij,nbhjd->nbhid", _unit_lower_inverse(a),
            jnp.concatenate([bc[..., None] * vc, (bc * g_in)[..., None] * kc], axis=-1),
        )
        qk = dot("nbhid,nbhjd->nbhij", qc, kc) * decay
        g_out = jnp.where(to_last, jnp.exp(g[..., -1:] - g), 0.0)

        def step(state, xs):  # state: (rows, heads, d_k, d_v)
            u0, w, qk, q_in, k_out, keep = xs
            u = u0 - dot("bhik,bhkv->bhiv", w, state)
            o = dot("bhik,bhkv->bhiv", q_in, state) + dot("bhij,bhjv->bhiv", qk, u)
            state = state * keep[..., None, None] + dot("bhik,bhiv->bhkv", k_out, u)
            return state, o

        return lax.scan(
            step, state,
            (
                solved[..., :dv], solved[..., dv:], qk, qc * g_in[..., None],
                kc * g_out[..., None], g_in[..., -1],
            ),
        )

    def fn(q, k, v, beta, log_decay):
        # a document's first token takes no decay: its state starts from zero
        decays = jnp.where(first[..., None], 0.0, log_decay)
        _, o = lax.scan(
            block_of_chunks, jnp.zeros((rows, heads, dk, dv), q.dtype),
            (
                chunks(q), chunks(k), chunks(v), chunks(beta), chunks(decays),
                same, carried, to_last,
            ),
        )
        # (blocks, per, rows, heads, c, d_v) -> (rows, seq, heads, d_v)
        o = o.reshape(n, rows, heads, c, dv)
        return jnp.moveaxis(jnp.moveaxis(o, 0, 1), 2, 3).reshape(rows, seq, heads, dv)

    with scope("gdn/scan"):
        o, pull = jax.vjp(fn, q, k, v, beta, log_decay)

    def back(do):
        with scope("gdn/scan"):
            return pull(do)

    return o, back


# ---------------------------------------------------------------------------
# The second token family's ops (``solar_open2``): the delta rule with a
# decay PER KEY CHANNEL (Kimi Delta Attention), an elementwise output gate,
# and a routed expert layer that holds a range of the experts it routes over.
# ---------------------------------------------------------------------------

KDA_CHUNK = 64  # tokens per chunk of the per-channel rule
KDA_SUB = 16  # tokens per sub-block of a chunk's pair matrices
KDA_BLOCK = 4  # chunks whose matrices are made (and, backward, rebuilt) at once
KDA_KERNEL_CHUNK = 64  # tokens per chunk of the kernel form (``kda_scan_path``)
MOE_TILE = 256  # rows of one expert's tile in the grouped product
ROUTER_PRECISION = lax.Precision.HIGHEST  # float32 passes: a rounded score flips selections


def sigmoid_gate(o, gate):
    """``o * sigmoid(gate)``, elementwise: the grouped-query layer's output
    gate."""
    return _pointwise("gdn/gate", lambda o, g: o * jax.nn.sigmoid(g), o, gate)


def head_norm_sigmoid_gate(o, gate, w, eps):
    """``rms_norm(o) * sigmoid(gate)`` per head (last axis)."""
    return _pointwise(
        "gdn/gate", lambda o, g, w: _rms(o, w, eps) * jax.nn.sigmoid(g), o, gate, w
    )


def channel_gates(b, f, a_log, dt_bias, neg_eigval):
    """``beta = sigmoid(b)`` (doubled under ``neg_eigval``), one a head, and
    the LOG of the decay, one a key channel: ``-exp(A_log_h) softplus(f +
    dt_bias)``. ``b``: (rows, seq, heads), ``f``: (rows, seq, heads, d_k),
    ``a_log``: (heads,), ``dt_bias``: (heads, d_k)."""

    def fn(b, f, a_log, dt_bias):
        beta = jax.nn.sigmoid(b) * (2.0 if neg_eigval else 1.0)
        return beta, -jnp.exp(a_log)[:, None] * jax.nn.softplus(f + dt_bias)

    return _pointwise("gdn/gate", fn, b, f, a_log, dt_bias)


def _decayed_pairs(x, k, g, sub, precision):
    """``P[i, j] = sum_d x_i[d] k_j[d] exp(g_i[d] - g_j[d])`` for ``j <= i``
    (garbage, finite, above the diagonal): ``x, k, g``: (..., c, d), ``g`` the
    running sum of a log decay that is never positive. The decay does not
    factor out of the product over ``d`` as a scalar one does, and ``exp(g_i)
    exp(-g_j)`` overflows where the decay is strong; so, in sub-blocks of
    ``sub`` tokens: a pair in two different sub-blocks goes through the
    running sum at the LATER sub-block's first token, ``(x_i exp(g_i - r))
    . (k_j exp(r - g_j))`` with both exponents at most zero, one matrix
    product a sub-block of queries; a pair inside one sub-block is summed
    channel by channel with its own exponent."""
    *lead, c, d = x.shape
    n = c // sub

    def subs(a):
        return a.reshape(*lead, n, sub, d)

    xs, ks, gs = subs(x), subs(k), subs(g)
    ref = gs[..., 0, :]  # (..., n, d): the running sum entering sub-block I
    x_in = xs * jnp.exp(gs - ref[..., None, :])
    # (..., n, c, d): every key as sub-block I's queries see it; a key that is
    # not before sub-block I has a positive exponent, clamped: it is masked
    k_out = k[..., None, :, :] * jnp.exp(
        jnp.minimum(ref[..., :, None, :] - g[..., None, :, :], 0.0)
    )
    across = jnp.einsum(
        "...nid,...njd->...nij", x_in, k_out, precision=precision
    ).reshape(*lead, n, sub, n, sub)
    lower = jnp.tril(jnp.ones((sub, sub), bool))[:, :, None]
    exponent = jnp.where(lower, gs[..., :, None, :] - gs[..., None, :, :], 0.0)
    within = jnp.sum(
        xs[..., :, None, :] * ks[..., None, :, :] * jnp.exp(exponent), axis=-1
    )  # (..., n, sub, sub)
    same_sub = jnp.eye(n, dtype=bool)[:, None, :, None]
    return jnp.where(same_sub, within[..., :, :, None, :], across).reshape(*lead, c, c)


def kda_scan(
    q, k, v, beta, log_decay, seg, precision=None, chunk=KDA_CHUNK,
    block=KDA_BLOCK, sub=KDA_SUB,
):
    """The delta rule with a decay per key channel: ``S_t = (I - b_t k_t
    k_t^T) Diag(exp g_t) S_{t-1} + b_t k_t v_t^T``, ``o_t = S_t^T q_t``, ``S``
    (d_k x d_v) zero at a document's first token; ``gated_delta_scan`` is the
    case of ``g_t`` constant over the channels. ``q, k``: (rows, seq, heads,
    d_k), ``v``: (rows, seq, heads, d_v), ``beta``: (rows, seq, heads),
    ``log_decay``: (rows, seq, heads, d_k), never positive, ``seg``: (rows,
    seq). The chunked (WY) form of ``gated_delta_scan``: the same inverse,
    state recurrence and document masks; what differs is that a pair's decay
    sits INSIDE its product over the channels and that the state's rows
    decay each at their own rate. -> ``o, back``; ``back(do) -> (dq, dk, dv,
    dbeta, dlog_decay)``. Every product is ``precision`` (float32 passes by
    default, ``SCAN_PRECISION``) in either form.

    Two forms, and ``kda_scan_path`` between them from the shapes alone (no
    flag, no environment variable): the kernels of ``_kda_scan_pallas``
    where the shapes tile, the ``jax.numpy`` form of ``_kda_scan_xla``
    (which ``chunk``, ``block`` and ``sub`` belong to) everywhere else. The
    second is the first's oracle (tests/test_solar_ops.py)."""
    if precision is None:
        precision = SCAN_PRECISION
    shapes = (q.shape[1], q.shape[2], q.shape[-1], v.shape[-1], q.dtype)
    if kda_scan_path(*shapes, precision) == "pallas":
        return _kda_scan_pallas(q, k, v, beta, log_decay, seg, precision)
    return _kda_scan_xla(q, k, v, beta, log_decay, seg, precision, chunk, block, sub)


# A model calls the kernels once a layer and pass (9 times in the cell's
# step), and tracing a kernel's body is seconds of the host's time (2 s a
# call on the chip's host: 19 s of set-up, PERF.md section 6, PR 36). Under
# ``jax.jit`` the calls of one shape share one trace and one lowering; what
# decides the program besides the shapes is static, the backend's choice of
# Mosaic or the interpreter among it. The scope is opened inside: a callee's
# instructions carry their own path, not the caller's.
@partial(jax.jit, static_argnames=("leaf", "precision", "interpret"))
def _kda_kernel_fwd(*operands, **static):
    from shallowspeed_tpu import pallas_ops as K

    with scope("kda/scan"):
        return K.kda_scan_fwd(*operands, **static)


@partial(jax.jit, static_argnames=("precision", "interpret"))
def _kda_kernel_bwd(*operands, **static):
    from shallowspeed_tpu import pallas_ops as K

    with scope("kda/scan"):
        return K.kda_scan_bwd(*operands, **static)


def _kda_scan_pallas(q, k, v, beta, log_decay, seg, precision, chunk=KDA_KERNEL_CHUNK):
    """``kda_scan`` through the kernels ``pallas_ops.kda_scan_fwd`` /
    ``_bwd``, which read ``q, k, v`` and the log decay where they lie (a
    block is a chunk of 8 heads, a head a sublane of each token's tile) and
    make the running sum of the log decay, and its pull-back, themselves;
    the backward reads the state entering each chunk, each chunk's inverse
    and its two pair matrices as the forward kept them. Left in XLA, under
    the same scope: the document masks, 8 rows a chunk,
    and each head's ``beta`` as a row a chunk, and ``dbeta`` taken out of
    the same."""
    from shallowspeed_tpu import pallas_ops as K

    rows, seq, heads, _ = q.shape
    c = chunk
    n = seq // c
    per = K.kda_heads_per_step(heads)
    groups = heads // per
    static = dict(precision=precision, interpret=K._interpret())

    with scope("kda/scan"):
        first, segc, carried, to_last = _document_masks(seg, n, c)
        by_row = {
            K.KDA_SEG: segc, K.KDA_CARRIED: carried, K.KDA_TO_LAST: to_last,
            K.KDA_FIRST: jnp.moveaxis(first.reshape(rows, n, c), 1, 0),
        }
        zero = jnp.zeros_like(segc)
        p = jnp.moveaxis(
            jnp.stack([by_row.get(r, zero) for r in range(K.KDA_ROWS)], axis=-2), 0, 1
        ).astype(q.dtype)  # (rows, n, 8, c)

        def by_group(beta):  # (rows, seq, heads) -> (rows * groups, n, 8, c)
            betas = jnp.transpose(beta.reshape(rows, n, c, groups, per), (0, 3, 1, 4, 2))
            betas = jnp.pad(betas, ((0, 0),) * 3 + ((0, K.KDA_ROWS - per), (0, 0)))
            return betas.reshape(rows * groups, n, K.KDA_ROWS, c)

        betas, pull_beta = jax.vjp(by_group, beta)
        o, states, inverses, pairs = _kda_kernel_fwd(
            q, k, v, log_decay, p, betas, leaf=min(INVERSE_LEAF, c), **static
        )

    def back(do):
        with scope("kda/scan"):
            dq, dk, dv, dg, dbetas = _kda_kernel_bwd(
                q, k, v, log_decay, p, betas, states, inverses, pairs, do, **static
            )
            (dbeta,) = pull_beta(dbetas)
            return dq, dk, dv, dbeta, dg

    return o, back


def _kda_scan_xla(q, k, v, beta, log_decay, seg, precision, chunk, block, sub):
    """``kda_scan`` in ``jax.numpy``, for any chunk and head size:
    ``_gated_delta_scan_xla``'s blocks of chunks and ``jax.vjp`` backward,
    the pair matrices by ``_decayed_pairs`` in sub-blocks of ``sub``
    tokens."""
    rows, seq, heads, dk = q.shape
    dv = v.shape[-1]
    c = _block_len(seq, chunk)
    n = seq // c
    per = _block_len(n, block)  # chunks per block
    sub = _block_len(c, sub)

    def dot(spec, a, b):
        return jnp.einsum(spec, a, b, precision=precision)

    def chunks(a):  # (rows, seq, heads, ...) -> (blocks, per, rows, heads, c, ...)
        a = a.reshape(rows, n, c, heads, *a.shape[3:])
        a = jnp.moveaxis(jnp.moveaxis(a, 3, 2), 1, 0)
        return a.reshape(n // per, per, *a.shape[1:])

    def blocks(a):  # (n, ...) -> (blocks, per, ...)
        return a.reshape(n // per, per, *a.shape[1:])

    with scope("kda/scan"):
        first, segc, carried, to_last = _document_masks(seg, n, c)
        same = blocks((segc[:, :, :, None] == segc[:, :, None, :])[:, :, None])
        carried = blocks(carried[:, :, None, :, None])  # (.., rows, 1, c, 1)
        to_last = blocks(to_last[:, :, None, :, None])
        lower = jnp.tril(jnp.ones((c, c), bool))
        strict = jnp.tril(jnp.ones((c, c), bool), -1)

    @jax.checkpoint
    def block_of_chunks(state, xs):
        qc, kc, vc, bc, gc, same, carried, to_last = xs  # (per, rows, heads, c, ..)
        g = jnp.cumsum(gc, axis=-2)  # (.., c, d_k)
        pair = same & lower
        kk = _decayed_pairs(kc, kc, g, sub, precision)
        a = jnp.where(same & strict, bc[..., None] * kk, 0.0)
        g_in = jnp.where(carried, jnp.exp(g), 0.0)  # decay from the entering state
        solved = dot(
            "nbhij,nbhjd->nbhid", _unit_lower_inverse(a),
            jnp.concatenate([bc[..., None] * vc, bc[..., None] * g_in * kc], axis=-1),
        )
        qk = jnp.where(pair, _decayed_pairs(qc, kc, g, sub, precision), 0.0)
        g_out = jnp.where(to_last, jnp.exp(g[..., -1:, :] - g), 0.0)

        def step(state, xs):  # state: (rows, heads, d_k, d_v)
            u0, w, qk, q_in, k_out, keep = xs
            u = u0 - dot("bhik,bhkv->bhiv", w, state)
            o = dot("bhik,bhkv->bhiv", q_in, state) + dot("bhij,bhjv->bhiv", qk, u)
            state = state * keep[..., None] + dot("bhik,bhiv->bhkv", k_out, u)
            return state, o

        return lax.scan(
            step, state,
            (solved[..., :dv], solved[..., dv:], qk, qc * g_in, kc * g_out, g_in[..., -1, :]),
        )

    def fn(q, k, v, beta, log_decay):
        # a document's first token takes no decay: its state starts from zero
        decays = jnp.where(first[..., None, None], 0.0, log_decay)
        _, o = lax.scan(
            block_of_chunks, jnp.zeros((rows, heads, dk, dv), q.dtype),
            (
                chunks(q), chunks(k), chunks(v), chunks(beta), chunks(decays),
                same, carried, to_last,
            ),
        )
        # (blocks, per, rows, heads, c, d_v) -> (rows, seq, heads, d_v)
        o = o.reshape(n, rows, heads, c, dv)
        return jnp.moveaxis(jnp.moveaxis(o, 0, 1), 2, 3).reshape(rows, seq, heads, dv)

    with scope("kda/scan"):
        o, pull = jax.vjp(fn, q, k, v, beta, log_decay)

    def back(do):
        with scope("kda/scan"):
            return pull(do)

    return o, back


def _top_indices(values, top):
    """The indices of the ``top`` largest along the last axis, largest first,
    ties to the lower index, as ``top`` passes of ``argmax`` (the chip's
    ``top_k`` sorts every row whole)."""
    lanes = jnp.arange(values.shape[-1])
    found = []
    for _ in range(top):
        best = jnp.argmax(values, axis=-1)
        found.append(best)
        values = jnp.where(lanes == best[..., None], -jnp.inf, values)
    return jnp.stack(found, axis=-1).astype(jnp.int32)


def route(x, w, top, normalise=True, scaling=1.0):
    """The router: ``scores = sigmoid(x w^T)`` over EVERY published expert
    (``w``: (experts, hidden); float32 passes whatever the session's
    precision), the ``top`` largest selected (the family's selection bias is
    zero and untrained: a constant, left out), the selected scores divided by
    their sum over all ``top`` (``normalise``), held here or not, and
    scaled. ``x``: (tokens, hidden). -> ``(weights, sel), back``: both
    (tokens, top), ``sel`` the published indices; ``back(dweights) -> (dx,
    dw)``. The selection carries no gradient."""

    def scores_of(x, w):
        return jax.nn.sigmoid(jnp.matmul(x, w.T, precision=ROUTER_PRECISION))

    with scope("moe/route"):
        sel = _top_indices(scores_of(x, w), top)
        # the selected scores by comparison, not a gather along the last axis
        chosen = jnp.arange(w.shape[0]) == sel[..., None]  # (tokens, top, experts)

    def fn(x, w):
        picked = jnp.sum(jnp.where(chosen, scores_of(x, w)[:, None, :], 0.0), axis=-1)
        if normalise:
            picked = picked / jnp.sum(picked, axis=-1, keepdims=True)
        return picked * scaling

    (weights, back) = _pointwise("moe/route", fn, x, w)
    return (weights, sel), back


def _add_into(stacked, e, product, reset=False):
    """``stacked`` with ``product`` added into its slice ``e`` along the
    first axis (put there in place of what it held where ``reset``, a bool,
    traced or not), as an update of that slice: in a loop that carries
    ``stacked`` the compiler writes it in place."""
    start = stacked[e] if reset is False else jnp.where(reset, 0.0, stacked[e])
    return lax.dynamic_update_index_in_dim(stacked, start + product, e, 0)


def _swiglu_tile(xt, w_gate, w_up, precision):
    gate = jnp.matmul(xt, w_gate.T, precision=precision)
    up = jnp.matmul(xt, w_up.T, precision=precision)
    return gate, up, _silu(gate) * up


def experts(x, sel, weights, held, w_gate, w_up, w_down, precision=DEFAULT_PRECISION,
            tile=MOE_TILE):
    """The routed experts THIS chip holds: ``sum over a token's slots whose
    expert is in held of weight * W_down,e(silu(W_gate,e x) * W_up,e x)``.
    ``x``: (tokens, hidden); ``sel``, ``weights``: (tokens, top) (``route``);
    ``held = (lo, hi)``: the published indices ``lo .. hi - 1`` are the
    experts whose weights ``w_*`` (hi - lo, out, in) hold, in that order. A
    slot routed elsewhere adds nothing here: it is another chip's.

    Dropless, and no shape depends on the routing: the (token, slot) pairs
    are sorted by expert (a stable sort, so by token within an expert); each
    held expert then takes its pairs ``tile`` rows at a time in a loop whose
    trip count is its own row count over ``tile``, rounded up: gather the
    rows, the three products, scatter the weighted result back. The device
    time follows the rows held through that loop alone. ``ops.dense``'s
    rounding policy (operands to bfloat16 under ``Precision.DEFAULT``).
    -> ``out, back, rows``: ``rows`` (hi - lo,) int32, the pairs routed to
    each held expert; ``back(dout, acc=None, fresh=False) -> (dx, dweights,
    dw_gate, dw_up, dw_down)``, the forward's tiles run again. ``acc``: a
    gradient accumulator's three stacked leaves ``(dw_gate, dw_up,
    dw_down)``; each tile's products are added into expert ``e``'s slice of
    them in place, and they come back holding ``acc + gradient``, or the
    gradient alone where ``fresh`` (a bool, traced or not: what ``acc``
    holds is then never read; an expert no row reaches runs one empty tile
    to clear its slice). Without ``acc`` the stacked gradients start from
    zeros. Either way no expert's gradient is made apart and stacked or
    added afterwards: a copy of every weight a microbatch."""
    x, dtype = jnp.asarray(x), x.dtype
    tokens, top = sel.shape
    lo, hi = held
    n_held = hi - lo
    rounds = precision == lax.Precision.DEFAULT
    rounded = _as_bfloat16 if rounds else (lambda a: a)

    with scope("moe/route"):
        local = jnp.where((sel >= lo) & (sel < hi), sel - lo, n_held).reshape(-1)
        order = jnp.argsort(local, stable=True).astype(jnp.int32)  # row -> pair
        rows = jnp.sum(
            local[:, None] == jnp.arange(n_held, dtype=local.dtype), axis=0,
            dtype=jnp.int32,
        )
        starts = jnp.cumsum(rows) - rows
        flat_weights = weights.reshape(-1)
        x_r = rounded(x)
    with scope("moe/experts"):
        w_gate_r, w_up_r, w_down_r = rounded(w_gate), rounded(w_up), rounded(w_down)

    def tile_rows(e, t):
        """The pairs, tokens and weights of tile ``t`` of expert ``e``, and
        which of its rows are the expert's: the rows past its last weigh
        nothing."""
        with scope("moe/route"):
            at = t * tile + jnp.arange(tile, dtype=jnp.int32)
            valid = at < rows[e]
            pair = order[jnp.minimum(starts[e] + at, tokens * top - 1)]
            return pair, pair // top, jnp.where(valid, flat_weights[pair], 0.0), valid

    def tiles_of(e):
        return (rows[e] + tile - 1) // tile

    def forward_tile(e, t, out):
        _, token, weight, _ = tile_rows(e, t)
        with scope("moe/route"):
            xt = x_r[token]
        with scope("moe/experts"):
            _, _, act = _swiglu_tile(xt, w_gate_r[e], w_up_r[e], precision)
            down = jnp.matmul(rounded(act), w_down_r[e].T, precision=precision)
        with scope("moe/route"):
            return out.at[token].add(weight[:, None] * down)

    out = jnp.zeros_like(x)
    for e in range(n_held):
        out = lax.fori_loop(0, tiles_of(e), partial(forward_tile, e), out)

    def back(dout, acc=None, fresh=False):
        dout = jnp.asarray(dout, dtype)

        def backward_tile(e, t, carry):
            dx, dweights, dw_gate, dw_up, dw_down = carry
            pair, token, weight, valid = tile_rows(e, t)
            # the tile's counter passes a barrier, as the looped token step's
            # microbatch counter does: the chip's compiler has dropped a
            # first-trip select on a loop's counter (PERF.md section 6)
            reset = fresh if fresh is False else jnp.logical_and(
                fresh, lax.optimization_barrier(t) == 0
            )
            with scope("moe/route"):
                xt, dout_t = x_r[token], dout[token]
            with scope("moe/experts"):
                gate, up, act = _swiglu_tile(xt, w_gate_r[e], w_up_r[e], precision)
                act_r = rounded(act)
                down = jnp.matmul(act_r, w_down_r[e].T, precision=precision)
                dweight = jnp.sum(dout_t * down, axis=-1)
                ddown = rounded(weight[:, None] * dout_t)
                dact = jnp.matmul(ddown, w_down_r[e], precision=precision)
                dw_down = _add_into(
                    dw_down, e, jnp.matmul(ddown.T, act_r, precision=precision), reset
                )
                dgate, dup = jax.vjp(lambda g, u: _silu(g) * u, gate, up)[1](dact)
                dgate, dup = rounded(dgate), rounded(dup)
                dw_gate = _add_into(
                    dw_gate, e, jnp.matmul(dgate.T, xt, precision=precision), reset
                )
                dw_up = _add_into(
                    dw_up, e, jnp.matmul(dup.T, xt, precision=precision), reset
                )
                dxt = jnp.matmul(dgate, w_gate_r[e], precision=precision) + jnp.matmul(
                    dup, w_up_r[e], precision=precision
                )
            with scope("moe/route"):
                # a row past the expert's last carries weight zero, so its
                # dxt is zero; its dweight is not, hence the where
                dweights = dweights.at[pair].add(jnp.where(valid, dweight, 0.0))
                return dx.at[token].add(dxt), dweights, dw_gate, dw_up, dw_down

        with scope("moe/route"):
            dx, dweights = jnp.zeros_like(x), jnp.zeros_like(flat_weights)
        dw = acc
        if dw is None:
            with scope("moe/experts"):
                dw = tuple(jnp.zeros_like(w) for w in (w_gate, w_up, w_down))
        for e in range(n_held):
            trips = jnp.maximum(tiles_of(e), jnp.asarray(fresh, jnp.int32))
            dx, dweights, *dw = lax.fori_loop(
                0, trips, partial(backward_tile, e), (dx, dweights, *dw)
            )
        return (dx, dweights.reshape(tokens, top), *dw)

    return out, back, rows
