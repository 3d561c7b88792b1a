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


@scoped("act")
def gelu_grad(g, z):
    """VJP of gelu given the cached pre-activation z."""
    return g * gelu_grad_mult(z)


@scoped("linear/fwd")
def linear(x, w, b, precision=DEFAULT_PRECISION):
    """y = x @ w.T + b with w: (out, in), b: (1, out) or (out,).

    Reference: functional.py:13-17.
    """
    return jnp.matmul(x, w.T, precision=precision) + jnp.reshape(b, (1, -1))


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
