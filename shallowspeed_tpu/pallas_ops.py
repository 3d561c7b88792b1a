"""Pallas TPU kernels: the MLP's fused linear + ReLU, forward & backward
(opt-in, below), and the two delta rules' chunked scans (``gdn_scan_fwd`` /
``gdn_scan_bwd`` and, for the rule with a decay per key channel,
``kda_scan_fwd`` / ``kda_scan_bwd``, at the end of the file: what
``ops.gated_delta_scan`` and ``ops.kda_scan`` run wherever ``ops.scan_path``
and ``ops.kda_scan_path`` say the shapes tile, with no switch of their own).

The framework's compute path is XLA-compiled jax.numpy (ops.py) — for this
model class XLA already fuses bias-add and ReLU into the matmul. These Pallas
kernels exist for the cases XLA can't schedule as one unit and as the
framework's custom-kernel layer.

Two regimes, auto-selected per shape at trace time:

- **single block** (the flagship model's regime): every operand of a layer
  fits VMEM at once, so each kernel is one block — HBM -> VMEM once, matmul
  on the MXU with fp32 accumulation, activation + bitmask on the VPU, one
  write back.
- **grid-tiled** (shapes beyond the VMEM budget): every dimension —
  including the contraction — is tiled, so per-block VMEM is ~4 tile^2
  floats (~4 MiB at tile=512) regardless of layer size. The innermost grid
  dimension accumulates partial products into the revisited output block:
  the forward accumulates z over contraction tiles and runs the
  bias+relu+mask epilogue on the final one; the backward splits into a dx
  kernel (accumulating over out-col tiles) and a dw/db kernel (accumulating
  over row tiles; db adds only on the first in-col tile so column tiling
  never double-counts it). Tiles are multiples of the 128-lane MXU width;
  ragged edges are zero-padded in the wrapper and sliced off after (exact:
  padded rows/cols contribute zeros).

- ``linear_relu_fwd(x, w, b) -> (y, mask)``: y = relu(x @ w.T + b), mask the
  pre-activation sign bitmask the backward needs (reference semantics:
  layers.py:68-71 caches the same bitmask).
- ``linear_relu_bwd(g, mask, x, w) -> (dx, dw, db)``: all three gradients
  from one VMEM residency of g/mask/x/w per block.

Enable with SHALLOWSPEED_PALLAS=1 (or ``ops.set_pallas(True)``); on a host
CPU the kernels run in interpreter mode (``_interpret``), so the same tests
cover CPU CI and real hardware. The flag applies to the SEQUENTIAL model path
(model.stage_forward/backward).

The PIPELINE EXECUTOR has its own kernel pair (``linear_flag_fwd`` /
``linear_flag_bwd``): its layer loop selects relu/identity behavior with
TRACED per-device flags (flags["relu"] picked per virtual chunk), so the
statically-fused relu kernels above can't be slotted in. The flag kernels
are branch-free — the relu flag rides in as an SMEM scalar operand and the
activation is ``where(flag, max(z, 0), z)`` on the VPU — so ONE compiled
kernel serves every stage, chunk and schedule. Like the relu pair, the flag
kernels auto-dispatch between single-block and grid-tiled per shape.
Executor opt-in: ``make_pipeline_step(..., kernel_backend="pallas")``, or
through the product surface: ``TrainingSession(kernel_backend="pallas")`` /
``train.py --kernel-backend pallas``.

None of these switches reaches the SCAN's kernels. A token model's Gated
DeltaNet layers run them by shape (chunks of 128 tokens, float32, head sizes
that are multiples of 8): one grid step is one chunk of two heads, the chunk
axis runs in order with the (d_k x d_v) state in a VMEM scratch, and a
chunk's (128 x 128) matrices never leave VMEM. On the chip one layer-row of
``olmo-hybrid-7b`` (8,192 tokens, 30 heads, 96 / 192) takes 8.8 ms forward
and 10.8 ms backward, the XLA around the kernels included, where the XLA
form took 10.9 and 31.6 (PERF.md section 6, PR 33). The per-channel rule's
layers (``solar_open2``) run theirs by shape too (rows of whole 64-token
chunks, heads in eights, 128 key channels): one layer-row of
``solar-open2-250b`` (2,048 tokens, 64 heads of 128) takes 4.8 ms forward
and 8.9 ms backward where the XLA form took 8.3 and 29.7 (PERF.md section
6, PR 36). A token model still refuses ``kernel_backend="pallas"``: that
names the MLP flag kernels.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _interpret() -> bool:
    """Mosaic on a TPU, the Pallas interpreter on a host CPU, and nothing
    else: a backend under any other name gets an error, not the
    interpreter — an interpreted run is a correctness aid and must never
    stand in for a kernel on hardware."""
    backend = jax.default_backend()
    if backend not in ("tpu", "cpu"):
        raise RuntimeError(
            f"the Pallas kernels compile for 'tpu' (Mosaic) and interpret on "
            f"'cpu'; the default JAX backend here is {backend!r}"
        )
    return backend == "cpu"


# VMEM is ~16 MiB/core; a single-block kernel must hold every operand at
# once, so leave generous headroom for double-buffering and the compiler.
SINGLE_BLOCK_BUDGET_BYTES = 8 * 1024 * 1024
TILE = 512  # grid tile edge (multiple of the 128-lane MXU width)


def _fwd_bytes(mb, din, dout):
    """f32 VMEM footprint of a single-block forward: x, w, b, y, mask."""
    return 4 * (mb * din + dout * din + dout + 2 * mb * dout)


def _bwd_bytes(mb, din, dout):
    """f32 VMEM footprint of a single-block backward: g, mask, x, w, dx, dw, db."""
    return 4 * (3 * mb * dout + mb * din + 2 * dout * din + dout)


def _pad_to(a, axis, mult):
    n = a.shape[axis]
    rem = (-n) % mult
    if rem == 0:
        return a
    widths = [(0, 0)] * a.ndim
    widths[axis] = (0, rem)
    return jnp.pad(a, widths)


def _fwd_kernel(x_ref, w_ref, b_ref, y_ref, mask_ref, *, precision):
    z = (
        jnp.dot(
            x_ref[:], w_ref[:].T,
            precision=precision, preferred_element_type=jnp.float32,
        )
        + b_ref[:]
    )
    mask_ref[:] = (z > 0.0).astype(jnp.float32)
    y_ref[:] = jnp.maximum(z, 0.0)


def _linear_relu_fwd_single(x, w, b2, precision):
    mb, _ = x.shape
    dout = w.shape[0]
    return pl.pallas_call(
        functools.partial(_fwd_kernel, precision=precision),
        out_shape=(
            jax.ShapeDtypeStruct((mb, dout), jnp.float32),
            jax.ShapeDtypeStruct((mb, dout), jnp.float32),
        ),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.VMEM),
        ],
        out_specs=(
            pl.BlockSpec(memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.VMEM),
        ),
        interpret=_interpret(),
    )(x, w, b2)


def linear_relu_fwd_tiled(x, w, b2, tile=TILE, precision=None):
    """Grid-tiled forward: every dim tiled (rows x out-cols x contraction),
    so per-block VMEM is ~4 tile^2 floats regardless of shape. Ragged edges
    zero-padded, sliced off after (exact: pads contribute zeros). The
    tiling plumbing exists ONCE, in the flag variant — relu is the flag
    pinned to 1 (``where(1, max(z, 0), z) == relu(z)``, value-exact)."""
    return linear_flag_fwd_tiled(x, w, b2, jnp.int32(1), tile=tile, precision=precision)


@functools.partial(jax.jit, static_argnames=("precision",))
def linear_relu_fwd(x, w, b, precision=None):
    """``precision`` is the MXU dot precision (lax.Precision; None = the
    backend default, a single bf16-input pass). The framework's ops layer
    passes its caller's precision through, so HIGHEST really means the
    multi-pass fp32-class dot inside the kernel too — without this the
    'pallas' and 'xla' backends would silently measure different math."""
    mb, din = x.shape
    dout = w.shape[0]
    b2 = jnp.reshape(b, (1, -1))
    if _fwd_bytes(mb, din, dout) <= SINGLE_BLOCK_BUDGET_BYTES:
        return _linear_relu_fwd_single(x, w, b2, precision)
    return linear_relu_fwd_tiled(x, w, b2, tile=TILE, precision=precision)


def _bwd_kernel(g_ref, mask_ref, x_ref, w_ref, dx_ref, dw_ref, db_ref, *, precision):
    ge = g_ref[:] * mask_ref[:]
    dx_ref[:] = jnp.dot(
        ge, w_ref[:], precision=precision, preferred_element_type=jnp.float32
    )
    dw_ref[:] = jnp.dot(
        ge.T, x_ref[:], precision=precision, preferred_element_type=jnp.float32
    )
    db_ref[:] = jnp.sum(ge, axis=0, keepdims=True)


def _linear_relu_bwd_single(g, mask, x, w, precision):
    mb, dout = g.shape
    din = x.shape[1]
    return pl.pallas_call(
        functools.partial(_bwd_kernel, precision=precision),
        out_shape=(
            jax.ShapeDtypeStruct((mb, din), jnp.float32),
            jax.ShapeDtypeStruct((dout, din), jnp.float32),
            jax.ShapeDtypeStruct((1, dout), jnp.float32),
        ),
        in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM)] * 4,
        out_specs=tuple([pl.BlockSpec(memory_space=pltpu.VMEM)] * 3),
        interpret=_interpret(),
    )(g, mask, x, w)


def linear_relu_bwd_tiled(g, mask, x, w, tile=TILE, precision=None):
    """Grid-tiled backward, two kernels, every dim tiled (per-block VMEM is
    ~4 tile^2 floats regardless of shape): dx on a (row x in-col x out-col)
    grid accumulating over the innermost out-col/contraction tiles; dw/db on
    a (out-col x in-col x row) grid accumulating over the innermost row
    tiles. Delegates to the flag variant with the flag pinned to 1 (the
    relu-mask multiply applied) — one tiling implementation."""
    return linear_flag_bwd_tiled(g, mask, x, w, jnp.int32(1), tile=tile, precision=precision)


@functools.partial(jax.jit, static_argnames=("precision",))
def linear_relu_bwd(g, mask, x, w, precision=None):
    """See linear_relu_fwd: ``precision`` makes the kernel's dots match the
    caller's precision class instead of silently using the backend default."""
    mb, dout = g.shape
    din = x.shape[1]
    if _bwd_bytes(mb, din, dout) <= SINGLE_BLOCK_BUDGET_BYTES:
        return _linear_relu_bwd_single(g, mask, x, w, precision)
    return linear_relu_bwd_tiled(g, mask, x, w, tile=TILE, precision=precision)


# ---------------------------------------------------------------------------
# Flag-operand kernels for the pipeline executor (traced relu selection)
# ---------------------------------------------------------------------------


def _flag_fwd_kernel(flag_ref, x_ref, w_ref, b_ref, y_ref, mask_ref, *, precision):
    # branch-free relu selection: flag is an SMEM scalar, the select runs on
    # the VPU — one compiled kernel serves relu AND identity layers, which is
    # what lets the executor's chunk-uniform layer loop call it with a
    # traced per-(stage, slot) flag
    z = (
        jnp.dot(
            x_ref[:], w_ref[:].T,
            precision=precision, preferred_element_type=jnp.float32,
        )
        + b_ref[:]
    )
    mask_ref[:] = (z > 0.0).astype(jnp.float32)
    y_ref[:] = jnp.where(flag_ref[0] != 0, jnp.maximum(z, 0.0), z)


def linear_flag_fwd(x, w, b2, flag, precision=None):
    """Executor forward unit: ``(y, mask)`` with ``y = relu(z) if flag else
    z``, ``z = x @ w.T + b``, ``mask = z > 0`` (f32). ``flag`` is a TRACED
    scalar (the executor's per-slot relu flag picked per virtual chunk).
    Auto-selects single-block (the flagship regime) or the grid-tiled
    variant per shape, like linear_relu_fwd."""
    mb, din = x.shape
    dout = w.shape[0]
    if _fwd_bytes(mb, din, dout) > SINGLE_BLOCK_BUDGET_BYTES:
        # tile=TILE at CALL time (not the def-time default) so the module
        # knob governs the flag path exactly like the relu dispatchers
        return linear_flag_fwd_tiled(x, w, b2, flag, tile=TILE, precision=precision)
    return pl.pallas_call(
        functools.partial(_flag_fwd_kernel, precision=precision),
        out_shape=(
            jax.ShapeDtypeStruct((mb, dout), jnp.float32),
            jax.ShapeDtypeStruct((mb, dout), jnp.float32),
        ),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.VMEM),
        ],
        out_specs=(
            pl.BlockSpec(memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.VMEM),
        ),
        interpret=_interpret(),
    )(jnp.reshape(flag, (1,)).astype(jnp.int32), x, w, b2)


def _flag_fwd_tiled_kernel(flag_ref, x_ref, w_ref, b_ref, y_ref, mask_ref, *, precision):
    # grid = (row tiles i, out-col tiles j, contraction tiles c); c is
    # INNERMOST: the revisited y block accumulates partial products, and the
    # bias/activation/mask epilogue runs once on the final contraction step.
    # The flag rides in SMEM with a constant index map (every grid step sees
    # the same scalar) and selects relu vs identity in the epilogue.
    c = pl.program_id(2)
    nc = pl.num_programs(2)
    partial = jnp.dot(
        x_ref[:], w_ref[:].T,
        precision=precision, preferred_element_type=jnp.float32,
    )

    @pl.when(c == 0)
    def _init():
        y_ref[:] = partial

    @pl.when(c != 0)
    def _acc():
        y_ref[:] += partial

    @pl.when(c == nc - 1)
    def _epilogue():
        z = y_ref[:] + b_ref[:]
        mask_ref[:] = (z > 0.0).astype(jnp.float32)
        y_ref[:] = jnp.where(flag_ref[0] != 0, jnp.maximum(z, 0.0), z)


def linear_flag_fwd_tiled(x, w, b2, flag, tile=TILE, precision=None):
    """Grid-tiled flag forward — linear_relu_fwd_tiled's tiling (rows x
    out-cols x contraction, ragged edges zero-padded and sliced) with the
    traced relu flag as an SMEM operand, so the executor's oversize slots
    run on the pallas backend instead of being rejected at build time."""
    mb, din = x.shape
    dout = w.shape[0]
    xp = _pad_to(_pad_to(x, 0, tile), 1, tile)
    wp = _pad_to(_pad_to(w, 0, tile), 1, tile)
    bp = _pad_to(b2, 1, tile)
    mbp, dinp = xp.shape
    doutp = wp.shape[0]
    y, mask = pl.pallas_call(
        functools.partial(_flag_fwd_tiled_kernel, precision=precision),
        grid=(mbp // tile, doutp // tile, dinp // tile),
        out_shape=(
            jax.ShapeDtypeStruct((mbp, doutp), jnp.float32),
            jax.ShapeDtypeStruct((mbp, doutp), jnp.float32),
        ),
        in_specs=[
            pl.BlockSpec((1,), lambda i, j, c: (0,), memory_space=pltpu.SMEM),
            pl.BlockSpec((tile, tile), lambda i, j, c: (i, c), memory_space=pltpu.VMEM),
            pl.BlockSpec((tile, tile), lambda i, j, c: (j, c), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, tile), lambda i, j, c: (0, j), memory_space=pltpu.VMEM),
        ],
        out_specs=(
            pl.BlockSpec((tile, tile), lambda i, j, c: (i, j), memory_space=pltpu.VMEM),
            pl.BlockSpec((tile, tile), lambda i, j, c: (i, j), memory_space=pltpu.VMEM),
        ),
        interpret=_interpret(),
    )(jnp.reshape(flag, (1,)).astype(jnp.int32), xp, wp, bp)
    return y[:mb, :dout], mask[:mb, :dout]


def _flag_bwd_kernel(
    flag_ref, g_ref, mask_ref, x_ref, w_ref, dx_ref, dw_ref, db_ref, *, precision
):
    ge = jnp.where(flag_ref[0] != 0, g_ref[:] * mask_ref[:], g_ref[:])
    dx_ref[:] = jnp.dot(
        ge, w_ref[:], precision=precision, preferred_element_type=jnp.float32
    )
    dw_ref[:] = jnp.dot(
        ge.T, x_ref[:], precision=precision, preferred_element_type=jnp.float32
    )
    db_ref[:] = jnp.sum(ge, axis=0, keepdims=True)


def linear_flag_bwd(g, mask, x, w, flag, precision=None):
    """Executor backward unit: ``(dx, dw, db)`` of linear_flag_fwd — the
    relu-mask multiply is applied iff ``flag`` (traced), then all three
    gradients come from one VMEM residency. Auto-selects single-block or
    the grid-tiled variant per shape, like linear_relu_bwd."""
    mb, dout = g.shape
    din = x.shape[1]
    if _bwd_bytes(mb, din, dout) > SINGLE_BLOCK_BUDGET_BYTES:
        return linear_flag_bwd_tiled(g, mask, x, w, flag, tile=TILE, precision=precision)
    return pl.pallas_call(
        functools.partial(_flag_bwd_kernel, precision=precision),
        out_shape=(
            jax.ShapeDtypeStruct((mb, din), jnp.float32),
            jax.ShapeDtypeStruct((dout, din), jnp.float32),
            jax.ShapeDtypeStruct((1, dout), jnp.float32),
        ),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM)]
        + [pl.BlockSpec(memory_space=pltpu.VMEM)] * 4,
        out_specs=tuple([pl.BlockSpec(memory_space=pltpu.VMEM)] * 3),
        interpret=_interpret(),
    )(jnp.reshape(flag, (1,)).astype(jnp.int32), g, mask, x, w)


def _flag_bwd_dx_kernel(flag_ref, g_ref, mask_ref, w_ref, dx_ref, *, precision):
    # grid = (row tiles i, in-col tiles j, out-col/contraction tiles c);
    # c INNERMOST accumulates into the revisited dx block; the relu-mask
    # multiply is flag-selected
    c = pl.program_id(2)
    ge = jnp.where(flag_ref[0] != 0, g_ref[:] * mask_ref[:], g_ref[:])
    partial = jnp.dot(
        ge, w_ref[:], precision=precision, preferred_element_type=jnp.float32
    )

    @pl.when(c == 0)
    def _init():
        dx_ref[:] = partial

    @pl.when(c != 0)
    def _acc():
        dx_ref[:] += partial


def _flag_bwd_dw_kernel(
    flag_ref, g_ref, mask_ref, x_ref, dw_ref, db_ref, *, precision
):
    # grid = (out-col tiles j, in-col tiles k, row tiles i); i is INNERMOST
    # so the revisited dw block accumulates partial products over row tiles;
    # db is independent of the in-col tiling and accumulates on k == 0 only
    k = pl.program_id(1)
    i = pl.program_id(2)
    ge = jnp.where(flag_ref[0] != 0, g_ref[:] * mask_ref[:], g_ref[:])
    contrib = jnp.dot(
        ge.T, x_ref[:], precision=precision, preferred_element_type=jnp.float32
    )

    @pl.when(i == 0)
    def _init():
        dw_ref[:] = contrib

    @pl.when(i != 0)
    def _acc():
        dw_ref[:] += contrib

    dbc = jnp.sum(ge, axis=0, keepdims=True)

    @pl.when((k == 0) & (i == 0))
    def _db_init():
        db_ref[:] = dbc

    @pl.when((k == 0) & (i != 0))
    def _db_acc():
        db_ref[:] += dbc


def linear_flag_bwd_tiled(g, mask, x, w, flag, tile=TILE, precision=None):
    """Grid-tiled flag backward — linear_relu_bwd_tiled's two-kernel tiling
    with the traced relu flag as an SMEM operand on both kernels."""
    mb, dout = g.shape
    din = x.shape[1]
    fl = jnp.reshape(flag, (1,)).astype(jnp.int32)
    gp = _pad_to(_pad_to(g, 0, tile), 1, tile)
    mp = _pad_to(_pad_to(mask, 0, tile), 1, tile)
    xp = _pad_to(_pad_to(x, 0, tile), 1, tile)
    wp = _pad_to(_pad_to(w, 0, tile), 1, tile)
    mbp, doutp = gp.shape
    dinp = xp.shape[1]
    dx = pl.pallas_call(
        functools.partial(_flag_bwd_dx_kernel, precision=precision),
        grid=(mbp // tile, dinp // tile, doutp // tile),
        out_shape=jax.ShapeDtypeStruct((mbp, dinp), jnp.float32),
        in_specs=[
            pl.BlockSpec((1,), lambda i, j, c: (0,), memory_space=pltpu.SMEM),
            pl.BlockSpec((tile, tile), lambda i, j, c: (i, c), memory_space=pltpu.VMEM),
            pl.BlockSpec((tile, tile), lambda i, j, c: (i, c), memory_space=pltpu.VMEM),
            pl.BlockSpec((tile, tile), lambda i, j, c: (c, j), memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec(
            (tile, tile), lambda i, j, c: (i, j), memory_space=pltpu.VMEM
        ),
        interpret=_interpret(),
    )(fl, gp, mp, wp)
    dw, db = pl.pallas_call(
        functools.partial(_flag_bwd_dw_kernel, precision=precision),
        grid=(doutp // tile, dinp // tile, mbp // tile),
        out_shape=(
            jax.ShapeDtypeStruct((doutp, dinp), jnp.float32),
            jax.ShapeDtypeStruct((1, doutp), jnp.float32),
        ),
        in_specs=[
            pl.BlockSpec((1,), lambda j, k, i: (0,), memory_space=pltpu.SMEM),
            pl.BlockSpec((tile, tile), lambda j, k, i: (i, j), memory_space=pltpu.VMEM),
            pl.BlockSpec((tile, tile), lambda j, k, i: (i, j), memory_space=pltpu.VMEM),
            pl.BlockSpec((tile, tile), lambda j, k, i: (i, k), memory_space=pltpu.VMEM),
        ],
        out_specs=(
            pl.BlockSpec((tile, tile), lambda j, k, i: (j, k), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, tile), lambda j, k, i: (0, j), memory_space=pltpu.VMEM),
        ),
        interpret=_interpret(),
    )(fl, gp, mp, xp)
    return dx[:mb, :din], dw[:dout, :din], db[:, :dout]


def flag_kernels_fit(mb, din, dout):
    """True when a (mb, din) x (dout, din) layer fits the single-block
    budget for BOTH flag kernels. No longer a rejection gate: oversize
    slots auto-dispatch to the grid-tiled flag kernels — kept as the
    introspection helper that says which regime a slot selects."""
    return (
        _fwd_bytes(mb, din, dout) <= SINGLE_BLOCK_BUDGET_BYTES
        and _bwd_bytes(mb, din, dout) <= SINGLE_BLOCK_BUDGET_BYTES
    )


# ---------------------------------------------------------------------------
# Whole-training-step mega-kernel (sequential fused path)
# ---------------------------------------------------------------------------
#
# Motivation (docs/performance.md roofline): the flagship epoch is op-issue
# bound — ~40 small XLA ops per batch retiring at ~240 ns each, serialized by
# SGD's step-to-step dependence. The model's ENTIRE working set (724 KB
# params + ~1 MB activations/masks) fits VMEM, so the whole per-batch
# computation — L-layer forward, grouped-softmax MSE head, backward, SGD
# update — can be ONE kernel: one op per batch on the serial chain instead
# of ~40, attacking the binding roofline directly. The expression is
# identical to the fused XLA path (same dots at the same precision, same
# grouped stability max, same 1e-7 softmax quirk, same update expression),
# INTERPRETER-verified bit-for-bit in tests/test_pallas_ops.py. Bitwise is an
# interpreter property: compiled by Mosaic on a v5e the step, epoch and run
# kernels (SGD, momentum, with clipping and weight decay) trained the
# flagship model to within 2e-4 of the size of the XLA path's own update
# (max |diff| 1.1e-7 on weights of order 0.1; PR 21), not bit for bit.


def _batch_grads(
    x, y, ws, bs, *, relu_flags, group_rows, batch_size, precision,
    clip_norm=None,
):
    """The per-batch gradient math shared by every training kernel, on param
    VALUES (already read from refs): L-layer forward with live
    activations/masks, the reference-quirk softmax-MSE head, backward.
    Returns ``(dws, dbs, loss)`` — gradient SUMS over the batch (the loss
    is pre-scaled by the global batch size, the reference's ledger). ONE
    definition so the bit-identity contract (fused XLA == step kernel ==
    epoch kernel, any optimizer variant) cannot drift between kernels.

    ``clip_norm``: optional global-norm gradient clipping, applied to the
    batch gradient before it is returned — the same point in the math where
    the XLA path applies ``optimizer.clip_tree`` to the accumulated batch
    gradient. The clip goes through ``optimizer.clip_tree`` itself (on the
    in-kernel gradient VALUES, arranged in the same per-layer {"W","b"}
    tree shape), so leaf order, accumulation and scale are identical to
    the XLA path's by construction."""
    L = len(ws)

    # ---- forward (activations/masks stay live in VMEM) ----
    a = x
    acts, masks = [], [None] * L
    for l in range(L):
        acts.append(a)
        z = (
            jnp.dot(
                a, ws[l].T, precision=precision,
                preferred_element_type=jnp.float32,
            )
            + bs[l]
        )
        if relu_flags[l]:
            masks[l] = (z > 0.0).astype(jnp.float32)
            a = jnp.maximum(z, 0.0)
        else:
            a = z

    # ---- head: softmax with the reference's quirks (ops.softmax) ----
    # stability max per consecutive group_rows-row group (the fused-microbatch
    # semantics, ops._stability_max) via STATIC row slices — scalar max +
    # broadcast per group, no 3-D reshapes (Mosaic-friendly)
    z_head = a
    rows = z_head.shape[0]
    parts = []
    for g0 in range(0, rows, group_rows):
        blk = z_head[g0 : g0 + group_rows, :]
        parts.append(jnp.full_like(blk, jnp.max(blk)))
    m = jnp.concatenate(parts, axis=0) if len(parts) > 1 else parts[0]
    ze = jnp.exp(z_head - m)
    p = ze / (ze.sum(axis=1, keepdims=True) + 1e-7)

    loss = jnp.sum((y - p) ** 2) / batch_size
    # d(MSE)/dp then softmax VJP (ops.mse_loss_grad + ops.softmax_grad,
    # same expression order for float identity)
    gl = -2.0 * (y - p) / batch_size
    gz = p * gl
    g = gz - p * gz.sum(axis=-1, keepdims=True)

    # ---- backward (dx from the PRE-update weights) ----
    dws, dbs = [None] * L, [None] * L
    for l in reversed(range(L)):
        ge = g * masks[l] if relu_flags[l] else g
        dws[l] = jnp.dot(
            ge.T, acts[l], precision=precision, preferred_element_type=jnp.float32
        )
        dbs[l] = jnp.sum(ge, axis=0, keepdims=True)  # b is stored (1, out)
        if l > 0:
            g = jnp.dot(
                ge, ws[l], precision=precision,
                preferred_element_type=jnp.float32,
            )

    if clip_norm is not None:
        from shallowspeed_tpu.optimizer import clip_tree

        clipped = clip_tree(
            [{"W": dws[l], "b": dbs[l]} for l in range(L)], clip_norm
        )
        dws = [layer["W"] for layer in clipped]
        dbs = [layer["b"] for layer in clipped]
    return dws, dbs, loss


def _sgd_batch_math(
    x, y, ws, bs, *, relu_flags, group_rows, batch_size, lr, decay, precision,
    clip_norm=None,
):
    """_batch_grads + the (decaying) SGD update: ``(new_ws, new_bs, loss)``.
    Same elementwise update expression as optimizer.SGD.apply."""
    dws, dbs, loss = _batch_grads(
        x, y, ws, bs, relu_flags=relu_flags, group_rows=group_rows,
        batch_size=batch_size, precision=precision, clip_norm=clip_norm,
    )
    L = len(ws)
    new_ws = [ws[l] * decay - lr * dws[l] for l in range(L)]
    new_bs = [bs[l] * decay - lr * dbs[l] for l in range(L)]
    return new_ws, new_bs, loss


def _momentum_batch_math(
    x, y, ws, bs, vws, vbs, *, relu_flags, group_rows, batch_size, lr, mu,
    decay, precision, clip_norm=None,
):
    """_batch_grads + the heavy-ball update (optimizer.MomentumSGD.apply:
    ``v <- mu*v + g; p <- decay(p) - lr*v``): returns ``(new_ws, new_bs,
    new_vws, new_vbs, loss)``."""
    dws, dbs, loss = _batch_grads(
        x, y, ws, bs, relu_flags=relu_flags, group_rows=group_rows,
        batch_size=batch_size, precision=precision, clip_norm=clip_norm,
    )
    L = len(ws)
    new_vws = [mu * vws[l] + dws[l] for l in range(L)]
    new_vbs = [mu * vbs[l] + dbs[l] for l in range(L)]
    new_ws = [ws[l] * decay - lr * new_vws[l] for l in range(L)]
    new_bs = [bs[l] * decay - lr * new_vbs[l] for l in range(L)]
    return new_ws, new_bs, new_vws, new_vbs, loss


# per-optimizer operand geometry: how many params-mirror state groups ride
# along with the params. Adam is absent on purpose: its bias correction is a
# scalar ``b ** t`` with a traced exponent, which Mosaic cannot legalize
# (``math.powf``, measured on a v5e, PR 21) — adam trains through XLA only.
_OPT_MIRRORS = {"sgd": 0, "momentum": 1}


def _train_kernel_body(
    x_ref, y_ref, *refs, L, relu_flags, group_rows, batch_size, lr, opt, decay,
    precision, epoch_mode, run_mode=False, clip_norm=None,
):
    """THE training kernel body — every public variant (step/epoch/run x
    sgd/momentum) compiles from this one definition so the plumbing cannot
    drift:

    - ``opt``: {"kind": "sgd"} | {"kind": "momentum", "mu": f}. The operand
      list carries one params-mirror group per state mirror (momentum: the
      velocity), per _OPT_MIRRORS.
    - ``epoch_mode``: False = one batch per launch (refs are plain in/out);
      True = the grid is the batch axis — inputs seed the REVISITED output
      blocks at grid step 0, which then hold the live params + state in
      VMEM for the whole epoch, and the loss accumulates the per-batch
      losses before a final divide (matching the epoch scan's
      sum-then-divide order exactly).
    - ``run_mode`` (requires ``epoch_mode``): the grid is (epochs, batches)
      — the ENTIRE multi-epoch run is one kernel. Params + state seed at
      the very first grid step and stay VMEM-resident for the whole run;
      each epoch accumulates its own mean into row ``e`` of the loss array
      with the same zero/sum/divide order as the single-epoch kernel.

    Operand layout: ``[x, y] + ins + outs + [loss]`` where ``ins``/``outs``
    are ``w*L + b*L`` then mirror groups (each ``w*L + b*L``-shaped), all
    VMEM blocks. The loss is a whole ``(n_epochs or 1, 1)`` SMEM array
    written one element at a time: Mosaic stores no scalar to VMEM.
    """
    kind = opt["kind"]
    n = 2 * L * (1 + _OPT_MIRRORS[kind])
    ins = refs[:n]
    outs = refs[n : 2 * n]
    loss_ref = refs[2 * n]
    e_row = 0  # this epoch's row of the (n_epochs, 1) loss array

    if epoch_mode:
        if run_mode:
            e_idx, b_idx = pl.program_id(0), pl.program_id(1)
            nb = pl.num_programs(1)
            first_step = (e_idx == 0) & (b_idx == 0)
            e_row = e_idx
        else:
            b_idx = pl.program_id(0)
            nb = pl.num_programs(0)
            first_step = b_idx == 0

        @pl.when(first_step)
        def _init():
            for i in range(n):
                outs[i][:] = ins[i][:]

        # each epoch's loss row zeroes at the START of that epoch — for the
        # single-epoch kernel this is the same b == 0 step _init runs on,
        # preserving the exact zero/sum/divide order
        @pl.when(b_idx == 0)
        def _zero_loss():
            loss_ref[e_row, 0] = 0.0

        src = outs  # current params + state live in the revisited out blocks
    else:
        src = ins

    ws = [src[i][:] for i in range(L)]
    bs = [src[L + i][:] for i in range(L)]
    common = dict(
        relu_flags=relu_flags, group_rows=group_rows, batch_size=batch_size,
        lr=lr, decay=decay, precision=precision, clip_norm=clip_norm,
    )
    if kind == "sgd":
        new_ws, new_bs, loss = _sgd_batch_math(
            x_ref[:], y_ref[:], ws, bs, **common
        )
        new_vals = new_ws + new_bs
    else:  # momentum
        vws = [src[2 * L + i][:] for i in range(L)]
        vbs = [src[3 * L + i][:] for i in range(L)]
        new_ws, new_bs, new_vws, new_vbs, loss = _momentum_batch_math(
            x_ref[:], y_ref[:], ws, bs, vws, vbs, mu=opt["mu"], **common
        )
        new_vals = new_ws + new_bs + new_vws + new_vbs
    for i, v in enumerate(new_vals):
        outs[i][:] = v

    if epoch_mode:
        loss_ref[e_row, 0] += loss

        @pl.when(b_idx == nb - 1)
        def _final():
            loss_ref[e_row, 0] = loss_ref[e_row, 0] / nb

    else:
        loss_ref[0, 0] = loss


# ---------------------------------------------------------------------------
# Whole-RUN mega-kernel: (epochs x batches) as the Pallas grid
# ---------------------------------------------------------------------------
#
# The epoch kernel collapses an epoch to one device op, but a 20-epoch
# convergence run is still ~20 serial dispatches (plus scan bookkeeping) on
# the op-issue-bound critical path. In run_mode the grid gains an OUTER
# epoch axis: TPU grid steps execute row-major (epoch-major), params and
# optimizer state seed once and live in the revisited output blocks for the
# WHOLE run, x/y blocks re-stream each epoch (their index map ignores the
# epoch axis), and the per-epoch mean losses land in a (n_epochs, 1) output
# whose block follows the epoch axis. The entire training RUN — the
# reference's outermost loop — becomes ONE device op. Bit-identical to
# looping the epoch kernel (tested); eval stays outside (per-epoch
# accuracies need per-epoch params, so the evaluated run keeps the
# epochs-outer scan).


def fused_train_call(
    stage_params, x, y, *, epoch_mode, relu_flags, group_rows,
    batch_size, lr, weight_decay, precision, opt=None, mirrors=(),
    clip_norm=None, n_epochs=None,
):
    """THE public entry point for every fused-training kernel variant
    (step/epoch/run x sgd/momentum — trainer._fused_kernel_call is the
    sole caller and owns the optimizer-state mapping): assembles the flat
    operand list (params, then one mirror group per optimizer state
    mirror), the (optional) batch-axis grid with constant-index blocks,
    and unpacks the outputs. ``opt`` is the kernel-body optimizer
    descriptor (default plain SGD; see _train_kernel_body); ``mirrors``
    must match its _OPT_MIRRORS. ``epoch_mode=False`` takes x: (B, in),
    y: (B, out) and runs one batch; ``epoch_mode=True`` takes X: (nb, B,
    in), Y: (nb, B, out) and runs the whole epoch as one kernel; with
    ``n_epochs`` set (requires epoch_mode) the grid is (n_epochs, nb) and
    the ENTIRE run is one kernel — ``loss`` comes back as the (n_epochs,)
    per-epoch means. ``clip_norm``: optional global-norm gradient clipping
    inside the kernel (see _batch_grads — bit-identical to the XLA path's
    optimizer.clip_tree). Returns ``(new_stage_params, new_mirrors,
    loss)``."""
    from shallowspeed_tpu.optimizer import _decay_factor

    opt = opt or {"kind": "sgd"}
    # explicit raise, not assert: the geometry contract must hold under
    # ``python -O`` too — a mismatched call would otherwise silently
    # mis-slice the flat operand list
    if _OPT_MIRRORS[opt["kind"]] != len(mirrors):
        raise ValueError(
            f"optimizer kind {opt['kind']!r} expects "
            f"{_OPT_MIRRORS[opt['kind']]} state mirror group(s), got "
            f"{len(mirrors)}"
        )
    L = len(stage_params)

    def flat_group(group):
        return [sp["W"] for sp in group] + [
            jnp.reshape(sp["b"], (1, -1)) for sp in group
        ]

    flat = flat_group(stage_params)
    for mirror in mirrors:
        flat += flat_group(mirror)
    decay = _decay_factor(lr, weight_decay) if weight_decay else 1.0
    if n_epochs is not None and not epoch_mode:
        raise ValueError("n_epochs requires epoch_mode=True")
    kernel = functools.partial(
        _train_kernel_body,
        L=L, relu_flags=tuple(relu_flags), group_rows=group_rows,
        batch_size=batch_size, lr=lr, opt=opt, decay=decay,
        precision=precision, epoch_mode=epoch_mode,
        run_mode=n_epochs is not None, clip_norm=clip_norm,
    )
    loss_shape = (1, 1) if n_epochs is None else (n_epochs, 1)
    out_shape = tuple(
        [jax.ShapeDtypeStruct(a.shape, jnp.float32) for a in flat]
        + [jax.ShapeDtypeStruct(loss_shape, jnp.float32)]
    )
    # Mosaic stores no scalar to VMEM: the loss is a whole SMEM array
    # written one element at a time; everything else is a VMEM block
    loss_spec = pl.BlockSpec(memory_space=pltpu.SMEM)
    if epoch_mode:
        nb, B_, din = x.shape
        dout = y.shape[-1]
        x = jnp.reshape(x, (nb * B_, din))
        y = jnp.reshape(y, (nb * B_, dout))
        if n_epochs is None:
            grid = (nb,)
            batch_block = lambda b: (b, 0)  # noqa: E731
        else:
            # epoch-major grid; the x/y index map ignores the epoch axis
            # (the same data re-streams every epoch)
            grid = (n_epochs, nb)
            batch_block = lambda e, b: (b, 0)  # noqa: E731
        const = lambda shape: pl.BlockSpec(  # noqa: E731
            shape, lambda *_: tuple(0 for _ in shape), memory_space=pltpu.VMEM
        )
        state_specs = [const(a.shape) for a in flat]
        call_kwargs = dict(
            grid=grid,
            in_specs=[
                pl.BlockSpec((B_, din), batch_block, memory_space=pltpu.VMEM),
                pl.BlockSpec((B_, dout), batch_block, memory_space=pltpu.VMEM),
            ]
            + state_specs,
            out_specs=tuple(state_specs + [loss_spec]),
        )
    else:
        vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
        call_kwargs = dict(
            in_specs=[vmem] * (2 + len(flat)),
            out_specs=tuple([vmem] * len(flat) + [loss_spec]),
        )
    outs = pl.pallas_call(
        kernel, out_shape=out_shape, interpret=_interpret(), **call_kwargs
    )(x, y, *flat)

    def unflat_group(g):
        base = 2 * L * g
        return [{"W": outs[base + l], "b": outs[base + L + l]} for l in range(L)]

    new_params = unflat_group(0)
    new_mirrors = [unflat_group(1 + i) for i in range(len(mirrors))]
    loss_out = outs[len(flat)]
    loss = loss_out[0, 0] if n_epochs is None else jnp.reshape(loss_out, (-1,))
    return new_params, new_mirrors, loss


# ---------------------------------------------------------------------------
# Whole-EPOCH mega-kernel: the batch dimension as the Pallas grid
# ---------------------------------------------------------------------------
#
# The step mega-kernel collapses ~40 XLA ops per batch into 1, but an epoch
# is still a lax.scan issuing one kernel per batch (~464 serial dispatches
# for the flagship dataset) — each paying the measured ~240 ns op-issue
# floor plus scan bookkeeping. In epoch_mode the GRID is the batch
# dimension: TPU grid steps execute sequentially, so the params (and
# velocity) live in the revisited output blocks (constant index maps keep
# them VMEM-resident across the whole grid; x/y stream in per-batch with
# Pallas's automatic double buffering) and the ENTIRE epoch is ONE kernel
# launch. Expressions are identical to the step variant per batch and the
# loss-mean accumulation matches the epoch scan's order, so the result is
# bit-identical to the scan-of-megakernel path in the interpreter (on a
# chip, see the tolerance measured above).


def train_step_kernel_fits(batch_rows, sizes, state_mirrors=0):
    """Conservative VMEM feasibility check for the mega-kernel: params (x2
    for the updated copies, plus in+out copies of each optimizer state
    mirror — momentum: 1 velocity mirror), activations + masks at
    ``batch_rows``, and the input batch, against the single-block budget."""
    return (
        _kernel_bytes(batch_rows, sizes, state_mirrors)
        <= SINGLE_BLOCK_BUDGET_BYTES
    )


def train_epoch_kernel_fits(batch_rows, sizes, state_mirrors=0):
    """VMEM feasibility for the whole-EPOCH kernel: the step kernel's
    working set PLUS a second copy of the streamed x/y blocks — Pallas
    double-buffers the per-grid-step input fetches, so two batches' worth
    of x/y can be resident at once.

    The same predicate on every backend. Checked against Mosaic on a v5e
    (PR 21, 784-H-H-10 at batch 128, no ``compiler_params``): models at
    0.92x and 0.99x of the budget compile and train, with and without a
    momentum mirror; at 1.5x the SGD kernel runs out of VMEM at compile
    time and at 2.5x both do. The byte model counts operands, not what
    Mosaic allocates, so the budget is a conservative gate with measured
    headroom — not an estimate of the compiler's limit."""
    widths = list(sizes)
    stream_extra = 4 * batch_rows * (widths[0] + widths[-1])
    return (
        _kernel_bytes(batch_rows, sizes, state_mirrors) + stream_extra
        <= SINGLE_BLOCK_BUDGET_BYTES
    )


def _kernel_bytes(batch_rows, sizes, state_mirrors=0):
    widths = list(sizes)
    params = sum(widths[i] * widths[i + 1] + widths[i + 1] for i in range(len(widths) - 1))
    state = 2 * params * state_mirrors  # in + out copies per state mirror
    acts = batch_rows * sum(widths)  # layer inputs
    masks = batch_rows * sum(widths[1:-1])
    io = batch_rows * (widths[0] + widths[-1])
    return 4 * (2 * params + state + acts + masks + io)


# ---------------------------------------------------------------------------
# The gated delta rule's chunked scan (``ops.gated_delta_scan``'s kernel
# form). One grid step is one chunk of ``heads_per_step`` heads of one row:
# the chunk axis is the grid's last and runs in order, the (d_k x d_v) state
# of each head lives in a VMEM scratch across it, and a chunk's (c x c)
# matrices are values that never leave VMEM. The forward hands back, beside
# ``o``, the state ENTERING each chunk and the chunk's triangular inverse;
# the backward walks the chunks in reverse with the state's cotangent in the
# scratch, rebuilds the rest from ``q, k, v`` and the packed per-token rows,
# and differentiates the inverse by ``dA = -T^T dT T^T``. The wrapper in
# ``ops.py`` makes the layout (heads before tokens), the per-token rows and
# their pull-back in XLA.
# ---------------------------------------------------------------------------

# rows of the packed per-token block (8, chunk) of one chunk and head
GDN_G, GDN_BETA, GDN_SEG, GDN_G_IN, GDN_G_OUT, GDN_KEEP = range(6)
GDN_ROWS = 8
GDN_HEADS_PER_STEP = 2

_NN, _NT, _TN = ((1,), (0,)), ((1,), (1,)), ((0,), (0,))


def _gdn_dot(precision):
    def dot(a, b, dims=_NN):
        return jax.lax.dot_general(
            a, b, (dims, ((), ())), precision=precision,
            preferred_element_type=jnp.float32,
        )

    return dot


def _gdn_chunk(k, p, dot):
    """What both passes build of one chunk of one head, before the inverse:
    the per-token rows as columns, the masked decay matrix ``D``, ``k k^T``
    and the strictly lower ``A``. ``p``: (8, c), rows ``GDN_*``."""
    c = k.shape[0]
    i = jax.lax.broadcasted_iota(jnp.int32, (c, c), 0)
    j = jax.lax.broadcasted_iota(jnp.int32, (c, c), 1)
    cols = p.T  # (c, 8): the rows as columns
    col = {r: cols[:, r : r + 1] for r in range(GDN_KEEP)}
    pair = (col[GDN_SEG] == p[GDN_SEG : GDN_SEG + 1]) & (i >= j)
    decay = jnp.where(
        pair, jnp.exp(jnp.where(pair, col[GDN_G] - p[GDN_G : GDN_G + 1], 0.0)), 0.0
    )
    kk = dot(k, k, _NT)
    a = jnp.where(i > j, col[GDN_BETA] * kk * decay, 0.0)
    return i, j, col, decay, kk, a


def _gdn_inverse(a, i, j, leaf, dot):
    """``(I + a)^-1`` for the strictly lower (c, c) ``a``, by
    ``ops._unit_lower_inverse``'s steps: the ``leaf``-row diagonal blocks by
    the finite series ``(I - a)(I + a^2)(I + a^4)...``, then neighbouring
    blocks merged, ``t21 = -(t22 a21) t11``, until one is left. A product
    costs the MXU its left operand's ROWS (on the chip 32 rows against a
    (128 x 128) right operand take a quarter of the time of 128), so the
    series runs on the blocks laid side by side, (leaf, c), against the
    same blocks as one block-diagonal right operand, and a merge multiplies
    only the rows of each pair's second block. ``leaf`` and ``c`` are
    powers of two, so ``i ^ j < b`` says "same block of b rows"."""
    return _gdn_inverses([a], i, j, leaf, dot)[0]


def _gdn_inverses(mats, i, j, leaf, dot):
    """``_gdn_inverse`` of each of ``mats``, step by step side by side: every
    product waits for the one before it, and the products of different
    matrices, issued in turn, fill each other's waits (on the chip the
    per-channel forward of a layer-row took 7.30 ms a head at a time, 5.93
    with two heads side by side and 5.23 with four: PERF.md section 6, PR
    36)."""
    c = mats[0].shape[0]
    each = range(len(mats))
    same_leaf = (i ^ j) < leaf

    def side_by_side(m):  # block-diagonal (c, c) -> (leaf, c)
        out = m[:leaf]
        for r in range(leaf, c, leaf):
            out = out + m[r : r + leaf]
        return out

    def diagonal(m):  # (leaf, c) -> block-diagonal (c, c)
        return jnp.where(same_leaf, jnp.concatenate([m] * (c // leaf), axis=0), 0.0)

    power_d = [jnp.where(same_leaf, -a, 0.0) for a in mats]
    power = [side_by_side(m) for m in power_d]
    at = jax.lax.broadcasted_iota(jnp.int32, (leaf, c), 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, (leaf, c), 1)
    inverse = [((lane & (leaf - 1)) == at).astype(m.dtype) + m for m in power]
    done = 2  # powers 0 .. done - 1 are in ``inverse``
    while done < leaf:
        power = [dot(power[x], power_d[x]) for x in each]
        power_d = [diagonal(m) for m in power]
        inverse = [inverse[x] + dot(inverse[x], power_d[x]) for x in each]
        done *= 2
    t = [diagonal(m) for m in inverse]
    b = leaf
    while b < c:
        a21 = [jnp.where(((i ^ j) < 2 * b) & ((i ^ j) >= b), a, 0.0) for a in mats]
        pairs = range(0, c, 2 * b)
        t22 = [jnp.concatenate([m[s + b : s + 2 * b] for s in pairs], axis=0) for m in t]
        t2a = [dot(t22[x], a21[x]) for x in each]
        t2 = [t22[x] - dot(t2a[x], t[x]) for x in each]  # the rows [t21 | t22] of every pair
        t = [
            jnp.concatenate(
                [
                    part
                    for n, s in enumerate(pairs)
                    for part in (t[x][s : s + b], t2[x][n * b : (n + 1) * b])
                ],
                axis=0,
            )
            for x in each
        ]
        b *= 2
    return t


def _gdn_fwd_kernel(
    q_ref, k_ref, v_ref, p_ref, o_ref, s_ref, t_ref, state, *, leaf, precision
):
    dot = _gdn_dot(precision)

    @pl.when(pl.program_id(1) == 0)
    def _():
        state[...] = jnp.zeros_like(state)

    for h in range(q_ref.shape[0]):
        q, k, v, p = q_ref[h], k_ref[h], v_ref[h], p_ref[h, 0]
        i, j, col, decay, _, a = _gdn_chunk(k, p, dot)
        # float32 passes whatever ``precision``, as ``ops._unit_lower_inverse``
        t = _gdn_inverse(a, i, j, leaf, _gdn_dot(jax.lax.Precision.HIGHEST))
        s = state[h]
        s_ref[h, 0] = s
        t_ref[h] = t
        w = dot(t, (col[GDN_BETA] * col[GDN_G_IN]) * k)
        u = dot(t, col[GDN_BETA] * v) - dot(w, s)
        o_ref[h] = dot(q * col[GDN_G_IN], s) + dot(dot(q, k, _NT) * decay, u)
        keep = jnp.sum(p[GDN_KEEP : GDN_KEEP + 1])  # lane 0 holds it, the rest 0
        state[h] = s * keep + dot(k * col[GDN_G_OUT], u, _TN)


def _gdn_bwd_kernel(
    q_ref, k_ref, v_ref, p_ref, s_ref, t_ref, do_ref,
    dq_ref, dk_ref, dv_ref, dp_ref, dstate, *, precision,
):
    dot = _gdn_dot(precision)

    @pl.when(pl.program_id(1) == 0)  # the LAST chunk: the index maps run reversed
    def _():
        dstate[...] = jnp.zeros_like(dstate)

    def lanes(x):  # (c, d) -> (c, 1)
        return jnp.sum(x, axis=1, keepdims=True)

    for h in range(q_ref.shape[0]):
        q, k, v, p = q_ref[h], k_ref[h], v_ref[h], p_ref[h, 0]
        s, t, do, ds_out = s_ref[h, 0], t_ref[h], do_ref[h], dstate[h]
        i, j, col, decay, kk, _ = _gdn_chunk(k, p, dot)
        beta, g_in, g_out = col[GDN_BETA], col[GDN_G_IN], col[GDN_G_OUT]
        keep = jnp.sum(p[GDN_KEEP : GDN_KEEP + 1])
        # the forward's values again
        u0 = dot(t, beta * v)
        w = dot(t, (beta * g_in) * k)
        u = u0 - dot(w, s)
        qk = dot(q, k, _NT)
        # O = (q g_in) S + (qk . D) U;  S' = keep S + (k g_out)^T U
        du = dot(qk * decay, do, _TN) + dot(k * g_out, ds_out)
        dqk = dot(do, u, _NT)
        dq_in = dot(do, s, _NT)
        dk_out = dot(u, ds_out, _NT)
        dw = -dot(du, s, _NT)  # U = U0 - W S
        dstate[h] = keep * ds_out + dot(q * g_in, do, _TN) - dot(w, du, _TN)
        dkeep = jnp.sum(ds_out * s)
        # [U0 | W] = T [beta v | beta g_in k];  dA = -T^T dT T^T, strictly lower
        dr_v = dot(t, du, _TN)
        dr_k = dot(t, dw, _TN)
        da = jnp.where(i > j, -(dot(dr_v, u0, _NT) + dot(dr_k, w, _NT)), 0.0)
        dkk = da * beta * decay  # A = beta (k k^T) D
        dpair = dqk * decay  # of q k^T
        e = (da * beta * kk + dqk * qk) * decay  # dD . D: g_i takes rows, g_j columns
        dk_rk = lanes(dr_k * k)
        dq_ref[h] = dq_in * g_in + dot(dpair, k)
        dk_ref[h] = (
            dk_out * g_out + (beta * g_in) * dr_k
            + dot(dkk + dkk.T, k) + dot(dpair, q, _TN)
        )
        dv_ref[h] = beta * dr_v
        # the per-token cotangents, columns -> the rows of ``p``
        z = (
            jnp.where(j == GDN_G, lanes(e), 0.0)
            + jnp.where(
                j == GDN_BETA, lanes(da * kk * decay) + lanes(dr_v * v) + g_in * dk_rk, 0.0
            )
            + jnp.where(j == GDN_G_IN, lanes(dq_in * q) + beta * dk_rk, 0.0)
            + jnp.where(j == GDN_G_OUT, lanes(dk_out * k), 0.0)
        )
        row = jax.lax.broadcasted_iota(jnp.int32, (GDN_ROWS, z.shape[1]), 0)
        dp_ref[h, 0] = (
            z.T[:GDN_ROWS]
            - jnp.where(row == GDN_G, jnp.sum(e, axis=0, keepdims=True), 0.0)
            + jnp.where(row == GDN_KEEP, dkeep, 0.0)
        )


def _gdn_specs(per, c, chunk_of):
    """Block specs by operand kind, ``per`` heads to a block: arrays per
    token (rows * heads, seq, d), and arrays per chunk, the packed rows
    (rows * heads, n, 8, c) and the states (rows * heads, n, d_k, d_v);
    ``chunk_of(j)`` is the chunk grid step ``j`` works on."""
    def tokens(d):
        return pl.BlockSpec((per, c, d), lambda i, j: (i, chunk_of(j), 0))

    def per_chunk(*dims):
        return pl.BlockSpec((per, 1, *dims), lambda i, j: (i, chunk_of(j), 0, 0))

    return tokens, per_chunk


def _gdn_heads_per_step(rh):
    return GDN_HEADS_PER_STEP if rh % GDN_HEADS_PER_STEP == 0 else 1


def gdn_scan_fwd(q, k, v, p, *, leaf, precision):
    """``q, k``: (rows * heads, seq, d_k), ``v``: (rows * heads, seq, d_v),
    ``p``: (rows * heads, n, 8, c) the packed per-token rows. -> ``o``
    (rows * heads, seq, d_v), the state entering each chunk (rows * heads,
    n, d_k, d_v) and each chunk's inverse (rows * heads, seq, c)."""
    rh, seq, dk = q.shape
    dv, n, c = v.shape[-1], p.shape[1], p.shape[-1]
    per = _gdn_heads_per_step(rh)
    tokens, per_chunk = _gdn_specs(per, c, lambda j: j)
    return pl.pallas_call(
        functools.partial(_gdn_fwd_kernel, leaf=leaf, precision=precision),
        grid=(rh // per, n),
        in_specs=[tokens(dk), tokens(dk), tokens(dv), per_chunk(GDN_ROWS, c)],
        out_specs=[tokens(dv), per_chunk(dk, dv), tokens(c)],
        out_shape=[
            jax.ShapeDtypeStruct((rh, seq, dv), q.dtype),
            jax.ShapeDtypeStruct((rh, n, dk, dv), q.dtype),
            jax.ShapeDtypeStruct((rh, seq, c), q.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((per, dk, dv), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")
        ),
        interpret=_interpret(),
        name="gdn_scan_fwd",
    )(q, k, v, p)


def gdn_scan_bwd(q, k, v, p, states, inverses, do, *, precision):
    """The pull-back of ``gdn_scan_fwd``'s ``o``: -> ``dq, dk, dv`` shaped
    like ``q, k, v`` and ``dp`` shaped like ``p`` (the cotangents of its
    rows ``g``, ``beta``, ``g_in``, ``g_out`` and ``keep``)."""
    rh, seq, dk = q.shape
    dv, n, c = v.shape[-1], p.shape[1], p.shape[-1]
    per = _gdn_heads_per_step(rh)
    tokens, per_chunk = _gdn_specs(per, c, lambda j: n - 1 - j)
    return pl.pallas_call(
        functools.partial(_gdn_bwd_kernel, precision=precision),
        grid=(rh // per, n),
        in_specs=[
            tokens(dk), tokens(dk), tokens(dv), per_chunk(GDN_ROWS, c),
            per_chunk(dk, dv), tokens(c), tokens(dv),
        ],
        out_specs=[tokens(dk), tokens(dk), tokens(dv), per_chunk(GDN_ROWS, c)],
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, q.dtype),
            jax.ShapeDtypeStruct(k.shape, q.dtype),
            jax.ShapeDtypeStruct(v.shape, q.dtype),
            jax.ShapeDtypeStruct(p.shape, q.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((per, dk, dv), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")
        ),
        interpret=_interpret(),
        name="gdn_scan_bwd",
    )(q, k, v, p, states, inverses, do)


# ---------------------------------------------------------------------------
# The per-channel delta rule's chunked scan (``ops.kda_scan``'s kernel form):
# the scalar rule's kernels above with the decay INSIDE each pair's product
# over the key channels. What that changes: ``g`` (the log decay) is an
# operand shaped like ``k``, and its running sum over the chunk is made in
# the kernel (a product with a triangle of ones; backward, the transposed
# triangle pulls the cotangent back); a chunk's pair matrices ``sum_d x_i[d]
# k_j[d] exp(g_i[d] - g_j[d])`` are built in VMEM by sub-blocks that are
# halved until one token is left (``_kda_levels``), every exponent at most
# zero, and handed to the backward, which builds none; the state is kept
# transposed, (d_v x d_k), so that the decay of a key channel is a
# broadcast along the lanes. The arrays per token are read and written
# where the model keeps them, (rows, seq, heads, d): a block is one chunk of
# 8 heads, a head one sublane of each token's (8, 128) tile, so nothing is
# transposed in XLA. The heads of a block are worked a few side by side
# (``KDA_SIDE_BY_SIDE``): a chunk is a chain of small products that each
# wait for the one before, and another head's fill the waits.
# ---------------------------------------------------------------------------

# rows of the packed block (8, chunk) of one chunk of one row: the document
# and three masks a token. Each head's beta comes in a block of its own, (8,
# chunk) a chunk and group of at most 8 heads.
KDA_SEG, KDA_FIRST, KDA_CARRIED, KDA_TO_LAST = range(4)
KDA_ROWS = 8
KDA_SIDE_BY_SIDE = 4
# of the chip's 128 MiB: the backward's blocks, twice, and its values take 18
# MiB at the cell's shapes and 25 at 256 value channels; Mosaic's own limit is 16
KDA_VMEM_BYTES = 64 * 2**20


def _kda_reference_rows(g, h):
    """Row ``t`` of the result is row ``(t & ~(h - 1)) | h`` of ``g`` (c, d):
    the first token of the second half of ``t``'s block of ``2 h`` tokens.
    Whole sublane tiles where ``h >= 8``; inside a tile, a broadcast of one
    sublane selected by the token's place in it."""
    c, d = g.shape
    if h >= 8:
        parts = [
            jnp.broadcast_to(g[s + h : s + h + 1], (2 * h, d)) for s in range(0, c, 2 * h)
        ]
        return parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=0)
    tiles = g.reshape(c // 8, 8, d)
    at = jax.lax.broadcasted_iota(jnp.int32, tiles.shape, 1)
    out = jnp.broadcast_to(tiles[:, h : h + 1], tiles.shape)
    for s in range(2 * h, 8, 2 * h):
        out = jnp.where(at >= s, jnp.broadcast_to(tiles[:, s + h : s + h + 1], tiles.shape), out)
    return out.reshape(c, d)


def _kda_halves(h, c):
    """-> ``rows(x, second)``, the rows of the second (or first) halves of
    the blocks of ``2 h`` tokens, (c, n) -> (c / 2, n), and ``back(y,
    second)``, which puts them back with zeros between: a product costs the
    MXU its left operand's rows, and at a level only second halves are an
    ``i`` and only first halves a ``j``. Where a half is whole sublane tiles
    (``h >= 8``); below that every row goes in, and both are the identity."""
    if h < 8:
        return (lambda x, second: x), (lambda y, second: y)

    def rows(x, second):
        parts = [x[s + h * second : s + h * (second + 1)] for s in range(0, c, 2 * h)]
        return parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=0)

    def back(y, second):
        zero = jnp.zeros((h, y.shape[1]), y.dtype)
        parts = []
        for n in range(c // (2 * h)):
            part = y[n * h : (n + 1) * h]
            parts += [zero, part] if second else [part, zero]
        return jnp.concatenate(parts, axis=0)

    return rows, back


def _kda_levels(g, i, j):
    """The pairs ``j < i`` of a chunk by the highest bit in which ``i`` and
    ``j`` differ, ``h`` = 1 ... c / 2: such a pair lies in one block of ``2
    h`` tokens, ``i`` in its second half and ``j`` in its first, and goes
    through the running sum ``r`` at the second half's first token, ``(x_i
    exp(g_i - r)) . (k_j exp(r - g_j))``: ``g`` never rises, so both
    exponents are at most zero whatever the decay. -> ``level_of`` (c, c):
    1 + the place of that bit, 0 on the diagonal; and for each level, the
    finest first, ``(e, upper)``: the factor ``e`` (c, d_k) of every token
    in its role at this level (a second half's as ``i``, a first half's as
    ``j``) and (c, 1) whether a token is an ``i``. ``ops._decayed_pairs``'
    sub-blocks, halved until a token is left: each level is one product in
    place of a sum over the channels pair by pair."""
    c = g.shape[0]
    differ = i ^ j
    level_of = jnp.zeros_like(differ)
    levels = []
    h = 1
    while h < c:
        ref = _kda_reference_rows(g, h)
        upper = (i[:, :1] & h) != 0
        levels.append((jnp.exp(jnp.where(upper, g - ref, ref - g)), upper))
        level_of = level_of + (differ >= h).astype(level_of.dtype)
        h *= 2
    return level_of, levels


def _kda_decays(gl, p, top):
    """What both passes build of one chunk of one head from its log decay
    ``gl`` (c, d_k) and the packed rows ``p`` (8, c) alone: the masks, the
    running sum of the decay (one product, ``top``, the float32-pass one: it
    stands for exact additions) and the factors made from it, each level's
    among them. The backward builds this and reads the pair matrices the
    forward kept."""
    c = gl.shape[0]
    i = jax.lax.broadcasted_iota(jnp.int32, (c, c), 0)
    j = jax.lax.broadcasted_iota(jnp.int32, (c, c), 1)
    cols = p.T  # (c, 8): the rows as columns
    col = lambda r: cols[:, r : r + 1]  # noqa: E731
    first = col(KDA_FIRST) > 0
    lower = (col(KDA_SEG) == p[KDA_SEG : KDA_SEG + 1]) & (i > j)
    # a document's first token takes no decay: its state starts from zero
    g = top((i >= j).astype(gl.dtype), jnp.where(first, 0.0, gl))
    level_of, levels = _kda_levels(g, i, j)
    g_in = jnp.where(col(KDA_CARRIED) > 0, jnp.exp(g), 0.0)
    g_out = jnp.where(col(KDA_TO_LAST) > 0, jnp.exp(g[c - 1 :] - g), 0.0)
    return dict(
        i=i, j=j, first=first, lower=lower, level_of=level_of, levels=levels,
        g_in=g_in, g_out=g_out, keep=g_in[c - 1 :],
    )


def _kda_chunk(q, k, gl, p, beta, dot, top):
    """``_kda_decays`` and the chunk's two pair matrices, which the forward
    builds and hands on: ``kk``, the strictly lower decayed ``k k^T``, and
    ``m``, ``q k^T`` with its diagonal. ``beta``: (c, 1)."""
    x = _kda_decays(gl, p, top)
    c = k.shape[0]
    # [k k^T; q k^T] with the decay inside, a level at a time: a coarser
    # level overwrites what a finer one left of its pairs
    level_of2 = jnp.concatenate([x["level_of"], x["level_of"]], axis=0)
    pairs = jnp.zeros((2 * c, c), k.dtype)
    for level, (e, _) in enumerate(x["levels"], 1):
        k_e, (rows, back) = k * e, _kda_halves(2 ** (level - 1), c)
        both = dot(jnp.concatenate([rows(k_e, 1), rows(q * e, 1)], axis=0), k_e, _NT)
        half = both.shape[0] // 2
        both = jnp.concatenate([back(both[:half], 1), back(both[half:], 1)], axis=0)
        pairs = jnp.where(level_of2 >= level, both, pairs)
    i, j, lower = x["i"], x["j"], x["lower"]
    kk = jnp.where(lower, pairs[:c], 0.0)
    qk = jnp.where(lower, pairs[c:], 0.0)
    qk = qk + jnp.where(i == j, jnp.sum(q * k, axis=1, keepdims=True), 0.0)
    return dict(x, kk=kk, a=beta * kk, m=qk)


def _kda_side_by_side(ref, body):
    """``body(heads)`` over the heads of a block (``ref``: heads first), ``KDA_
    SIDE_BY_SIDE`` at a time where they come in so many."""
    heads = ref.shape[0]
    width = next(w for w in (KDA_SIDE_BY_SIDE, 2, 1) if heads % w == 0)
    jax.lax.fori_loop(
        0, heads // width,
        lambda step, _: body([step * width + n for n in range(width)]), None,
    )


def _kda_operands(refs, p_ref, b_ref, heads):
    """Of each of ``heads``: -> ``at`` (its index in a block (1, c, heads, d)
    of an array per token), ``beta`` (c, 1) and ``q, k, v`` and the log
    decay, a list each; and the packed rows (8, c)."""
    p, betas = p_ref[0, 0], b_ref[0, 0].T  # (8, c), (c, 8)
    head_of = jax.lax.broadcasted_iota(jnp.int32, betas.shape, 1)
    at = [(0, slice(None), h, slice(None)) for h in heads]
    beta = [jnp.sum(jnp.where(head_of == h, betas, 0.0), axis=1, keepdims=True) for h in heads]
    q, k, v, gl = ([ref[a] for a in at] for ref in refs)
    return at, beta, q, k, v, gl, p


def _kda_corrected(t, beta, k, v, g_in, s, dot):
    """``U0 = T (beta v)``, ``W = T (beta g_in k)`` and the corrected values
    ``U = U0 - W S`` (``S`` as (d_v, d_k)), of several heads side by side."""
    each = range(len(t))
    u0 = [dot(t[n], beta[n] * v[n]) for n in each]
    w = [dot(t[n], (beta[n] * g_in[n]) * k[n]) for n in each]
    return u0, w, [u0[n] - dot(w[n], s[n], _NT) for n in each]


def _kda_fwd_kernel(
    q_ref, k_ref, v_ref, g_ref, p_ref, b_ref, o_ref, s_ref, t_ref, r_ref, state, *,
    leaf, precision,
):
    dot, top = _gdn_dot(precision), _gdn_dot(jax.lax.Precision.HIGHEST)

    @pl.when(pl.program_id(1) == 0)
    def _():
        state[...] = jnp.zeros_like(state)

    def several(heads):
        at, beta, q, k, v, gl, p = _kda_operands(
            (q_ref, k_ref, v_ref, g_ref), p_ref, b_ref, heads
        )
        each = range(len(heads))
        x = [_kda_chunk(q[n], k[n], gl[n], p, beta[n], dot, top) for n in each]
        g_in = [c["g_in"] for c in x]
        # float32 passes whatever ``precision``, as ``ops._unit_lower_inverse``
        t = _gdn_inverses([c["a"] for c in x], x[0]["i"], x[0]["j"], leaf, top)
        s = [state[h] for h in heads]  # (d_v, d_k)
        _, _, u = _kda_corrected(t, beta, k, v, g_in, s, dot)
        o_in = [dot(q[n] * g_in[n], s[n], _NT) for n in each]
        o = [o_in[n] + dot(x[n]["m"], u[n]) for n in each]
        left = [dot(u[n], k[n] * x[n]["g_out"], _TN) for n in each]
        for n, h in enumerate(heads):
            s_ref[h, 0] = s[n]
            t_ref[h] = t[n]
            r_ref[h] = jnp.concatenate([x[n]["kk"], x[n]["m"]], axis=1)
            o_ref[at[n]] = o[n]
            state[h] = s[n] * x[n]["keep"] + left[n]

    _kda_side_by_side(state, several)


def _kda_bwd_kernel(
    q_ref, k_ref, v_ref, g_ref, p_ref, b_ref, s_ref, t_ref, r_ref, do_ref,
    dq_ref, dk_ref, dv_ref, dg_ref, db_ref, dstate, *, precision,
):
    dot, top = _gdn_dot(precision), _gdn_dot(jax.lax.Precision.HIGHEST)

    @pl.when(pl.program_id(1) == 0)  # the LAST chunk: the index maps run reversed
    def _():
        dstate[...] = jnp.zeros_like(dstate)

    def lanes(x):  # (c, d) -> (c, 1)
        return jnp.sum(x, axis=1, keepdims=True)

    db_ref[...] = jnp.zeros_like(db_ref)

    def several(heads):
        at, beta, q, k, v, gl, p = _kda_operands(
            (q_ref, k_ref, v_ref, g_ref), p_ref, b_ref, heads
        )
        each = range(len(heads))
        x = [_kda_decays(gl[n], p, top) for n in each]
        i, j, c = x[0]["i"], x[0]["j"], k[0].shape[0]
        g_in, g_out, keep = ([x[n][name] for n in each] for name in ("g_in", "g_out", "keep"))
        # the pair matrices the forward built, [kk | m] side by side
        pairs = [r_ref[h] for h in heads]
        kk, m = [r[:, :c] for r in pairs], [r[:, c:] for r in pairs]
        s, t = [s_ref[h, 0] for h in heads], [t_ref[h] for h in heads]
        do, ds_out = [do_ref[a] for a in at], [dstate[h] for h in heads]
        u0, w, u = _kda_corrected(t, beta, k, v, g_in, s, dot)  # the forward's again
        # O = (q g_in) S + M U;  S' = S keep + U^T (k g_out), S as (d_v, d_k)
        du = [dot(m[n], do[n], _TN) for n in each]
        du = [du[n] + dot(k[n] * g_out[n], ds_out[n], _NT) for n in each]
        dm = [dot(do[n], u[n], _NT) for n in each]
        dq_in = [dot(do[n], s[n]) for n in each]
        dk_out = [dot(u[n], ds_out[n]) for n in each]
        dw = [-dot(du[n], s[n]) for n in each]  # U = U0 - W S
        ds_in = [dot(do[n], q[n] * g_in[n], _TN) for n in each]
        ds_in = [ds_in[n] - dot(du[n], w[n], _TN) for n in each]
        # [U0 | W] = T [beta v | beta g_in k];  dA = -T^T dT T^T, strictly lower
        dr_v = [dot(t[n], du[n], _TN) for n in each]
        dr_k = [dot(t[n], dw[n], _TN) for n in each]
        da = [dot(dr_v[n], u0[n], _NT) for n in each]
        da = [jnp.where(x[n]["lower"], -(da[n] + dot(dr_k[n], w[n], _NT)), 0.0) for n in each]
        dq, dk, dg, sym, dqk, dqk_t = [], [], [], [], [], []
        for n, h in enumerate(heads):
            dstate[h] = ds_out[n] * keep[n] + ds_in[n]
            dkeep = jnp.sum(ds_out[n] * s[n], axis=0, keepdims=True)  # (1, d_k)
            on_diagonal = lanes(jnp.where(i == j, dm[n], 0.0))  # of sum_d q_i k_i
            dq.append(dq_in[n] * g_in[n] + on_diagonal * k[n])
            dk.append(
                dk_out[n] * g_out[n] + (beta[n] * g_in[n]) * dr_k[n] + on_diagonal * q[n]
            )
            # of g: through g_in (and ``keep``, its last row), g_out (whose
            # exponent holds the last row too), and each level's exponents
            leaving = (dk_out[n] * k[n]) * g_out[n]
            last = jnp.sum(leaving, axis=0, keepdims=True) + dkeep * keep[n]
            through = (dq_in[n] * q[n] + beta[n] * dr_k[n] * k[n]) * g_in[n] - leaving
            dg.append(through + jnp.where(i[:, :1] == c - 1, last, 0.0))
            dkk = da[n] * beta[n]  # A = beta KK
            sym.append(dkk + dkk.T)  # a level's mask is symmetric
            dqk.append(jnp.where(x[n]["lower"], dm[n], 0.0))
            dqk_t.append(dqk[n].T)
            dv_ref[at[n]] = beta[n] * dr_v[n]
            dbeta = lanes(da[n] * kk[n]) + lanes(dr_v[n] * v[n])
            dbeta = dbeta + lanes(dr_k[n] * k[n] * g_in[n])
            # the column, as row ``h`` of the block
            db_ref[0, 0] += jnp.where(j == h, dbeta, 0.0).T[:KDA_ROWS]
        for level in range(1, len(x[0]["levels"]) + 1):
            here = x[0]["level_of"] == level
            e = [x[n]["levels"][level - 1][0] for n in each]
            k_e = [k[n] * e[n] for n in each]
            q_e = [q[n] * e[n] for n in each]
            # an ``i`` of this level reads ``dqk`` and ``sym``'s lower half, a
            # ``j`` the transposes
            rows, back = _kda_halves(2 ** (level - 1), c)
            both = [
                dot(
                    jnp.concatenate(
                        [rows(jnp.where(here, dqk[n], 0.0), 1), jnp.where(here, sym[n], 0.0)],
                        axis=0,
                    ),
                    k_e[n],
                )
                for n in each
            ]
            as_i, of_kk = [back(m[:-c], 1) for m in both], [m[-c:] for m in both]
            as_j = [
                back(dot(rows(jnp.where(here, dqk_t[n], 0.0), 0), q_e[n]), 0) for n in each
            ]
            for n in each:
                upper = x[n]["levels"][level - 1][1]
                dx = as_i[n] * e[n]
                dk_level = (of_kk[n] + as_j[n]) * e[n]
                dq[n] = dq[n] + dx
                dk[n] = dk[n] + dk_level
                signed = jnp.where(upper, k[n] * dk_level, -(k[n] * dk_level))
                dg[n] = dg[n] + q[n] * dx + signed
        pulled = [top((i <= j).astype(dg[n].dtype), dg[n]) for n in each]
        for n in each:
            dq_ref[at[n]] = dq[n]
            dk_ref[at[n]] = dk[n]
            dg_ref[at[n]] = jnp.where(x[n]["first"], 0.0, pulled[n])

    _kda_side_by_side(dstate, several)


def kda_heads_per_step(heads):
    """Heads a grid step: a sublane tile of them, or all of fewer than eight
    (a block then spans the array's whole axis; a block of ``betas`` has a
    row a head and 8 rows)."""
    return 8 if heads % 8 == 0 else heads


def _kda_operand_specs(q, v, p, chunk_of, interpret):
    rows, seq, heads, dk = q.shape
    dv, n, c = v.shape[-1], p.shape[1], p.shape[-1]
    per = kda_heads_per_step(heads)
    groups = heads // per
    tokens, per_chunk = _gdn_specs(per, c, chunk_of)
    _, of_group = _gdn_specs(1, c, chunk_of)

    def in_place(d):
        return pl.BlockSpec(
            (1, c, per, d), lambda i, j: (i // groups, chunk_of(j), i % groups, 0)
        )

    return dict(
        qk=in_place(dk), v=in_place(dv), betas=of_group(KDA_ROWS, c),
        p=pl.BlockSpec((1, 1, KDA_ROWS, c), lambda i, j: (i // groups, chunk_of(j), 0, 0)),
        states=per_chunk(dv, dk), inverses=tokens(c), pairs=tokens(2 * c),
        states_shape=jax.ShapeDtypeStruct((rows * heads, n, dv, dk), q.dtype),
        inverses_shape=jax.ShapeDtypeStruct((rows * heads, seq, c), q.dtype),
        pairs_shape=jax.ShapeDtypeStruct((rows * heads, seq, 2 * c), q.dtype),
        call=dict(
            grid=(rows * groups, n),
            scratch_shapes=[pltpu.VMEM((per, dv, dk), jnp.float32)],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary"),
                vmem_limit_bytes=KDA_VMEM_BYTES,
            ),
            interpret=interpret,
        ),
    )


def kda_scan_fwd(q, k, v, g, p, betas, *, leaf, precision, interpret):
    """``q, k, g``: (rows, seq, heads, d_k) (``g`` the log decay a token and
    channel), ``v``: (rows, seq, heads, d_v), ``p``: (rows, n, 8, c) the
    packed rows ``KDA_*``, ``betas``: (rows * groups, n, 8, c), a row a head
    of each group of ``kda_heads_per_step`` heads; ``interpret``: what
    ``_interpret()`` says (the caller's, which keys a trace by it). -> ``o``
    shaped like ``v``, the state entering each chunk (rows * heads, n, d_v,
    d_k), each chunk's inverse (rows * heads, seq, c) and its two pair
    matrices side by side, ``[kk | m]`` (rows * heads, seq, 2 c): at the
    kernels' chunk of 64 one lane-dense tile a chunk and head, where the two
    apart would each be padded to 128 lanes."""
    x = _kda_operand_specs(q, v, p, lambda j: j, interpret)
    return pl.pallas_call(
        functools.partial(_kda_fwd_kernel, leaf=leaf, precision=precision),
        in_specs=[x["qk"], x["qk"], x["v"], x["qk"], x["p"], x["betas"]],
        out_specs=[x["v"], x["states"], x["inverses"], x["pairs"]],
        out_shape=[
            jax.ShapeDtypeStruct(v.shape, q.dtype), x["states_shape"], x["inverses_shape"],
            x["pairs_shape"],
        ],
        name="kda_scan_fwd",
        **x["call"],
    )(q, k, v, g, p, betas)


def kda_scan_bwd(q, k, v, g, p, betas, states, inverses, pairs, do, *, precision, interpret):
    """The pull-back of ``kda_scan_fwd``'s ``o``, from the states, inverses
    and pair matrices it kept: -> ``dq, dk, dv, dg`` shaped like ``q, k, v,
    g`` (``dg`` of the log decay itself: the running sum is pulled back in
    the kernel) and ``dbetas`` shaped like ``betas``."""
    n = p.shape[1]
    x = _kda_operand_specs(q, v, p, lambda j: n - 1 - j, interpret)
    return pl.pallas_call(
        functools.partial(_kda_bwd_kernel, precision=precision),
        in_specs=[
            x["qk"], x["qk"], x["v"], x["qk"], x["p"], x["betas"], x["states"],
            x["inverses"], x["pairs"], x["v"],
        ],
        out_specs=[x["qk"], x["qk"], x["v"], x["qk"], x["betas"]],
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, q.dtype),
            jax.ShapeDtypeStruct(k.shape, q.dtype),
            jax.ShapeDtypeStruct(v.shape, q.dtype),
            jax.ShapeDtypeStruct(g.shape, q.dtype),
            jax.ShapeDtypeStruct(betas.shape, q.dtype),
        ],
        name="kda_scan_bwd",
        **x["call"],
    )(q, k, v, g, p, betas, states, inverses, pairs, do)
