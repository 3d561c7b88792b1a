"""Sequential (single-device) training path: jitted step with microbatch scan.

Reference equivalent: running train.py with DP=1, PP=1, where the Worker
interprets [ZeroGrad, {Load, Forward, Load, Backward} x M, OptimizerStep] per
batch (/root/reference/shallowspeed/pipe.py:184-222 with one stage). Here the
whole batch — M microbatch forward+backward passes with gradient accumulation,
plus the SGD update — is ONE jitted XLA computation: the microbatch loop is a
``lax.scan`` whose carry is the gradient pytree, and ``train_epoch`` scans that
step over every batch of the epoch so an epoch is a single device program with
no host round-trips.

Gradient-correctness ledger (identical to the reference, SURVEY §3.3): the
loss gradient is scaled once by the GLOBAL batch size; each Linear backward
sums over its microbatch rows; the scan sums over microbatches; (under DP
the executor sums over replicas — one whole-tree psum at the
gradient-sync anchor, an elementwise sum). Three sums, no
averaging — bitwise the same ledger as sequential full-batch training. The
sequential path itself has no replicas and no collectives.
"""

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

from shallowspeed_tpu import ops
from shallowspeed_tpu.model import (
    ModelSpec,
    TokenModelSpec,
    model_backward,
    model_forward,
    token_loss_and_grads,
)
from shallowspeed_tpu.observability.scopes import scope


def _digest_aux(params, grads):
    """The sequential per-layer digest vectors (numerics provenance): for
    every logical (W, b) block, the uint32 wrap-around checksum of the
    POST-update float32 bits (bitcast, never a float sum — bit-identical
    runs produce bit-identical checksums), the post-update param L2 norm,
    and the post-sync PRE-clip grad L2 norm. Same block definition and
    order as ``utils.iter_param_blocks`` (global layer order), so the
    stream joins against the host digests and ``model_hash``'s blocks.
    Ordinary data flow inside the fused step — no host callbacks."""
    cw, cb, pw, pb, gw, gb = [], [], [], [], [], []
    for stage_p, stage_g in zip(params, grads):
        for lay_p, lay_g in zip(stage_p, stage_g):
            for key, crcs, pns, gns in (
                ("W", cw, pw, gw), ("b", cb, pb, gb),
            ):
                p32 = lay_p[key].astype(jnp.float32)
                crcs.append(
                    jnp.sum(
                        lax.bitcast_convert_type(p32, jnp.uint32),
                        dtype=jnp.uint32,
                    )
                )
                pns.append(jnp.sqrt(jnp.sum(p32 * p32)))
                g32 = lay_g[key].astype(jnp.float32)
                gns.append(jnp.sqrt(jnp.sum(g32 * g32)))
    return {
        "crc_w": jnp.stack(cw), "crc_b": jnp.stack(cb),
        "pnorm_w": jnp.stack(pw), "pnorm_b": jnp.stack(pb),
        "gnorm_w": jnp.stack(gw), "gnorm_b": jnp.stack(gb),
    }


def data_layout(mubatch_rows, sizes, precision, scanned=True):
    """The orientation in which the sequential path keeps its training set
    resident: ``"feature_major"``, X as ``(num_batches, M, in_dim, mubatch)``,
    or ``"row_major"``, X as ``(num_batches, M, mubatch, in_dim)``.

    The TPU runtime stores an array in the compact (8, 128)-tiled layout
    that pads least, whatever its shape says: with ``in_dim`` 784 (no
    multiple of 128) and 2,048-row microbatches (one) it lays the ROW axis
    minor, the set is resident feature-major under a row-major shape, and
    the scan re-lays-out each step's slab of X before the first Linear may
    read it (a 26 MB transpose per step at B 8,192). Feature-major in shape
    too, the scan reads ``x.T`` as it lies: the transposition moves out of
    a copy of its own and into the first Linear's two matmuls, off an
    ``in_dim``-wide operand and onto ``sizes[1]``-wide results. All four
    conditions are what a v5e measured (PERF.md section 6, PR 31):

    - a microbatch tiles rows-minor without padding (``mubatch_rows`` a
      multiple of 128 lanes, ``in_dim`` of 8 sublanes): at 32 rows the shape
      would pad 32 rows to 128 lanes, four times the set;
    - the first Linear is narrower than its input by half or more: an
      epoch at 784 -> 128, 256, 384 took 0.85, 0.98, 0.96 of the row-major
      time, at 512 0.99, at 768 and above 1.04 to 1.05;
    - ``Precision.HIGHEST``, six passes over an operand read once: at
      ``DEFAULT`` the set is packed to bfloat16 first and the result swung
      with the shape (0.90x the time at 2,048 rows, 1.52x at 16,384);
    - ``scanned``: the fused and kernel paths reshape a whole batch to
      ``(rows, in_dim)`` and stay row-major.
    """
    in_dim, first_out = sizes[0], sizes[1]
    if (
        scanned
        and precision == lax.Precision.HIGHEST
        and mubatch_rows % 128 == 0
        and in_dim % 8 == 0
        and 2 * first_out <= in_dim
    ):
        return "feature_major"
    return "row_major"


@jax.jit
def feature_major(X):
    """``(num_batches, M, mubatch, in_dim)`` -> ``(num_batches, M, in_dim,
    mubatch)``, the resident argument of an ``x_layout="feature_major"``
    program. Where ``data_layout`` engages, the chip already stores the
    argument features-major and this compiles to one copy with no temporary
    (tests/test_op_index.py): the set in and the set out."""
    return X.swapaxes(2, 3)


# A token model's step takes at most this many microbatches as one
# straight-line program; more go through a loop (``_token_step_scanned``)
_UNROLLED_MUBATCHES = 2


def token_step_is_scanned(mubatches):
    """Whether a token model's step loops over its microbatches
    (``_token_step_scanned``: the session then wraps its optimizer in
    ``optimizer.WithGradScratch``) or takes them as one straight-line
    program (``_token_step_unrolled``)."""
    return mubatches > _UNROLLED_MUBATCHES


def accumulated_expert_leaves(spec, mubatches):
    """How many of the held experts' weight-gradient leaves (``W1``, ``W3``,
    ``W2`` of each routed layer) one step makes in its accumulator itself
    (``ops.experts``): those of every microbatch that has an accumulator,
    which the straight-line step's first has not."""
    with_acc = mubatches if token_step_is_scanned(mubatches) else mubatches - 1
    return spec.routed_layers * 3 * with_acc


def _token_step_unrolled(params, opt_state, spec, xb, yb, precision):
    """A token model's microbatches one after another in ONE straight-line
    program: the first microbatch's gradient IS the accumulator, unzeroed,
    and each later layer's gradient is added as it is made. The program, and
    the time to compile it, grow with the microbatches. -> ``(grads, loss,
    census)``; ``census``: the (routed layers, experts held) int32 count of
    (token, slot) pairs routed, ``None`` for a model that routes nothing."""
    acc, loss, census = None, jnp.zeros(()), None
    for m in range(xb.shape[0]):
        with scope("batch"):
            tokens, segments = xb[m], yb[m]
        routed = []  # one (experts held,) int32 count a routed layer
        mb_loss, acc = token_loss_and_grads(
            params, spec, tokens, segments, precision, acc=acc, census=routed
        )
        with scope("loss"):
            loss = loss + mb_loss
        if routed:
            with scope("moe/route"):
                rows = jnp.stack(routed)
                census = rows if census is None else census + rows
    return acc, loss, census


def _token_step_scanned(params, opt_state, spec, xb, yb, precision):
    """The same step as a loop over the microbatches: one copy of the
    microbatch's program whatever their number (four unrolled microbatches
    of the 4-layer expert model took the chip's compiler 4.6 minutes here,
    the loop 1.4). The accumulator is the loop's carry and starts from
    ``opt_state["grads"]`` (``optimizer.WithGradScratch``: what it holds is
    never read, where it lives is the point): the first microbatch takes it
    as zero (``fresh``), its dense leaves' adds and the held experts' first
    tiles selecting zero in place of what it holds. The flag passes an
    optimization barrier: written on the loop's own counter as ``where(m >
    0, acc, 0)``, the select is dropped by the TPU compiler, which then adds
    the first microbatch onto the scratch's old value (PERF.md section 6). The
    weights pass an optimization barrier together with the microbatch's
    rows, and again before a layer's recomputed forward (``fresh_weights``):
    what a layer makes of its weights alone (``ops.dense``'s rounded copies)
    is then made where it is used, not once before the loop and kept
    through it, half a model's worth of memory."""

    def microbatch(carry, rows):
        acc, m = carry
        with scope("batch"):
            weights, (tokens, segments) = lax.optimization_barrier((params, rows))
        with scope("acc"):
            fresh = lax.optimization_barrier(m) == 0
        routed = []
        loss, acc = token_loss_and_grads(
            weights, spec, tokens, segments, precision, acc=acc, census=routed,
            fresh_weights=True, fresh=fresh,
        )
        with scope("moe/route"):
            census = jnp.stack(routed) if routed else None
        return (acc, m + 1), (loss, census)

    (acc, _), (losses, census) = lax.scan(
        microbatch, (opt_state["grads"], jnp.zeros((), jnp.int32)), (xb, yb)
    )
    with scope("loss"):
        loss = jnp.sum(losses)
    if census is not None:
        with scope("moe/route"):
            census = jnp.sum(census, axis=0)
    return acc, loss, census


def _make_batch_step(
    spec: ModelSpec, opt, precision, fuse_mubatches=False, clip_norm=None,
    megakernel=False, with_grad_norm=False, with_digests=False,
    x_layout="row_major",
):
    """The shared per-batch body: microbatch gradient accumulation + optimizer
    apply. Used by both the per-batch step and the epoch scan.
    ``clip_norm``: optional global-norm gradient clipping (over ALL params)
    applied to the accumulated batch gradient before the optimizer.
    ``with_grad_norm``: also return the PRE-clip global gradient norm as a
    fourth output — an aux scalar for training telemetry (it rides the scan
    as data flow, never a host callback, so jit fusion is untouched).

    ``fuse_mubatches=True`` computes the whole batch in ONE forward/backward
    instead of scanning microbatches. This is the same training computation:
    the loss is a sum scaled by the global batch size, so the full-batch
    gradient IS the sum of microbatch gradients (the ledger the reference
    builds its equivalence on, SURVEY §3.3), and the softmax head's
    stability-max quirk is evaluated per microbatch-row-group
    (``head_group_rows``) so even that grouping-sensitive detail matches the
    scanned path float-for-float. The fused path feeds the MXU
    microbatch-count-times larger matmuls; the microbatch path exists for
    mechanism parity with the reference and for the pipeline executor, where
    microbatches are semantic.

    ``megakernel=True`` (requires ``fuse_mubatches``, a kernel-supported
    optimizer, a single-stage spec) runs the ENTIRE batch — forward,
    head, backward, (optional global-norm clip), update — as ONE Pallas
    kernel (pallas_ops.fused_train_call). Identical float math; exists
    because the epoch is op-issue-latency bound (docs/performance.md
    roofline) and one op per batch is the shortest possible serial chain.

    ``x_layout`` (``data_layout``'s answer, scanned path only): with
    ``"feature_major"`` ``xb`` is ``(M, in_dim, mubatch)`` and each
    microbatch is read transposed, as it lies.

    A ``TokenModelSpec`` takes its microbatches one after another (unrolled,
    see ``batch_step``): ``xb`` is a step's token ids and ``yb`` its document
    numbers, both ``(M, mubatch, seq_len + 1)`` int32; a microbatch's loss
    and gradients are ``model.token_loss_and_grads``'s, which adds each
    layer's gradient into the accumulator as it is made; the update is
    applied like an MLP's.
    """
    token = isinstance(spec, TokenModelSpec)
    if token and (fuse_mubatches or megakernel or x_layout != "row_major"):
        raise ValueError(
            "a token model runs the row-major microbatch loop only: the "
            "fused and kernel paths are written for stacks of Linears"
        )
    if x_layout != "row_major" and (fuse_mubatches or megakernel):
        raise ValueError(
            f"x_layout={x_layout!r} is the microbatch scan's: the fused and "
            f"kernel paths reshape a batch to (rows, in_dim)"
        )
    if megakernel:
        if with_grad_norm or with_digests:
            raise ValueError(
                "with_grad_norm/with_digests are unavailable on the kernel "
                "paths: the gradient never leaves the Pallas kernel's VMEM"
            )
        sspec = _validate_megakernel(spec, opt, fuse_mubatches)

        def mega_step(params, opt_state, xb, yb):
            rows = xb.shape[1]
            x = xb.reshape(-1, xb.shape[-1])
            y = yb.reshape(-1, yb.shape[-1])
            return _fused_kernel_call(
                spec, sspec, opt, precision, params, opt_state, x, y,
                epoch_mode=False, group_rows=rows, clip_norm=clip_norm,
            )

        return mega_step

    def clipped(grads):
        if clip_norm is None:
            return grads
        from shallowspeed_tpu.optimizer import clip_tree

        return clip_tree(grads, clip_norm)

    def finish(params, opt_state, grads, loss):
        """Shared tail: (optional) pre-clip norm aux, clip, apply. With
        ``with_digests`` the per-layer digest dict of the NEW params (and
        the pre-clip grads) rides as the LAST output."""
        if with_grad_norm:
            from shallowspeed_tpu.optimizer import global_norm

            gnorm = global_norm(grads)
            new_params, opt_state = opt.apply(
                params, clipped(grads), opt_state
            )
            outs = (new_params, opt_state, loss, gnorm)
        else:
            new_params, opt_state = opt.apply(
                params, clipped(grads), opt_state
            )
            outs = (new_params, opt_state, loss)
        if with_digests:
            outs += (_digest_aux(new_params, grads),)
        return outs

    def batch_step(params, opt_state, xb, yb):
        """Returns (params, opt_state, batch_loss) — the loss is the global-
        batch-scaled MSE of the batch under the pre-update params. With
        ``with_grad_norm`` a fourth output carries the pre-clip global
        gradient norm."""
        if token:
            step = (
                _token_step_scanned if token_step_is_scanned(xb.shape[0])
                else _token_step_unrolled
            )
            grads, loss, census = step(params, opt_state, spec, xb, yb, precision)
            outs = finish(params, opt_state, grads, loss)
            # a model with routed layers: the step's routing census rides LAST
            return outs if census is None else outs + (census,)
        if fuse_mubatches:
            rows = xb.shape[1]
            with scope("batch"):
                x = xb.reshape(-1, xb.shape[-1])
                y = yb.reshape(-1, yb.shape[-1])
            out, res = model_forward(
                params, spec, x, precision=precision, head_group_rows=rows
            )
            _, grads = model_backward(
                params, spec, res, y, precision=precision, head_group_rows=rows
            )
            loss = ops.mse_loss(out, y, spec.global_batch_size)
            return finish(params, opt_state, grads, loss)

        def accumulate(carry, mxy):
            acc, loss = carry
            x, y = mxy
            if x_layout == "feature_major":
                # AT the microbatch: a swapaxes of the whole set at the top
                # of the program is the bitcast the compiler makes anyway,
                # and gives the per-step transpose back
                with scope("batch"):
                    x = x.T
            out, res = model_forward(params, spec, x, precision=precision)
            _, grads = model_backward(params, spec, res, y, precision=precision)
            with scope("loss"):
                loss = loss + ops.mse_loss(out, y, spec.global_batch_size)
            with scope("acc"):
                acc = jax.tree.map(jnp.add, acc, grads)
            return (acc, loss), None

        zeros = jax.tree.map(jnp.zeros_like, params)
        (grads, loss), _ = lax.scan(
            accumulate, (zeros, jnp.zeros(())), (xb, yb)
        )
        return finish(params, opt_state, grads, loss)

    return batch_step


def _kernel_opt_descriptor(opt):
    """Map a framework optimizer onto the unified kernel's descriptor
    (pallas_ops._train_kernel_body's ``opt``), or None if the kernels don't
    support it. The descriptor's kind keys _OPT_MIRRORS (state mirror
    groups), so the VMEM accounting and operand assembly stay in lockstep
    with this one mapping. Adam has no kernel: see pallas_ops._OPT_MIRRORS."""
    from shallowspeed_tpu.optimizer import SGD, MomentumSGD

    if type(opt) is SGD:
        return {"kind": "sgd"}
    if type(opt) is MomentumSGD:
        return {"kind": "momentum", "mu": opt.momentum}
    return None


def _validate_megakernel(spec, opt, fuse_mubatches, name="megakernel"):
    """The mega-kernel constraint set, shared by the per-batch and whole-epoch
    variants: fused microbatches, a kernel-supported optimizer (SGD or
    momentum), single stage, within the variant's VMEM budget (each
    optimizer state mirror — momentum's velocity — adds a
    params-sized in+out pair to the footprint; the epoch kernel
    additionally holds the double-buffered streamed x/y blocks). Global-
    norm clipping is supported: the gradient sums are live in VMEM, so the
    norm is one scalar reduction inside the kernel (pallas_ops._batch_grads).
    Returns the single stage's spec."""
    from shallowspeed_tpu import pallas_ops

    if not fuse_mubatches:
        raise ValueError(f"{name} requires fuse_mubatches=True")
    if getattr(spec, "act", "relu") != "relu":
        # the fused kernels hard-code the relu/identity slot expressions
        # (pallas_ops fused units); the gelu family's f32 grad-multiplier
        # masks and residual adds have no kernel path
        raise ValueError(
            f"{name} supports the relu activation family only "
            f"(model act={spec.act!r})"
        )
    desc = _kernel_opt_descriptor(opt)
    if desc is None:
        raise ValueError(
            f"{name} supports the (decaying) SGD and momentum optimizers "
            f"only (adam's traced-exponent bias correction does not "
            f"compile under Mosaic)"
        )
    if spec.n_stages != 1 or not spec.stages[0].has_head:
        raise ValueError(f"{name} runs the single-stage sequential path only")
    sspec = spec.stages[0]
    # the run kernel streams x/y per grid step exactly like the epoch
    # kernel (the extra epoch axis adds no VMEM), so it shares that budget
    fits = (
        pallas_ops.train_epoch_kernel_fits
        if name in ("epoch_kernel", "run_kernel")
        else pallas_ops.train_step_kernel_fits
    )
    n_mirrors = pallas_ops._OPT_MIRRORS[desc["kind"]]
    if not fits(
        spec.global_batch_size, sspec.local_sizes, state_mirrors=n_mirrors
    ):
        raise ValueError(f"model + batch exceed the {name} VMEM budget")
    return sspec


def _make_epoch_kernel_core(spec, opt, precision, fuse_mubatches, clip_norm):
    """Whole-epoch mega-kernel core (pallas_ops.fused_train_call with
    epoch_mode=True): the
    batch axis becomes the Pallas grid, params stay VMEM-resident across the
    epoch, and the per-epoch serial op chain drops from one kernel per batch
    to ONE kernel total. Same signature as _make_epoch_core's result; batch
    expressions and loss-mean order are bit-identical to scanning the
    per-batch mega-kernel (tested)."""
    sspec = _validate_megakernel(spec, opt, fuse_mubatches, name="epoch_kernel")

    def epoch_core(params, opt_state, X, Y):
        nb, M_, mb, din = X.shape
        x = X.reshape(nb, M_ * mb, din)
        y = Y.reshape(nb, M_ * mb, Y.shape[-1])
        return _fused_kernel_call(
            spec, sspec, opt, precision, params, opt_state, x, y,
            epoch_mode=True, group_rows=mb, clip_norm=clip_norm,
        )

    return epoch_core


def _fused_kernel_call(
    spec, sspec, opt, precision, params, opt_state, x, y, *, epoch_mode,
    group_rows, clip_norm=None, n_epochs=None,
):
    """The one trainer->pallas_ops bridge for every mega/epoch-kernel
    variant: maps the framework optimizer state onto the kernel's mirror
    groups and back. Returns ``(params, opt_state, loss)``. State mapping:
    SGD () stays (); momentum's params-mirror rides as one mirror group."""
    from shallowspeed_tpu import pallas_ops

    desc = _kernel_opt_descriptor(opt)
    momentum = desc["kind"] == "momentum"
    new_stage, new_mirrors, loss = pallas_ops.fused_train_call(
        params[0], x, y,
        epoch_mode=epoch_mode,
        relu_flags=sspec.relu_flags,
        group_rows=group_rows,
        batch_size=spec.global_batch_size,
        lr=opt.lr,
        weight_decay=opt.weight_decay,
        precision=precision,
        opt=desc, mirrors=(opt_state[0],) if momentum else (),
        clip_norm=clip_norm, n_epochs=n_epochs,
    )
    new_state = [new_mirrors[0]] if momentum else opt_state
    return [new_stage], new_state, loss


def make_train_step(
    spec: ModelSpec,
    opt,
    precision=ops.DEFAULT_PRECISION,
    fuse_mubatches=False,
    clip_norm=None,
    megakernel=False,
):
    """Returns jitted ``step(params, opt_state, xb, yb) -> (params, opt_state)``.

    ``xb``: (M, mubatch, in_dim); ``yb``: (M, mubatch, out_dim) one-hot.
    """
    batch_step = _make_batch_step(
        spec, opt, precision, fuse_mubatches, clip_norm, megakernel
    )

    def step(params, opt_state, xb, yb):
        params, opt_state, _ = batch_step(params, opt_state, xb, yb)
        return params, opt_state

    return jax.jit(step, donate_argnums=(0, 1))


def make_train_epoch(
    spec: ModelSpec,
    opt,
    precision=ops.DEFAULT_PRECISION,
    fuse_mubatches=False,
    unroll=1,
    clip_norm=None,
    megakernel=False,
    epoch_kernel=False,
    with_grad_norm=False,
    with_step_stats=False,
    with_digests=False,
    x_layout="row_major",
):
    """Whole-epoch scan: ``epoch(params, opt_state, X, Y) -> (params,
    opt_state, mean_loss)`` with X: (num_batches, M, mubatch, in_dim), or
    (num_batches, M, in_dim, mubatch) under ``x_layout="feature_major"``
    (see ``data_layout``). One XLA program per epoch; mean_loss is the true
    mean batch training loss (same definition as the pipeline executor's).

    ``unroll``: lax.scan unroll factor over batches — for this model each
    batch body is a handful of small matmuls, so unrolling amortizes the
    per-iteration loop overhead (a throughput knob; identical numerics).
    ``megakernel``: run each batch as one Pallas kernel (see
    _make_batch_step; identical numerics, shortest serial op chain per
    batch). ``epoch_kernel``: run the ENTIRE epoch as one Pallas kernel
    (the batch axis is the kernel grid, params stay VMEM-resident — see
    _make_epoch_kernel_core; identical numerics, one op per epoch).
    ``with_grad_norm``: telemetry aux — the epoch returns a FOURTH output,
    an aux dict ``{"grad_norm": mean pre-clip global grad norm}``. The aux
    is an ordinary scan output (data flow, not a host callback), so the
    epoch stays one fused XLA program; unavailable on the kernel paths
    (the gradient never leaves VMEM there).
    ``with_step_stats``: the flight-recorder aux — the aux dict also
    carries per-STEP (per-batch) vectors ``step_loss`` /
    ``step_grad_norm`` (pre-clip) / ``step_param_norm`` (post-update), as
    ordinary stacked scan outputs of the same fused program. Same kernel-
    path restriction as ``with_grad_norm``.
    ``with_digests``: the numerics-provenance aux — the aux dict also
    carries per-step per-layer digest vectors under ``"digests"`` (each
    leaf stacked to ``(num_batches, n_layers)``: bitcast-uint32 checksums
    ``crc_w``/``crc_b`` of the post-update params plus param/pre-clip-grad
    L2 norms — see ``_digest_aux``). Same kernel-path restriction.
    """
    if epoch_kernel:
        if megakernel:
            raise ValueError("megakernel and epoch_kernel are exclusive")
        if with_grad_norm or with_step_stats or with_digests:
            raise ValueError(
                "with_grad_norm/with_step_stats/with_digests are "
                "unavailable on the kernel paths: the gradient never "
                "leaves the Pallas kernel's VMEM"
            )
        epoch_core = _make_epoch_kernel_core(
            spec, opt, precision, fuse_mubatches, clip_norm
        )
    else:
        batch_step = _make_batch_step(
            spec, opt, precision, fuse_mubatches, clip_norm, megakernel,
            with_grad_norm or with_step_stats, with_digests,
            x_layout=x_layout,
        )
        routed = getattr(spec, "routed_layers", 0)
        epoch_core = _make_epoch_core(
            batch_step, unroll, with_grad_norm, with_step_stats, with_digests,
            census_shape=(
                (routed, spec.experts_held[1] - spec.experts_held[0]) if routed else None
            ),
        )
    return jax.jit(epoch_core, donate_argnums=(0, 1))


def _make_epoch_core(
    batch_step, unroll, with_grad_norm=False, with_step_stats=False,
    with_digests=False, census_shape=None,
):
    """The one epoch-scan body shared by make_train_epoch and make_train_run:
    ``core(params, opt_state, X, Y) -> (params, opt_state, mean_loss)`` —
    plus an aux dict when instrumented: ``{"grad_norm": mean}`` under
    ``with_grad_norm``, and per-step stacked vectors ``step_loss`` /
    ``step_grad_norm`` / ``step_param_norm`` under ``with_step_stats``
    (ordinary scan ys — data flow, never host callbacks, so the epoch stays
    one fused XLA program). One scan body serves every arity: the grad-norm
    slot always rides the carry (zero when the aux is off) and XLA
    dead-code-eliminates it from the uninstrumented program.
    ``census_shape`` (a token model with routed layers: ``(routed layers,
    experts held)``): ``batch_step``'s last output is the step's routing
    census, int32 of that shape. The epoch's two counters ride the carry and
    are the program's LAST output, after the aux dict where there is one, a
    (2,) int32: the (token, slot) pairs routed to the experts held, summed
    over the epoch, and the most any one held expert of any layer took in
    one step (the session reads them back with the loss,
    ``api._run_epoch_program``)."""
    if census_shape and with_digests:
        raise ValueError("the digest aux and a routing census both ride last")
    track_gn = with_grad_norm or with_step_stats

    def epoch_core(params, opt_state, X, Y):
        def body(carry, xy):
            params, opt_state, loss_sum, gn_sum, *routed = carry
            out = batch_step(params, opt_state, *xy)
            params, opt_state, loss = out[0], out[1], out[2]
            gn = out[3] if track_gn else jnp.zeros(())
            carry = (params, opt_state, loss_sum + loss, gn_sum + gn)
            if routed:
                with scope("moe/route"):
                    held, most = routed[0][0], routed[0][1]
                    carry += (jnp.stack(
                        [held + jnp.sum(out[-1]), jnp.maximum(most, jnp.max(out[-1]))]
                    ),)
            ys = ()
            if with_step_stats:
                from shallowspeed_tpu.optimizer import global_norm

                # post-update param norm: the "did the step blow the
                # weights up" scalar the health monitor watches
                ys += (loss, gn, global_norm(params))
            if with_digests:
                ys += (out[-1],)  # the digest dict rides last (see finish)
            return carry, (ys if ys else None)

        start = (params, opt_state, jnp.zeros(()), jnp.zeros(()))
        if census_shape:
            start += (jnp.zeros((2,), jnp.int32),)
        (params, opt_state, loss_sum, gn_sum, *routed), ys = lax.scan(
            body, start, (X, Y), unroll=unroll,
        )
        nb = X.shape[0]
        if not (with_grad_norm or with_step_stats or with_digests):
            return (params, opt_state, loss_sum / nb, *routed)
        aux = {}
        if with_grad_norm:
            aux["grad_norm"] = gn_sum / nb
        if with_step_stats:
            aux["step_loss"], aux["step_grad_norm"], aux["step_param_norm"] = (
                ys[0], ys[1], ys[2]
            )
        if with_digests:
            aux["digests"] = ys[-1]
        return (params, opt_state, loss_sum / nb, aux, *routed)

    return epoch_core


def make_train_run(
    spec: ModelSpec,
    opt,
    precision=ops.DEFAULT_PRECISION,
    fuse_mubatches=False,
    unroll=1,
    clip_norm=None,
    with_eval=True,
    megakernel=False,
    epoch_kernel=False,
    run_kernel=False,
    with_grad_norm=False,
    x_layout="row_major",
):
    """Whole-RUN scan: every epoch (and its validation accuracy) in ONE program.

    ``run(params, opt_state, X, Y, vx, vy, n_epochs) -> (params, opt_state,
    losses[n_epochs], accs[n_epochs])`` — an epochs-outer scan around the
    shared epoch core, with the full-split argmax accuracy computed on-device
    after each epoch. Zero host round-trips for the whole training run: the
    n_epochs per-epoch readbacks of the loop form are gone.

    ``with_eval=False`` drops the vx/vy arguments and the accuracy output:
    ``run(params, opt_state, X, Y, n_epochs) -> (params, opt_state, losses)``.

    Same math as looping ``make_train_epoch`` + ``accuracy``: the reference's
    epoch structure (train then validate, /root/reference/train.py:132-137)
    expressed as data flow instead of a host loop. ``n_epochs`` is static
    (one compile per value). vx: (n_val, in_dim); vy: (n_val, out_dim)
    one-hot.

    ``run_kernel=True`` (requires the epoch-kernel constraint set and
    ``with_eval=False``) runs the ENTIRE multi-epoch training run as ONE
    Pallas kernel: the grid is (n_epochs, batches), params + optimizer
    state stay VMEM-resident for the whole run, and the per-epoch mean
    losses come back as the losses vector — the last rung of the
    batch -> epoch -> run dispatch-collapse ladder (one device op for the
    reference's whole outermost loop). Bit-identical to looping the epoch
    kernel. Per-epoch eval needs per-epoch params, so the evaluated run
    keeps the epochs-outer scan.

    ``with_grad_norm=True`` (telemetry aux, scan paths only): the run
    returns one EXTRA trailing output, an aux dict whose ``"grad_norm"``
    is the (n_epochs,) vector of per-epoch mean pre-clip global gradient
    norms — ordinary scan outputs, so the run stays one fused program.

    ``x_layout``: as ``make_train_epoch``'s (X is scanned on axis 0 only).
    """
    if with_grad_norm and (megakernel or epoch_kernel or run_kernel):
        raise ValueError(
            "with_grad_norm is unavailable on the kernel paths: the "
            "gradient never leaves the Pallas kernel's VMEM"
        )
    if run_kernel:
        if megakernel or epoch_kernel:
            raise ValueError(
                "run_kernel already subsumes the epoch/mega kernels; pass "
                "only run_kernel=True"
            )
        if with_eval:
            raise ValueError(
                "run_kernel supports with_eval=False only (per-epoch eval "
                "needs per-epoch params outside the kernel)"
            )
        sspec = _validate_megakernel(spec, opt, fuse_mubatches, name="run_kernel")

        @partial(jax.jit, static_argnums=(4,), donate_argnums=(0, 1))
        def run(params, opt_state, X, Y, n_epochs):
            # static check at trace time: a (0, nb) grid never writes the
            # output blocks, so n_epochs=0 would return undefined buffers
            # where the scan path returns the inputs unchanged
            if n_epochs < 1:
                raise ValueError("run_kernel requires n_epochs >= 1")
            nb, M_, mb, din = X.shape
            x = X.reshape(nb, M_ * mb, din)
            y = Y.reshape(nb, M_ * mb, Y.shape[-1])
            return _fused_kernel_call(
                spec, sspec, opt, precision, params, opt_state, x, y,
                epoch_mode=True, group_rows=mb, clip_norm=clip_norm,
                n_epochs=n_epochs,
            )

        return run

    if epoch_kernel:
        if megakernel:
            raise ValueError("megakernel and epoch_kernel are exclusive")
        epoch_core = _make_epoch_kernel_core(
            spec, opt, precision, fuse_mubatches, clip_norm
        )
    else:
        batch_step = _make_batch_step(
            spec, opt, precision, fuse_mubatches, clip_norm, megakernel,
            with_grad_norm, x_layout=x_layout,
        )
        epoch_core = _make_epoch_core(batch_step, unroll, with_grad_norm)

    def run_epoch(params, opt_state, X, Y):
        """Uniform (params, opt_state, loss, gnorm) view of the epoch core
        (gnorm 0 when the aux is off — dropped again before returning)."""
        if with_grad_norm:
            params, opt_state, mean_loss, aux = epoch_core(params, opt_state, X, Y)
            return params, opt_state, mean_loss, aux["grad_norm"]
        params, opt_state, mean_loss = epoch_core(params, opt_state, X, Y)
        return params, opt_state, mean_loss, jnp.zeros(())

    if with_eval:

        @partial(jax.jit, static_argnums=(6,), donate_argnums=(0, 1))
        def run(params, opt_state, X, Y, vx, vy, n_epochs):
            def epoch_body(carry, _):
                params, opt_state, mean_loss, gn = run_epoch(*carry, X, Y)
                preds, _ = model_forward(params, spec, vx, precision=precision)
                acc = jnp.mean(
                    (jnp.argmax(preds, axis=1) == jnp.argmax(vy, axis=1)).astype(
                        jnp.float32
                    )
                )
                return (params, opt_state), (mean_loss, acc, gn)

            (params, opt_state), (losses, accs, gns) = lax.scan(
                epoch_body, (params, opt_state), None, length=n_epochs
            )
            if with_grad_norm:
                return params, opt_state, losses, accs, {"grad_norm": gns}
            return params, opt_state, losses, accs

    else:

        @partial(jax.jit, static_argnums=(4,), donate_argnums=(0, 1))
        def run(params, opt_state, X, Y, n_epochs):
            def epoch_body(carry, _):
                params, opt_state, mean_loss, gn = run_epoch(*carry, X, Y)
                return (params, opt_state), (mean_loss, gn)

            (params, opt_state), (losses, gns) = lax.scan(
                epoch_body, (params, opt_state), None, length=n_epochs
            )
            if with_grad_norm:
                return params, opt_state, losses, {"grad_norm": gns}
            return params, opt_state, losses

    return run


def make_predict(spec: ModelSpec, precision=ops.DEFAULT_PRECISION):
    """Jitted inference: softmax predictions for a (batch, in_dim) array."""

    @jax.jit
    def predict(params, x):
        out, _ = model_forward(params, spec, x, precision=precision)
        return out

    return predict


def make_loss_fn(spec: ModelSpec, precision=ops.DEFAULT_PRECISION):
    """Monitoring-only loss (the reference never computes the training loss,
    layers.py:150-155; we expose it as an opt-in observability feature)."""

    @jax.jit
    def loss_fn(params, x, y):
        out, _ = model_forward(params, spec, x, precision=precision)
        return ops.mse_loss(out, y, spec.global_batch_size)

    return loss_fn


def accuracy(predict, params, X, Y, batch_size=1024):
    """Host-side argmax accuracy over a full split (reference train.py:21-47).

    Evaluates every sample: the ragged tail chunk runs at its natural size
    (it only triggers one extra XLA specialization).
    """
    correct = total = 0
    for i in range(0, len(X), batch_size):
        xb, yb = X[i : i + batch_size], Y[i : i + batch_size]
        preds = predict(params, xb)
        correct += int((jnp.argmax(preds, axis=1) == jnp.argmax(yb, axis=1)).sum())
        total += len(xb)
    return correct / max(total, 1)
