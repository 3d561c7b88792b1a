"""SPMD pipeline executor: tick programs over a (dp, pp) mesh via shard_map.

This is the TPU-native replacement for the reference's Worker runtime
(/root/reference/shallowspeed/pipe.py:330-466). Where the Worker interprets
instructions against NumPy buffers and blocking MPI calls, here the whole
batch — every pipeline tick of every stage, the DP gradient reduction and the
optimizer step — is ONE jitted XLA computation:

- stages live on the ``pp`` mesh axis; each device holds its stage's
  parameters as one row of zero-padded stacked arrays, so the deliberately-
  unequal stages (2/2/2/1 Linears at PP=4, SURVEY §7.3) run under a single
  SPMD program. Padding is PER LAYER SLOT, not global: slot l is stacked to
  ``(S, max_out_l, max_in_l)`` — for the flagship model that is (S,128,784)
  and (S,127,128) instead of (S,2,784,784), an ~10x cut in padded FLOPs;
- the per-batch instruction streams are pre-compiled by ``lowering`` into a
  static tick table; the executor ``lax.scan``s one tick function whose body
  ``lax.switch``es between {noop, forward, backward} — pipeline bubbles are
  the noop branch (masked compute, like the blank cells of the pebble graph);
- stage-to-stage activation/grad relays are ``jax.lax.ppermute`` shifts over
  ``pp`` (the reference's blocking Send/Recv pairs, pipe.py:367-381), and
  they follow the send tables: a direction's ``ppermute`` and its mailbox
  write are issued only in the ticks in which SOME stage has a payload due
  in it, over the device pairs on which some tick sends. That predicate is
  a column of the static tick table, the same scalar on every device:
  uniform across devices is what SPMD asks of a collective, uniform across
  ticks it does not. A tick with nothing due issues no collective and does
  not make two stages wait for each other;
- microbatch activation stashes (reference Module._cache) are fixed-shape
  ring buffers carried through the scan, one per residual, shaped
  ``(slots + 1, width, mb)``: a slot is stored FEATURE-MAJOR, the
  orientation a v5e's forward matmuls leave their outputs in, so that a
  tick writes its one slot in place (``_stash`` parks ``val.T``,
  ``_unstash`` returns ``buf[slot].T``; the reason and the compiled-text
  figures are where the rings are allocated). Mailbox slots come from the
  lowering; the forward mailbox and its payload are feature-major too (the
  forward writes the one and reads the other), the backward ones stay
  ``(mb, width)``;
- split-backward programs (``backward_split`` schedules, 2BP arxiv
  2405.18047) add a FOURTH switch branch: OP_BWD cells run only the
  relay-critical dgrad chain (B-input, stashing the per-slot effective
  output-grads into a grad-stash ring, the one ring whose slots stay
  ``(mb, width)``), and OP_BWD_W cells — packed by the
  lowering into former bubble ticks — finish the deferred wgrads from the
  activation + grad stashes, accumulating in the combined schedule's order
  so the fp sums (and the weight hash) are bit-identical;
- the dp axis is a four-point memory lattice (``zero`` in {0, 1, 2, 3} —
  arXiv 2004.13336's stages over this executor's stacked layout). Stage 0
  (plain DP): one ``jax.lax.psum`` of the whole accumulated gradient
  pytree over ``dp`` at the tail anchor, every replica repeats the full
  update. Stage 1 (ZeRO-1): the tail reduce-scatters the FLAT gradient,
  each replica updates its 1/dp chunk with its optimizer-state shard, and
  one deferred all-gather rebuilds the params. Stage 2 (ZeRO-2): every
  backward tick reduce-scatters its slots' gradients straight into this
  rank's persistent shard in the block-cyclic layout below — neither the
  full gradient slabs nor the flat gradient concat ever materialize —
  and per-slot all-gathers rebuild the updated params. Stage 3 (ZeRO-3):
  params REST in the block-cyclic shard and every tick branch all-gathers
  just the active chunk's slots on demand (gathered copies die with the
  branch), while the backward reduce-scatters each tick's slot gradients
  immediately — peak live params is one stage chunk, not the model.
  Stages 0-1 are bitwise identical to each other modulo norm-scalar
  reassociation (elementwise collectives; see the ZeRO sections below),
  and stage 2 joins them at ``mubatches=1``; the per-tick sync of stages
  2-3 reassociates the microbatch/replica sum order and carries the
  standard cross-layout tolerance otherwise;
- the optimizer step happens on-device on the padded params (padded regions
  receive exactly-zero gradients, so they stay zero — see tests);
- on a mesh with a ``tp`` axis (parallel/mesh.py, ``--tp``), every slot's
  W is additionally Megatron-sharded across the tp ranks — even slots
  column-parallel, odd slots row-parallel, one ``psum`` over ``tp`` per
  row slot forward and per column slot backward (2 all-reduces per layer
  pair per pass; see the tp stage functions below). Slot dims round up to
  tp multiples (``slot_shapes(spec, tp)``), per-device weight memory /
  optimizer state / matmul FLOPs divide by tp, and tp composes with DP,
  ZeRO-1, the split backward and every schedule. At
  ``tp == 1`` none of this code is traced: the historical 2-axis programs
  are byte-identical.

Zero-padding invariant: weights are zero outside each layer's logical
(out_dim, in_dim) block, activations are zero beyond each boundary's true
width, the softmax head masks invalid columns to probability zero, and
targets are zero-padded — so every gradient is exactly zero outside its
logical block and padded compute is numerically inert, not approximately so.
Width changes between slots use ``_fit`` (slice-or-pad), which is exact
because stacked-slot widths always cover the true content (validated at
stack time).
"""

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax, shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from shallowspeed_tpu import ops
from shallowspeed_tpu.model import ModelSpec, init_model
from shallowspeed_tpu.observability.scopes import scope, scoped
from shallowspeed_tpu.parallel.lowering import (
    OP_BWD,
    OP_BWD_W,
    OP_FWD,
    OP_RECOMPUTE,
    TickProgram,
)
from shallowspeed_tpu.parallel.mesh import mesh_tp


# ---------------------------------------------------------------------------
# Per-slot stacked parameters
# ---------------------------------------------------------------------------


def slot_shapes(spec: ModelSpec, tp: int = 1):
    """Static per-slot stacked dims: [(out_l, in_l)] with maxima over stages.

    Also validates the passthrough-width invariant: any stage that is shorter
    than the deepest stage must have an out_dim that fits through every
    later slot's widths (true for the reference's monotone size lists).

    ``tp > 1`` (tensor parallelism): each dim is rounded up to a multiple
    of ``tp`` so every slot splits evenly across the tp ranks — the same
    zero-padding invariant that already makes unequal stages exact makes
    the extra columns exact. TP additionally requires the CHAINED width
    equality ``in_{l} == out_{l-1}`` at every row-parallel (odd) slot: a
    column slot hands its successor a rank-SHARD, and a shard of a
    narrower fit is not the fit of a shard, so unequal chained widths
    cannot be repaired locally. Monotone-decreasing size lists (the
    reference family, and everything the fuzz generates) satisfy it.
    """
    L = max((s.n_linears for s in spec.stages), default=0) or 1
    dims = []
    for l in range(L):
        outs = [s.local_sizes[l + 1] for s in spec.stages if s.n_linears > l]
        ins = [s.local_sizes[l] for s in spec.stages if s.n_linears > l]
        dims.append((max(outs), max(ins)))
    for s in spec.stages:
        for l in range(s.n_linears, L):
            o, i = dims[l]
            if s.out_dim > min(o, i):
                raise ValueError(
                    f"stage with out_dim={s.out_dim} cannot pass through slot {l} "
                    f"of width {min(o, i)}; use equal-depth stages for this size list"
                )
    if tp > 1:
        for l in range(1, L, 2):  # row-parallel slots consume a rank shard
            if dims[l][1] != dims[l - 1][0]:
                raise ValueError(
                    f"tp={tp} needs chained slot widths (in_{l} == out_{l - 1}) "
                    f"but slot {l} consumes {dims[l][1]} from a slot producing "
                    f"{dims[l - 1][0]}; use a monotone-decreasing size list"
                )
        dims = [(-(-o // tp) * tp, -(-i // tp) * tp) for o, i in dims]
    return dims


def tp_local_dims(dims, tp: int):
    """Per-device slot geometry under ``tp``-way Megatron sharding, derived
    from the (already tp-rounded) global stacked dims. Returns
    ``(w_dims, b_widths, xs_widths, mask_widths)``:

    - ``w_dims[l]``: this rank's W block — even (COLUMN-parallel) slots
      hold an ``(out/tp, in)`` row band, odd (ROW-parallel) slots an
      ``(out, in/tp)`` column band;
    - ``b_widths[l]``: every bias is sharded ``out/tp`` (row-parallel
      biases are rank-scattered and summed by the slot's psum, so no
      parameter is ever tp-replicated — the grad-norm reduction over
      ('pp','tp') counts each element exactly once);
    - ``xs_widths[l]`` / ``mask_widths[l]``: the stashed residuals in the
      representation the backward consumes — a column slot stashes its
      FULL input and its SHARDED pre-activation mask, a row slot the
      sharded input and the full post-psum mask.

    At ``tp == 1`` every formula collapses to the unsharded dims, so the
    tp=1 trace is byte-identical to the historical one.
    """
    w_dims = [
        (o // tp, i) if l % 2 == 0 else (o, i // tp)
        for l, (o, i) in enumerate(dims)
    ]
    b_widths = [o // tp for o, _ in dims]
    xs_widths = [i if l % 2 == 0 else i // tp for l, (_, i) in enumerate(dims)]
    mask_widths = [o // tp if l % 2 == 0 else o for l, (o, _) in enumerate(dims)]
    return w_dims, b_widths, xs_widths, mask_widths


def tp_allreduce_sites(spec: ModelSpec, tp: int, training: bool = True):
    """The Megatron all-reduce sites of ONE stage pass at this tp degree:
    ``(fwd_widths, bwd_widths)`` — payload widths (f32 columns of one
    ``(mubatch, width)`` psum over 'tp') in execution order. Forward: one
    psum per row-parallel (odd) slot, plus the closing reassembly when the
    last slot is column-parallel (the stage boundary must relay the FULL
    activation); backward (training only): one psum per column-parallel
    (even) slot — the Megatron f-operator. For an even slot count this is
    exactly 2 all-reduces per column/row layer pair per fwd+bwd pass.

    This is the ONE site list: the executor's tp stage functions place
    their psums by the same slot parity, and ``expected_comms`` sizes the
    tp axis of the census contract from these widths — so the audited
    contract and the traced program can never disagree about where the
    tp collectives sit or how big they are.
    """
    dims = slot_shapes(spec, tp)
    L = len(dims)
    fwd = [dims[l][0] for l in range(1, L, 2)]
    if (L - 1) % 2 == 0:
        fwd.append(dims[-1][0])
    bwd = [dims[l][1] for l in range(0, L, 2)] if training else []
    return fwd, bwd


def stash_slot_nbytes(spec: ModelSpec, mubatch_size: int, tp: int = 1):
    """Per-slot byte cost of each stash ring the executor carries, from the
    real spec's padded slot shapes — the ONE sizing the observability layer
    (``program_stats(spec=...)``, the report CLI's Memory section) uses to
    turn lowering slot counts into HBM bytes. Returns a dict:

    - ``"stash"``: one residual-stash slot — the per-slot activations
      (``xs_widths``, f32), the backward multipliers (``mask_widths``;
      1-byte bools for the relu family, f32 gelu-derivative values for the
      gelu family) and the head-logit stash row (``D_out``, f32);
    - ``"xin"``: one recompute input-stash slot (the stage input, f32);
    - ``"gstash"``: one split grad-stash slot (per-slot effective
      output-grads — f32 at the mask widths, because g_eff lives in the
      same representation as its mask).
    """
    dims = slot_shapes(spec, tp)
    _, _, xs_widths, mask_widths = tp_local_dims(dims, tp)
    mask_bytes = 1 if spec.act == "relu" else 4
    mb = mubatch_size
    return {
        "stash": 4 * mb * sum(xs_widths)
        + mask_bytes * mb * sum(mask_widths)
        + 4 * mb * dims[-1][0],
        "xin": 4 * mb * dims[0][1],
        "gstash": 4 * mb * sum(mask_widths),
    }


def relay_width(spec: ModelSpec) -> int:
    """True maximum inter-stage boundary width: the widest activation (and
    therefore activation-gradient) ever shipped over the ``pp`` axis.

    Stage ``s`` sends its out_dim forward (= stage ``s+1``'s in_dim) and its
    in_dim backward, so both relay directions are bounded by
    ``max(in_dim of stages 1..S-1)``. For the flagship model at PP=4 that is
    127 (stage in_dims 127/125/123) —
    ~6x narrower than sizing payloads to the model input width (784), which
    is what the reference's per-boundary buffers get for free
    (pipe.py:446-454) and the padded SPMD program must compute explicitly.
    """
    return max((s.in_dim for s in spec.stages[1:]), default=1)


def interleave_order(n_stages: int, n_devices: int):
    """Device-major stacked-row order for interleaved layouts: stacked row
    ``r = device * V + chunk`` holds model stage ``chunk * P + device``, so a
    plain P('pp') shard of the stage axis gives device ``d`` exactly its V
    virtual chunks, contiguously."""
    assert n_stages % n_devices == 0
    V = n_stages // n_devices
    return [(r % V) * n_devices + (r // V) for r in range(n_stages)]


def stack_params(params_list, spec: ModelSpec, order=None, tp: int = 1):
    """Per-stage ragged params -> per-slot zero-padded stacks + flags.

    Returns (stacked, flags):
      stacked = {"W": tuple_l of (S, out_l, in_l), "b": tuple_l of (S, out_l)}
      flags   = {"active": (S,L), "relu": (S,L), "residual": (S,L),
                 "head_mask": (S, out_last)}

    ``relu[r, l]`` is the stage's per-slot ACTIVATION flag (the key predates
    the model zoo): apply the spec's activation family (relu or gelu) after
    slot l. ``residual[r, l]`` marks the gelu family's residual adds
    (y_l += x_{l-1}); always all-False for relu-family specs, whose traces
    never read it.
    All numpy; device-put with ``put_stacked`` (P('pp') on the stage axis;
    per-slot column/row tp shards on a tp mesh). ``order[r]`` names the
    model stage stored at stacked row r (identity by default;
    ``interleave_order`` for virtual-stage layouts). ``tp`` pads the slot
    dims to tp multiples (slot_shapes) — the HOST layout stays the full
    global stack either way, so checkpoints are tp-independent.
    """
    dims = slot_shapes(spec, tp)
    S = spec.n_stages
    L = len(dims)
    order = list(range(S)) if order is None else list(order)
    assert sorted(order) == list(range(S)), "order must permute 0..S-1"
    Ws = [np.zeros((S, o, i), np.float32) for o, i in dims]
    bs = [np.zeros((S, o), np.float32) for o, _ in dims]
    active = np.zeros((S, L), np.bool_)
    relu = np.zeros((S, L), np.bool_)
    residual = np.zeros((S, L), np.bool_)
    head_mask = np.zeros((S, dims[-1][0]), np.bool_)
    for r, s in enumerate(order):
        sspec, sparams = spec.stages[s], params_list[s]
        res_flags = sspec.res_flags
        for l, layer in enumerate(sparams):
            out_d, in_d = layer["W"].shape
            Ws[l][r, :out_d, :in_d] = np.asarray(layer["W"])
            bs[l][r, :out_d] = np.asarray(layer["b"]).reshape(-1)
            active[r, l] = True
            relu[r, l] = sspec.relu_flags[l]
            residual[r, l] = res_flags[l]
        if sspec.has_head:
            head_mask[r, : sspec.out_dim] = True
    return (
        {"W": tuple(Ws), "b": tuple(bs)},
        {
            "active": active,
            "relu": relu,
            "residual": residual,
            "head_mask": head_mask,
        },
    )


def unstack_params(stacked, spec: ModelSpec, order=None):
    """Extract the logical ragged per-stage params back out (host numpy),
    inverting the stacking ``order`` so the result is in model-stage order."""
    Ws = [np.asarray(jax.device_get(w)) for w in stacked["W"]]
    bs = [np.asarray(jax.device_get(b)) for b in stacked["b"]]
    S = spec.n_stages
    order = list(range(S)) if order is None else list(order)
    row_of = {s: r for r, s in enumerate(order)}
    out = []
    for s, sspec in enumerate(spec.stages):
        r = row_of[s]
        layers = []
        for l in range(sspec.n_linears):
            in_d, out_d = sspec.local_sizes[l], sspec.local_sizes[l + 1]
            layers.append(
                {
                    "W": Ws[l][r, :out_d, :in_d].copy(),
                    "b": bs[l][r, :out_d].reshape(1, -1).copy(),
                }
            )
        out.append(layers)
    return out


def put_pp(tree, mesh: Mesh):
    """device_put a stage-stacked pytree with P('pp') sharding on the stage
    axis — the ONE place the stacked placement is defined for tp-replicated
    data (flags; params and state parts go through ``put_stacked_tree``,
    which adds the per-slot tp shards on a tp mesh)."""
    pp = NamedSharding(mesh, P("pp"))
    return jax.tree.map(lambda x: jax.device_put(x, pp), tree)


def stacked_param_specs(tp: int, L: int):
    """The per-slot PartitionSpecs of a stacked {"W", "b"} tree: P('pp')
    everywhere at tp == 1 (the historical placement, byte for byte); at
    tp > 1, Megatron shards — even slots split W on the OUT dim
    (column-parallel), odd slots on the IN dim (row-parallel), and every
    bias on its out dim. One definition shared by ``put_stacked_tree``
    and the executor's shard_map specs, so placement and program can
    never disagree."""
    if tp == 1:
        pp = P("pp")
        return {"W": (pp,) * L, "b": (pp,) * L}
    return {
        "W": tuple(
            P("pp", "tp", None) if l % 2 == 0 else P("pp", None, "tp")
            for l in range(L)
        ),
        "b": (P("pp", "tp"),) * L,
    }


def put_stacked_tree(stacked, mesh: Mesh):
    """device_put one stacked {"W": tuple, "b": tuple} tree with the mesh's
    per-slot shardings (``stacked_param_specs``). Params and every
    params-mirroring optimizer-state part go through here."""
    tp = mesh_tp(mesh)
    if tp == 1:
        return put_pp(stacked, mesh)
    specs = stacked_param_specs(tp, len(stacked["W"]))
    return {
        k: tuple(
            jax.device_put(x, NamedSharding(mesh, s))
            for x, s in zip(stacked[k], specs[k])
        )
        for k in ("W", "b")
    }


def put_stacked(stacked, flags, mesh: Mesh):
    """device_put stacked params + flags (see ``put_stacked_tree``/``put_pp``)."""
    return put_stacked_tree(stacked, mesh), put_pp(flags, mesh)


def init_stacked(spec: ModelSpec, mesh: Mesh, order=None):
    """Deterministic init, stacked + device_put with the mesh's sharding."""
    stacked, flags = stack_params(
        init_model(spec), spec, order=order, tp=mesh_tp(mesh)
    )
    return put_stacked(stacked, flags, mesh)


# ---------------------------------------------------------------------------
# ZeRO-1 optimizer-state sharding over dp
# ---------------------------------------------------------------------------
#
# With plain DP every replica holds the full optimizer state and repeats the
# identical update. ZeRO-1 (Rajbhandari et al. 2019) shards both over the dp
# axis: the gradient all-reduce becomes a reduce-scatter (each replica gets
# the summed gradient for 1/dp of the parameters), the update runs on that
# shard only, and an all-gather rebuilds the full parameters. Chunking
# commutes with elementwise optimizer math; the state_layout() protocol
# (optimizer.py) drives the flat layout — each 'params' state part (momentum
# velocity, Adam's m and v) becomes its own (pp, dp*chunk) array, 'scalar'
# parts (Adam's step count) replicate. On TPU both collectives ride ICI; the
# path uses IS reduce-scatter + all-gather internally, so the comm volume is
# the same while state memory and update FLOPs drop by dp. (The reference has
# no optimizer sharding at all — its DP engine is pipe.py:302-327.)
#
# Flat layout per pp-device: every W slot (V, o, i) then every b slot (V, o),
# concatenated flat and zero-padded to a dp multiple. Helpers below pack and
# unpack host-side state for layout-independent checkpoints.


def stacked_flat_len(spec: ModelSpec, pp: int, tp: int = 1) -> int:
    """Per-DEVICE flattened param count of the stacked layout (every W slot
    then every b slot, V virtual rows each; this rank's tp shard of each) —
    the ONE definition of the flat layout's size. ``zero1_flat_len`` and
    the audit's comms model (``gradsync.sync_comm_bytes``) read it, so a
    layout change here propagates to every consumer at once. Under tp the
    per-device count shrinks by exactly tp (slot dims are tp-rounded, and
    both the column and row shard of a slot hold ``o*i/tp`` elements)."""
    dims = slot_shapes(spec, tp)
    V = spec.n_stages // pp
    return sum(V * o * i // tp for o, i in dims) + sum(
        V * (o // tp) for o, _ in dims
    )


def zero1_flat_len(spec: ModelSpec, mesh: Mesh):
    """(flat_len, chunk_size): per-device flattened param count and the
    padded per-dp-replica chunk size."""
    flat = stacked_flat_len(spec, mesh.shape["pp"], mesh_tp(mesh))
    return flat, -(-flat // mesh.shape["dp"])


def _zero1_device_rows(spec, mesh):
    """The zero1 flat layout's device-row iteration: yields ``(row_index,
    stage_slice, tp_rank)`` in (pp-major, tp-minor) order — exactly how
    ``P(('pp','tp'), 'dp')`` assigns the state matrix's rows to devices."""
    P_ = mesh.shape["pp"]
    tp = mesh_tp(mesh)
    V = spec.n_stages // P_
    for d in range(P_):
        for t in range(tp):
            yield d * tp + t, slice(d * V, (d + 1) * V), t


def _zero1_flatten_rows(stacked_np, spec, mesh):
    """Host-side: stacked {W,b} (numpy, stage axis S) -> (pp*tp, flat_len).
    Each row is one device's flat view — its V stage rows, and at tp > 1
    its column/row shard of each W slot and its out-shard of each b slot,
    in the exact order the in-program ``gvec``/``pvec`` concats produce."""
    tp = mesh_tp(mesh)
    dims = slot_shapes(spec, tp)
    rows = [None] * (mesh.shape["pp"] * tp)
    for r, sl, t in _zero1_device_rows(spec, mesh):
        parts = []
        for l, (o, i) in enumerate(dims):
            w = np.asarray(stacked_np["W"][l][sl])
            if tp > 1:
                o_s, i_s = o // tp, i // tp
                if l % 2 == 0:
                    w = w[:, t * o_s : (t + 1) * o_s, :]
                else:
                    w = w[:, :, t * i_s : (t + 1) * i_s]
            parts.append(np.ascontiguousarray(w).reshape(-1))
        for l, (o, _) in enumerate(dims):
            b = np.asarray(stacked_np["b"][l][sl])
            if tp > 1:
                o_s = o // tp
                b = b[:, t * o_s : (t + 1) * o_s]
            parts.append(np.ascontiguousarray(b).reshape(-1))
        rows[r] = np.concatenate(parts)
    return np.stack(rows)


def _zero1_unflatten_rows(arr, spec, mesh):
    """Host-side inverse of _zero1_flatten_rows: (pp*tp, >=flat_len) ->
    stacked (full global arrays — every device row writes its shard back)."""
    tp = mesh_tp(mesh)
    dims = slot_shapes(spec, tp)
    V = spec.n_stages // mesh.shape["pp"]
    Ws = [np.zeros((spec.n_stages, o, i), np.float32) for o, i in dims]
    bs = [np.zeros((spec.n_stages, o), np.float32) for o, _ in dims]
    for r, sl, t in _zero1_device_rows(spec, mesh):
        off = 0
        for l, (o, i) in enumerate(dims):
            o_s, i_s = o // tp, i // tp
            if tp == 1:
                n = V * o * i
                Ws[l][sl] = arr[r, off : off + n].reshape(V, o, i)
            elif l % 2 == 0:
                n = V * o_s * i
                Ws[l][sl, t * o_s : (t + 1) * o_s, :] = arr[
                    r, off : off + n
                ].reshape(V, o_s, i)
            else:
                n = V * o * i_s
                Ws[l][sl, :, t * i_s : (t + 1) * i_s] = arr[
                    r, off : off + n
                ].reshape(V, o, i_s)
            off += n
        for l, (o, _) in enumerate(dims):
            o_s = o // tp
            n = V * o_s
            bs[l][sl, t * o_s : (t + 1) * o_s] = arr[r, off : off + n].reshape(
                V, o_s
            )
            off += n
    return {"W": tuple(Ws), "b": tuple(bs)}


def _zero1_check_state(opt, csz):
    """zero1's flat layout requires each 'params' state part to come out of
    ``opt.init(chunk)`` as one chunk-shaped zeros array; reject anything the
    state_layout protocol doesn't describe, loudly."""
    from shallowspeed_tpu.optimizer import split_state

    probe = opt.init(np.zeros((csz,), np.float32))
    parts, scalars = split_state(opt, probe)
    for key, leaf in parts.items():
        if not (
            hasattr(leaf, "shape")
            and tuple(leaf.shape) == (csz,)
            and not np.any(np.asarray(leaf))
        ):
            raise ValueError(
                f"zero1: state part {key!r} of {type(opt).__name__} is not a "
                "zeros-initialized chunk mirror — its state_layout() does "
                "not match its init()"
            )
    for key, leaf in scalars.items():
        if np.ndim(leaf) != 0:
            raise ValueError(
                f"zero1: state part {key!r} of {type(opt).__name__} is "
                "declared 'scalar' but is not 0-d"
            )
    return parts, scalars


def zero1_part_spec(tp: int):
    """The PartitionSpec of one zero1 'params' state part: rows are devices
    of the (pp[, tp]) grid, columns chunk over dp. At tp == 1 this is the
    historical P('pp', 'dp') (byte-identical programs); at tp > 1 the row
    axis splits over BOTH non-dp axes — row ``p*tp + t`` is device (p, t),
    matching ``_zero1_device_rows``'s flat layout. The ONE definition:
    ``zero1_part_sharding`` (placement) and ``make_pipeline_step``'s
    shard_map state specs both read it, so device placement and program
    specs can never disagree."""
    if tp == 1:
        return P("pp", "dp")
    return P(("pp", "tp"), "dp")


def zero1_part_sharding(mesh: Mesh):
    """``zero1_part_spec`` bound to a mesh (see its docstring)."""
    return NamedSharding(mesh, zero1_part_spec(mesh_tp(mesh)))


def zero1_init_state(opt, spec: ModelSpec, mesh: Mesh):
    """Device-put initial ZeRO-1 optimizer state: a dict with one
    (pp[*tp], dp*chunk) array per 'params' state part — sharded so each
    device holds its own (1, chunk) shard — plus replicated 0-d arrays
    for 'scalar' parts; () for stateless optimizers."""
    from shallowspeed_tpu.optimizer import is_stateless

    flat, csz = zero1_flat_len(spec, mesh)
    if is_stateless(opt):
        return ()
    parts, scalars = _zero1_check_state(opt, csz)
    dp = mesh.shape["dp"]
    n_rows = mesh.shape["pp"] * mesh_tp(mesh)
    part_sh = zero1_part_sharding(mesh)
    rep_sh = NamedSharding(mesh, P())
    state = {
        key: jax.device_put(np.zeros((n_rows, dp * csz), np.float32), part_sh)
        for key in parts
    }
    state.update(
        {
            key: jax.device_put(np.asarray(leaf, np.float32), rep_sh)
            for key, leaf in scalars.items()
        }
    )
    return state


def zero1_state_to_logical(state, opt, spec: ModelSpec, mesh: Mesh, order=None):
    """ZeRO-1 state dict -> {"parts": {key: ragged_list}, "scalars":
    {key: float}} mirroring params (for layout-independent checkpoints);
    None for stateless state."""
    if isinstance(state, tuple) and state == ():
        return None
    layout = opt.state_layout()
    flat, _ = zero1_flat_len(spec, mesh)
    parts, scalars = {}, {}
    for key, kind in layout.items():
        if kind == "params":
            arr = np.asarray(jax.device_get(state[key]))[:, :flat]
            stacked = _zero1_unflatten_rows(arr, spec, mesh)
            parts[key] = unstack_params(stacked, spec, order=order)
        else:
            scalars[key] = float(jax.device_get(state[key]))
    return {"parts": parts, "scalars": scalars}


def _zero1_state_rows(logical_part, spec, mesh, order):
    """Stack one logical state part and flatten it into the zero1 device
    rows (tp-aware)."""
    stacked, _ = stack_params(logical_part, spec, order=order, tp=mesh_tp(mesh))
    return _zero1_flatten_rows(stacked, spec, mesh)


def zero1_state_from_logical(logical, opt, spec: ModelSpec, mesh: Mesh, order=None):
    """Inverse: logical {"parts", "scalars"} dict -> device-put state."""
    if logical is None:
        return zero1_init_state(opt, spec, mesh)
    flat, csz = zero1_flat_len(spec, mesh)
    dp = mesh.shape["dp"]
    layout = opt.state_layout()
    part_sh = zero1_part_sharding(mesh)
    rep_sh = NamedSharding(mesh, P())
    n_rows = mesh.shape["pp"] * mesh_tp(mesh)
    state = {}
    for key, kind in layout.items():
        if kind == "params":
            rows = _zero1_state_rows(logical["parts"][key], spec, mesh, order)
            padded = np.zeros((n_rows, dp * csz), np.float32)
            padded[:, :flat] = rows
            state[key] = jax.device_put(padded, part_sh)
        else:
            state[key] = jax.device_put(
                np.asarray(logical["scalars"][key], np.float32), rep_sh
            )
    return state


# ---------------------------------------------------------------------------
# ZeRO-2/3: the block-cyclic per-slot shard layout over dp
# ---------------------------------------------------------------------------
#
# ZeRO-1 shards only the optimizer STATE: the program still concatenates the
# full flat gradient (gvec) and the full flat params (pvec) before the one
# reduce-scatter / chunk-slice, so three flat-sized temporaries coexist at
# the tail. The higher stages kill those temporaries by making the shard
# layout PER LAYER SLOT instead of per flat vector:
#
#   every slot (V virtual rows of sz elements; W slots then b slots, the
#   same order as the flat layout) pads each row to dp*k columns
#   (k = ceil(sz/dp)) and deals column-block d to dp rank d. Rank d's local
#   shard is the concatenation over slots of its (V, k) blocks flattened
#   v-major — csz3 = sum_slots V*k elements per rank.
#
# Why block-cyclic and not the zero1 flat chunking: a slot's gradient slab
# (V, sz) reduce-scatters DIRECTLY into this layout (pad the row, deal the
# column blocks — one collective per slot, no flat concat), and a single
# row's gradient reduce-scatters into ONE (k,) segment of the shard — which
# is what lets ZeRO-2 and ZeRO-3 sync per tick from inside the scan.
#
# ZeRO-2 = params still replicated (stacked {W, b} as ever) + each tick's
# slot gradients reduce-scattered into the persistent (csz3,) gradient
# shard + optimizer state sharded in this layout. Elementwise collectives:
# each element's dp-sum lands with identical bits wherever it is
# scattered, so at ``mubatches=1`` ZeRO-2 weights are BITWISE equal to
# ZeRO-1's at a fixed layout for elementwise optimizer math (the
# clip/grad-norm scalar partitions its partial sums differently — pin
# bitwise equality on clip-free runs); with more microbatches the shard
# sums microbatch-outer and carries ZeRO-3's tolerance.
#
# ZeRO-3 = params AT REST in this layout ({"P": (pp*tp, dp*csz3)} under
# ``zero1_part_spec``) — each tick branch all-gathers just the active
# chunk's slot segments (under tp only the 1/tp local shard, since the
# layout is built from tp-local slot shapes), uses them, and lets them die
# with the branch; the backward reduce-scatters each tick's slot gradients
# immediately into the persistent (csz3,) gradient shard. The per-tick sync
# reassociates the microbatch/replica sum order (sum_m sum_d vs the slab
# path's sum_d sum_m), hence ZeRO-3's tolerance-not-bitwise contract.
#
# Host helpers below transform between the flat device rows (the zero1
# layout) and the block-cyclic rows, so checkpoints stay logical and
# layout-independent.


class ZeroSlot(NamedTuple):
    """One layer slot's geometry in the block-cyclic dp-shard layout."""

    kind: str  # "W" | "b"
    layer: int  # slot index within its kind
    rows: int  # V virtual chunk rows
    shape: tuple  # per-row tp-LOCAL shape: (o, i) W shard or (o,) b shard
    sz: int  # elements per row = prod(shape)
    k: int  # per-dp-rank columns = ceil(sz / dp)
    off: int  # start within a rank's csz3 block (cumulative V*k)
    flat_off: int  # start within the flat layout (cumulative V*sz)


def zero_block_slots(spec: ModelSpec, pp: int, dp: int, tp: int = 1):
    """(slots, csz3): the per-slot block-cyclic geometry and the per-rank
    shard length. Slot order == the flat layout's (every W slot then every
    b slot), so ``flat_off`` walks ``stacked_flat_len`` exactly."""
    dims = slot_shapes(spec, tp)
    V = spec.n_stages // pp
    slots = []
    off = flat_off = 0
    for l, (o, i) in enumerate(dims):
        if tp == 1:
            shape = (o, i)
        elif l % 2 == 0:  # column-parallel slot: out-dim sharded
            shape = (o // tp, i)
        else:  # row-parallel slot: in-dim sharded
            shape = (o, i // tp)
        sz = shape[0] * shape[1]
        k = -(-sz // dp)
        slots.append(ZeroSlot("W", l, V, shape, sz, k, off, flat_off))
        off += V * k
        flat_off += V * sz
    for l, (o, _) in enumerate(dims):
        sz = o // tp
        k = -(-sz // dp)
        slots.append(ZeroSlot("b", l, V, (sz,), sz, k, off, flat_off))
        off += V * k
        flat_off += V * sz
    return tuple(slots), off


def zero_block_len(spec: ModelSpec, mesh: Mesh):
    """(flat_len, csz3): the flat per-device param count and the
    block-cyclic per-dp-rank shard length (>= ceil(flat/dp); per-slot
    padding rounds each slot separately)."""
    slots, csz3 = zero_block_slots(
        spec, mesh.shape["pp"], mesh.shape["dp"], mesh_tp(mesh)
    )
    return slots[-1].flat_off + slots[-1].rows * slots[-1].sz, csz3


def _zb_scatter_rows(g2d, dp, k):
    """(V, sz) slot rows -> the (dp, V*k) per-rank column-block deal: pad
    each row to dp*k, deal column block d to output row d (row v lands
    v-major at columns [v*k, (v+1)*k) of its rank). Works on numpy or jnp
    arrays (pure reshape/transpose)."""
    V, sz = g2d.shape
    mod = np if isinstance(g2d, np.ndarray) else jnp
    pad = mod.pad(g2d, ((0, 0), (0, dp * k - sz)))
    return pad.reshape(V, dp, k).transpose(1, 0, 2).reshape(dp, V * k)


def _zb_unscatter_rows(mat, V, k, sz):
    """(dp, V*k) -> (V, sz): inverse of ``_zb_scatter_rows`` (drops the
    per-row padding)."""
    dp = mat.shape[0]
    return (
        mat.reshape(dp, V, k).transpose(1, 0, 2).reshape(V, dp * k)[:, :sz]
    )


def _zb_deal_view(g2d, dp, k):
    """(V, sz) slot rows -> the (V, dp, k) deal VIEW: the same per-rank
    column deal as ``_zb_scatter_rows`` but as a pad + reshape only —
    element (v, d, j) is padded row v's column d*k+j, so a dp-collective
    on axis 1 touches exactly the elements the (dp, V*k) layout's axis-0
    collective does, without ever materializing the transposed full-slot
    slab (the ZeRO-2 tail's peak-HBM discipline: live temporaries stay
    shard-sized, not model-sized)."""
    V, sz = g2d.shape
    return jnp.pad(g2d, ((0, 0), (0, dp * k - sz))).reshape(V, dp, k)


def _zero_block_rows_from_flat(flat_rows, slots, dp, csz3):
    """Host-side: flat device rows (n_rows, >=flat_len) -> block-cyclic
    rows (n_rows, dp*csz3), where columns [d*csz3, (d+1)*csz3) are rank d's
    shard (so ``zero1_part_spec`` column-chunking lands each rank its own
    block)."""
    n_rows = flat_rows.shape[0]
    out = np.zeros((n_rows, dp * csz3), np.float32)
    for s in slots:
        seg = flat_rows[:, s.flat_off : s.flat_off + s.rows * s.sz]
        for r in range(n_rows):
            mat = _zb_scatter_rows(
                np.asarray(seg[r], np.float32).reshape(s.rows, s.sz), dp, s.k
            )
            for d in range(dp):
                a = d * csz3 + s.off
                out[r, a : a + s.rows * s.k] = mat[d]
    return out


def _zero_flat_from_block_rows(block_rows, slots, dp, csz3, flat):
    """Host-side inverse of ``_zero_block_rows_from_flat``."""
    n_rows = block_rows.shape[0]
    out = np.zeros((n_rows, flat), np.float32)
    for s in slots:
        for r in range(n_rows):
            mat = np.stack(
                [
                    block_rows[
                        r, d * csz3 + s.off : d * csz3 + s.off + s.rows * s.k
                    ]
                    for d in range(dp)
                ]
            )
            full = _zb_unscatter_rows(mat, s.rows, s.k, s.sz)
            out[r, s.flat_off : s.flat_off + s.rows * s.sz] = full.reshape(-1)
    return out


def zero_block_flatten_rows(stacked_np, spec, mesh):
    """Host-side: stacked {W,b} (numpy) -> (pp*tp, dp*csz3) block-cyclic
    device rows, ready for ``zero1_part_sharding`` placement (the ZeRO-3
    at-rest param layout)."""
    dp = mesh.shape["dp"]
    slots, csz3 = zero_block_slots(
        spec, mesh.shape["pp"], dp, mesh_tp(mesh)
    )
    return _zero_block_rows_from_flat(
        _zero1_flatten_rows(stacked_np, spec, mesh), slots, dp, csz3
    )


def zero_block_unflatten_rows(arr, spec, mesh):
    """Host-side inverse: (pp*tp, dp*csz3) -> stacked {W,b} full global
    arrays."""
    dp = mesh.shape["dp"]
    slots, csz3 = zero_block_slots(
        spec, mesh.shape["pp"], dp, mesh_tp(mesh)
    )
    flat = stacked_flat_len(spec, mesh.shape["pp"], mesh_tp(mesh))
    return _zero1_unflatten_rows(
        _zero_flat_from_block_rows(arr, slots, dp, csz3, flat), spec, mesh
    )


def zero_block_init_state(opt, spec: ModelSpec, mesh: Mesh):
    """Device-put initial ZeRO-2/3 optimizer state: like
    ``zero1_init_state`` but columns are the block-cyclic csz3 shard."""
    from shallowspeed_tpu.optimizer import is_stateless

    _, csz3 = zero_block_len(spec, mesh)
    if is_stateless(opt):
        return ()
    parts, scalars = _zero1_check_state(opt, csz3)
    dp = mesh.shape["dp"]
    n_rows = mesh.shape["pp"] * mesh_tp(mesh)
    part_sh = zero1_part_sharding(mesh)
    rep_sh = NamedSharding(mesh, P())
    state = {
        key: jax.device_put(
            np.zeros((n_rows, dp * csz3), np.float32), part_sh
        )
        for key in parts
    }
    state.update(
        {
            key: jax.device_put(np.asarray(leaf, np.float32), rep_sh)
            for key, leaf in scalars.items()
        }
    )
    return state


def zero_block_state_to_logical(state, opt, spec: ModelSpec, mesh: Mesh, order=None):
    """ZeRO-2/3 state dict -> logical {"parts", "scalars"} (for
    layout-independent checkpoints); None for stateless state."""
    if isinstance(state, tuple) and state == ():
        return None
    layout = opt.state_layout()
    parts, scalars = {}, {}
    for key, kind in layout.items():
        if kind == "params":
            arr = np.asarray(jax.device_get(state[key]))
            stacked = zero_block_unflatten_rows(arr, spec, mesh)
            parts[key] = unstack_params(stacked, spec, order=order)
        else:
            scalars[key] = float(jax.device_get(state[key]))
    return {"parts": parts, "scalars": scalars}


def zero_block_state_from_logical(logical, opt, spec: ModelSpec, mesh: Mesh, order=None):
    """Inverse: logical {"parts", "scalars"} dict -> device-put ZeRO-2/3
    state."""
    if logical is None:
        return zero_block_init_state(opt, spec, mesh)
    layout = opt.state_layout()
    part_sh = zero1_part_sharding(mesh)
    rep_sh = NamedSharding(mesh, P())
    dp = mesh.shape["dp"]
    slots, csz3 = zero_block_slots(
        spec, mesh.shape["pp"], dp, mesh_tp(mesh)
    )
    state = {}
    for key, kind in layout.items():
        if kind == "params":
            rows = _zero1_state_rows(logical["parts"][key], spec, mesh, order)
            state[key] = jax.device_put(
                _zero_block_rows_from_flat(rows, slots, dp, csz3), part_sh
            )
        else:
            state[key] = jax.device_put(
                np.asarray(logical["scalars"][key], np.float32), rep_sh
            )
    return state


# ---------------------------------------------------------------------------
# The tick-program step builder
# ---------------------------------------------------------------------------


def _stash(buf, slot, val):
    """Park ``val`` (mb, width) in a stash ring's lowering-assigned ``slot``.
    Slots are stored feature-major, ``(width, mb)``: see the ring
    allocations in ``make_pipeline_step``."""
    with scope("stash"):
        return buf.at[slot].set(val.T)


def _unstash(buf, slot):
    """The ``(mb, width)`` value parked in ``slot``."""
    with scope("unstash"):
        return buf[slot].T


def _microbatch(a, i):
    """One microbatch's rows of the step's local batch."""
    with scope("batch"):
        return a[i]


def _fit(a, width):
    """Slice or zero-pad the last dim to ``width`` (exact under the padding
    invariant: dropped columns are always zero)."""
    cur = a.shape[-1]
    if cur == width:
        return a
    if cur > width:
        return a[..., :width]
    return jnp.pad(a, [(0, 0)] * (a.ndim - 1) + [(0, width - cur)])


@scoped("act")
def _stage_fwd(
    Ws, bs, active, relu, dims, x, precision, kernel_backend="xla",
    act="relu", residual=None,
):
    """Forward through the per-slot stacks; returns (out, xs, masks) where
    xs[l]: (mb, in_l) and masks[l]: (mb, out_l).

    ``kernel_backend="pallas"`` runs each slot as one fused Pallas unit
    (pallas_ops.linear_flag_fwd): the traced relu flag rides into the kernel
    as a scalar operand, so the chunk-uniform layer loop needs no static
    per-stage specialization. Same math (flag-selected relu on z = x@w.T+b,
    mask = z > 0) either way.

    ``act`` is the spec's STATIC activation family: "relu" traces exactly
    the historical program (bool bitmask residuals, no residual-add
    expressions anywhere — byte-identical); "gelu" stores the f32
    derivative ``gelu_grad_mult(z)`` in the mask slot (1.0 where the flag
    is off) and adds the ``residual`` flags' skip connections
    (y_l += x_{l-1}, exact under _fit because residual widths are equal by
    spec construction and padding is exact zeros)."""
    xs, masks = [], []
    x_prev = None
    for l, (o, i) in enumerate(dims):
        x_l = _fit(x, i)
        if kernel_backend == "pallas":
            from shallowspeed_tpu import pallas_ops

            y_act, mask_f = pallas_ops.linear_flag_fwd(
                x_l, Ws[l], jnp.reshape(bs[l], (1, -1)), relu[l],
                precision=precision,
            )
            xs.append(x_l)
            masks.append(mask_f > 0)
        elif act == "gelu":
            y = ops.linear(x_l, Ws[l], bs[l], precision=precision)
            xs.append(x_l)
            masks.append(jnp.where(relu[l], ops.gelu_grad_mult(y), 1.0))
            y_act = jnp.where(relu[l], ops.gelu(y), y)
            if l > 0:
                y_act = y_act + jnp.where(
                    residual[l], _fit(x_prev, o), 0.0
                )
        else:
            y = ops.linear(x_l, Ws[l], bs[l], precision=precision)
            xs.append(x_l)
            masks.append(y > 0)
            y_act = jnp.where(relu[l], ops.relu(y), y)
        x_prev = x_l
        x = jnp.where(active[l], y_act, _fit(x_l, o))
    return x, tuple(xs), tuple(masks)


@scoped("act")
def _stage_bwd(
    Ws, active, relu, dims, xs, masks, g, precision, kernel_backend="xla",
    act="relu", residual=None,
):
    """Backward through the per-slot stacks; returns (dx, gWs, gbs).

    Gelu family: ``masks`` carry the stashed f32 derivative values, so the
    effective-grad expression is the SAME ``g * mask`` character string as
    relu's; residual skip grads add the NEXT slot's incoming grad to this
    slot's dx (x_{l-1} fed both linear l and the residual at slot l's
    output)."""
    L = len(dims)
    gWs, gbs = [None] * L, [None] * L
    g_prev = None
    for l in reversed(range(L)):
        o, i = dims[l]
        g_l = _fit(g, o)
        if kernel_backend == "pallas":
            from shallowspeed_tpu import pallas_ops

            dx, dw, db2 = pallas_ops.linear_flag_bwd(
                g_l, masks[l].astype(jnp.float32), xs[l], Ws[l], relu[l],
                precision=precision,
            )
            db = jnp.reshape(db2, (-1,))
        else:
            g_eff = jnp.where(relu[l], g_l * masks[l], g_l)
            dx, dw, db = ops.linear_grad(g_eff, xs[l], Ws[l], precision=precision)
            if act == "gelu" and l + 1 < L:
                dx = dx + jnp.where(residual[l + 1], _fit(g_prev, i), 0.0)
        gWs[l] = jnp.where(active[l], dw, 0.0)
        gbs[l] = jnp.where(active[l], db, 0.0)
        g = jnp.where(active[l], dx, _fit(g_l, i))
        g_prev = g_l
    return g, tuple(gWs), tuple(gbs)


@scoped("act")
def _stage_bwd_input(Ws, active, relu, dims, masks, g, precision,
                     act="relu", residual=None):
    """The relay-critical half of the split backward: the dgrad chain only.

    Returns ``(dx, g_effs)`` — the input gradient the upstream stage waits
    for, plus the per-slot effective output-grads (the relu-masked ``g`` at
    each slot, the SAME tensors the combined backward feeds its wgrad
    matmuls). Those are free intermediates of the dx chain; the executor
    stashes them so the deferred B-weight never recomputes a dgrad matmul.
    Bit-parity: each slot's ``g_eff``/``dx`` expressions are character-
    identical to ``_stage_bwd``'s, so the downstream wgrads are too.
    Residual skip grads (gelu family) ride the dx chain here too — they
    never touch ``g_eff``, so the deferred B-weight is family-agnostic.
    """
    L = len(dims)
    g_effs = [None] * L
    g_prev = None
    for l in reversed(range(L)):
        o, i = dims[l]
        g_l = _fit(g, o)
        g_eff = jnp.where(relu[l], g_l * masks[l], g_l)
        g_effs[l] = g_eff
        dx = ops.linear_grad_input(g_eff, Ws[l], precision=precision)
        if act == "gelu" and l + 1 < L:
            dx = dx + jnp.where(residual[l + 1], _fit(g_prev, i), 0.0)
        g = jnp.where(active[l], dx, _fit(g_l, i))
        g_prev = g_l
    return g, tuple(g_effs)


@scoped("act")
def _stage_bwd_weight(active, dims, xs, g_effs, precision):
    """The deferred half of the split backward: per-slot wgrads from the
    stashed activations and the stashed effective output-grads. Slots are
    independent (no chain), and the expressions match ``_stage_bwd``'s
    wgrad leg exactly — bit-identical per-microbatch contributions."""
    L = len(dims)
    gWs, gbs = [None] * L, [None] * L
    for l in range(L):
        dw, db = ops.linear_grad_weight(g_effs[l], xs[l], precision=precision)
        gWs[l] = jnp.where(active[l], dw, 0.0)
        gbs[l] = jnp.where(active[l], db, 0.0)
    return tuple(gWs), tuple(gbs)


# ---------------------------------------------------------------------------
# Megatron-sharded (tp > 1) stage functions
#
# Slot parity is the sharding: EVEN slots are column-parallel (W split on the
# out dim — the forward contracts the full input locally, no collective),
# ODD slots are row-parallel (W split on the in dim over the column slot's
# output shard — partial products summed by ONE psum over 'tp', the Megatron
# g-operator). The backward mirrors: row slots are local, column slots psum
# their dx partials (the f-operator) — exactly 2 all-reduces per layer pair
# per fwd+bwd pass (``tp_allreduce_sites`` is the audited site list).
#
# Exactness notes:
# - every psum that reassembles a sharded value (inactive-slot passthrough,
#   the closing stage-boundary gather, the scattered row-parallel bias) sums
#   contributions where each element is written by exactly ONE rank and the
#   others add exact zeros — exact data movement, like _fit;
# - the psums that sum PARTIAL PRODUCTS (row forward, column dx) split a
#   contraction across ranks, which reassociates the fp sum: tp > 1 layouts
#   therefore match the sequential oracle under the repo's standard
#   cross-layout tolerance (exactly like a different dp width reassociating
#   the gradient all-reduce — docs/numerics.md), while tp=1 stays byte-
#   identical (these functions are never traced at tp == 1) and same-layout
#   A/B knobs at fixed tp (split vs combined
#   backward, fused-run vs step loop) remain bitwise;
# - these psums sit inside ``lax.switch`` branches; the branch predicate is
#   the stage's op code, identical for every member of a tp group (same
#   (dp, pp) coordinates), so each all-reduce group executes uniformly.
# ---------------------------------------------------------------------------


def _tp_shard(a, t, w):
    """Rank t's width-``w`` slice of a full-width last dim (exact: column
    selection). The inverse of ``_tp_scatter``."""
    return lax.dynamic_slice_in_dim(a, t * w, w, axis=-1)


def _tp_scatter(a_loc, t, full_w):
    """Place rank t's shard at its column offset in a zero full-width
    array — a psum over 'tp' of every rank's scatter IS the all-gather
    (each column written by exactly one rank; the rest add exact 0.0)."""
    z = jnp.zeros(a_loc.shape[:-1] + (full_w,), a_loc.dtype)
    return lax.dynamic_update_slice_in_dim(z, a_loc, t * a_loc.shape[-1], axis=-1)


def _stage_fwd_tp(Ws, bs, active, relu, dims, x, precision, tp_idx, tp,
                  act="relu", residual=None):
    """Megatron-sharded forward through the per-slot stacks (tp > 1).

    Returns ``(out_full, xs, masks)``: the stage output completed to full
    width (the boundary — relay payload or softmax head — never sees a
    shard), plus the residuals in the representation the backward
    consumes — ``xs[l]`` is slot l's input as its wgrad contracts it (full
    for column slots, this rank's shard for row slots), ``masks[l]`` the
    pre-activation bitmask as its dgrad masks it (rank-sharded for column
    slots, full post-psum for row slots).

    Inactive slots keep the representation state machine running: an even
    passthrough takes the rank's shard of the fitted activation, an odd
    passthrough scatters the shard back to full width THROUGH the slot's
    own psum (the inactive branch rides the same collective — uniform
    collectives, masked payloads, the executor's house idiom).

    Gelu family (``act="gelu"``): the mask slots carry the f32 derivative
    values in the same representation (sharded pre-activation at column
    slots, full post-psum at row slots), and the ``residual`` skip adds
    land at ROW slots only (the zoo's residual flags sit on odd global
    parity, which even per-stage slices preserve locally) AFTER the slot's
    psum — both operands are full-width there, so the add is replicated,
    never collective-scaled."""
    L = len(dims)
    xs, masks = [], []
    x_prev = None
    for l, (o, i) in enumerate(dims):
        if l % 2 == 0:  # column-parallel: full input, sharded output
            x_l = _fit(x, i)
            z_loc = ops.linear(x_l, Ws[l], bs[l], precision=precision)
            xs.append(x_l)
            if act == "gelu":
                masks.append(jnp.where(relu[l], ops.gelu_grad_mult(z_loc), 1.0))
                y_loc = jnp.where(relu[l], ops.gelu(z_loc), z_loc)
            else:
                masks.append(z_loc > 0)
                y_loc = jnp.where(relu[l], ops.relu(z_loc), z_loc)
            x_prev = x_l
            x = jnp.where(
                active[l], y_loc, _tp_shard(_fit(x_l, o), tp_idx, o // tp)
            )
        else:  # row-parallel: sharded input, one psum, full output
            z_part = jnp.matmul(x, Ws[l].T, precision=precision)
            b_full = _tp_scatter(jnp.reshape(bs[l], (-1,)), tp_idx, o)
            pre = jnp.where(
                active[l],
                z_part + b_full[None, :],
                _fit(_tp_scatter(x, tp_idx, i), o),
            )
            z_full = lax.psum(pre, "tp")
            xs.append(x)
            if act == "gelu":
                masks.append(
                    jnp.where(relu[l], ops.gelu_grad_mult(z_full), 1.0)
                )
                y = jnp.where(relu[l], ops.gelu(z_full), z_full)
                y = y + jnp.where(residual[l], _fit(x_prev, o), 0.0)
            else:
                masks.append(z_full > 0)
                y = jnp.where(relu[l], ops.relu(z_full), z_full)
            x = jnp.where(active[l], y, z_full)
    if (L - 1) % 2 == 0:
        # trailing column slot left the stage output sharded: complete it
        # (the closing gather of tp_allreduce_sites' forward list)
        x = lax.psum(_tp_scatter(x, tp_idx, dims[-1][0]), "tp")
    return x, tuple(xs), tuple(masks)


def _stage_bwd_input_tp(Ws, active, relu, dims, masks, g, precision, tp_idx, tp,
                        act="relu", residual=None):
    """The dgrad chain of the Megatron backward (tp > 1): the split
    B-input, and — composed with ``_stage_bwd_weight_tp`` below — the
    combined backward's first half. Returns ``(dx_full, g_effs)``; the
    per-slot effective output-grads are stashed in the SAME representation
    the masks use (sharded for column slots, full for row slots).

    Gelu residual grads land at COLUMN slots only (the skip's producer is
    the even slot's full-width input), AFTER the slot's dx psum — both
    operands full-width and replicated, exactly mirroring the forward."""
    L = len(dims)
    g_effs = [None] * L
    g_prev = None
    if (L - 1) % 2 == 0:
        # the stage output was completed to full width; the trailing
        # column slot's dgrad consumes this rank's shard of its grad
        o = dims[-1][0]
        g = _tp_shard(_fit(g, o), tp_idx, o // tp)
    for l in reversed(range(L)):
        o, i = dims[l]
        if l % 2 == 0:  # column-parallel: sharded g, psum'd full dx
            g_eff = jnp.where(relu[l], g * masks[l], g)
            g_effs[l] = g_eff
            part = jnp.matmul(g_eff, Ws[l], precision=precision)
            pre = jnp.where(
                active[l], part, _fit(_tp_scatter(g, tp_idx, o), i)
            )
            g = lax.psum(pre, "tp")
            if act == "gelu" and l + 1 < L:
                g = g + jnp.where(residual[l + 1], _fit(g_prev, i), 0.0)
        else:  # row-parallel: full g, local sharded dx
            g_l = _fit(g, o)
            g_eff = jnp.where(relu[l], g_l * masks[l], g_l)
            g_effs[l] = g_eff
            dx = jnp.matmul(g_eff, Ws[l], precision=precision)
            g = jnp.where(
                active[l], dx, _tp_shard(_fit(g_l, i), tp_idx, i // tp)
            )
            g_prev = g_l
    return g, tuple(g_effs)


def _stage_bwd_weight_tp(active, dims, xs, g_effs, precision, tp_idx, tp):
    """The wgrad half of the Megatron backward (tp > 1): every product is
    LOCAL (dW contracts over the microbatch rows, never over a sharded
    dim), so the deferred B-weight stays collective-free under tp too.
    Row-slot biases are stored sharded; their db is the rank's slice of
    the full row-sum (exact column selection)."""
    L = len(dims)
    gWs, gbs = [None] * L, [None] * L
    for l in range(L):
        o, _ = dims[l]
        dw = jnp.matmul(g_effs[l].T, xs[l], precision=precision)
        if l % 2 == 0:
            db = g_effs[l].sum(axis=0)
        else:
            db = _tp_shard(g_effs[l].sum(axis=0), tp_idx, o // tp)
        gWs[l] = jnp.where(active[l], dw, 0.0)
        gbs[l] = jnp.where(active[l], db, 0.0)
    return tuple(gWs), tuple(gbs)


def _stage_bwd_tp(Ws, active, relu, dims, xs, masks, g, precision, tp_idx, tp,
                  act="relu", residual=None):
    """Combined Megatron backward: the literal composition of the two
    halves (same composition contract as ops.linear_grad — split and
    combined schedules can never disagree, at any tp)."""
    dx, g_effs = _stage_bwd_input_tp(
        Ws, active, relu, dims, masks, g, precision, tp_idx, tp,
        act=act, residual=residual,
    )
    gWs, gbs = _stage_bwd_weight_tp(
        active, dims, xs, g_effs, precision, tp_idx, tp
    )
    return dx, gWs, gbs


def make_pipeline_step(
    mesh: Mesh,
    spec: ModelSpec,
    prog: TickProgram,
    mubatch_size: int,
    opt=None,
    precision=ops.DEFAULT_PRECISION,
    jit=True,
    tick_unroll=1,
    zero1=False,
    zero=None,
    clip_norm=None,
    kernel_backend="xla",
    with_grad_norm=False,
    with_step_stats=False,
    with_digests=False,
):
    """Build the jitted SPMD step executing one TickProgram over the mesh.

    Training (prog.is_training, opt required):
        step(stacked, flags, opt_state, x, y) -> (stacked, opt_state, loss)
      x: (global_batch, in_dim) sharded P('dp'); y: (global_batch, out_dim).
      opt_state is threaded exactly like the sequential trainer's, so
      stateful optimizers (momentum et al.) behave identically on every
      layout; loss is the global-batch MSE (computed on the fly at the head
      stage — an observability bonus the reference never offers).

    ``zero1``: shard the optimizer update over dp — reduce_scatter the
    gradients, update 1/dp of the (flattened) params per replica with 1/dp
    of the optimizer state, all_gather the result (see the ZeRO-1 section
    above; opt_state must come from ``zero1_init_state``). Exact for
    elementwise optimizers; bit-identical math to the plain path up to
    collective reassociation.

    ``zero``: the full dp-axis stage selector {0, 1, 2, 3} superseding the
    ``zero1`` boolean (``zero=1`` IS the zero1 path, verbatim). Stage 2
    keeps params replicated but reduce-scatters the gradient PER LAYER
    SLOT into the block-cyclic shard layout (see the ZeRO-2/3 section
    above) — the flat gradient/param concats never materialize; opt_state
    must come from ``zero_block_init_state``. Stage 3 additionally shards
    the params at rest: ``stacked`` becomes ``{"P": (pp*tp, dp*csz3)}``
    under ``zero1_part_spec``, every tick branch all-gathers just the
    active chunk's slot segments, and the backward reduce-scatters each
    tick's gradients immediately (per-tick sync => the tolerance-not-
    bitwise contract; stages 0-2 stay bitwise-comparable).

    ``clip_norm``: optional global-norm gradient clipping before the update.
    The norm is GLOBAL over every parameter of the model: the local squared
    sum is psum'd over ``pp`` (and, under zero1, over ``dp`` where the
    summed gradient lives chunked) — padded entries are exactly zero, so the
    stacked norm equals the logical norm. The norm always reads the
    POST-SYNC gradient.

    ``with_grad_norm`` (training only): telemetry aux — the step returns a
    FOURTH output, the pre-clip global gradient norm (replicated scalar,
    same reduction geometry as the clip's). Pure data flow out of the
    shard_map, so the fused step program is unchanged in structure.

    ``with_step_stats`` (training only; implies the grad-norm output): the
    flight-recorder aux — a FIFTH output, the post-update global parameter
    norm (replicated scalar; padded entries are exactly zero, so the
    stacked norm IS the logical norm, psum'd over ``pp``). Together with
    the per-step loss these are the scalars the numerics health monitor
    checks on host after each epoch's single readback.

    ``with_digests`` (training only): the numerics-provenance aux — one
    EXTRA trailing output, a dict of layout-independent ``(S, L)`` grids
    (stacked-row x layer-slot): ``crc_w``/``crc_b`` are the per-block
    uint32 wrap-around checksums of the POST-update float32 param bits
    (bitcast, so bit-identical runs match bit for bit; psum on uint32
    wraps mod 2^32, and padding is exactly +0.0 = 0x00000000, so the
    psum'd stacked checksum EQUALS the logical per-layer checksum —
    ``utils.block_checksum``); ``pnorm_w``/``pnorm_b`` are post-update
    per-block L2 norms and ``gnorm_w``/``gnorm_b`` the post-sync
    PRE-clip per-block grad norms. Each device scatters its local rows
    into the grid and one psum over the param-sharded axes replicates
    the full matrix — pure data flow, no host callbacks.

    Inference:
        step(stacked, flags, x) -> preds (global_eval_batch, out_width) P('dp')

    Activation residuals live in stash slots assigned by the lowering, so a
    schedule's real peak activation memory is its scheduling property:
    GPipe allocates M slots, PipeDream-Flush min(M, depth) — the 1F1B memory
    advantage is physical buffer sizes here, not just a diagram.

    ``kernel_backend``: "xla" (default) or "pallas" — the per-slot compute
    unit inside every tick. "pallas" uses the flag-operand fused kernels
    (pallas_ops.linear_flag_fwd/bwd; the traced relu flag is a kernel
    operand, so one kernel serves every stage/chunk). Slots within the
    single-block VMEM budget run as one block; larger slots auto-dispatch
    to the grid-tiled flag kernels (pallas_ops.flag_kernels_fit reports
    the regime per slot).

    Tensor parallelism is a MESH property, not a parameter: when ``mesh``
    carries a ``tp`` axis the per-slot stacks arrive Megatron-sharded
    (``stacked_param_specs``) and the tick branches dispatch the tp stage
    functions instead of the flat ones (xla backend only). Everything
    else — tick tables, relays, gradient sync modes, the optimizer tail —
    is unchanged in structure; the cross-device norm reductions simply
    span ('pp','tp').
    """
    if kernel_backend not in ("xla", "pallas"):
        raise ValueError(f"unknown kernel_backend {kernel_backend!r}")
    if zero is None:
        zero = 1 if zero1 else 0
    else:
        zero = int(zero)
        if zero1 and zero != 1:
            raise ValueError(
                f"conflicting dp-stage selectors: zero1=True but zero={zero}"
            )
    if zero not in (0, 1, 2, 3):
        raise ValueError(f"zero must be one of 0/1/2/3, got {zero}")
    zero1 = zero == 1  # the legacy flag IS stage 1 — that path is verbatim
    if zero == 3 and kernel_backend == "pallas":
        raise ValueError(
            "zero=3 all-gathers parameter segments inside every tick "
            "branch; the fused pallas flag kernels take whole resident "
            "slots — use kernel_backend='xla' with --zero 3"
        )
    tp_n = mesh_tp(mesh)
    if tp_n > 1 and kernel_backend == "pallas":
        raise ValueError(
            "tensor parallelism shards each slot's W across the tp axis; "
            "the fused pallas flag kernels compute whole slots — use "
            "kernel_backend='xla' with --tp"
        )
    split = bool(getattr(prog, "backward_split", False))
    if split and kernel_backend == "pallas":
        raise ValueError(
            "backward_split needs the XLA per-slot backward (the fused "
            "pallas flag kernel computes dgrad+wgrad in one unit and has "
            "no split halves); use kernel_backend='xla'"
        )
    act = spec.act
    if act != "relu" and kernel_backend == "pallas":
        raise ValueError(
            f"the fused pallas flag kernels implement the relu family only; "
            f"use kernel_backend='xla' for act={act!r} models"
        )
    rec = bool(getattr(prog, "recompute", False))
    if rec and kernel_backend == "pallas":
        raise ValueError(
            "recompute re-runs the stage forward through the XLA slot "
            "functions; use kernel_backend='xla' with --recompute"
        )
    dims = slot_shapes(spec, tp_n)
    # this device's slot geometry: at tp == 1 these ARE the global dims
    # (identical trace, byte for byte); at tp > 1 the Megatron shards
    w_dims, b_widths, xs_widths, mask_widths = tp_local_dims(dims, tp_n)
    S_, L = spec.n_stages, len(dims)
    D_in, D_out = dims[0][1], dims[-1][0]
    # the cross-device axes params/grads are sharded over: the reductions
    # behind the clip/grad-norm/param-norm scalars must span them all
    pp_axes = "pp" if tp_n == 1 else ("pp", "tp")
    z1_axes = ("dp", "pp") if tp_n == 1 else ("dp", "pp", "tp")

    def sum_pp(sq):
        with scope("sync/pp"):
            return lax.psum(sq, pp_axes)

    def sum_z1(sq):
        with scope("sync/dp"):
            return lax.psum(sq, z1_axes)

    W_rel = relay_width(spec)  # ppermute payload / mailbox width (<= D_in)
    M = prog.num_micro_batches
    Kf, Kb = prog.n_fwd_slots, prog.n_bwd_slots
    Ks = prog.n_stash_slots
    Kg = prog.n_gstash_slots  # grad-stash depth (split programs only)
    Kx = prog.n_xin_slots  # input-stash depth (recompute programs only)
    mb_sz = mubatch_size
    B_global = spec.global_batch_size
    training = prog.is_training
    if training and opt is None:
        raise ValueError("training program needs an optimizer")
    if (with_grad_norm or with_step_stats or with_digests) and not training:
        raise ValueError(
            "with_grad_norm/with_step_stats/with_digests apply to training "
            "programs only"
        )
    if with_step_stats:
        with_grad_norm = True  # step stats carry the grad norm per step
    P_ = mesh.shape["pp"]  # devices on the pp axis
    V = prog.num_chunks  # virtual stages per device
    assert prog.num_stages == P_, "program/mesh device-count mismatch"
    assert S_ == P_ * V, "model stages must equal devices x virtual chunks"
    dp_n = mesh.shape["dp"]
    if zero >= 2 and with_digests:
        raise ValueError(
            "with_digests reads the zero1 flat-chunk segment map; the "
            "block-cyclic shard layout of zero>=2 has no flat chunk — "
            "run digests at --zero 1 or below"
        )
    if zero >= 1:
        if not training:
            if zero1:
                raise ValueError("zero1 applies to training programs only")
            raise ValueError(f"zero={zero} applies to training programs only")
        from shallowspeed_tpu.optimizer import is_stateless

        z1_stateful = not is_stateless(opt)
        if zero1:
            z1_flat, z1_csz = zero1_flat_len(spec, mesh)
            if z1_stateful:
                _zero1_check_state(opt, z1_csz)
        else:
            zb_slots, zb_csz = zero_block_slots(
                spec, mesh.shape["pp"], mesh.shape["dp"], tp_n
            )
            if z1_stateful:
                _zero1_check_state(opt, zb_csz)
        if z1_stateful:
            z1_layout = opt.state_layout()

    # ZeRO-2/3 persistent gradient shard: every zero-2 and zero-3 program
    # accumulates the dp-summed gradient as this rank's (csz3,)
    # block-cyclic shard, reduce-scattered per tick (canonical ZeRO-2
    # ordering: the shard sums microbatch-outer, so it is bitwise equal to
    # zero-1's dp-outer sum only at mubatches=1; see docs/performance.md).
    shard_grads = zero >= 2

    if with_digests:
        # the digest-grid builders (see the docstring): per-slot columns of
        # per-chunk reductions, scattered at this device's pp row block and
        # psum'd over the axes the params are sharded across, so EVERY
        # device returns the same (S, L) matrix. uint32 checksums wrap mod
        # 2^32 under psum — the same wrap the host reference
        # (utils.block_checksum) computes, so stacked == logical exactly.
        def _digest_scatter(col_fn, slot_vals, dtype, axes):
            grid = jnp.zeros((S_, L), dtype)
            r0 = lax.axis_index("pp") * V
            for sl, a in enumerate(slot_vals):
                col = col_fn(a.astype(jnp.float32))
                grid = lax.dynamic_update_slice(
                    grid, col.reshape(V, 1).astype(dtype), (r0, sl)
                )
            return lax.psum(grid, axes)

        def _crc_col(a32):
            return jnp.sum(
                lax.bitcast_convert_type(a32, jnp.uint32).reshape(V, -1),
                axis=1,
                dtype=jnp.uint32,
            )

        def _sq_col(a32):
            return jnp.sum((a32 * a32).reshape(V, -1), axis=1)

        def _digest_grids(new_p, gsq_w, gsq_b):
            """The step's digest dict from the post-update local params +
            the pre-computed post-sync grad squared-sum grids."""
            return {
                "crc_w": _digest_scatter(
                    _crc_col, new_p["W"], jnp.uint32, pp_axes
                ),
                "crc_b": _digest_scatter(
                    _crc_col, new_p["b"], jnp.uint32, pp_axes
                ),
                "pnorm_w": jnp.sqrt(
                    _digest_scatter(_sq_col, new_p["W"], jnp.float32, pp_axes)
                ),
                "pnorm_b": jnp.sqrt(
                    _digest_scatter(_sq_col, new_p["b"], jnp.float32, pp_axes)
                ),
                "gnorm_w": jnp.sqrt(gsq_w),
                "gnorm_b": jnp.sqrt(gsq_b),
            }

        if zero1:
            # under ZeRO-1 the post-sync gradient lives as this replica's
            # flat (csz,) chunk, so the per-(chunk, slot) squared sums come
            # from a STATIC segment-id map over the padded flat layout
            # (W slots then b slots, chunk-major inside each slot; padding
            # lands in a trash segment) — sliced at this replica's offset
            # and segment-summed, then scattered + psum'd like the rest
            _seg_np = np.concatenate(
                [
                    np.repeat(np.arange(sl * V, (sl + 1) * V), o * i)
                    for sl, (o, i) in enumerate(w_dims)
                ]
                + [
                    np.repeat(np.arange((L + sl) * V, (L + sl + 1) * V), w)
                    for sl, w in enumerate(b_widths)
                ]
            )
            _pad_n = z1_csz * mesh.shape["dp"] - z1_flat
            z1_seg_ids = jnp.asarray(
                np.concatenate([_seg_np, np.full(_pad_n, 2 * L * V)]),
                jnp.int32,
            )

    # tick tables as program constants, scanned over their leading (T) axis.
    # Host arrays: they compile to the same constants as device arrays
    # would, and a closure of device arrays would pin them for as long as
    # observability.scopes keeps the jitted callable
    tab_dict = dict(
        op=prog.op,
        mb=prog.mb,
        rf=prog.read_fwd_slot,
        rb=prog.read_bwd_slot,
        inf=prog.in_fwd_slot,
        inb=prog.in_bwd_slot,
        sf=prog.send_fwd,
        sb=prog.send_bwd,
        sw=prog.stash_write,
        sr=prog.stash_read,
        ck=prog.chunk,
        li=prog.load_in,
        ih=prog.is_head,
    )
    if split:
        # split programs route three extra slot tables: the activation-
        # stash peek (B-input) and the grad-stash write/read pair
        tab_dict.update(
            sp=prog.stash_peek, gw=prog.gstash_write, gr=prog.gstash_read
        )
    if rec:
        # recompute programs route the input-stash write/read pair (the
        # forward stores its stage input; the recompute frees it)
        tab_dict.update(xw=prog.xin_write, xr=prog.xin_read)
    # relays follow the send tables, in space and in time. In space: a
    # direction's perm holds the device pairs on which some tick sends (with
    # virtual chunks the device-(P-1) -> device-0 wrap IS a stage boundary,
    # chunk c on the last device feeding chunk c+1 on the first; without
    # chunks nothing ever sends on the wrap link and the perm leaves it out),
    # and a direction that never sends (inference's backward, a pp 1 mesh)
    # has no perm and no ppermute. In time: ``rlf``/``rlb`` are (T,) columns,
    # true in the ticks in which SOME device sends in that direction: one
    # scalar per tick, the same on every device, so every member of the
    # collective's group takes the same side of the conditional around it.
    fwd_perm, bwd_perm = prog.relay_perms()
    tab_dict.update(rlf=prog.relay_fwd, rlb=prog.relay_bwd)
    tabs = jax.tree.map(np.asarray, tab_dict)

    def per_device(stacked, flags, opt_state, x, y):
        # local views: stage axis is sharded to V rows per device on pp
        # (device-major interleaved order, so row v IS virtual chunk v)
        if zero == 3:
            # ZeRO-3: params at rest are this rank's block-cyclic shard;
            # tick branches gather the active chunk's segments on demand
            pshard = stacked["P"][0]  # (csz3,)
            WsV = bsV = None
        else:
            WsV = stacked["W"]  # per slot (V, out_l, in_l)
            bsV = stacked["b"]
        activeV = flags["active"]  # (V, L)
        reluV = flags["relu"]
        residualV = flags["residual"]  # (V, L); all-False for relu specs
        head_maskV = flags["head_mask"]  # (V, D_out)
        stage = lax.axis_index("pp")
        tp_idx = lax.axis_index("tp") if tp_n > 1 else 0

        def pick(a, v):
            """Select the active virtual chunk's row (static for V == 1)."""
            if V == 1:
                return a[0]
            return lax.dynamic_index_in_dim(a, v, 0, keepdims=False)

        with scope("batch"):
            x = x.reshape(M, mb_sz, D_in)  # local dp shard, padded to D_in
            y = y.reshape(M, mb_sz, D_out) if y is not None else None

        # The forward mailbox and the forward payload are FEATURE-MAJOR,
        # (width, mb), like the stash rings below and for their reason: that
        # is how a v5e's forward leaves its output and wants its input. The
        # relay's conditional is laid out before the tick body that calls
        # it, from its operands' logical shapes alone: with (mb, width) it
        # kept the mailbox batch-major and the forward branch then turned
        # the WHOLE mailbox to read one slot (text compiled for v5e:2x2 at
        # mlp-deep's shapes: a 134 MB copy per forward tick). This way
        # neither the payload (67 MB, twice a tick on the parent) nor the
        # mailbox is re-laid-out anywhere; tests/test_op_index.py holds the
        # compiled text to that. The backward's dgrad leaves its payload
        # batch-major, so that direction stays (mb, width).
        with scope("mail"):
            # named here: only conditionals touch the mailboxes now, and the
            # op index does not follow a carry leaf into one to class this
            carry = dict(
                fwd_mail=jnp.zeros((Kf + 1, W_rel, mb_sz), jnp.float32),
                bwd_mail=jnp.zeros((Kb + 1, mb_sz, W_rel), jnp.float32),
            )
        if training:
            # residual stashes (lowering-assigned slots, +1 trash), grad
            # accumulators, head-logit stash and the loss tally only exist in
            # training programs — inference never runs a backward, so it
            # carries only its predictions
            # the mask stash holds relu bitmasks (bool) for the relu family
            # and gelu derivative VALUES (f32) for the gelu family — same
            # slot discipline, family-appropriate dtype
            mask_dtype = jnp.bool_ if act == "relu" else jnp.float32
            # Every ring the forward side writes (xs, masks, z, xin, and
            # inference's preds) keeps its slots FEATURE-MAJOR, (width, mb):
            # ``_stash`` parks ``val.T``, ``_unstash`` hands ``buf[slot].T``
            # back, callers see (mb, width) and the transposes are exact. On
            # a v5e the forward's matmuls (x @ W.T, W stored (out, in)) leave
            # their outputs, and the masks made from them, batch-minor, and
            # the one-slot write wants ring and value in one layout: with
            # rings shaped (K+1, mb, width) XLA re-laid-out every WHOLE ring
            # twice around it. The dp2 x pp2 pipedream step at mlp-deep's
            # shapes, compiled for v5e:2x2, held 50 whole-ring copies in its
            # forward branch (3.4 GB per tick, 8.20 GB of temporaries); this
            # way it holds none (5.35 GB). tests/test_op_index.py keeps the
            # compiled text to that; docs/observability.md has the recipe.
            carry.update(
                xs=tuple(
                    jnp.zeros((Ks + 1, w, mb_sz), jnp.float32)
                    for w in xs_widths
                ),
                masks=tuple(
                    jnp.zeros((Ks + 1, w, mb_sz), mask_dtype)
                    for w in mask_widths
                ),
                z=jnp.zeros((Ks + 1, D_out, mb_sz), jnp.float32),
                loss=jnp.zeros((), jnp.float32),
            )
            if shard_grads:
                # ZeRO-2 and ZeRO-3 accumulate the dp-summed
                # gradient directly as this rank's persistent (csz3,)
                # shard — reduce-scattered per tick, never as full
                # (V, o, i) slabs: the stage's gradient-residency claim
                carry.update(gz=jnp.zeros((zb_csz,), jnp.float32))
            else:
                carry.update(
                    gW=tuple(
                        jnp.zeros((V, o, i), jnp.float32) for o, i in w_dims
                    ),
                    gb=tuple(jnp.zeros((V, w), jnp.float32) for w in b_widths),
                )
            if split:
                # grad stash: per-slot effective output-grads, held from
                # each B-input tick to its deferred B-weight tick (slots
                # assigned by the lowering, +1 trash — sized exactly like
                # the activation stash, because it IS the same discipline;
                # widths match the masks': the g_eff of a slot lives in
                # the same representation as its relu mask).
                # The one ring that stays BATCH-major, (Kg+1, mb, width): the
                # backward writes it, and turned it (a) breaks the bitwise
                # split == unsplit contract on the CPU, whose wgrad matmul
                # sums a turned g_eff in another order, and (b) at mlp-deep's
                # shapes makes the v5e compiler copy 11.2 GB of rings per
                # B-input tick where it copies 3.2 (step temporaries 10.5 ->
                # 16.8 GB). So g_eff goes through ``_stash`` already turned.
                carry.update(
                    gstash=tuple(
                        jnp.zeros((Kg + 1, mb_sz, w), jnp.float32)
                        for w in mask_widths
                    )
                )
            if rec:
                # recompute input stash: the stage input each forward tick
                # parks (slots assigned by the lowering, +1 trash; the
                # global stage 0 reloads from HBM instead and never claims
                # one). Freed at the recompute tick — the short lifetime
                # analysis/stash.py proves
                carry.update(
                    xin=jnp.zeros((Kx + 1, D_in, mb_sz), jnp.float32)
                )
        else:
            carry.update(preds=jnp.zeros((M + 1, D_out, mb_sz), jnp.float32))
        # The payload a branch does not produce. The forward one is made
        # inside each branch and the backward one once, outside the loop: two
        # hoisted zero buffers (they no longer have one shape to share) cost
        # the epoch program 25 MB of temporaries over the parent's, and two
        # made in the branches let the compiler hand the backward payload on
        # batch-minor and turn it twice per backward tick; this way the
        # program's temporaries are 42 MB UNDER the parent's (compiled for
        # v5e:2x2 at mlp-deep's shapes: 4,192 MB for 4,234).
        def zero_fwd():
            return jnp.zeros((W_rel, mb_sz), jnp.float32)

        zero_bwd = jnp.zeros((mb_sz, W_rel), jnp.float32)

        @scoped("tick")
        def tick(carry, row):
            opv = row["op"][stage]
            mb_i = row["mb"][stage]  # M = trash
            mb_r = jnp.minimum(mb_i, M - 1)  # clamped read index
            v = row["ck"][stage]  # active virtual chunk (0 when V == 1)
            load_in = row["li"][stage] == 1  # compute is the global stage 0 fwd
            is_head = row["ih"][stage] == 1  # compute is the global last stage

            def chunk_flags():
                """The active chunk's flag rows — no weights, so branches
                that never touch weights (split B-weight) emit no ZeRO-3
                gathers."""
                return (
                    pick(activeV, v),
                    pick(reluV, v),
                    pick(residualV, v),
                    pick(head_maskV, v),
                )

            def chunk_weights():
                """The active chunk's weights: resident-row picks at
                stages 0-2; just-in-time per-slot all-gathers of this
                chunk's shard segments at ZeRO-3 (gathered copies die with
                the branch — peak live params is one chunk, not the
                model)."""
                if zero != 3:
                    return [pick(w, v) for w in WsV], [pick(b, v) for b in bsV]
                gathered = []
                for s in zb_slots:
                    if V == 1:
                        seg = lax.slice_in_dim(pshard, s.off, s.off + s.k)
                    else:
                        seg = lax.dynamic_slice(
                            pshard, (s.off + v * s.k,), (s.k,)
                        )
                    with scope("sync/dp"):
                        full = lax.all_gather(seg, "dp", axis=0, tiled=True)
                    gathered.append(full[: s.sz].reshape(s.shape))
                return gathered[:L], gathered[L:]

            def chunk_params():
                Ws, bs = chunk_weights()
                return (Ws, bs) + chunk_flags()

            def z3_scatter_grads(c, gW_d, gb_d):
                """ZeRO-2/3 per-tick gradient sync: reduce-scatter each
                slot's chunk-row gradient over dp and accumulate the (k,)
                shard at this chunk's segment of the persistent gz."""
                gz = c["gz"]
                for s, g in zip(zb_slots, list(gW_d) + list(gb_d)):
                    vec = jnp.pad(g.reshape(-1), (0, dp_n * s.k - s.sz))
                    with scope("sync/dp"):
                        sh = lax.psum_scatter(
                            vec, "dp", scatter_dimension=0, tiled=True
                        )
                    if V == 1:
                        gz = gz.at[s.off : s.off + s.k].add(sh)
                    else:
                        a = s.off + v * s.k
                        seg = lax.dynamic_slice(gz, (a,), (s.k,))
                        gz = lax.dynamic_update_slice(gz, seg + sh, (a,))
                c = dict(c)
                c["gz"] = gz
                return c

            def accumulate(c, gW_d, gb_d):
                """Add one microbatch's weight gradients to the step's
                accumulators (the chunk's row, or the ZeRO-2/3 shard)."""
                with scope("acc"):
                    if shard_grads:
                        return z3_scatter_grads(c, gW_d, gb_d)
                    r = 0 if V == 1 else v
                    c["gW"] = tuple(a.at[r].add(d) for a, d in zip(c["gW"], gW_d))
                    c["gb"] = tuple(a.at[r].add(d) for a, d in zip(c["gb"], gb_d))
                    return c

            def stage_input(buf, slot, read="mail"):
                """The stage's input: the microbatch's rows on the global
                first stage, else what the relay left in the mailbox (or,
                at a recompute tick, what the forward parked in the input
                stash), padded up to D_in so both branches of the where
                agree (exact: relayed activations are zero beyond their
                true boundary width)."""
                x_mb = _microbatch(x, mb_r)
                with scope(read):
                    parked = _unstash(buf, slot) if read == "unstash" else buf[slot].T
                    return jnp.where(load_in, x_mb, _fit(parked, D_in))

            def incoming_grad(c, g0):
                """The backward's input gradient: the head's own on the
                global last stage, else the relayed one. The head grad is
                D_out wide, relayed grads W_rel wide; fit both to the wider
                so the where agrees (padding is exact zeros)."""
                Wb = max(D_out, W_rel)
                with scope("mail"):
                    return jnp.where(
                        is_head,
                        _fit(g0, Wb),
                        _fit(c["bwd_mail"][row["rb"][stage]], Wb),
                    )

            def payload_of(send, out):
                with scope("mail"):
                    return jnp.where(send == 1, _fit(out, W_rel), 0.0)

            def noop(c):
                return c, zero_fwd(), zero_bwd

            def run_stage_fwd(Ws, bs, active, relu, residual, x_in):
                """The ONE stage-forward call both the forward tick and the
                recompute tick make — character-identical expressions from
                a bitwise-identical input are the recompute parity
                contract."""
                if tp_n > 1:
                    return _stage_fwd_tp(
                        Ws, bs, active, relu, dims, x_in, precision,
                        tp_idx, tp_n, act=act, residual=residual,
                    )
                return _stage_fwd(
                    Ws, bs, active, relu, dims, x_in, precision,
                    kernel_backend, act=act, residual=residual,
                )

            def forward(c):
                Ws, bs, active, relu, residual, head_mask = chunk_params()
                x_in = stage_input(c["fwd_mail"], row["rf"][stage])
                out, xs_l, masks_l = run_stage_fwd(
                    Ws, bs, active, relu, residual, x_in
                )
                c = dict(c)
                p = ops.softmax(out, valid_mask=head_mask[None, :])
                if training:
                    if rec:
                        # recompute program: park only the stage INPUT (the
                        # residuals are re-derived at the recompute tick);
                        # the global stage 0 reloads from HBM, its xw is
                        # the trash slot
                        xw = row["xw"][stage]
                        c["xin"] = _stash(c["xin"], xw, x_in)
                    else:
                        sw = row["sw"][stage]  # lowering-assigned stash slot
                        c["xs"] = tuple(
                            _stash(buf, sw, val)
                            for buf, val in zip(c["xs"], xs_l)
                        )
                        c["masks"] = tuple(
                            _stash(buf, sw, val)
                            for buf, val in zip(c["masks"], masks_l)
                        )
                        c["z"] = _stash(c["z"], sw, out)
                    mb_loss = ops.mse_loss(p, _microbatch(y, mb_r), B_global)
                    with scope("loss"):
                        c["loss"] = c["loss"] + jnp.where(is_head, mb_loss, 0.0)
                else:
                    c["preds"] = _stash(c["preds"], mb_i, jnp.where(is_head, p, 0.0))
                return c, payload_of(row["sf"][stage], out).T, zero_bwd

            def recompute(c):
                # OP_RECOMPUTE: re-run the stage forward from the parked
                # input and stash the residuals the imminent backward
                # consumes. Same input bits + the same run_stage_fwd
                # expressions = bitwise-identical xs/masks/z to what the
                # stashed twin's forward tick stored. No loss accumulation
                # (the forward tick already tallied it), no sends.
                Ws, bs, active, relu, residual, head_mask = chunk_params()
                x_in = stage_input(c["xin"], row["xr"][stage], read="unstash")
                out, xs_l, masks_l = run_stage_fwd(
                    Ws, bs, active, relu, residual, x_in
                )
                c = dict(c)
                sw = row["sw"][stage]
                c["xs"] = tuple(
                    _stash(buf, sw, val) for buf, val in zip(c["xs"], xs_l)
                )
                c["masks"] = tuple(
                    _stash(buf, sw, val) for buf, val in zip(c["masks"], masks_l)
                )
                c["z"] = _stash(c["z"], sw, out)
                return c, zero_fwd(), zero_bwd

            def backward(c):
                Ws, bs, active, relu, residual, head_mask = chunk_params()
                # lowering guarantees every training backward has a real
                # stash slot in [0, Ks) (replay-asserted), so no clamp needed
                sr = row["sr"][stage]
                g0 = ops.softmax_mse_head_grad(
                    _unstash(c["z"], sr), _microbatch(y, mb_r), B_global,
                    valid_mask=head_mask[None, :],
                )
                g_in = incoming_grad(c, g0)
                xs_r = tuple(_unstash(buf, sr) for buf in c["xs"])
                masks_r = tuple(_unstash(buf, sr) for buf in c["masks"])
                if tp_n > 1:
                    dx, gW_d, gb_d = _stage_bwd_tp(
                        Ws, active, relu, dims, xs_r, masks_r, g_in,
                        precision, tp_idx, tp_n, act=act, residual=residual,
                    )
                else:
                    dx, gW_d, gb_d = _stage_bwd(
                        Ws, active, relu, dims, xs_r, masks_r, g_in,
                        precision, kernel_backend, act=act, residual=residual,
                    )
                c = accumulate(dict(c), gW_d, gb_d)
                return c, zero_fwd(), payload_of(row["sb"][stage], dx)

            def backward_input(c):
                # split B-input: the combined backward's dgrad chain at the
                # SAME tick — PEEKS the activation stash (masks + logits;
                # the B-weight frees it later) and stashes the per-slot
                # effective output-grads for the deferred wgrad
                Ws, bs, active, relu, residual, head_mask = chunk_params()
                sp = row["sp"][stage]
                g0 = ops.softmax_mse_head_grad(
                    _unstash(c["z"], sp), _microbatch(y, mb_r), B_global,
                    valid_mask=head_mask[None, :],
                )
                g_in = incoming_grad(c, g0)
                masks_r = tuple(_unstash(buf, sp) for buf in c["masks"])
                if tp_n > 1:
                    dx, g_effs = _stage_bwd_input_tp(
                        Ws, active, relu, dims, masks_r, g_in, precision,
                        tp_idx, tp_n, act=act, residual=residual,
                    )
                else:
                    dx, g_effs = _stage_bwd_input(
                        Ws, active, relu, dims, masks_r, g_in, precision,
                        act=act, residual=residual,
                    )
                c = dict(c)
                gw = row["gw"][stage]
                c["gstash"] = tuple(
                    _stash(buf, gw, val.T) for buf, val in zip(c["gstash"], g_effs)
                )
                return c, zero_fwd(), payload_of(row["sb"][stage], dx)

            def backward_weight(c):
                # split B-weight: wgrads from the two stashes, accumulated
                # in lowering-enforced B-input order (bit-identical fp sums
                # vs the combined schedule); frees both stash slots by
                # overwrite-on-reuse — no messages in or out. Flags only:
                # wgrad never touches weights, so ZeRO-3 gathers nothing
                active, _, _, _ = chunk_flags()
                sr = row["sr"][stage]
                gr = row["gr"][stage]
                xs_r = tuple(_unstash(buf, sr) for buf in c["xs"])
                geff_r = tuple(_unstash(buf, gr).T for buf in c["gstash"])
                if tp_n > 1:
                    gW_d, gb_d = _stage_bwd_weight_tp(
                        active, dims, xs_r, geff_r, precision, tp_idx, tp_n
                    )
                else:
                    gW_d, gb_d = _stage_bwd_weight(
                        active, dims, xs_r, geff_r, precision
                    )
                c = accumulate(dict(c), gW_d, gb_d)
                return c, zero_fwd(), zero_bwd

            # branch order is the op-code encoding: OP_NOOP=0, OP_FWD=1,
            # OP_BWD=2 (B-input when split), OP_BWD_W=3, OP_RECOMPUTE=4
            assert (OP_FWD, OP_BWD, OP_BWD_W, OP_RECOMPUTE) == (1, 2, 3, 4)
            if training and split:
                branches = [noop, forward, backward_input, backward_weight]
            else:
                branches = [noop, forward] + ([backward] if training else [noop])
            if training and rec:
                # recompute programs may not use OP_BWD_W without split, but
                # the switch is indexed by op code, so pad to position 4
                while len(branches) < OP_RECOMPUTE:
                    branches.append(noop)
                branches.append(recompute)
            carry, fwd_out, bwd_out = lax.switch(opv, branches, carry)

            # collectives outside the switch, uniform ACROSS DEVICES and not
            # across ticks: a direction's ppermute and its mailbox write run
            # only in the ticks in which the tick table has a payload due in
            # that direction (row["rlf"]/row["rlb"], read without [stage]).
            # A skipped relay would have carried zeros into a trash slot no
            # branch reads, and it would have held the stages to each other
            # for a tick in which neither has anything for the other.
            def relay(mail, payload, slot, perm):
                with scope("relay"):
                    incoming = lax.ppermute(payload, "pp", perm)
                with scope("mail"):
                    return mail.at[slot].set(incoming)

            for mail, out, perm, due, slots in (
                ("fwd_mail", fwd_out, fwd_perm, row["rlf"], row["inf"]),
                ("bwd_mail", bwd_out, bwd_perm, row["rlb"], row["inb"]),
            ):
                if perm:
                    carry[mail] = lax.cond(
                        due,
                        partial(relay, perm=perm),
                        lambda mail, payload, slot: mail,
                        carry[mail],
                        out,
                        slots[stage],
                    )
            return carry, None

        # tick_unroll amortizes the scan's per-tick loop overhead (each tick
        # body is one small stage compute + the relays it has due); numerics
        # identical
        carry, _ = lax.scan(tick, carry, tabs, unroll=tick_unroll)

        if not training:
            preds = carry["preds"][:M].swapaxes(1, 2).reshape(M * mb_sz, D_out)
            # only head-stage ticks ever wrote predictions (zeros elsewhere);
            # broadcast them over pp
            with scope("sync/pp"):
                return lax.psum(preds, "pp")

        # loss was only accumulated on head-stage ticks (zero elsewhere)
        with scope("sync/dp"):
            loss = lax.psum(carry["loss"], "dp")
        with scope("sync/pp"):
            loss = lax.pmax(loss, "pp")  # replicate scalar across devices

        if zero >= 2:
            # ZeRO-2/3 tail: the dp-summed gradient lives as this rank's
            # block-cyclic (csz3,) shard, accumulated per tick.
            gsh = carry["gz"]
            if with_grad_norm:
                # shards partition the dp-summed gradient across every
                # sharded axis; per-slot padding is exactly zero
                gnorm = jnp.sqrt(sum_z1(jnp.sum(gsh * gsh)))
            if clip_norm is not None:
                from shallowspeed_tpu.optimizer import clip_tree

                gsh = clip_tree(
                    gsh, clip_norm, sum_z1
                )
            if zero == 3:
                pch = pshard
            else:
                # this rank's param chunk: the same per-slot column deal,
                # sliced at the dp index on the deal VIEW — shard-sized
                # temporaries, no transposed slab
                d0 = lax.axis_index("dp")
                pch = jnp.concatenate(
                    [
                        lax.dynamic_slice(
                            _zb_deal_view(
                                p.reshape(s.rows, s.sz), dp_n, s.k
                            ),
                            (0, d0, 0),
                            (s.rows, 1, s.k),
                        ).reshape(-1)
                        for s, p in zip(
                            zb_slots, list(stacked["W"]) + list(stacked["b"])
                        )
                    ]
                )
            if z1_stateful:
                from shallowspeed_tpu.optimizer import join_state, split_state

                chunk_state = join_state(
                    opt,
                    {k: opt_state[k][0] for k, kd in z1_layout.items() if kd == "params"},
                    {k: opt_state[k] for k, kd in z1_layout.items() if kd == "scalar"},
                )
                new_ch, new_state = opt.apply(pch, gsh, chunk_state)
                nparts, nscalars = split_state(opt, new_state)
                opt_state = {k: v[None] for k, v in nparts.items()}
                opt_state.update(nscalars)
            else:
                new_ch, _ = opt.apply(pch, gsh, ())
            if zero == 3:
                # params stay at rest in the shard layout; the next step's
                # tick branches gather from the updated chunk
                new_stacked = {"P": new_ch[None]}
            else:
                # per-slot all-gather of the updated chunks rebuilds the
                # resident params: gathering on axis 1 of the (rows, 1, k)
                # segment lands ranks straight into the deal view's
                # (rows, dp, k) layout, so the inverse is a reshape +
                # padding slice — no transposed slab
                outW, outb = [], []
                for s in zb_slots:
                    seg = new_ch[s.off : s.off + s.rows * s.k].reshape(
                        s.rows, 1, s.k
                    )
                    with scope("sync/dp"):
                        mat = lax.all_gather(seg, "dp", axis=1, tiled=True)
                    full = mat.reshape(s.rows, dp_n * s.k)[:, : s.sz]
                    (outW if s.kind == "W" else outb).append(
                        full.reshape((s.rows,) + s.shape)
                    )
                new_stacked = {"W": tuple(outW), "b": tuple(outb)}
            outs = (new_stacked, opt_state, loss)
            if with_grad_norm:
                outs += (gnorm,)
            if with_step_stats:
                if zero == 3:
                    # chunk shards partition the params exactly (padding
                    # is exactly zero), so the shard norm IS the logical
                    # norm after the cross-axis psum
                    outs += (
                        jnp.sqrt(sum_z1(jnp.sum(new_ch * new_ch))),
                    )
                else:
                    from shallowspeed_tpu.optimizer import (
                        global_norm as gnorm_of,
                    )

                    outs += (
                        gnorm_of(
                            new_stacked, sum_pp
                        ),
                    )
            return outs

        if zero1:
            # ZeRO-1: reduce_scatter the flattened gradient over dp, update
            # this replica's param chunk with its state shard, all_gather
            flat, csz = z1_flat, z1_csz
            pad = csz * dp_n - flat
            gvec = jnp.concatenate(
                [g.reshape(-1) for g in carry["gW"]]
                + [g.reshape(-1) for g in carry["gb"]]
            )
            # the gradient sync: one flat reduce-scatter at the anchor
            gpad = jnp.pad(gvec, (0, pad))
            with scope("sync/dp"):
                gsh = lax.psum_scatter(
                    gpad, "dp", scatter_dimension=0, tiled=True
                )
            if with_grad_norm:
                # chunks partition the dp-summed gradient across every
                # sharded axis, so the pre-clip global norm is one
                # cross-axis reduction
                gnorm = jnp.sqrt(sum_z1(jnp.sum(gsh * gsh)))
            if with_digests:
                # per-(chunk, slot) grad squared sums from this replica's
                # flat chunk: static segment ids sliced at the chunk
                # offset, one psum over EVERY sharded axis (dp chunks +
                # pp rows + tp shards are all disjoint)
                ids = lax.dynamic_slice(
                    z1_seg_ids, (lax.axis_index("dp") * csz,), (csz,)
                )
                seg = jax.ops.segment_sum(
                    gsh * gsh, ids, num_segments=2 * L * V + 1
                )[: 2 * L * V]
                r0 = lax.axis_index("pp") * V
                dgsq_w = lax.psum(
                    lax.dynamic_update_slice(
                        jnp.zeros((S_, L), jnp.float32),
                        seg[: L * V].reshape(L, V).T,
                        (r0, 0),
                    ),
                    z1_axes,
                )
                dgsq_b = lax.psum(
                    lax.dynamic_update_slice(
                        jnp.zeros((S_, L), jnp.float32),
                        seg[L * V :].reshape(L, V).T,
                        (r0, 0),
                    ),
                    z1_axes,
                )
            if clip_norm is not None:
                from shallowspeed_tpu.optimizer import clip_tree

                # chunks partition the full summed gradient across the
                # sharded axes (dp, pp[, tp])
                gsh = clip_tree(
                    gsh, clip_norm, sum_z1
                )
            pvec = jnp.concatenate(
                [w.reshape(-1) for w in stacked["W"]]
                + [b.reshape(-1) for b in stacked["b"]]
            )
            pvec = jnp.pad(pvec, (0, pad))
            i0 = lax.axis_index("dp") * csz
            pch = lax.dynamic_slice(pvec, (i0,), (csz,))
            if z1_stateful:
                from shallowspeed_tpu.optimizer import join_state, split_state

                # per-device views: 'params' parts are (1, csz) blocks,
                # scalars are replicated 0-d
                chunk_state = join_state(
                    opt,
                    {k: opt_state[k][0] for k, kd in z1_layout.items() if kd == "params"},
                    {k: opt_state[k] for k, kd in z1_layout.items() if kd == "scalar"},
                )
                new_ch, new_state = opt.apply(pch, gsh, chunk_state)
                nparts, nscalars = split_state(opt, new_state)
                opt_state = {k: v[None] for k, v in nparts.items()}
                opt_state.update(nscalars)
            else:
                new_ch, _ = opt.apply(pch, gsh, ())
            with scope("sync/dp"):
                new_vec = lax.all_gather(new_ch, "dp", axis=0, tiled=True)[:flat]
            outW, outb, off = [], [], 0
            for o, i in w_dims:  # this device's LOCAL slot shapes
                n = V * o * i
                outW.append(new_vec[off : off + n].reshape(V, o, i))
                off += n
            for w in b_widths:
                n = V * w
                outb.append(new_vec[off : off + n].reshape(V, w))
                off += n
            new_stacked = {"W": tuple(outW), "b": tuple(outb)}
            outs = (new_stacked, opt_state, loss)
            if with_grad_norm:
                outs += (gnorm,)
            if with_step_stats:
                from shallowspeed_tpu.optimizer import global_norm as gnorm_of

                # post-update param norm: padded entries are exactly zero,
                # so the pp-psum'd stacked norm IS the logical norm
                outs += (gnorm_of(new_stacked, sum_pp),)
            if with_digests:
                outs += (_digest_grids(new_stacked, dgsq_w, dgsq_b),)
            return outs

        # the BackwardGradAllReduce anchor (reference pipe.py:302-327): one
        # SUM-psum of the whole gradient pytree over dp per batch. The
        # clip-norm / grad-norm consumers below always read the POST-SYNC
        # tree.
        with scope("sync/dp"):
            gW = lax.psum(carry["gW"], "dp")
            gb = lax.psum(carry["gb"], "dp")
        grads = {"W": gW, "b": gb}  # (V, ...) leaves, mirroring the shards
        if with_grad_norm:
            from shallowspeed_tpu.optimizer import global_norm

            # each pp device holds its stages' full (dp-summed) gradient;
            # padded entries are exactly zero so this IS the logical norm
            gnorm = global_norm(grads, sum_pp)
        if with_digests:
            # post-sync PRE-clip per-block grad squared sums (the clip
            # below reassigns ``grads``)
            dgsq_w = _digest_scatter(_sq_col, grads["W"], jnp.float32, pp_axes)
            dgsq_b = _digest_scatter(_sq_col, grads["b"], jnp.float32, pp_axes)
        if clip_norm is not None:
            from shallowspeed_tpu.optimizer import clip_tree

            # each pp device holds its stages' full (dp-summed) gradient;
            # the global norm needs the cross-stage total
            grads = clip_tree(grads, clip_norm, sum_pp)
        local = {"W": stacked["W"], "b": stacked["b"]}
        new_local, opt_state = opt.apply(local, grads, opt_state)
        outs = (new_local, opt_state, loss)
        if with_grad_norm:
            outs += (gnorm,)
        if with_step_stats:
            from shallowspeed_tpu.optimizer import global_norm as gnorm_of

            outs += (gnorm_of(new_local, sum_pp),)
        if with_digests:
            outs += (_digest_grids(new_local, dgsq_w, dgsq_b),)
        return outs

    pp = P("pp")
    dp_spec = P("dp")
    flags_specs = {"active": pp, "relu": pp, "residual": pp, "head_mask": pp}
    if zero == 3:
        # ZeRO-3 params at rest: one (pp*tp, dp*csz3) block-cyclic array,
        # rows per (pp, tp) device, column-chunk per dp rank — the same
        # spec the sharded optimizer state rides
        stacked_specs = {"P": zero1_part_spec(tp_n)}
    else:
        stacked_specs = stacked_param_specs(tp_n, L)

    if training:
        if zero >= 1:
            # ZeRO-1/2/3 state: one (pp[*tp], dp*chunk) array per 'params'
            # part (row per (pp, tp) device, column-chunk per dp replica)
            # + replicated scalars; () for stateless optimizers
            state_specs = (
                {
                    k: (zero1_part_spec(tp_n) if kd == "params" else P())
                    for k, kd in z1_layout.items()
                }
                if z1_stateful
                else ()
            )
        elif tp_n == 1:
            # optimizer-state specs mirror the state's pytree: stage-axis
            # sharded like the params it tracks (SGD's state is the empty
            # tuple)
            stacked_struct = {
                "W": tuple(
                    jax.ShapeDtypeStruct((S_, o, i), jnp.float32) for o, i in dims
                ),
                "b": tuple(
                    jax.ShapeDtypeStruct((S_, o), jnp.float32) for o, _ in dims
                ),
            }
            state_struct = jax.eval_shape(opt.init, stacked_struct)
            # stage-stacked state leaves (leading axis S, like the params
            # they track) shard over pp; anything else (scalar step counts
            # etc.) is replicated
            state_specs = jax.tree.map(
                lambda leaf: pp if leaf.ndim > 0 and leaf.shape[0] == S_ else P(),
                state_struct,
            )
        else:
            # tp > 1: state parts must mirror the params EXACTLY (the
            # state_layout protocol — same requirement zero1 enforces), so
            # each part takes the params' per-slot column/row shards and
            # scalars replicate
            from shallowspeed_tpu.optimizer import join_state, split_state

            stacked_struct = {
                "W": tuple(
                    jax.ShapeDtypeStruct((S_, o, i), jnp.float32) for o, i in dims
                ),
                "b": tuple(
                    jax.ShapeDtypeStruct((S_, o), jnp.float32) for o, _ in dims
                ),
            }
            state_struct = jax.eval_shape(opt.init, stacked_struct)
            parts, scalars = split_state(opt, state_struct)
            state_specs = join_state(
                opt,
                {k: stacked_specs for k in parts},
                {k: P() for k in scalars},
            )

        out_specs = (stacked_specs, state_specs, P())
        if with_grad_norm:
            out_specs = out_specs + (P(),)  # replicated pre-clip grad norm
        if with_step_stats:
            out_specs = out_specs + (P(),)  # replicated post-update param norm
        if with_digests:
            # the psum'd digest grids are replicated (S, L) matrices
            out_specs = out_specs + (
                {
                    k: P()
                    for k in (
                        "crc_w", "crc_b", "pnorm_w", "pnorm_b",
                        "gnorm_w", "gnorm_b",
                    )
                },
            )
        smapped = shard_map(
            per_device,
            mesh=mesh,
            in_specs=(stacked_specs, flags_specs, state_specs, dp_spec, dp_spec),
            out_specs=out_specs,
            check_vma=False,
        )

        def step_impl(stacked, flags, opt_state, x, y):
            with scope("batch"):
                x, y = _fit(x, D_in), _fit(y, D_out)
            return smapped(stacked, flags, opt_state, x, y)

        if jit:
            return jax.jit(step_impl, donate_argnums=(0, 2))
        return step_impl

    smapped = shard_map(
        lambda stacked, flags, x: per_device(stacked, flags, (), x, None),
        mesh=mesh,
        in_specs=(stacked_specs, flags_specs, dp_spec),
        out_specs=P("dp"),
        check_vma=False,
    )

    def eval_impl(stacked, flags, x):
        with scope("batch"):
            x = _fit(x, D_in)
        return smapped(stacked, flags, x)

    return jax.jit(eval_impl) if jit else eval_impl


def make_pipeline_epoch(
    mesh,
    spec,
    prog,
    mubatch_size,
    opt,
    precision=ops.DEFAULT_PRECISION,
    unroll=1,
    tick_unroll=1,
    zero1=False,
    zero=None,
    clip_norm=None,
    kernel_backend="xla",
    with_grad_norm=False,
    with_step_stats=False,
    with_digests=False,
):
    """Scan the pipeline train step over all batches of an epoch: one XLA
    program per epoch. X: (num_batches, global_batch, in_dim), batch axis
    sharded over dp. ``epoch(stacked, flags, opt_state, X, Y) -> (stacked,
    opt_state, mean_loss)``. ``unroll``/``tick_unroll``: lax.scan unroll
    factors for the batch loop / the per-tick loop (throughput knobs,
    identical numerics); ``zero1`` shards the optimizer update over dp;
    ``clip_norm`` clips the global gradient norm before each update;
    ``kernel_backend`` selects the per-slot compute unit (see
    make_pipeline_step); ``with_grad_norm`` appends a telemetry aux dict
    ``{"grad_norm": mean pre-clip global grad norm}`` as a fourth output;
    ``with_step_stats`` adds per-step ``step_loss``/``step_grad_norm``/
    ``step_param_norm`` vectors to that aux (both mirror
    trainer.make_train_epoch's aux, so TrainingSession records the same
    scalars on every layout); ``with_digests`` adds the per-step stacked
    digest grids under the aux's ``"digests"`` key (each leaf
    ``(num_batches, S, L)`` — see make_pipeline_step's digest contract);
    ``zero`` selects the full dp-axis ZeRO stage {0..3} (supersedes the
    ``zero1`` boolean; see make_pipeline_step — at stage 3 ``stacked`` is
    the ``{"P"}`` shard layout throughout the epoch)."""
    step = make_pipeline_step(
        mesh, spec, prog, mubatch_size, opt, precision, jit=False,
        tick_unroll=tick_unroll, zero1=zero1, zero=zero, clip_norm=clip_norm,
        kernel_backend=kernel_backend, with_grad_norm=with_grad_norm,
        with_step_stats=with_step_stats, with_digests=with_digests,
    )
    return jax.jit(
        _make_pipeline_epoch_core(
            step, unroll, with_grad_norm, with_step_stats, with_digests
        ),
        donate_argnums=(0, 2),
    )


def _make_pipeline_epoch_core(
    step, unroll, with_grad_norm=False, with_step_stats=False,
    with_digests=False,
):
    """The one batch-scan epoch body shared by make_pipeline_epoch and
    make_pipeline_run: ``core(stacked, flags, opt_state, X, Y) ->
    (stacked, opt_state, mean_loss)`` — plus an aux dict when instrumented
    (``grad_norm`` mean under ``with_grad_norm``; stacked per-step
    ``step_loss``/``step_grad_norm``/``step_param_norm`` vectors under
    ``with_step_stats``, as ordinary scan ys). One scan body serves every
    arity: the grad-norm slot always rides the carry (zero when the aux is
    off) and XLA dead-code-eliminates it from the uninstrumented program."""
    track_gn = with_grad_norm or with_step_stats

    def epoch_core(stacked, flags, opt_state, X, Y):
        def body(carry, xy):
            stacked, opt_state, loss_sum, gn_sum = carry
            out = step(stacked, flags, opt_state, xy[0], xy[1])
            stacked, opt_state, loss = out[0], out[1], out[2]
            gn = out[3] if track_gn else jnp.zeros(())
            carry = (stacked, opt_state, loss_sum + loss, gn_sum + gn)
            ys = ()
            if with_step_stats:
                ys += (loss, gn, out[4])
            if with_digests:
                ys += (out[-1],)  # the digest dict rides last (see step)
            return carry, (ys if ys else None)

        (stacked, opt_state, loss_sum, gn_sum), ys = lax.scan(
            body,
            (stacked, opt_state, jnp.zeros(()), jnp.zeros(())),
            (X, Y),
            unroll=unroll,
        )
        nb = X.shape[0]
        if not (with_grad_norm or with_step_stats or with_digests):
            return stacked, opt_state, loss_sum / nb
        aux = {}
        if with_grad_norm:
            aux["grad_norm"] = gn_sum / nb
        if with_step_stats:
            aux["step_loss"], aux["step_grad_norm"], aux["step_param_norm"] = (
                ys[0], ys[1], ys[2]
            )
        if with_digests:
            aux["digests"] = ys[-1]
        return stacked, opt_state, loss_sum / nb, aux

    return epoch_core


def make_pipeline_run(
    mesh,
    spec,
    prog,
    mubatch_size,
    opt,
    precision=ops.DEFAULT_PRECISION,
    unroll=1,
    tick_unroll=1,
    zero1=False,
    zero=None,
    clip_norm=None,
    eval_prog=None,
    eval_mubatch_size=None,
    kernel_backend="xla",
    with_grad_norm=False,
):
    """Epochs-outer scan around the pipeline epoch: the whole multi-epoch run
    as ONE XLA program over the mesh (the pipeline counterpart of
    trainer.make_train_run — zero host round-trips for the full run).

    Without eval: ``run(stacked, flags, opt_state, X, Y, n_epochs) ->
    (stacked, opt_state, losses[n_epochs])``.

    With ``eval_prog`` (an InferenceSchedule TickProgram lowered for the
    padded validation row count): ``run(stacked, flags, opt_state, X, Y,
    vx_padded, vy_labels, n_epochs) -> (stacked, opt_state, losses, accs)``
    where the full-split argmax accuracy is computed on-device after each
    epoch (vy_labels: (n_val,) int labels, unpadded — the static slice
    drops the padded rows).

    ``with_grad_norm``: telemetry aux, mirroring trainer.make_train_run's —
    one EXTRA trailing output, an aux dict whose ``"grad_norm"`` is the
    (n_epochs,) vector of per-epoch mean pre-clip global gradient norms
    (ordinary scan outputs, so the run stays one fused program; this closes
    the mesh-fused-run gap docs/observability.md used to document).

    ``n_epochs`` is static (one compile per value).
    """
    if zero is not None and int(zero) == 3:
        raise ValueError(
            "the fused multi-epoch run cannot shard params at rest: its "
            "eval step consumes the full stacked layout every epoch — "
            "use --zero 3 without --fused-run (per-epoch dispatch)"
        )
    step = make_pipeline_step(
        mesh, spec, prog, mubatch_size, opt, precision, jit=False,
        tick_unroll=tick_unroll, zero1=zero1, zero=zero, clip_norm=clip_norm,
        kernel_backend=kernel_backend, with_grad_norm=with_grad_norm,
    )
    eval_step = None
    if eval_prog is not None:
        eval_step = make_pipeline_step(
            mesh, spec, eval_prog, eval_mubatch_size, precision=precision,
            jit=False, kernel_backend=kernel_backend,
        )
    out_dim = spec.out_dim
    epoch_core = _make_pipeline_epoch_core(step, unroll, with_grad_norm)

    def run_epoch(stacked, flags, opt_state, X, Y):
        """Uniform (stacked, opt_state, loss, gnorm) view of the epoch core
        (gnorm 0 when the aux is off — dropped again before returning)."""
        if with_grad_norm:
            stacked, opt_state, mean_loss, aux = epoch_core(
                stacked, flags, opt_state, X, Y
            )
            return stacked, opt_state, mean_loss, aux["grad_norm"]
        stacked, opt_state, mean_loss = epoch_core(stacked, flags, opt_state, X, Y)
        return stacked, opt_state, mean_loss, jnp.zeros(())

    if eval_step is None:

        @partial(jax.jit, static_argnums=(5,), donate_argnums=(0, 2))
        def run(stacked, flags, opt_state, X, Y, n_epochs):
            def epoch_body(carry, _):
                stacked, opt_state = carry
                stacked, opt_state, mean_loss, gn = run_epoch(
                    stacked, flags, opt_state, X, Y
                )
                return (stacked, opt_state), (mean_loss, gn)

            (stacked, opt_state), (losses, gns) = lax.scan(
                epoch_body, (stacked, opt_state), None, length=n_epochs
            )
            if with_grad_norm:
                return stacked, opt_state, losses, {"grad_norm": gns}
            return stacked, opt_state, losses

        return run

    @partial(jax.jit, static_argnums=(7,), donate_argnums=(0, 2))
    def run(stacked, flags, opt_state, X, Y, vx_padded, vy_labels, n_epochs):
        n_val = vy_labels.shape[0]

        def epoch_body(carry, _):
            stacked, opt_state = carry
            stacked, opt_state, mean_loss, gn = run_epoch(
                stacked, flags, opt_state, X, Y
            )
            preds = eval_step(stacked, flags, vx_padded)[:n_val, :out_dim]
            acc = jnp.mean((jnp.argmax(preds, axis=1) == vy_labels).astype(jnp.float32))
            return (stacked, opt_state), (mean_loss, acc, gn)

        (stacked, opt_state), (losses, accs, gns) = lax.scan(
            epoch_body, (stacked, opt_state), None, length=n_epochs
        )
        if with_grad_norm:
            return stacked, opt_state, losses, accs, {"grad_norm": gns}
        return stacked, opt_state, losses, accs

    return run
