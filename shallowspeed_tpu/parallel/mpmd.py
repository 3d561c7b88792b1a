"""MPMD per-stage pipeline runtime: async host dispatch + device relays.

The lockstep executor (parallel/executor.py) runs the whole dp x pp x tp
lattice as ONE SPMD program: every tick costs the maximum op across
stages, and pipeline bubbles are real ``lax.switch`` noop dispatches.
This module is the MPMD form of arXiv 2412.14374 (Scaling Deep Learning
Training with MPMD Pipeline Parallelism): one compiled program per STAGE
ROLE — a stage's forward, its backward (or split B-input / B-weight
halves), its optimizer update — dispatched asynchronously from the host,
with activations relayed stage-to-stage by device-to-device transfers
(``jax.device_put`` onto the next stage's sub-mesh) instead of
in-program ``ppermute`` shifts:

- **no noop dispatches**: bubble cells of the tick table simply never
  dispatch anything — the op-issue cost of a bubble is zero, not a
  ``lax.switch`` entry into a masked branch;
- **no lockstep barrier**: each stage's device queue advances at its own
  pace; JAX's async dispatch issues the whole batch's per-stage streams
  ahead of execution and the data dependencies (relay payloads, stash
  reads) are what order the devices, so unequal stages run unpadded in
  TIME (a short stage never waits for the longest stage's tick);
- **the simulator stays the spec**: the host scheduler is driven
  directly by the lowered tick tables (``TickProgram``) — the SAME
  artifact the lockstep executor scans — and
  ``analysis.progcheck.analyze_program`` (the tick-free happens-before
  proof PR 13 built for exactly this runtime) is the admission gate:
  a program whose tables were tampered with is refused BEFORE any stage
  program dispatches;
- **bitwise parity is the contract**: every per-slot expression is the
  executor's own (``_stage_fwd`` / ``_stage_bwd`` / the tp and split
  variants), the per-slot zero-padded widths are retained (a different
  contraction length would re-block the fp sums — docs/numerics.md),
  and gradient accumulation order per stage is the tick-table stream
  order, so MPMD epoch weights hash-equal the lockstep twin's. The
  "unpadded" win is the TICK dimension (no max-over-stages, no noop
  cells), not the slot widths.

Feature envelope: the runtime refuses (loudly, at construction) the
knobs whose lockstep implementations live in the fused program's tail —
``zero1``, ``clip_norm`` (cross-stage global
norm), the pallas kernel backend, and the fused-run/step-stats aux.
Those stay lockstep-only until a follow-up teaches the per-stage update
their math; ``TrainingSession(runtime=...)`` enforces the envelope.

Serving rides the same machinery: ``MpmdInferenceRunner`` streams
request slots through per-stage forward programs — slot k enters stage 0
while slot k-1 occupies stage 1 — so a response is no longer quantized
to the whole rung program's makespan (the tail-latency payoff measured
in MPMD_r01.json).
"""

import time

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax, shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from shallowspeed_tpu import ops
from shallowspeed_tpu.parallel import executor as E
from shallowspeed_tpu.parallel.lowering import (
    OP_BWD,
    OP_BWD_W,
    OP_FWD,
    OP_NOOP,
    OP_RECOMPUTE,
)
from shallowspeed_tpu.parallel.mesh import mesh_tp


# ---------------------------------------------------------------------------
# Stage sub-meshes and zero-copy stage views
# ---------------------------------------------------------------------------


def stage_submeshes(mesh: Mesh):
    """One (dp, tp) sub-mesh per pp device column. The full mesh's device
    array is (dp, pp, tp); stage s's sub-mesh is the devices at pp
    coordinate s — the SAME physical devices the lockstep program uses
    for that stage, so stage views are zero-copy buffer reinterpretation,
    never data movement."""
    devs = mesh.devices  # (dp, pp) or (dp, pp, tp)
    if devs.ndim == 2:  # tp == 1 meshes carry no tp axis (mesh.py)
        devs = devs[:, :, None]
    return [Mesh(devs[:, s, :], ("dp", "tp")) for s in range(devs.shape[1])]


def _drop_pp(spec):
    """A full-mesh PartitionSpec with the leading 'pp' factor removed:
    the stage view's sharding over the (dp, tp) sub-mesh. P('pp') ->
    P(); P('pp', 'tp', None) -> P(None, 'tp', None)."""
    parts = tuple(spec)
    if not parts:
        return P()
    assert parts[0] == "pp", f"stage-axis spec must lead with 'pp': {spec}"
    return P(None, *parts[1:])


def _view(arr, shape, ns, rows=None):
    """Zero-copy reinterpretation of ``arr``'s device buffers under a new
    (global shape, sharding): the stage-view primitive. Every target
    device must already hold exactly its shard of the new view — true by
    construction for stage rows of a P('pp', ...)-sharded stack. Arrays
    that are not yet mesh-placed (a fresh ``opt.init`` state before its
    first dispatch) fall back to one explicit reshard copy (``rows``
    slices the stage block first); after the first update the
    reassembled state is mesh-placed and the fast path takes over."""
    by_dev = {s.device: s.data for s in arr.addressable_shards}
    target = list(ns.mesh.devices.flat)
    if len(target) == 1:
        # singleton fast path: the stage's buffer IS the view — return
        # the single-device array itself so every downstream program
        # sees one consistent sharding type (SingleDeviceSharding, the
        # type plain-jit outputs carry)
        dev = target[0]
        if dev in by_dev and by_dev[dev].shape == shape:
            return by_dev[dev]
        return jax.device_put(arr if rows is None else arr[rows], dev)
    if all(d in by_dev for d in target) and all(
        by_dev[d].shape == ns.shard_shape(shape) for d in target
    ):
        return jax.make_array_from_single_device_arrays(
            shape, ns, [by_dev[d] for d in target]
        )
    src = arr if rows is None else arr[rows]
    return jax.device_put(src, ns)


def stage_param_view(stacked, s, submesh, tp, V):
    """Stage s's (V, ...) rows of the full stacked {"W", "b"} tree as
    sub-mesh arrays (zero-copy; Megatron tp shards preserved)."""
    L = len(stacked["W"])
    specs = E.stacked_param_specs(tp, L)
    rows = slice(s * V, (s + 1) * V)
    out = {}
    for k in ("W", "b"):
        leaves = []
        for arr, sp in zip(stacked[k], specs[k]):
            ns = NamedSharding(submesh, _drop_pp(sp))
            leaves.append(_view(arr, (V,) + arr.shape[1:], ns, rows=rows))
        out[k] = tuple(leaves)
    return out


def stage_flags_view(flags, s, submesh, V):
    """Stage s's flag rows (active/relu/residual/head_mask), replicated
    over the sub-mesh like the lockstep per-device view."""
    rows = slice(s * V, (s + 1) * V)
    return {
        k: _view(
            flags[k], (V,) + flags[k].shape[1:],
            NamedSharding(submesh, P()), rows=rows,
        )
        for k in ("active", "relu", "residual", "head_mask")
    }


def stage_state_view(opt, state, s, submesh, tp, V):
    """Stage s's optimizer-state view: 'params' parts mirror the param
    stage view, 'scalar' parts replicate; () for stateless state."""
    if isinstance(state, tuple) and state == ():
        return ()
    from shallowspeed_tpu.optimizer import join_state, split_state

    parts, scalars = split_state(opt, state)
    return join_state(
        opt,
        {k: stage_param_view(v, s, submesh, tp, V) for k, v in parts.items()},
        {
            k: _view(v, v.shape, NamedSharding(submesh, P()))
            for k, v in scalars.items()
        },
    )


def full_from_stage(stage_arrs, mesh, full_shape, full_spec):
    """Reassemble one full-mesh array from its P per-stage views (the
    inverse of ``_view``, zero-copy): collect every stage array's device
    buffers and reinterpret them under the full sharding."""
    shards = []
    for arr in stage_arrs:
        shards.extend(s.data for s in arr.addressable_shards)
    return jax.make_array_from_single_device_arrays(
        full_shape, NamedSharding(mesh, full_spec), shards
    )


def full_param_from_stage(stage_params, mesh, S, tp):
    """Per-stage {"W","b"} views -> the full stacked tree (zero-copy),
    with the session's canonical shardings (``stacked_param_specs``)."""
    L = len(stage_params[0]["W"])
    specs = E.stacked_param_specs(tp, L)
    out = {}
    for k in ("W", "b"):
        leaves = []
        for l in range(len(stage_params[0][k])):
            arrs = [sp[k][l] for sp in stage_params]
            shape = (S,) + arrs[0].shape[1:]
            leaves.append(full_from_stage(arrs, mesh, shape, specs[k][l]))
        out[k] = tuple(leaves)
    return out


def full_state_from_stage(opt, stage_states, mesh, S, tp):
    """Per-stage optimizer-state views -> the full-mesh state tree."""
    if stage_states[0] == ():
        return ()
    from shallowspeed_tpu.optimizer import join_state, split_state

    split = [split_state(opt, st) for st in stage_states]
    parts = {
        k: full_param_from_stage([p[k] for p, _ in split], mesh, S, tp)
        for k in split[0][0]
    }
    scalars = {
        k: full_from_stage([sc[k] for _, sc in split], mesh, (), P())
        for k in split[0][1]
    }
    return join_state(opt, parts, scalars)


# ---------------------------------------------------------------------------
# Per-stage census contracts (the audit satellite)
# ---------------------------------------------------------------------------

_NEVER = ["collective_permute", "all_to_all", "reduce_scatter", "all_gather"]


def expected_stage_comms(role, spec, dp, tp, sends=True):
    """The per-stage-program collective contract ``check_census`` style:
    relays left the program, so a ``collective_permute`` ANYWHERE in a
    stage program is a contract violation (the defining MPMD property);
    the only lawful all-reduces are the Megatron tp psums inside compute
    roles and the dp gradient/loss psum inside the update/loss roles.

    ``sends`` (backward roles): whether this program RETURNS its dx
    relay payload. A non-relaying backward (the first pipeline stage)
    never consumes the dgrad chain's final value, so XLA dead-code
    eliminates the LAST column slot's dx psum — the structural floor
    must not demand an op the compiler lawfully removed."""
    required, forbidden = [], list(_NEVER)
    axes = {}
    # the recompute roles run the SAME stage forward expression as "fwd"
    # (fwd_ns: the no-stash forward at the fwd tick; recompute: the
    # re-materializing forward at the backward tick), so their collective
    # contract is the forward's — the tp psum count doubles per (chunk,
    # microbatch) only because the forward runs twice
    if role in ("fwd", "fwd_ns", "recompute", "bwd", "bwd_in"):
        fwd_like = role in ("fwd", "fwd_ns", "recompute")
        if tp > 1:
            fwd_w, bwd_w = E.tp_allreduce_sites(spec, tp, training=True)
            sites = len(fwd_w) if fwd_like else len(bwd_w)
            if role in ("bwd", "bwd_in") and not sends:
                # slot 0's dx psum feeds only the (unreturned) relay
                sites -= 1
            if sites > 0:
                required.append("all_reduce")
                axes["tp"] = {
                    "kind": "all_reduce",
                    "sites_fwd": sites if fwd_like else 0,
                    "sites_bwd": 0 if fwd_like else sites,
                    "hlo_min_all_reduce_ops": sites,
                }
            # sites == 0: the one potential psum is dead code — whether
            # the backend actually elides it is its business, so the
            # kind is neither required nor forbidden
        else:
            forbidden.append("all_reduce")
    elif role == "bwd_w":
        # the deferred wgrads are collective-free at every tp degree
        forbidden.append("all_reduce")
    elif role in ("pack", "unpack", "state_pack", "state_unpack"):
        # pure data movement at the run boundary — no collective, ever
        forbidden.append("all_reduce")
    elif role in ("update", "loss_sync"):
        if dp > 1:
            required.append("all_reduce")
    elif role == "infer_fwd":
        if tp > 1:
            fwd_w, _ = E.tp_allreduce_sites(spec, tp, training=False)
            if fwd_w:
                required.append("all_reduce")
                axes["tp"] = {
                    "kind": "all_reduce",
                    "sites_fwd": len(fwd_w),
                    "sites_bwd": 0,
                    "hlo_min_all_reduce_ops": len(fwd_w),
                }
        else:
            forbidden.append("all_reduce")
    else:
        raise ValueError(f"unknown stage-program role {role!r}")
    return {
        "dp": int(dp),
        "tp": int(tp),
        "zero1": False,
        "inference": False,
        "mpmd_role": role,
        "required": required,
        "forbidden": forbidden,
        "axes": axes,
    }


# ---------------------------------------------------------------------------
# The tick-table-driven host plan
# ---------------------------------------------------------------------------


def stage_cells(prog):
    """The per-stage MPMD streams, read directly from the lowered tick
    tables (the simulator is the spec): a list over ticks of the ACTIVE
    cells only — noop cells produce nothing, which is the whole point.
    Each cell carries the static facts a dispatch needs; mailbox slot
    numbers are deliberately absent (host dataflow is keyed by
    (chunk, microbatch); the slot discipline was proven by progcheck)."""
    P_ = prog.num_stages
    out = []
    for t in range(prog.num_ticks):
        row = []
        for s in range(P_):
            op = int(prog.op[t, s])
            if op == OP_NOOP:
                continue
            row.append(
                dict(
                    s=s,
                    op=op,
                    mb=int(prog.mb[t, s]),
                    v=int(prog.chunk[t, s]) if prog.chunk is not None else 0,
                    load=bool(prog.load_in[t, s]),
                    head=bool(prog.is_head[t, s]),
                    send_fwd=bool(prog.send_fwd[t, s]),
                    send_bwd=bool(prog.send_bwd[t, s]),
                )
            )
        if row:
            out.append(row)
    return out


class _StagePrograms:
    """Lazily-built jitted per-stage programs for one (mesh, spec, prog)
    triple. Programs are keyed ``(stage, role, variant)``; ``resolve``
    (optional) intercepts compilation — the session points it at the
    per-stage audit, so every program is census/donation-verified before
    its first dispatch."""

    def __init__(self, mesh, spec, prog, mubatch_size, opt=None,
                 precision=ops.DEFAULT_PRECISION):
        self.mesh = mesh
        self.spec = spec
        self.prog = prog
        self.tp = mesh_tp(mesh)
        self.dp = mesh.shape["dp"]
        self.V = prog.num_chunks
        self.opt = opt
        self.precision = precision
        self.submeshes = stage_submeshes(mesh)
        # the activation family is STATIC (model.py): it picks which
        # per-slot expressions the stage programs trace, exactly like the
        # lockstep executor — and the mask stash dtype follows it (relu
        # stashes sign bits; the gelu family stashes the f32 grad
        # multiplier, docs/lowering.md)
        self.act = getattr(spec, "act", "relu")
        self.mask_dtype = jnp.bool_ if self.act == "relu" else jnp.float32
        self.rec = bool(getattr(prog, "recompute", False))
        # singleton-axis fast path: with dp == tp == 1 each stage's
        # sub-mesh is ONE device, every collective in the stage programs
        # is a 1-member group (bitwise identity), and shard_map buys
        # nothing but Python dispatch cost — so the programs compile as
        # plain jit over committed single-device arrays (the C++
        # fast-path dispatch, ~5x cheaper per call) and relays target
        # the device directly. Multi-member axes keep shard_map (the
        # psums are real).
        self.single = self.dp == 1 and self.tp == 1
        # packed mode rides the singleton path: per-program latency on
        # the XLA CPU client scales with BUFFER COUNT (measured ~535us
        # per chained link at ~55 buffers vs ~145us at 4, same bytes),
        # so the per-stage params/grads/stashes travel as ONE flat
        # buffer each and the programs slice static views out (exact:
        # reshape/slice reproduce the leaves bit for bit, and the
        # optimizer math is elementwise — the same flat-vector trick
        # ZeRO-1's chunked update already pins bitwise). Multi-member
        # axes keep the per-leaf representation (their shard_map specs
        # are per-leaf, and dispatch cost is not their binding tax).
        self.packed = self.single
        self.stage_device = [m.devices.flat[0] for m in self.submeshes]
        self.dims = E.slot_shapes(spec, self.tp)
        self.L = len(self.dims)
        self.D_in = self.dims[0][1]
        self.D_out = self.dims[-1][0]
        self.W_rel = E.relay_width(spec)
        self.mb_sz = mubatch_size  # per-dp-replica rows per microbatch
        self.B_global = spec.global_batch_size
        self._fns = {}
        # per-slot stash specs over the (dp, tp) sub-mesh, in the exact
        # representation the lockstep carry uses (executor.tp_local_dims):
        # a column slot's input is full-width (tp-replicated), a row
        # slot's is the rank shard; masks mirror inversely
        if self.tp == 1:
            self._xs_specs = (P("dp"),) * self.L
            self._mask_specs = (P("dp"),) * self.L
        else:
            self._xs_specs = tuple(
                P("dp") if l % 2 == 0 else P("dp", "tp")
                for l in range(self.L)
            )
            self._mask_specs = tuple(
                P("dp", "tp") if l % 2 == 0 else P("dp")
                for l in range(self.L)
            )
        self._param_specs = {
            k: tuple(_drop_pp(sp) for sp in v)
            for k, v in E.stacked_param_specs(self.tp, self.L).items()
        }
        self._flag_specs = {
            "active": P(), "relu": P(), "residual": P(), "head_mask": P(),
        }
        if opt is not None:
            from shallowspeed_tpu.optimizer import (
                is_stateless,
                join_state,
                split_state,
            )

            if is_stateless(opt):
                self._state_specs = ()
            else:
                struct = jax.eval_shape(
                    opt.init,
                    {
                        "W": tuple(
                            jax.ShapeDtypeStruct((self.V, o, i), jnp.float32)
                            for o, i in self.dims
                        ),
                        "b": tuple(
                            jax.ShapeDtypeStruct((self.V, o), jnp.float32)
                            for o, _ in self.dims
                        ),
                    },
                )
                parts, scalars = split_state(opt, struct)
                self._state_specs = join_state(
                    opt,
                    {k: self._param_specs for k in parts},
                    {k: P() for k in scalars},
                )

    # -- packed-representation helpers (traced; packed mode only) -----------

    @property
    def plen(self):
        """Flat length of one stage's packed {W, b} vector (the zero1
        leaf order: every W slot raveled, then every b slot)."""
        V = self.V
        return sum(V * o * i for o, i in self.dims) + sum(
            V * o for o, _ in self.dims
        )

    def _unpack_wb(self, pvec):
        """Static slice+reshape views of the packed vector — the exact
        leaves, bit for bit."""
        V = self.V
        Ws, bs, off = [], [], 0
        for o, i in self.dims:
            n = V * o * i
            Ws.append(pvec[off : off + n].reshape(V, o, i))
            off += n
        for o, _ in self.dims:
            n = V * o
            bs.append(pvec[off : off + n].reshape(V, o))
            off += n
        return Ws, bs

    def _chunk_params(self, stacked, v):
        """Chunk v's (Ws, bs) rows from either representation (static
        selection — value-identical to the lockstep dynamic pick)."""
        if self.packed:
            Ws, bs = self._unpack_wb(stacked)
        else:
            Ws, bs = stacked["W"], stacked["b"]
        return [w[v] for w in Ws], [b[v] for b in bs]

    def _acc(self, grads, v, gW_d, gb_d):
        """Accumulate one cell's per-slot gradient contributions — the
        lockstep ``.at[v].add`` per leaf, expressed against either
        representation (same elements added, others copied: bitwise)."""
        if not self.packed:
            gW, gb = grads
            return (
                tuple(a.at[v].add(d) for a, d in zip(gW, gW_d)),
                tuple(a.at[v].add(d) for a, d in zip(gb, gb_d)),
            )
        gvec, off = grads, 0
        V = self.V
        for d, (o, i) in zip(gW_d, self.dims):
            n = o * i
            gvec = gvec.at[off + v * n : off + (v + 1) * n].add(d.reshape(-1))
            off += V * n
        for d, (o, _) in zip(gb_d, self.dims):
            gvec = gvec.at[off + v * o : off + (v + 1) * o].add(d.reshape(-1))
            off += V * o
        return gvec

    def _stash_out(self, xs, masks):
        """The stash representation a forward returns: per-slot tuples
        (shard_map path — the specs are per-leaf) or ONE concatenated
        buffer per stash (packed path)."""
        if not self.packed:
            return xs, masks
        return (
            jnp.concatenate(xs, axis=1),
            jnp.concatenate(masks, axis=1),
        )

    def _split_stash(self, cat, widths):
        """Inverse of the packed concat: static column slices — the
        original per-slot tensors, bit for bit."""
        if not self.packed:
            return cat
        out, off = [], 0
        for w in widths:
            out.append(cat[:, off : off + w])
            off += w
        return tuple(out)

    @property
    def _xs_widths(self):
        _, _, xs_w, _ = E.tp_local_dims(self.dims, self.tp)
        return xs_w

    @property
    def _mask_widths(self):
        _, _, _, mask_w = E.tp_local_dims(self.dims, self.tp)
        return mask_w

    # -- builders -----------------------------------------------------------

    def _jit(self, s, per_device, in_specs, out_specs):
        if self.single:
            # one device per stage: plain jit over committed arrays (the
            # C++ fast-path dispatch); the per-device body is identical —
            # its singleton collectives were already elided by the
            # builders below, which is bitwise-exact (a 1-member psum is
            # the identity in the lockstep program too)
            return jax.jit(per_device)
        return jax.jit(
            shard_map(
                per_device,
                mesh=self.submeshes[s],
                in_specs=in_specs,
                out_specs=out_specs,
                check_vma=False,
            )
        )

    def _build_fwd(self, s, v, load, head, send, training, stash=True):
        """The stage forward. Training signatures (``mb`` is a traced
        index into the ONE per-batch device-resident x/y stack — value-
        identical to a static slice, and it keeps program count
        M-independent):

            load+head: (params, flags, x_full, y_full, mb, loss_acc)
            load:      (params, flags, x_full, mb)
            head:      (params, flags, x_in, y_full, mb, loss_acc)
            neither:   (params, flags, x_in)

        ``stash=False`` (the fwd tick of a recompute program) drops the
        stash outputs — the host keeps only the stage-INPUT handle and
        the matching recompute program re-materializes the residuals;
        the loss (head) and the relay payload are still produced here,
        so the traced per-element expressions are character-identical.

        Inference keeps the direct per-slot signature
        ``(params, flags, x_in)``."""
        tp, dims, prec = self.tp, self.dims, self.precision
        act = self.act
        W_rel, D_in, D_out, B = self.W_rel, self.D_in, self.D_out, self.B_global

        def per_device(*args):
            it = iter(args)
            stacked, flags = next(it), next(it)
            if training and load:
                x_full = next(it)
            else:
                x_in = next(it)
            if training and head:
                y_full = next(it)
            if training and (load or head):
                mb = next(it)
            if training and head:
                loss_acc = next(it)
            Ws, bs = self._chunk_params(stacked, v)
            active = flags["active"][v]
            relu = flags["relu"][v]
            residual = flags["residual"][v]
            head_mask = flags["head_mask"][v]
            if training and load:
                x = lax.dynamic_index_in_dim(x_full, mb, 0, keepdims=False)
            elif load:
                x = x_in
            else:
                x = E._fit(x_in, D_in)
            if tp > 1:
                tp_idx = lax.axis_index("tp")
                out, xs, masks = E._stage_fwd_tp(
                    Ws, bs, active, relu, dims, x, prec, tp_idx, tp,
                    act=act, residual=residual,
                )
            else:
                out, xs, masks = E._stage_fwd(
                    Ws, bs, active, relu, dims, x, prec,
                    act=act, residual=residual,
                )
            rets = []
            if send:
                rets.append(E._fit(out, W_rel))
            if training:
                if stash:
                    xs_o, masks_o = self._stash_out(xs, masks)
                    rets.append(xs_o)
                    rets.append(masks_o)
                if head:
                    y_mb = lax.dynamic_index_in_dim(
                        y_full, mb, 0, keepdims=False
                    )
                    p = ops.softmax(out, valid_mask=head_mask[None, :])
                    mb_loss = ops.mse_loss(p, y_mb, B)
                    if stash:
                        rets.append(out)  # the z stash (head-grad logits)
                    rets.append(loss_acc + mb_loss.reshape(1))
            elif head:
                rets.append(ops.softmax(out, valid_mask=head_mask[None, :]))
            return tuple(rets)

        in_specs = [self._param_specs, self._flag_specs]
        in_specs.append(P(None, "dp") if training and load else P("dp"))
        out_specs = []
        if send:
            out_specs.append(P("dp"))
        if training:
            if stash:
                out_specs.append(self._xs_specs)
                out_specs.append(self._mask_specs)
            if head:
                in_specs.append(P(None, "dp"))  # y_full
            if load or head:
                in_specs.append(P())  # mb index, replicated
            if head:
                in_specs.append(P("dp"))  # loss accumulator
                out_specs += [P("dp"), P("dp")] if stash else [P("dp")]
        elif head:
            out_specs.append(P("dp"))
        return self._jit(s, per_device, tuple(in_specs), tuple(out_specs))

    def _build_recompute(self, s, v, load, head):
        """The OP_RECOMPUTE stage program: re-run the stage forward from
        the kept INPUT (stage 0 reloads its microbatch from the device-
        resident batch stack — the HBM-reload exemption) and return the
        residual stashes the backward is about to consume. The forward
        expression is the shared builder's own (``_build_fwd`` traces
        the identical ``E._stage_fwd``/``_stage_fwd_tp`` call), so the
        stashes are bitwise the ones the stashed twin stored at the fwd
        tick. No relay (the output already traveled at the fwd tick) and
        no loss tally (counted once, at the fwd tick)."""
        tp, dims, prec = self.tp, self.dims, self.precision
        act = self.act
        D_in = self.D_in

        def per_device(*args):
            it = iter(args)
            stacked, flags = next(it), next(it)
            if load:
                x_full, mb = next(it), next(it)
                x = lax.dynamic_index_in_dim(x_full, mb, 0, keepdims=False)
            else:
                x = E._fit(next(it), D_in)
            Ws, bs = self._chunk_params(stacked, v)
            active = flags["active"][v]
            relu = flags["relu"][v]
            residual = flags["residual"][v]
            if tp > 1:
                out, xs, masks = E._stage_fwd_tp(
                    Ws, bs, active, relu, dims, x, prec,
                    lax.axis_index("tp"), tp, act=act, residual=residual,
                )
            else:
                out, xs, masks = E._stage_fwd(
                    Ws, bs, active, relu, dims, x, prec,
                    act=act, residual=residual,
                )
            xs_o, masks_o = self._stash_out(xs, masks)
            rets = [xs_o, masks_o]
            if head:
                rets.append(out)  # the z stash (head-grad logits)
            return tuple(rets)

        in_specs = [self._param_specs, self._flag_specs]
        if load:
            in_specs += [P(None, "dp"), P()]  # x stack, mb index
        else:
            in_specs.append(P("dp"))  # the kept stage-input handle
        out_specs = [self._xs_specs, self._mask_specs]
        if head:
            out_specs.append(P("dp"))
        return self._jit(s, per_device, tuple(in_specs), tuple(out_specs))

    def _build_bwd(self, s, v, head, send, split_input):
        """The combined backward, or — ``split_input=True`` — the split
        B-input half (dgrad chain + g_eff stash instead of the wgrad
        accumulation)."""
        tp, dims, prec = self.tp, self.dims, self.precision
        act = self.act
        W_rel, D_out, B = self.W_rel, self.D_out, self.B_global
        Wb = max(D_out, W_rel)

        def per_device(*args):
            if head:
                if split_input:
                    stacked, flags, masks, z, y_full, mb = args
                else:
                    stacked, flags, xs, masks, z, y_full, mb, grads = args
            else:
                if split_input:
                    stacked, flags, masks, g_relay = args
                else:
                    stacked, flags, xs, masks, g_relay, grads = args
            Ws, _ = self._chunk_params(stacked, v)
            active = flags["active"][v]
            relu = flags["relu"][v]
            residual = flags["residual"][v]
            head_mask = flags["head_mask"][v]
            masks = self._split_stash(masks, self._mask_widths)
            if not split_input:
                xs = self._split_stash(xs, self._xs_widths)
            if head:
                y_mb = lax.dynamic_index_in_dim(y_full, mb, 0, keepdims=False)
                g0 = ops.softmax_mse_head_grad(
                    z, y_mb, B, valid_mask=head_mask[None, :]
                )
                g_in = E._fit(g0, Wb)
            else:
                g_in = E._fit(g_relay, Wb)
            rets = []
            if split_input:
                if tp > 1:
                    dx, g_effs = E._stage_bwd_input_tp(
                        Ws, active, relu, dims, masks, g_in, prec,
                        lax.axis_index("tp"), tp,
                        act=act, residual=residual,
                    )
                else:
                    dx, g_effs = E._stage_bwd_input(
                        Ws, active, relu, dims, masks, g_in, prec,
                        act=act, residual=residual,
                    )
                if send:
                    rets.append(E._fit(dx, W_rel))
                if self.packed:
                    rets.append(jnp.concatenate(g_effs, axis=1))
                else:
                    rets.append(g_effs)
                return tuple(rets)
            if tp > 1:
                dx, gW_d, gb_d = E._stage_bwd_tp(
                    Ws, active, relu, dims, xs, masks, g_in, prec,
                    lax.axis_index("tp"), tp, act=act, residual=residual,
                )
            else:
                dx, gW_d, gb_d = E._stage_bwd(
                    Ws, active, relu, dims, xs, masks, g_in, prec,
                    act=act, residual=residual,
                )
            if send:
                rets.append(E._fit(dx, W_rel))
            rets.append(self._acc(grads, v, gW_d, gb_d))
            return tuple(rets)

        in_specs = [self._param_specs, self._flag_specs]
        if not split_input:
            in_specs.append(self._xs_specs)
        in_specs.append(self._mask_specs)
        if head:
            in_specs += [P("dp"), P(None, "dp"), P()]  # z stash, y stack, mb
        else:
            in_specs.append(P("dp"))  # relayed output-grad
        out_specs = [P("dp")] if send else []
        if split_input:
            out_specs.append(self._mask_specs)  # g_effs ride the mask repr
        else:
            grad_specs = (self._param_specs["W"], self._param_specs["b"])
            in_specs.append(grad_specs)
            out_specs.append(grad_specs)
        return self._jit(s, per_device, tuple(in_specs), tuple(out_specs))

    def _build_bwd_w(self, s, v):
        """The deferred B-weight half: wgrads from the two stashes,
        accumulated in tick-table (= B-input = combined) order."""
        tp, dims, prec = self.tp, self.dims, self.precision

        def per_device(flags, xs, g_effs, grads):
            active = flags["active"][v]
            xs = self._split_stash(xs, self._xs_widths)
            g_effs = self._split_stash(g_effs, self._mask_widths)
            if tp > 1:
                gW_d, gb_d = E._stage_bwd_weight_tp(
                    active, dims, xs, g_effs, prec, lax.axis_index("tp"), tp
                )
            else:
                gW_d, gb_d = E._stage_bwd_weight(active, dims, xs, g_effs, prec)
            return self._acc(grads, v, gW_d, gb_d)

        in_specs = (
            self._flag_specs, self._xs_specs, self._mask_specs,
            (self._param_specs["W"], self._param_specs["b"]),
        )
        out_specs = (self._param_specs["W"], self._param_specs["b"])
        return self._jit(s, per_device, in_specs, out_specs)

    def _build_update(self, s):
        """The per-stage optimizer tail: dp gradient psum (the lockstep
        anchor, per stage) + the on-device update of this stage's rows.
        On the singleton fast path the 1-member psum is elided (bitwise
        identity — the lockstep program's dp=1 psum is one too)."""
        opt = self.opt
        packed = self.packed

        def per_device(stacked, grads, state):
            if packed:
                # the flat-vector update: elementwise optimizer math on
                # the packed params/grads/state mirrors — per-element
                # expressions identical to the per-leaf apply (the
                # zero1 chunk update's established bitwise property)
                new_p, new_state = opt.apply(stacked, grads, state)
                return new_p, new_state
            gW, gb = grads
            g = {"W": lax.psum(gW, "dp"), "b": lax.psum(gb, "dp")}
            local = {"W": stacked["W"], "b": stacked["b"]}
            new_local, new_state = opt.apply(local, g, state)
            return new_local, new_state

        in_specs = (
            self._param_specs,
            (self._param_specs["W"], self._param_specs["b"]),
            self._state_specs,
        )
        out_specs = (self._param_specs, self._state_specs)
        return self._jit(s, per_device, in_specs, out_specs)

    def _build_loss_sync(self, s):
        single = self.single

        def per_device(loss_acc):
            if single:
                return loss_acc[0]
            return lax.psum(loss_acc[0], "dp")

        return self._jit(s, per_device, (P("dp"),), P())

    # -- packed-mode boundary programs (one dispatch per stage per run) -----

    def _build_pack(self, s):
        def per_device(stacked):
            return jnp.concatenate(
                [w.reshape(-1) for w in stacked["W"]]
                + [b.reshape(-1) for b in stacked["b"]]
            )

        return jax.jit(per_device)

    def _build_unpack(self, s):
        def per_device(pvec):
            Ws, bs = self._unpack_wb(pvec)
            return {"W": tuple(Ws), "b": tuple(bs)}

        return jax.jit(per_device)

    def _build_state_pack(self, s):
        opt = self.opt

        def per_device(state):
            from shallowspeed_tpu.optimizer import join_state, split_state

            parts, scalars = split_state(opt, state)
            packed = {
                k: jnp.concatenate(
                    [w.reshape(-1) for w in p["W"]]
                    + [b.reshape(-1) for b in p["b"]]
                )
                for k, p in parts.items()
            }
            return join_state(opt, packed, scalars)

        return jax.jit(per_device)

    def _build_state_unpack(self, s):
        opt = self.opt

        def per_device(state):
            from shallowspeed_tpu.optimizer import join_state, split_state

            parts, scalars = split_state(opt, state)
            unpacked = {}
            for k, vec in parts.items():
                Ws, bs = self._unpack_wb(vec)
                unpacked[k] = {"W": tuple(Ws), "b": tuple(bs)}
            return join_state(opt, unpacked, scalars)

        return jax.jit(per_device)

    # -- lookup -------------------------------------------------------------

    def get(self, s, role, variant=()):
        key = (s, role, variant)
        fn = self._fns.get(key)
        if fn is not None:
            return fn
        if role == "fwd":
            v, load, head, send = variant
            fn = self._build_fwd(s, v, load, head, send, training=True)
        elif role == "fwd_ns":
            v, load, head, send = variant
            fn = self._build_fwd(
                s, v, load, head, send, training=True, stash=False
            )
        elif role == "recompute":
            v, load, head = variant
            fn = self._build_recompute(s, v, load, head)
        elif role == "infer_fwd":
            v, load, head, send = variant
            fn = self._build_fwd(s, v, load, head, send, training=False)
        elif role == "bwd":
            v, head, send = variant
            fn = self._build_bwd(s, v, head, send, split_input=False)
        elif role == "bwd_in":
            v, head, send = variant
            fn = self._build_bwd(s, v, head, send, split_input=True)
        elif role == "bwd_w":
            (v,) = variant
            fn = self._build_bwd_w(s, v)
        elif role == "update":
            fn = self._build_update(s)
        elif role == "loss_sync":
            fn = self._build_loss_sync(s)
        elif role == "pack":
            fn = self._build_pack(s)
        elif role == "unpack":
            fn = self._build_unpack(s)
        elif role == "state_pack":
            fn = self._build_state_pack(s)
        elif role == "state_unpack":
            fn = self._build_state_unpack(s)
        else:
            raise ValueError(f"unknown stage-program role {role!r}")
        self._fns[key] = fn
        return fn

    def label(self, s, role, variant=()):
        """Audit label for one stage program. The inference program
        set gets its own namespace — its pack programs are content-
        identical to the trainer's, but the session's audit dedup is
        label-keyed, and a shared label would skip the second runner's
        resolve-and-swap (leaving an un-audited jit wrapper on its
        dispatch path)."""
        kind = "mpmd" if self.prog.is_training else "mpmd_inf"
        tag = "".join(str(int(x)) for x in variant)
        return f"{kind}_s{s}_{role}" + (f"_{tag}" if tag else "")


def _resolve_program(programs, s, role, variant, args, expected, resolve):
    """The one resolve-and-swap step both runners' warm passes share:
    skip programs already swapped onto an executable, otherwise hand the
    jit wrapper to the session hook (audit) and install whatever it
    returns. Returns True when the hook ran."""
    key = (s, role, variant)
    fn = programs._fns.get(key)
    if fn is not None and not hasattr(fn, "lower"):
        return False  # already an executable
    compiled = resolve(
        programs.label(s, role, variant), role,
        programs.get(s, role, variant), args, expected,
    )
    if compiled is not None:
        programs._fns[key] = compiled
    return True


class MpmdTrainRunner:
    """The training-side MPMD runtime: per-stage programs + the
    tick-table-driven async host scheduler.

    ``run(stacked, flags, opt_state, X, Y)`` has the lockstep epoch
    program's exact signature and state contract — full-mesh stacked
    arrays in, full-mesh stacked arrays out (reassembled zero-copy from
    the per-stage views), so checkpoints, ``params()``, hot reloads and
    the serving engine are runtime-independent by construction.

    Construction runs the admission gate: ``analyze_program`` must prove
    the tick tables deadlock-free / send-recv-matched BEFORE any stage
    program is built or dispatched (``ProgramAnalysisError`` otherwise).
    """

    def __init__(self, mesh, spec, prog, mubatch_size, opt,
                 precision=ops.DEFAULT_PRECISION,
                 tracer=None, trace_batches=1):
        from shallowspeed_tpu.analysis import analyze_program

        # the admission gate: refuse a tampered/mislowered table BEFORE
        # anything compiles or dispatches (the happens-before proof is
        # exactly what asynchronous dispatch relies on)
        self.admission = analyze_program(prog, program="mpmd_train")
        if not prog.is_training:
            raise ValueError("MpmdTrainRunner needs a training TickProgram")
        self.mesh = mesh
        self.spec = spec
        self.prog = prog
        self.P = prog.num_stages
        self.V = prog.num_chunks
        self.S = spec.n_stages
        self.dp = mesh.shape["dp"]
        self.tp = mesh_tp(mesh)
        self.opt = opt
        self.split = bool(prog.backward_split)
        self.programs = _StagePrograms(
            mesh, spec, prog, mubatch_size, opt, precision
        )
        self.cells = stage_cells(prog)
        self.M = prog.num_micro_batches
        self.mb_sz = mubatch_size
        self.D_in = self.programs.D_in
        self.D_out = self.programs.D_out
        self._tracer = tracer
        self._trace_batches = int(trace_batches)
        self.dispatch_count = 0  # stage-program dispatches issued
        self.relay_count = 0  # device-to-device transfers issued
        # cached zero gradient accumulators / loss tally (never mutated:
        # every dispatch is functional, so one set serves every batch)
        subs = self.programs.submeshes
        dims = self.programs.dims
        single = self.programs.single
        devs = self.programs.stage_device
        # per-stage zero gradient accumulators, in the programs' grads
        # representation: one packed vector (singleton fast path) or the
        # ((gW leaves), (gb leaves)) pair (shard_map path). Never
        # mutated — every dispatch is functional, one set serves every
        # batch (0.0 + d == the lockstep .at[v].add from zeros, bitwise)
        self._zero_g = []
        pspecs = self.programs._param_specs
        for s in range(self.P):
            if self.programs.packed:
                self._zero_g.append(
                    jax.device_put(
                        np.zeros((self.programs.plen,), np.float32), devs[s]
                    )
                )
                continue

            def place(a, sp, s=s):
                return jax.device_put(a, NamedSharding(subs[s], sp))

            self._zero_g.append(
                (
                    tuple(
                        place(np.zeros((self.V, o, i), np.float32), sp)
                        for (o, i), sp in zip(dims, pspecs["W"])
                    ),
                    tuple(
                        place(np.zeros((self.V, o), np.float32), sp)
                        for (o, _), sp in zip(dims, pspecs["b"])
                    ),
                )
            )
        self._zero_loss = jax.device_put(
            np.zeros((self.dp,), np.float32),
            devs[self.P - 1] if single
            else NamedSharding(subs[self.P - 1], P("dp")),
        )
        # the per-batch x/y stacks ride ONE device_put each; load/head
        # cells index them with a pre-staged traced scalar (one device
        # array per microbatch id per endpoint stage — M-independent
        # program count, two host->device transfers per batch)
        self._x_sharding = (
            devs[0] if single else NamedSharding(subs[0], P(None, "dp"))
        )
        self._y_sharding = (
            devs[self.P - 1] if single
            else NamedSharding(subs[self.P - 1], P(None, "dp"))
        )
        self._mb_idx = {}
        for s in (0, self.P - 1):
            sh = devs[s] if single else NamedSharding(subs[s], P())
            self._mb_idx[s] = [
                jax.device_put(np.int32(m), sh) for m in range(self.M)
            ]

    # -- one batch ----------------------------------------------------------

    def _put_batch(self, xb, yb):
        """Host batch -> ONE (M, dp*mb, width) device stack for each
        endpoint: x on stage 0's sub-mesh, y on the head stage's, rows
        sharded over dp with rank r's microbatch rows exactly the
        lockstep shard's. Widths are padded to the executor's D_in/D_out
        here (host-side, exact zeros) — the lockstep program applies the
        identical ``_fit`` on device."""

        def stack(a, w, sharding):
            a = np.asarray(a, np.float32).reshape(a.shape[0], -1)
            if a.shape[-1] != w:
                a = np.pad(a, ((0, 0), (0, w - a.shape[-1])))
            dp, M, mb = self.dp, self.M, self.mb_sz
            a = np.ascontiguousarray(
                a.reshape(dp, M, mb, w).transpose(1, 0, 2, 3)
            ).reshape(M, dp * mb, w)
            return jax.device_put(a, sharding)

        return (
            stack(xb, self.D_in, self._x_sharding),
            stack(yb, self.D_out, self._y_sharding),
        )

    def _span(self, spans, name, t0, **fields):
        if spans is not None:
            spans.append((name, t0, time.perf_counter(), fields))

    def run_batch(self, params, flags, state, xb, yb, spans=None):
        """Dispatch one global batch through the per-stage streams; pure
        issue — nothing here blocks on device execution. Returns the new
        per-stage (params, state) plus the un-synced loss handle."""
        progs = self.programs
        rec = progs.rec
        x_full, y_full = self._put_batch(xb, yb)
        mail = {}
        stash = [dict() for _ in range(self.P)]
        gstash = [dict() for _ in range(self.P)]
        # recompute programs: the stage-INPUT handles kept from the fwd
        # tick (stage 0 exempt — its recompute reloads from the batch
        # stack), freed by the OP_RECOMPUTE dispatch that consumes them
        xin = [dict() for _ in range(self.P)]
        grads = list(self._zero_g)
        loss_acc = self._zero_loss
        subs = progs.submeshes
        single = progs.single
        idx = self._mb_idx

        def relay(direction, src, payload, key):
            dst = (src + 1) % self.P if direction == "fwd" else (src - 1) % self.P
            v, mb = key
            if direction == "fwd" and src == self.P - 1:
                v += 1
            elif direction == "bwd" and src == 0:
                v -= 1
            t0 = time.perf_counter()
            moved = jax.device_put(
                payload,
                progs.stage_device[dst] if single
                else NamedSharding(subs[dst], P("dp")),
            )
            self.relay_count += 1
            self._span(
                spans, "stage.relay", t0, stage=src, to_stage=dst,
                direction=direction, mb=mb,
            )
            mail[(direction, dst, (v, mb))] = moved

        for row in self.cells:
            for c in row:
                s, v, mb = c["s"], c["v"], c["mb"]
                key = (v, mb)
                t0 = time.perf_counter()
                if c["op"] == OP_FWD:
                    fn = c.get("_fn")
                    if fn is None:
                        fn = c["_fn"] = progs.get(
                            s, "fwd_ns" if rec else "fwd",
                            (v, c["load"], c["head"], c["send_fwd"]),
                        )
                    args = (params[s], flags[s])
                    if c["load"]:
                        args += (x_full,)
                    else:
                        x_in = mail.pop(("fwd", s, key))
                        if rec:
                            xin[s][key] = x_in  # kept for the recompute
                        args += (x_in,)
                    if c["head"]:
                        args += (y_full, idx[s][mb], loss_acc)
                    elif c["load"]:
                        args += (idx[s][mb],)
                    outs = fn(*args)
                    i = 1 if c["send_fwd"] else 0
                    if rec:
                        if c["head"]:
                            loss_acc = outs[i]
                    elif c["head"]:
                        stash[s][key] = (outs[i], outs[i + 1], outs[i + 2])
                        loss_acc = outs[i + 3]
                    else:
                        stash[s][key] = (outs[i], outs[i + 1], None)
                    self.dispatch_count += 1
                    self._span(
                        spans, "stage.dispatch", t0, stage=s, op="fwd", mb=mb
                    )
                    if c["send_fwd"]:
                        relay("fwd", s, outs[0], key)
                elif c["op"] == OP_RECOMPUTE:
                    fn = c.get("_fn")
                    if fn is None:
                        fn = c["_fn"] = progs.get(
                            s, "recompute", (v, c["load"], c["head"])
                        )
                    args = (params[s], flags[s])
                    if c["load"]:
                        args += (x_full, idx[s][mb])
                    else:
                        args += (xin[s].pop(key),)
                    outs = fn(*args)
                    stash[s][key] = (
                        outs[0], outs[1], outs[2] if c["head"] else None
                    )
                    self.dispatch_count += 1
                    self._span(
                        spans, "stage.dispatch", t0, stage=s, op="recompute",
                        mb=mb,
                    )
                elif c["op"] == OP_BWD and self.split:
                    xs, masks, z = stash[s][key]  # peek (B-weight frees)
                    fn = c.get("_fn")
                    if fn is None:
                        fn = c["_fn"] = progs.get(
                            s, "bwd_in", (v, c["head"], c["send_bwd"])
                        )
                    if c["head"]:
                        outs = fn(
                            params[s], flags[s], masks, z, y_full, idx[s][mb]
                        )
                    else:
                        g_in = mail.pop(("bwd", s, key))
                        outs = fn(params[s], flags[s], masks, g_in)
                    gstash[s][key] = outs[-1]
                    self.dispatch_count += 1
                    self._span(
                        spans, "stage.dispatch", t0, stage=s, op="bwd_in", mb=mb
                    )
                    if c["send_bwd"]:
                        relay("bwd", s, outs[0], key)
                elif c["op"] == OP_BWD:
                    xs, masks, z = stash[s].pop(key)
                    fn = c.get("_fn")
                    if fn is None:
                        fn = c["_fn"] = progs.get(
                            s, "bwd", (v, c["head"], c["send_bwd"])
                        )
                    if c["head"]:
                        outs = fn(
                            params[s], flags[s], xs, masks, z, y_full,
                            idx[s][mb], grads[s],
                        )
                    else:
                        g_in = mail.pop(("bwd", s, key))
                        outs = fn(
                            params[s], flags[s], xs, masks, g_in, grads[s]
                        )
                    grads[s] = outs[-1]
                    self.dispatch_count += 1
                    self._span(
                        spans, "stage.dispatch", t0, stage=s, op="bwd", mb=mb
                    )
                    if c["send_bwd"]:
                        relay("bwd", s, outs[0], key)
                else:  # OP_BWD_W
                    xs, masks, _ = stash[s].pop(key)
                    g_effs = gstash[s].pop(key)
                    fn = c.get("_fn")
                    if fn is None:
                        fn = c["_fn"] = progs.get(s, "bwd_w", (v,))
                    grads[s] = fn(flags[s], xs, g_effs, grads[s])
                    self.dispatch_count += 1
                    self._span(
                        spans, "stage.dispatch", t0, stage=s, op="bwd_w", mb=mb
                    )

        assert not mail, "undelivered relay payloads (tables violated)"
        assert not any(xin), "unconsumed recompute input handles"
        # the per-stage optimizer tail: dp psum + update, one dispatch per
        # stage (the lockstep program's exact reduction and update math,
        # stage-local)
        new_params, new_state = [], []
        for s in range(self.P):
            t0 = time.perf_counter()
            p_new, st_new = progs.get(s, "update")(
                params[s], grads[s], state[s]
            )
            self.dispatch_count += 1
            self._span(spans, "stage.dispatch", t0, stage=s, op="update")
            new_params.append(p_new)
            new_state.append(st_new)
        loss = progs.get(self.P - 1, "loss_sync")(loss_acc)
        self.dispatch_count += 1
        return new_params, new_state, loss

    def run(self, stacked, flags, opt_state, X, Y, trace_id=None):
        """The epoch-shaped entry point (lockstep signature): loop the
        batches of ``X``/``Y`` (host arrays, (nb, B, ...)) through
        ``run_batch`` and reassemble the full-mesh state. Returns
        ``(stacked, opt_state, mean_loss)``."""
        subs = self.programs.submeshes
        progs = self.programs
        params = [
            stage_param_view(stacked, s, subs[s], self.tp, self.V)
            for s in range(self.P)
        ]
        flag_views = [
            stage_flags_view(flags, s, subs[s], self.V) for s in range(self.P)
        ]
        states = [
            stage_state_view(self.opt, opt_state, s, subs[s], self.tp, self.V)
            for s in range(self.P)
        ]
        stateful = not (isinstance(states[0], tuple) and states[0] == ())
        if progs.packed:
            # enter the packed representation once per run call (one
            # pack dispatch per stage; the inverse pair runs at the end
            # — the whole batch loop stays flat-buffer)
            params = [
                progs.get(s, "pack")(params[s]) for s in range(self.P)
            ]
            if stateful:
                states = [
                    progs.get(s, "state_pack")(states[s])
                    for s in range(self.P)
                ]
        losses = []
        nb = len(X)
        for k in range(nb):
            spans = None
            if (
                self._tracer is not None
                and self._tracer.enabled
                and k < self._trace_batches
            ):
                spans = []
            params, states, loss = self.run_batch(
                params, flag_views, states, X[k], Y[k], spans=spans
            )
            if spans is not None:
                # one chain per traced batch; the final update span is
                # the terminal so the chain is COMPLETE and the Tracing
                # attribution can aggregate it (the chain's timeline is
                # the HOST ISSUE window of the batch — where MPMD
                # dispatch wall goes, the number judged against the
                # lockstep op-issue roofline)
                tid = trace_id or "mpmd"
                for i, (name, t0, t1, fields) in enumerate(spans):
                    last = i == len(spans) - 1
                    self._tracer.span(
                        name, f"{tid}-b{k}", t0, t1, terminal=last,
                        **(dict(fields, verdict="ok") if last else fields),
                    )
            losses.append(loss)
        mean_loss = float(np.mean([float(v) for v in losses])) if nb else 0.0
        if progs.packed:
            params = [
                progs.get(s, "unpack")(params[s]) for s in range(self.P)
            ]
            if stateful:
                states = [
                    progs.get(s, "state_unpack")(states[s])
                    for s in range(self.P)
                ]
        new_stacked = full_param_from_stage(params, self.mesh, self.S, self.tp)
        new_state = full_state_from_stage(
            self.opt, states, self.mesh, self.S, self.tp
        )
        # gate on FULL completion before returning: the loss only
        # depends on the head stage's chain, so without this the
        # caller's float(loss) would close its timing window while the
        # other stages' final updates still execute (the lockstep
        # epoch's loss output gates everything; the timing contract
        # must match across runtimes)
        jax.block_until_ready(jax.tree.leaves(new_stacked))
        return new_stacked, new_state, np.float32(mean_loss)

    # -- warm / audit -------------------------------------------------------

    def planned_programs(self):
        """Every (stage, role, variant) the plan can dispatch — the
        enumeration the warm/audit pass compiles, so a warm start covers
        exactly the dispatch surface."""
        seen = {}
        rec = self.programs.rec
        for row in self.cells:
            for c in row:
                s, v = c["s"], c["v"]
                if c["op"] == OP_FWD:
                    role = "fwd_ns" if rec else "fwd"
                    seen[(s, role, (v, c["load"], c["head"], c["send_fwd"]))] = c
                elif c["op"] == OP_RECOMPUTE:
                    seen[(s, "recompute", (v, c["load"], c["head"]))] = c
                elif c["op"] == OP_BWD and self.split:
                    seen[(s, "bwd_in", (v, c["head"], c["send_bwd"]))] = c
                elif c["op"] == OP_BWD:
                    seen[(s, "bwd", (v, c["head"], c["send_bwd"]))] = c
                else:
                    seen[(s, "bwd_w", (v,))] = c
        keys = list(seen)
        for s in range(self.P):
            keys.append((s, "update", ()))
        keys.append((self.P - 1, "loss_sync", ()))
        if self.programs.packed:
            from shallowspeed_tpu.optimizer import is_stateless

            roles = ["pack", "unpack"]
            if not is_stateless(self.opt):
                roles += ["state_pack", "state_unpack"]
            for s in range(self.P):
                for r in roles:
                    keys.append((s, r, ()))
        return keys

    def example_args(self, s, role, variant, stacked, flags, opt_state,
                     cache=None):
        """Shape-correct example arguments for one planned program (the
        lower/compile inputs of the warm/audit pass). ``cache`` (a
        dict the warm loop owns) memoizes the per-stage views and pack
        dispatches across the ~6 planned programs of each stage."""
        subs = self.programs.submeshes
        progs = self.programs
        # the pack-boundary roles take the RAW views (building the shared
        # cache entry would dispatch the very programs being resolved —
        # warm() resolves these two first for exactly that reason)
        if role == "pack":
            return (stage_param_view(stacked, s, subs[s], self.tp, self.V),)
        if role == "state_pack":
            return (
                stage_state_view(
                    self.opt, opt_state, s, subs[s], self.tp, self.V
                ),
            )
        entry = cache.get(s) if cache is not None else None
        if entry is None:
            pv_leaves = stage_param_view(stacked, s, subs[s], self.tp, self.V)
            pv = (
                progs.get(s, "pack")(pv_leaves) if progs.packed else pv_leaves
            )
            fv = stage_flags_view(flags, s, subs[s], self.V)
            st = stage_state_view(
                self.opt, opt_state, s, subs[s], self.tp, self.V
            )
            if progs.packed and not (isinstance(st, tuple) and st == ()):
                st = progs.get(s, "state_pack")(st)
            entry = (pv, fv, st)
            if cache is not None:
                cache[s] = entry
        pv, fv, st_packed = entry
        mb_rows = self.dp * self.mb_sz
        # on the singleton fast path every struct carries the stage
        # device's sharding: the lowered executable must expect EXACTLY
        # the committed single-device arrays dispatch will pass (the
        # shard_map path infers placement from its in_specs instead)
        sds = None
        if self.programs.single:
            from jax.sharding import SingleDeviceSharding

            sds = SingleDeviceSharding(self.programs.stage_device[s])

        def struct(shape, dtype=jnp.float32):
            if sds is not None:
                return jax.ShapeDtypeStruct(shape, dtype, sharding=sds)
            return jax.ShapeDtypeStruct(shape, dtype)

        f32 = struct

        def stash_structs():
            _, _, xs_w, mask_w = E.tp_local_dims(self.programs.dims, self.tp)
            mdt = self.programs.mask_dtype
            if progs.packed:  # one concatenated buffer per stash
                return (
                    f32((mb_rows, sum(xs_w))),
                    struct((mb_rows, sum(mask_w)), mdt),
                )
            # global widths: tp-local widths x tp where the spec shards
            xs = tuple(
                f32((mb_rows, w * (self.tp if l % 2 else 1)))
                for l, w in enumerate(xs_w)
            )
            masks = tuple(
                struct((mb_rows, w * (1 if l % 2 else self.tp)), mdt)
                for l, w in enumerate(mask_w)
            )
            return xs, masks

        mb_i = (
            self._mb_idx[s][0] if s in self._mb_idx
            else jax.ShapeDtypeStruct((), jnp.int32)
        )
        if role in ("fwd", "fwd_ns", "infer_fwd"):
            training = role != "infer_fwd"
            v, load, head, send = variant
            if training and load:
                args = (pv, fv, f32((self.M, mb_rows, self.D_in)))
            elif load:
                args = (pv, fv, f32((mb_rows, self.spec.sizes[0])))
            else:
                args = (pv, fv, f32((mb_rows, self.programs.W_rel)))
            if training and head:
                args += (
                    f32((self.M, mb_rows, self.D_out)), mb_i, self._zero_loss,
                )
            elif training and load:
                args += (mb_i,)
            return args
        if role == "recompute":
            v, load, head = variant
            if load:
                return (pv, fv, f32((self.M, mb_rows, self.D_in)), mb_i)
            return (pv, fv, f32((mb_rows, self.programs.W_rel)))
        if role in ("bwd", "bwd_in"):
            v, head, send = variant
            xs, masks = stash_structs()
            args = (pv, fv) + (() if role == "bwd_in" else (xs,)) + (masks,)
            if head:
                args += (
                    f32((mb_rows, self.D_out)),
                    f32((self.M, mb_rows, self.D_out)),
                    mb_i,
                )
            else:
                args += (f32((mb_rows, self.programs.W_rel)),)
            if role == "bwd":
                args += (self._zero_g[s],)
            return args
        if role == "bwd_w":
            xs, masks = stash_structs()
            if progs.packed:
                g_effs = f32(masks.shape)
            else:
                g_effs = tuple(f32(m.shape) for m in masks)
            return (fv, xs, g_effs, self._zero_g[s])
        if role in ("update", "state_unpack", "unpack"):
            if role == "unpack":
                return (pv,)
            if role == "state_unpack":
                return (st_packed,)
            return (pv, self._zero_g[s], st_packed)
        if role == "loss_sync":
            return (f32((self.dp,)),)
        raise ValueError(f"unknown role {role!r}")

    def warm(self, stacked, flags, opt_state, resolve):
        """Compile + audit every planned stage program and
        swap the dispatch path onto the resolved executables. ``resolve``
        is the session's hook ``(label, role, jit_fn, args, expected) ->
        compiled`` — it owns the per-stage census and the
        donation-safety proof. Returns the number of programs resolved."""
        n = 0
        view_cache = {}
        planned = sorted(
            self.planned_programs(),
            # pack/state_pack first: every other role's example args are
            # built THROUGH them, and a warm start must not compile them
            # implicitly via the jit wrapper
            key=lambda k: 0 if k[1] in ("pack", "state_pack") else 1,
        )
        for s, role, variant in planned:
            args = self.example_args(
                s, role, variant, stacked, flags, opt_state, cache=view_cache
            )
            # a non-relaying backward's contract drops the dead dx psum
            sends = variant[2] if role in ("bwd", "bwd_in") else True
            expected = expected_stage_comms(
                role, self.spec, self.dp, self.tp, sends=sends
            )
            if _resolve_program(
                self.programs, s, role, variant, args, expected, resolve
            ):
                n += 1
        # drop any per-cell dispatch caches so the next batch picks up
        # the resolved executables
        for row in self.cells:
            for c in row:
                c.pop("_fn", None)
        return n


class MpmdInferenceRunner:
    """Forward-only MPMD streaming: per-stage inference programs fed by
    the lowered inference tick tables, one microbatch SLOT per stream
    entry. ``submit()`` issues a slot's whole stage chain asynchronously
    and returns a handle; consecutive submits pipeline — slot k enters
    stage 0 while slot k-1 occupies stage 1 — so a response is bound by
    its own chain, not by the rung program's makespan. Admission-gated
    like the trainer (``analyze_program`` before anything dispatches)."""

    def __init__(self, mesh, spec, prog, mubatch_size,
                 precision=ops.DEFAULT_PRECISION):
        from shallowspeed_tpu.analysis import analyze_program

        self.admission = analyze_program(prog, program="mpmd_infer")
        if prog.is_training:
            raise ValueError("MpmdInferenceRunner needs an inference program")
        self.mesh = mesh
        self.spec = spec
        self.prog = prog
        self.P = prog.num_stages
        self.V = prog.num_chunks
        self.dp = mesh.shape["dp"]
        self.tp = mesh_tp(mesh)
        self.programs = _StagePrograms(
            mesh, spec, prog, mubatch_size, None, precision
        )
        self.mb_sz = mubatch_size
        self.dispatch_count = 0
        # ONE slot's per-stage chain, from the tables: the per-slot cell
        # sequence is identical for every slot (the inference schedule is
        # a straight pipeline), so the M-slot table collapses to the
        # chain of stage hops for slot 0
        chain = []
        for row in stage_cells(prog):
            for c in row:
                if c["mb"] == 0:
                    chain.append(c)
        self.chain = chain
        self._x_sharding = NamedSharding(
            self.programs.submeshes[0], P("dp")
        )

    def submit(self, params, flag_views, x_slot):
        """Issue one slot (``(slot_rows, in_dim)`` host rows) through the
        stage chain; returns the async head-output array (materialize
        with ``np.asarray``). Nothing blocks here."""
        subs = self.programs.submeshes
        single = self.programs.single
        x = jax.device_put(
            np.ascontiguousarray(np.asarray(x_slot, np.float32)),
            self.programs.stage_device[0] if single else self._x_sharding,
        )
        preds = None
        for c in self.chain:
            s, v = c["s"], c["v"]
            fn = c.get("_fn")
            if fn is None:
                fn = c["_fn"] = self.programs.get(
                    s, "infer_fwd", (v, c["load"], c["head"], c["send_fwd"])
                )
            outs = fn(params[s], flag_views[s], x)
            self.dispatch_count += 1
            if c["head"]:
                preds = outs[-1]
            if c["send_fwd"]:
                dst = (s + 1) % self.P
                x = jax.device_put(
                    outs[0],
                    self.programs.stage_device[dst] if single
                    else NamedSharding(subs[dst], P("dp")),
                )
        return preds

    def warm(self, stacked, flags, resolve):
        """Resolve (audit) every program this chain can dispatch —
        the pack boundary first, then each chain cell — and swap the
        dispatch path onto the executables; the serving-side mirror of
        ``MpmdTrainRunner.warm``. Returns the number resolved."""
        n = 0
        if self.programs.packed:
            # pack first: views() dispatches it, and a warm start must
            # not compile it implicitly through the jit wrapper
            for s in range(self.P):
                leaves = stage_param_view(
                    stacked, s, self.programs.submeshes[s], self.tp, self.V
                )
                if _resolve_program(
                    self.programs, s, "pack", (), (leaves,),
                    expected_stage_comms("pack", self.spec, self.dp, self.tp),
                    resolve,
                ):
                    n += 1
        params, fls = self.views(stacked, flags)
        for c in self.chain:
            s, v = c["s"], c["v"]
            variant = (v, c["load"], c["head"], c["send_fwd"])
            if _resolve_program(
                self.programs, s, "infer_fwd", variant,
                self.example_args(c, params, fls),
                expected_stage_comms(
                    "infer_fwd", self.spec, self.dp, self.tp
                ),
                resolve,
            ):
                c.pop("_fn", None)
                n += 1
        return n

    def example_args(self, c, params, flag_views):
        """Shape/sharding-correct lower() arguments for one chain cell's
        program (the warm/audit pass)."""
        s = c["s"]
        width = self.spec.sizes[0] if c["load"] else self.programs.W_rel
        shape = (self.dp * self.mb_sz, width)
        if self.programs.single:
            from jax.sharding import SingleDeviceSharding

            x = jax.ShapeDtypeStruct(
                shape, jnp.float32,
                sharding=SingleDeviceSharding(self.programs.stage_device[s]),
            )
        else:
            x = jax.ShapeDtypeStruct(shape, jnp.float32)
        return (params[s], flag_views[s], x)

    def views(self, stacked, flags):
        """Per-stage param/flag views of the session's full-mesh arrays
        (zero-copy, plus one pack dispatch per stage in packed mode;
        rebuild after a hot weight reload)."""
        subs = self.programs.submeshes
        params = [
            stage_param_view(stacked, s, subs[s], self.tp, self.V)
            for s in range(self.P)
        ]
        if self.programs.packed:
            params = [
                self.programs.get(s, "pack")(params[s])
                for s in range(self.P)
            ]
        fls = [stage_flags_view(flags, s, subs[s], self.V) for s in range(self.P)]
        return params, fls
