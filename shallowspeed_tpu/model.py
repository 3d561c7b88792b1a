"""Model layer: stage partitioning + explicit forward/backward over pytrees.

Capability parity with the reference's Module/Sequential/MLP stack
(/root/reference/shallowspeed/layers.py), re-designed functionally for JAX:

- parameters are a pytree ``[{"W": (out,in), "b": (1,out)}, ...]`` per stage —
  no Parameter objects, no mutable .grad fields;
- the per-microbatch activation caches (reference ``Module._cache`` keyed by
  mubatch_id, layers.py:70,86,117) become *residuals returned by the forward
  pass* and threaded explicitly into the backward pass — idiomatic JAX, and
  what lets the whole step jit/scan cleanly;
- gradient accumulation (reference ``param.grad +=``, layers.py:135-136) is a
  pytree add performed by the caller (a lax.scan carry), not hidden state.

Stage partitioning semantics match reference layers.py:236-270 ("MLP"):
``len(sizes) % n_stages == 0``; stage i owns the sizes slice
``[i*ss : i*ss+ss+1]`` (overlapping boundary entry) giving ``len(local)-1``
Linear layers; every Linear has a fused ReLU except the last Linear of the
last stage; the last stage appends the softmax + MSE loss head. Stages are
deliberately UNEQUAL (e.g. 2/2/2/1 Linears at PP=4) — the SPMD executor
handles that via zero-padded stacked params (see parallel/executor.py).

Faithful reference quirk: when the last stage owns ZERO Linears (e.g. 8
sizes at PP=8), the no-relu-on-final-Linear rule never fires — the global
final Linear (owned by the second-to-last stage) keeps its ReLU, so that
layout is architecturally DIFFERENT from the sequential model. This matches
the reference exactly (layers.py:253-257); layout/sequential equivalence
holds whenever the last stage has at least one Linear.
"""

import dataclasses
from typing import Sequence

import jax.numpy as jnp

from shallowspeed_tpu import ops
from shallowspeed_tpu.init import linear_init


@dataclasses.dataclass(frozen=True)
class StageSpec:
    """Static description of one pipeline stage (trace-time constant)."""

    local_sizes: tuple  # activation dims owned by this stage, len = n_linears+1
    relu_flags: tuple  # per-Linear fused-activation flag (act names which one)
    has_head: bool  # softmax + MSE head lives on the last stage
    global_batch_size: int
    act: str = "relu"  # activation family: "relu" (MLP) or "gelu" (block zoo)
    residual_flags: tuple = ()  # per-Linear: output += the PREVIOUS Linear's
    # input (the transformer-style skip over one up/down projection pair);
    # () means no residuals (every relu-family spec)

    @property
    def n_linears(self):
        return len(self.local_sizes) - 1

    @property
    def res_flags(self):
        """residual_flags normalized to one bool per Linear."""
        if len(self.residual_flags) == self.n_linears:
            return self.residual_flags
        return (False,) * self.n_linears

    @property
    def in_dim(self):
        return self.local_sizes[0]

    @property
    def out_dim(self):
        # softmax & loss head do not change the output dim (layers.py:268-270)
        return self.local_sizes[-1]


@dataclasses.dataclass(frozen=True)
class ModelSpec:
    """Static description of the whole (possibly pipelined) model."""

    sizes: tuple
    n_stages: int
    global_batch_size: int
    stages: tuple  # tuple[StageSpec]
    act: str = "relu"

    @property
    def in_dim(self):
        return self.sizes[0]

    @property
    def out_dim(self):
        return self.sizes[-1]

    @property
    def has_residual(self):
        return any(any(s.res_flags) for s in self.stages)


def partition_sizes(sizes: Sequence[int], n_stages: int):
    """Slice the global layer-size list into per-stage local size lists.

    Same arithmetic as reference layers.py:242-250, including the overlapping
    boundary entry and the possibility of a 0-Linear trailing stage.
    """
    sizes = tuple(int(s) for s in sizes)
    if len(sizes) % n_stages != 0:
        raise ValueError(
            f"len(sizes)={len(sizes)} must be divisible by n_stages={n_stages}"
        )
    stage_size = len(sizes) // n_stages
    return [
        sizes[i * stage_size : min(len(sizes), i * stage_size + stage_size + 1)]
        for i in range(n_stages)
    ]


def make_model_spec(sizes, n_stages, global_batch_size, act="relu") -> ModelSpec:
    if act not in ("relu", "gelu"):
        raise ValueError(f"unknown activation family {act!r} (relu|gelu)")
    locals_ = partition_sizes(sizes, n_stages)
    stage_size = len(sizes) // n_stages
    n_lin_total = len(sizes) - 1
    if act == "gelu" and n_stages > 1 and stage_size % 2 != 0:
        # the gelu family assigns activation/residual by GLOBAL Linear
        # parity; an odd per-stage slice would flip local parity stage to
        # stage, breaking the even/odd slot contract tp sharding and the
        # stacked executor's static slot loop key off
        raise ValueError(
            f"gelu-family models need an even per-stage slice so local slot "
            f"parity equals global Linear parity; len(sizes)={len(sizes)} "
            f"over {n_stages} stages gives {stage_size}"
        )
    if act == "relu" and len(locals_[-1]) == 1:
        import warnings

        warnings.warn(
            f"the last of {n_stages} pipeline stages owns no Linear under "
            "this partitioning, so the 'no relu on the final Linear' rule "
            "never fires and the trained MODEL differs from shallower "
            "partitionings (faithful reference quirk, layers.py:253-257) — "
            "expect worse accuracy; prefer a size list that gives every "
            "stage a Linear",
            stacklevel=2,
        )
    stages = []
    for i, loc in enumerate(locals_):
        is_last = i == n_stages - 1
        n_lin = len(loc) - 1
        if act == "relu":
            # last Linear of last stage has no activation (layers.py:253-257)
            act_flags = tuple(
                not (is_last and l == n_lin - 1) for l in range(n_lin)
            )
            res_flags = ()
        else:
            # transformer-style block family: per global Linear index g,
            # even g is the up-projection (gelu), odd g the down-projection
            # (no activation) whose output takes the block-input residual
            # whenever the dims agree; the GLOBAL final Linear feeds the
            # softmax head raw
            act_flags = []
            res_flags = []
            for l in range(n_lin):
                g = i * stage_size + l
                act_flags.append(g % 2 == 0 and g != n_lin_total - 1)
                res_flags.append(
                    g % 2 == 1 and sizes[g - 1] == sizes[g + 1]
                )
            act_flags = tuple(act_flags)
            res_flags = tuple(res_flags)
        stages.append(
            StageSpec(
                local_sizes=tuple(loc),
                relu_flags=act_flags,
                has_head=is_last,
                global_batch_size=global_batch_size,
                act=act,
                residual_flags=res_flags,
            )
        )
    return ModelSpec(
        sizes=tuple(int(s) for s in sizes),
        n_stages=n_stages,
        global_batch_size=global_batch_size,
        stages=tuple(stages),
        act=act,
    )


# ---------------------------------------------------------------------------
# Model zoo: named compute-bound configurations, all flowing through the
# same ops/schedules/lowering/executor stack (docs/performance.md "--model").
# ``mnist-mlp`` is the flagship reference model (api.FLAGSHIP_SIZES aliases
# it); the others exist to make per-tick compute dominate dispatch on hosts
# where the flagship epoch is op-issue-bound.
# ---------------------------------------------------------------------------

MODEL_ZOO = {
    # the reference ShallowSpeed MNIST MLP (uneven stages at pp4 by design)
    "mnist-mlp": dict(sizes=(784, 128, 127, 126, 125, 124, 123, 10), act="relu"),
    # compute-bound MLP: ~10.5 MFLOP/sample forward+backward, same depth /
    # pp divisibility as the flagship — the bench default for COMPUTE_r01
    "mlp-wide": dict(sizes=(784, 512, 512, 512, 512, 512, 512, 10), act="relu"),
    # showcase depth: 23 Linears x 2048 wide (~0.5 GFLOP/sample) — the
    # stash-peak-bound regime where recompute pays (24 sizes: pp 2/3/4/6/8)
    "mlp-deep": dict(sizes=(784,) + (2048,) * 22 + (10,), act="relu"),
    # transformer-style blocks: 256-wide trunk, 1024-wide gelu up/down
    # projections with residual adds on every dim-matched block
    "transformer": dict(
        sizes=(784, 1024, 256, 1024, 256, 1024, 256, 10), act="gelu"
    ),
}


def resolve_model(name):
    """MODEL_ZOO name -> (sizes, act)."""
    try:
        entry = MODEL_ZOO[name]
    except KeyError:
        raise ValueError(
            f"unknown model {name!r}; zoo: {', '.join(sorted(MODEL_ZOO))}"
        ) from None
    return tuple(entry["sizes"]), entry["act"]


def init_stage_params(spec: StageSpec):
    """Host-side deterministic init for one stage; list of {"W","b"} numpy."""
    return [
        dict(zip(("W", "b"), linear_init(spec.local_sizes[l], spec.local_sizes[l + 1])))
        for l in range(spec.n_linears)
    ]


def init_model(spec: ModelSpec):
    """Per-stage parameter pytrees (host numpy; caller device_puts/shards)."""
    return [init_stage_params(s) for s in spec.stages]


# ---------------------------------------------------------------------------
# Forward / backward. Pure functions; residuals are explicit.
#
# Residuals structure per stage (static given the spec):
#   (layer_caches, z)
#     layer_caches: tuple per Linear of (x_in, relu_bitmask)  — bitmask is a
#                   zero-size placeholder for no-relu layers
#     z:            head-input logits if has_head else zero-size placeholder
# ---------------------------------------------------------------------------


def _placeholder(dtype=jnp.float32):
    return jnp.zeros((0,), dtype)


def stage_forward(
    params, spec: StageSpec, x, precision=ops.DEFAULT_PRECISION, head_group_rows=None
):
    """Run one stage's Linears (+head); return (out, residuals).

    In training the caller keeps residuals; for inference discard them (XLA
    dead-code-eliminates the cache outputs under jit).

    ``head_group_rows``: when several microbatches are fused into one call,
    the softmax head's stability max is taken per group of this many rows so
    the result is float-identical to a per-microbatch loop.

    Mirrors reference Sequential.forward + Linear.forward + head modules
    (layers.py:115-122,152-155,176-180) with caches made explicit.
    """
    caches = []
    if spec.act == "gelu":
        res = spec.res_flags
        x_prev = None  # input of the PREVIOUS Linear (the block input)
        for l in range(spec.n_linears):
            y = ops.linear(x, params[l]["W"], params[l]["b"], precision=precision)
            if spec.relu_flags[l]:
                caches.append((x, ops.gelu_grad_mult(y)))
                y_act = ops.gelu(y)
            else:
                caches.append((x, _placeholder()))
                y_act = y
            if res[l]:
                y_act = y_act + x_prev
            x_prev = x
            x = y_act
    else:
        for l in range(spec.n_linears):
            if spec.relu_flags[l]:
                y, mask = ops.linear_relu_fused(
                    x, params[l]["W"], params[l]["b"], precision=precision
                )
                caches.append((x, mask))
                x = y
            else:
                y = ops.linear(x, params[l]["W"], params[l]["b"], precision=precision)
                caches.append((x, _placeholder(jnp.bool_)))
                x = y
    if spec.has_head:
        z = x
        out = ops.softmax(z, group_rows=head_group_rows)
        return out, (tuple(caches), z)
    return x, (tuple(caches), _placeholder())


def stage_backward(
    params,
    spec: StageSpec,
    residuals,
    dout,
    precision=ops.DEFAULT_PRECISION,
    head_group_rows=None,
):
    """Backward through one stage; returns (dx, grads) with grads ≅ params.

    Contract matches the reference Worker: for the head stage ``dout`` is the
    TARGET microbatch (the reference loads targets into the output buffer and
    MSELoss.backward consumes them, pipe.py:361-365 + layers.py:157-163);
    for other stages it is the gradient w.r.t. this stage's output.
    """
    caches, z = residuals
    if spec.has_head:
        g = ops.softmax_mse_head_grad(
            z, dout, spec.global_batch_size, group_rows=head_group_rows
        )
    else:
        g = dout
    grads = [None] * spec.n_linears
    if spec.act == "gelu":
        res = spec.res_flags
        g_prev = None  # incoming grad at the previously-processed Linear l+1
        for l in reversed(range(spec.n_linears)):
            x_in, dact = caches[l]
            g_in = g
            g_pre = g_in * dact if spec.relu_flags[l] else g_in
            g, dw, db = ops.linear_grad(
                g_pre, x_in, params[l]["W"], precision=precision
            )
            if l + 1 < spec.n_linears and res[l + 1]:
                # residual at l+1 adds this Linear's INPUT to y_{l+1}: the
                # incoming grad there flows straight into dx here
                g = g + g_prev
            grads[l] = {"W": dw, "b": jnp.reshape(db, (1, -1))}
            g_prev = g_in
    else:
        for l in reversed(range(spec.n_linears)):
            x_in, bitmask = caches[l]
            if spec.relu_flags[l]:
                g, dw, db = ops.linear_relu_grad_fused(
                    g, bitmask, x_in, params[l]["W"], precision=precision
                )
            else:
                g, dw, db = ops.linear_grad(g, x_in, params[l]["W"], precision=precision)
            grads[l] = {"W": dw, "b": jnp.reshape(db, (1, -1))}
    return g, grads


def model_forward(
    params_list, spec: ModelSpec, x, precision=ops.DEFAULT_PRECISION, head_group_rows=None
):
    """Chain all stages (the sequential / single-process path)."""
    residuals = []
    for params, sspec in zip(params_list, spec.stages):
        x, res = stage_forward(
            params, sspec, x, precision=precision, head_group_rows=head_group_rows
        )
        residuals.append(res)
    return x, residuals


def model_backward(
    params_list,
    spec: ModelSpec,
    residuals,
    target,
    precision=ops.DEFAULT_PRECISION,
    head_group_rows=None,
):
    """Chain all stages backward; ``target`` feeds the head stage."""
    g = target
    grads_list = [None] * spec.n_stages
    for i in reversed(range(spec.n_stages)):
        g, grads_list[i] = stage_backward(
            params_list[i],
            spec.stages[i],
            residuals[i],
            g,
            precision=precision,
            head_group_rows=head_group_rows,
        )
    return g, grads_list
