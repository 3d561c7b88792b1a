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
import json
import math
from pathlib import Path
from typing import Callable, NamedTuple, Sequence

import jax.numpy as jnp
from jax import lax

from shallowspeed_tpu import ops
from shallowspeed_tpu.init import Draw, draw_leaves, linear_init, token_leaf_init
from shallowspeed_tpu.observability.scopes import scope


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


def _init_stages(stages, metrics):
    """Every Linear of ``stages`` in one ``init.draw_leaves`` (one draw a
    Linear: its ``(W, b)``), reassembled stage by stage."""
    drawn = iter(
        draw_leaves(
            [
                Draw(linear_init, (fan_in, fan_out), (fan_out * fan_in, fan_out))
                for s in stages
                for fan_in, fan_out in zip(s.local_sizes, s.local_sizes[1:])
            ],
            metrics,
        )
    )
    return [
        [dict(zip(("W", "b"), next(drawn))) for _ in range(s.n_linears)]
        for s in stages
    ]


def init_model(spec: ModelSpec, metrics=None):
    """Per-stage parameter pytrees (host numpy; caller device_puts/shards)."""
    return _init_stages(spec.stages, metrics)


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


# ---------------------------------------------------------------------------
# Token models: a function of token ids, built from the keys of a published
# ``config.json`` (not from a tuple of ``sizes``), each family from ITS keys.
# A layer is a residual form around a mixer and a feed-forward, each a
# ``Part``; a family (``model_type``) is a ``Family``: the keys it reads and
# the parts it uses. ``MIXERS`` holds a part a layer kind (the names of
# ``layer_types``), ``FAMILIES`` a family; nothing outside this module names
# either. A third family is a ``FAMILIES`` entry, a part for each mechanism
# it adds and a ``TOKEN_MODELS`` line (ARCHITECTURE.md).
#
# ``olmo_hybrid``: layers of Gated DeltaNet (``linear_attention``) and of full
# attention in the pattern ``layer_types`` gives, the norms on the branches
# (``BRANCH_NORM``: ``h = x + norm(mix(x))``), a dense SwiGLU (``SWIGLU``).
# Written out in benchmarks/references/olmo_hybrid.py.
#
# ``solar_open2``: layers of Kimi-style delta attention with a decay per key
# channel (``kda``) and of gated grouped-query attention without a positional
# term (``gqa``, at the indices ``gqa_layers`` gives), pre-norm residuals
# (``PRE_NORM``: ``h = x + mix(norm(x))``), and in every layer a routed
# mixture of SwiGLU experts with a shared expert (``ROUTED``). The router
# scores all ``n_routed_experts``; this chip HOLDS the range
# ``routed_experts_held = [lo, hi)`` of them and computes their part of the
# result (the others are other chips'; nothing stands in for them). Written
# out in benchmarks/references/solar_open2.py.
#
# Both: an embedding, a final RMSNorm, an untied head, mean cross-entropy
# over the (sliced) vocabulary. The sequential path only (trainer.py); the
# mesh executor's stage functions know Linears and nothing else (ROADMAP
# R0a, D2).
#
# Parameters: ONE stage whose layers are dictionaries of arrays: the
# embedding ``{"E"}``, one dictionary per layer (its parts' leaves), the head
# ``{"norm", "W"}``. Weights are (out, in), as every Linear's; the held
# experts' are stacked, (held, out, in).
# ---------------------------------------------------------------------------

# name -> the configuration file, relative to the checkout: the published
# keys live in ONE place, the file the benchmark's configuration names too
TOKEN_MODELS = {
    "olmo-hybrid-7b": "benchmarks/configs/olmo-hybrid-7b.json",
    "solar-open2-250b": "benchmarks/configs/solar-open2-250b.json",
}

# residual bytes of one microbatch above which a layer's forward is run
# again in the backward instead of kept
_RECOMPUTE_ABOVE_BYTES = 1 << 30


def is_token_model(model):
    """A name of ``TOKEN_MODELS`` or a dictionary of a config.json's keys."""
    return isinstance(model, dict) or model in TOKEN_MODELS


def token_model_config(model):
    """``model`` (a name of ``TOKEN_MODELS`` or the keys themselves) -> the
    dictionary of the published keys."""
    if isinstance(model, dict):
        return model
    path = Path(__file__).resolve().parent.parent / TOKEN_MODELS[model]
    return json.loads(path.read_text())


@dataclasses.dataclass(frozen=True)
class TokenModelSpec:
    """Static description of a token model and the job's shape, each group of fields
    under what reads it; a family without experts holds none (``experts_held == (0, 0)``)."""

    # every family
    vocab_size: int
    hidden_size: int
    intermediate_size: int  # the feed-forward's width: SWIGLU's, a routed expert's
    layer_types: tuple  # per layer, its kind: a key of ``MIXERS``
    num_attention_heads: int
    rms_norm_eps: float
    # the mixers that scan (``linear_attention``, ``kda``)
    linear_num_heads: int
    linear_key_head_dim: int
    linear_value_head_dim: int
    linear_conv_kernel_dim: int
    linear_allow_neg_eigval: bool
    # the job
    seq_len: int
    global_batch_size: int
    recompute: bool = True  # run forwards again in the backward (``recomputed``)
    scan_chunk: int = ops.SCAN_CHUNK
    attn_block: int = ops.ATTN_BLOCK
    family: str = "olmo_hybrid"  # a key of ``FAMILIES``
    # the ``gqa`` and ``kda`` mixers
    num_key_value_heads: int = 0  # 0: as many as query heads
    head_dim: int = 0  # 0: hidden_size // num_attention_heads
    gate_rank: int = 0  # the low-rank decay and output-gate projections' rank
    # ROUTED
    n_routed_experts: int = 0  # the router's width, as published
    experts_held: tuple = (0, 0)  # [lo, hi) of them are this chip's
    num_experts_per_tok: int = 0
    shared_size: int = 0  # the shared expert's width (all of them side by side)
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0
    moe_tile: int = ops.MOE_TILE
    n_stages = 1

    @property
    def step_tokens(self):
        return self.global_batch_size * self.seq_len

    @property
    def attn_head_dim(self):
        return self.head_dim or self.hidden_size // self.num_attention_heads

    @property
    def kv_heads(self):
        return self.num_key_value_heads or self.num_attention_heads

    @property
    def routed_layers(self):
        """How many layers route (every layer of a family with experts)."""
        return len(self.layer_types) if self.n_routed_experts else 0

    @property
    def recomputed(self):
        """Per layer, whether its forward runs again when its backward is
        due: under ``recompute`` every layer's but the last's, whose
        backward is the first one due, right after the head's."""
        last = len(self.layer_types) - 1
        return tuple(self.recompute and i < last for i in range(last + 1))

    @property
    def layer_records(self):
        """Per layer, its mixer's record and its feed-forward's."""
        ffn = FAMILIES[self.family].ffn
        return tuple((MIXERS[kind], ffn) for kind in self.layer_types)


def token_scan_plan(spec: "TokenModelSpec", mubatches):
    """Which form the recurrent layers' scan runs at this spec's shapes and
    the kernel launches one optimizer step makes: microbatches x the scan
    layers' passes (a forward, the forward again where the layer is
    recomputed, a backward; none where the XLA form runs), and how many
    layers of any kind are recomputed (``spec.recomputed``), and the
    backward launches that read the pair matrices their forward kept (one a
    ``reads_pairs`` layer and microbatch). Each rule has a kernel form where
    its shapes tile, and the first scan layer's rule (``Part.scan``) says
    where and which chunk. -> the ``scan_path`` event's fields."""
    mixers = [MIXERS[kind] for kind in spec.layer_types]
    rules = [mixer.scan for mixer in mixers if mixer.scan]
    path, chunk = (rules[0] if rules else _delta_scan)(spec)
    passes = sum(3 if again else 2 for m, again in zip(mixers, spec.recomputed) if m.scan)
    pallas = path == "pallas"
    return {
        "path": path,
        "chunk": chunk,
        "d_k": spec.linear_key_head_dim,
        "d_v": spec.linear_value_head_dim,
        "kernel_calls_per_step": mubatches * passes if pallas else 0,
        "recomputed_layers": sum(spec.recomputed),
        "pairs_read_per_step": mubatches * sum(m.reads_pairs for m in mixers) if pallas else 0,
    }


def _olmo_spec_fields(config):
    if config["num_key_value_heads"] != config["num_attention_heads"]:
        raise ValueError(
            "olmo_hybrid is covered with num_key_value_heads == "
            "num_attention_heads, its published shape (grouped key/value "
            "heads run in the solar_open2 family's layers)"
        )
    if config["linear_num_key_heads"] != config["linear_num_value_heads"]:
        raise ValueError("linear_num_key_heads != linear_num_value_heads is not covered")
    if (config.get("rope_parameters") or {}).get("rope_theta") is not None:
        raise ValueError("rotary embeddings are not covered (ROADMAP R8)")
    if config["hidden_size"] % config["num_attention_heads"]:
        raise ValueError("num_attention_heads must divide hidden_size")
    layer_types = tuple(config["layer_types"])
    unknown = set(layer_types) - {"linear_attention", "full_attention"}
    if unknown:
        raise ValueError(f"unknown layer types {sorted(unknown)}")
    return dict(
        intermediate_size=config["intermediate_size"],
        layer_types=layer_types,
        linear_num_heads=config["linear_num_value_heads"],
        linear_key_head_dim=config["linear_key_head_dim"],
        linear_value_head_dim=config["linear_value_head_dim"],
        linear_conv_kernel_dim=config["linear_conv_kernel_dim"],
        linear_allow_neg_eigval=bool(config["linear_allow_neg_eigval"]),
    ), max(config["intermediate_size"], 2 * config["hidden_size"])


def _solar_spec_fields(config):
    linear = config["linear_attn_config"]
    heads, kv = config["num_attention_heads"], config["num_key_value_heads"]
    if heads % kv:
        raise ValueError("num_key_value_heads must divide num_attention_heads")
    if linear.get("num_kv_heads") not in (None, linear["num_heads"]):
        raise ValueError("linear_attn_config.num_kv_heads other than num_heads is not covered")
    gqa = set(config["gqa_layers"])
    if not gqa <= set(range(config["num_hidden_layers"])):
        raise ValueError("gqa_layers names a layer past num_hidden_layers")
    lo, hi = config["routed_experts_held"]
    if not 0 <= lo < hi <= config["n_routed_experts"]:
        raise ValueError(
            f"routed_experts_held {[lo, hi]} is no range of the "
            f"{config['n_routed_experts']} routed experts"
        )
    if config["num_experts_per_tok"] > config["n_routed_experts"]:
        raise ValueError("num_experts_per_tok exceeds n_routed_experts")
    head_dim = linear["head_dim"]
    return dict(
        intermediate_size=config["moe_intermediate_size"],
        layer_types=tuple(
            "gqa" if i in gqa else "kda" for i in range(config["num_hidden_layers"])
        ),
        linear_num_heads=linear["num_heads"],
        linear_key_head_dim=head_dim,
        linear_value_head_dim=head_dim,
        linear_conv_kernel_dim=linear["short_conv_kernel_size"],
        linear_allow_neg_eigval=bool(config["kda_allow_neg_eigval"]),
        scan_chunk=ops.KDA_CHUNK,
        num_key_value_heads=kv,
        head_dim=config["head_dim"],
        # assumed: the low-rank projections' rank is one head's width
        gate_rank=config.get("kda_gate_rank", head_dim),
        n_routed_experts=config["n_routed_experts"],
        experts_held=(lo, hi),
        num_experts_per_tok=config["num_experts_per_tok"],
        shared_size=config["moe_intermediate_size"] * config["n_shared_experts"],
        norm_topk_prob=bool(config["norm_topk_prob"]),
        routed_scaling_factor=float(config["routed_scaling_factor"]),
    ), max(2 * config["hidden_size"], linear["num_heads"] * head_dim)


def make_token_spec(
    config, seq_len, global_batch_size, mubatch_rows=None, recompute=None
) -> TokenModelSpec:
    """``config``: the published keys (``token_model_config``), read by the
    family ``model_type`` names. Refuses what the equations here do not
    cover. ``recompute=None`` decides from the microbatch: kept where one
    microbatch's residuals of all layers stay under
    ``_RECOMPUTE_ABOVE_BYTES``."""
    family = config.get("model_type", "olmo_hybrid")
    if family not in FAMILIES:
        raise ValueError(
            f"token models cover model_type {sorted(FAMILIES)}, got {family!r}"
        )
    missing = [k for k in FAMILIES[family].keys if k not in config]
    if missing:
        raise ValueError(f"token model configuration lacks {missing}")
    for key, value in FAMILIES[family].fixed.items():
        if config.get(key, value) != value:
            raise ValueError(f"{family} is covered with {key}={value!r} only, got {config[key]!r}")
    if seq_len is None or seq_len < 1:
        raise ValueError("a token model needs seq_len (train.py --seq-len)")
    fields, widest = FAMILIES[family].spec_fields(config)
    if len(fields["layer_types"]) != config["num_hidden_layers"]:
        raise ValueError("layer_types does not list num_hidden_layers layers")
    if recompute is None:
        # float32 bytes the layers keep between forward and backward where
        # nothing is recomputed, roughly: a layer's widest intermediate a
        # handful of times for each token of a microbatch
        kept = 4 * 8 * widest * (mubatch_rows or global_batch_size) * seq_len
        recompute = len(fields["layer_types"]) * kept > _RECOMPUTE_ABOVE_BYTES
    return TokenModelSpec(
        vocab_size=config["vocab_size"],
        hidden_size=config["hidden_size"],
        num_attention_heads=config["num_attention_heads"],
        rms_norm_eps=config["rms_norm_eps"],
        seq_len=int(seq_len),
        global_batch_size=int(global_batch_size),
        recompute=bool(recompute),
        family=family,
        **fields,
    )


def token_layer_shapes(spec: TokenModelSpec):
    """The model's layers in order, each ``{leaf name: (shape, kind)}`` (a layer's:
    its parts'); ``kind`` picks the leaf's initial values (``init.token_leaf_init``)."""
    d, v, family = spec.hidden_size, spec.vocab_size, FAMILIES[spec.family]
    form, ffn = family.form.leaves(spec), family.ffn.leaves(spec)
    return (
        [{"E": ((v, d), "weight")}]
        + [{**MIXERS[kind].leaves(spec), **form, **ffn} for kind in spec.layer_types]
        + [{"norm": ((d,), "ones"), "W": ((v, d), "weight")}]
    )


def init_token_model(spec: TokenModelSpec, metrics=None):
    """Host-side deterministic init: one stage, a list of layers, each a
    dictionary of float32 arrays seeded by the layer's index and the leaf's
    name, drawn in one ``init.draw_leaves``."""
    layers = token_layer_shapes(spec)
    drawn = iter(
        draw_leaves(
            [
                Draw(token_leaf_init, (index, name, shape, kind), (math.prod(shape),))
                for index, layer in enumerate(layers)
                for name, (shape, kind) in layer.items()
            ],
            metrics,
        )
    )
    return [[{name: next(drawn) for name in layer} for layer in layers]]


def _heads(x, heads):
    """(rows, seq, heads * d) -> (rows, seq, heads, d)."""
    return x.reshape(*x.shape[:2], heads, -1)


def _delta_scan(spec):
    """The Gated DeltaNet's rule, ``ops.scan_path`` -> ``(path, chunk)``."""
    shapes = (spec.linear_key_head_dim, spec.linear_value_head_dim, jnp.float32)
    path = ops.scan_path(spec.seq_len, spec.scan_chunk, *shapes)
    return path, ops._block_len(spec.seq_len, spec.scan_chunk)


def _channel_scan(spec):
    """The per-channel rule's, ``ops.kda_scan_path``, whose kernels take
    chunks of ``ops.KDA_KERNEL_CHUNK`` tokens."""
    shapes = (spec.linear_key_head_dim, spec.linear_value_head_dim, jnp.float32)
    path = ops.kda_scan_path(spec.seq_len, spec.linear_num_heads, *shapes)
    if path == "pallas":
        return path, ops.KDA_KERNEL_CHUNK
    return path, ops._block_len(spec.seq_len, spec.scan_chunk)


def _linear_attention_leaves(spec):
    d, h, taps = spec.hidden_size, spec.linear_num_heads, spec.linear_conv_kernel_dim
    dk, dv = spec.linear_key_head_dim, spec.linear_value_head_dim
    return {
        "Wq": ((h * dk, d), "weight"), "Wk": ((h * dk, d), "weight"),
        "Wv": ((h * dv, d), "weight"), "Wg": ((h * dv, d), "weight"),
        "Wo": ((d, h * dv), "weight"), "Wb": ((h, d), "weight"), "Wa": ((h, d), "weight"),
        "conv_q": ((h * dk, taps), "taps"), "conv_k": ((h * dk, taps), "taps"),
        "conv_v": ((h * dv, taps), "taps"), "A_log": ((h,), "a_log"),
        "dt_bias": ((h,), "dt_bias"), "o_norm": ((dv,), "ones"),
    }


def _linear_attention_mix(p, x, seg, spec, precision):
    """The Gated DeltaNet mixer. -> ``(out, back)``; ``back(dout) -> (dx,
    grads)``."""
    h = spec.linear_num_heads
    projected = {
        name: ops.dense(x, p[name], precision)
        for name in ("Wq", "Wk", "Wv", "Wg", "Wb", "Wa")
    }
    q1, conv_q = ops.conv_silu(projected["Wq"][0], p["conv_q"], seg)
    k1, conv_k = ops.conv_silu(projected["Wk"][0], p["conv_k"], seg)
    v1, conv_v = ops.conv_silu(projected["Wv"][0], p["conv_v"], seg)
    (q2, k2), unit = ops.qk_l2norm(_heads(q1, h), _heads(k1, h))
    (beta, log_decay), gates = ops.delta_gates(
        projected["Wb"][0], projected["Wa"][0], p["A_log"], p["dt_bias"],
        spec.linear_allow_neg_eigval,
    )
    o, scan = ops.gated_delta_scan(
        q2, k2, _heads(v1, h), beta, log_decay, seg, chunk=spec.scan_chunk
    )
    gated, gate = ops.gated_head_norm(
        o, _heads(projected["Wg"][0], h), p["o_norm"], spec.rms_norm_eps
    )
    out, out_back = ops.dense(gated.reshape(*x.shape[:2], -1), p["Wo"], precision)

    def back(dout):
        grads = {}
        dgated, grads["Wo"] = out_back(dout)
        do, dg, grads["o_norm"] = gate(dgated.reshape(gated.shape))
        dq2, dk2, dv1, dbeta, dlog_decay = scan(do)
        db, da, grads["A_log"], grads["dt_bias"] = gates((dbeta, dlog_decay))
        dq1, dk1 = unit((dq2, dk2))
        dq0, grads["conv_q"] = conv_q(dq1.reshape(q1.shape))
        dk0, grads["conv_k"] = conv_k(dk1.reshape(k1.shape))
        dv0, grads["conv_v"] = conv_v(dv1.reshape(v1.shape))
        parts = []
        for name, d in (
            ("Wq", dq0), ("Wk", dk0), ("Wv", dv0), ("Wg", dg.reshape(v1.shape)),
            ("Wb", db), ("Wa", da),
        ):
            dx_part, grads[name] = projected[name][1](d)
            parts.append(dx_part)
        return ops.fan_in(*parts), grads

    return out, back


def _full_attention_leaves(spec):
    d = spec.hidden_size
    return {
        "Wq": ((d, d), "weight"), "Wk": ((d, d), "weight"),
        "Wv": ((d, d), "weight"), "Wo": ((d, d), "weight"),
        "q_norm": ((d,), "ones"), "k_norm": ((d,), "ones"),
    }


def _full_attention_mix(p, x, seg, spec, precision):
    """Full attention with QK-norm over the whole projection, no rotary
    embedding, masked by document. -> ``(out, back)``."""
    h = spec.num_attention_heads
    q0, q_back = ops.dense(x, p["Wq"], precision)
    k0, k_back = ops.dense(x, p["Wk"], precision)
    v0, v_back = ops.dense(x, p["Wv"], precision)
    q1, q_norm = ops.rms_norm(q0, p["q_norm"], spec.rms_norm_eps)
    k1, k_norm = ops.rms_norm(k0, p["k_norm"], spec.rms_norm_eps)

    def split(a):  # (rows, seq, hidden) -> (rows, heads, seq, head_dim)
        with scope("attn/core"):
            return _heads(a, h).transpose(0, 2, 1, 3)

    def merge(a):
        with scope("attn/core"):
            return a.transpose(0, 2, 1, 3).reshape(x.shape)

    o, core = ops.attention(
        split(q1), split(k1), split(v0), seg, precision, spec.attn_block
    )
    out, out_back = ops.dense(merge(o), p["Wo"], precision)

    def back(dout):
        grads = {}
        do, grads["Wo"] = out_back(dout)
        dq1, dk1, dv0 = map(merge, core(split(do)))
        dq0, grads["q_norm"] = q_norm(dq1)
        dk0, grads["k_norm"] = k_norm(dk1)
        dx_q, grads["Wq"] = q_back(dq0)
        dx_k, grads["Wk"] = k_back(dk0)
        dx_v, grads["Wv"] = v_back(dv0)
        return ops.fan_in(dx_q, dx_k, dx_v), grads

    return out, back


def _kda_leaves(spec):
    d, h, taps = spec.hidden_size, spec.linear_num_heads, spec.linear_conv_kernel_dim
    dk, dv, rank = spec.linear_key_head_dim, spec.linear_value_head_dim, spec.gate_rank
    return {
        "Wq": ((h * dk, d), "weight"), "Wk": ((h * dk, d), "weight"),
        "Wv": ((h * dv, d), "weight"), "Wo": ((d, h * dv), "weight"),
        "Wb": ((h, d), "weight"),
        "W_fa": ((rank, d), "weight"), "W_fb": ((h * dk, rank), "weight"),
        "W_ga": ((rank, d), "weight"), "W_gb": ((h * dv, rank), "weight"),
        "conv_q": ((h * dk, taps), "taps"), "conv_k": ((h * dk, taps), "taps"),
        "conv_v": ((h * dv, taps), "taps"), "A_log": ((h,), "a_log"),
        "dt_bias": ((h * dk,), "dt_bias"), "o_norm": ((dv,), "ones"),
    }


def _kda_mix(p, x, seg, spec, precision):
    """Kimi-style delta attention: the Gated DeltaNet's projections,
    convolutions and normalisation, a decay per key channel through a
    low-rank projection (``W_fa``, ``W_fb``), the per-channel scan
    (``ops.kda_scan``), the output RMS-normed per head and gated by the
    sigmoid of a second low-rank projection. -> ``(out, back)``."""
    h = spec.linear_num_heads
    names = ("Wq", "Wk", "Wv", "Wb", "W_fa", "W_ga")
    projected = {name: ops.dense(x, p[name], precision) for name in names}
    decay, decay_back = ops.dense(projected["W_fa"][0], p["W_fb"], precision)
    gate, gate_back = ops.dense(projected["W_ga"][0], p["W_gb"], precision)
    q1, conv_q = ops.conv_silu(projected["Wq"][0], p["conv_q"], seg)
    k1, conv_k = ops.conv_silu(projected["Wk"][0], p["conv_k"], seg)
    v1, conv_v = ops.conv_silu(projected["Wv"][0], p["conv_v"], seg)
    (q2, k2), unit = ops.qk_l2norm(_heads(q1, h), _heads(k1, h))
    (beta, log_decay), gates = ops.channel_gates(
        projected["Wb"][0], _heads(decay, h), p["A_log"],
        p["dt_bias"].reshape(h, -1), spec.linear_allow_neg_eigval,
    )
    o, scan = ops.kda_scan(
        q2, k2, _heads(v1, h), beta, log_decay, seg, chunk=spec.scan_chunk
    )
    gated, out_gate = ops.head_norm_sigmoid_gate(
        o, _heads(gate, h), p["o_norm"], spec.rms_norm_eps
    )
    out, out_back = ops.dense(gated.reshape(*x.shape[:2], -1), p["Wo"], precision)

    def back(dout):
        grads = {}
        dgated, grads["Wo"] = out_back(dout)
        do, dgate, grads["o_norm"] = out_gate(dgated.reshape(gated.shape))
        dq2, dk2, dv1, dbeta, dlog_decay = scan(do)
        db, ddecay, grads["A_log"], d_dt_bias = gates((dbeta, dlog_decay))
        grads["dt_bias"] = d_dt_bias.reshape(-1)
        dq1, dk1 = unit((dq2, dk2))
        dq0, grads["conv_q"] = conv_q(dq1.reshape(q1.shape))
        dk0, grads["conv_k"] = conv_k(dk1.reshape(k1.shape))
        dv0, grads["conv_v"] = conv_v(dv1.reshape(v1.shape))
        d_fa, grads["W_fb"] = decay_back(ddecay.reshape(decay.shape))
        d_ga, grads["W_gb"] = gate_back(dgate.reshape(gate.shape))
        parts = []
        for name, d in zip(names, (dq0, dk0, dv0, db, d_fa, d_ga)):
            dx_part, grads[name] = projected[name][1](d)
            parts.append(dx_part)
        return ops.fan_in(*parts), grads

    return out, back


def _gqa_leaves(spec):
    d, hd = spec.hidden_size, spec.attn_head_dim
    q, kv = spec.num_attention_heads * hd, spec.kv_heads * hd
    return {
        "Wq": ((q, d), "weight"), "Wk": ((kv, d), "weight"), "Wv": ((kv, d), "weight"),
        "Wz": ((q, d), "weight"), "Wo": ((d, q), "weight"),
    }


def _gqa_mix(p, x, seg, spec, precision):
    """Grouped-query attention without a positional term, masked by
    document, its output gated elementwise by ``sigmoid(x W_z)``. ->
    ``(out, back)``."""
    heads, kv = spec.num_attention_heads, spec.kv_heads
    names = ("Wq", "Wk", "Wv", "Wz")
    projected = {name: ops.dense(x, p[name], precision) for name in names}

    def split(a, n):  # (rows, seq, n * d) -> (rows, n, seq, d)
        with scope("attn/core"):
            return _heads(a, n).transpose(0, 2, 1, 3)

    def merge(a):
        with scope("attn/core"):
            return a.transpose(0, 2, 1, 3).reshape(*a.shape[::2], -1)

    o, core = ops.attention(
        split(projected["Wq"][0], heads), split(projected["Wk"][0], kv),
        split(projected["Wv"][0], kv), seg, precision, spec.attn_block,
    )
    merged = merge(o)
    gated, gate = ops.sigmoid_gate(merged, projected["Wz"][0])
    out, out_back = ops.dense(gated, p["Wo"], precision)

    def back(dout):
        grads = {}
        dgated, grads["Wo"] = out_back(dout)
        dmerged, dz = gate(dgated)
        dq, dk, dv = map(merge, core(split(dmerged, heads)))
        parts = []
        for name, d in zip(names, (dq, dk, dv, dz)):
            dx_part, grads[name] = projected[name][1](d)
            parts.append(dx_part)
        return ops.fan_in(*parts), grads

    return out, back


def _summed(acc, grads, fresh=False):
    """``grads`` added leaf by leaf to ``acc`` (a dict holding at least
    their names; read as zero where ``fresh``, a bool, traced or not), or
    ``grads`` itself where ``acc`` is None."""
    if acc is None:
        return grads
    with scope("acc"):
        return {
            k: (acc[k] if fresh is False else jnp.where(fresh, 0.0, acc[k])) + g
            for k, g in grads.items()
        }


def _swiglu_leaves(spec):
    d, ff = spec.hidden_size, spec.intermediate_size
    gate, down = ((ff, d), "weight"), ((d, ff), "weight")
    return {"W_gate": gate, "W_up": gate, "W_down": down}


def _swiglu_ffn(p, x, spec, precision, census=None, names=("W_gate", "W_up", "W_down")):
    """The dense SwiGLU, ``W_down(silu(W_gate x) * W_up x)``, of the leaves
    ``names``."""
    gate_w, up_w, down_w = names
    gate, gate_back = ops.dense(x, p[gate_w], precision)
    up, up_back = ops.dense(x, p[up_w], precision)
    act, act_back = ops.swiglu(gate, up)
    down, down_back = ops.dense(act, p[down_w], precision)

    def back(dout, acc=None, fresh=False):
        grads = {}
        dact, grads[down_w] = down_back(dout)
        dgate, dup = act_back(dact)
        dx_g, grads[gate_w] = gate_back(dgate)
        dx_u, grads[up_w] = up_back(dup)
        return (dx_g, dx_u), grads, {}

    return down, back


def _routed_leaves(spec):
    d, ff, shared = spec.hidden_size, spec.intermediate_size, spec.shared_size
    held = spec.experts_held[1] - spec.experts_held[0]
    return {
        "W_r": ((spec.n_routed_experts, d), "weight"),
        "W1": ((held, ff, d), "weight"), "W3": ((held, ff, d), "weight"),
        "W2": ((held, d, ff), "weight"), "Ws1": ((shared, d), "weight"),
        "Ws3": ((shared, d), "weight"), "Ws2": ((d, shared), "weight"),
    }


def _routed_ffn(p, x, spec, precision, census=None):
    """The routed mixture: the shared expert (a SwiGLU every token takes)
    plus, of the ``num_experts_per_tok`` experts the router picks among all
    ``n_routed_experts``, those this chip holds (``ops.route``,
    ``ops.experts``). ``census`` (a list) gains the (held,) int32 count of
    (token, slot) pairs routed to each held expert. -> ``(out, back)``;
    ``back(dout, acc=None, fresh=False) -> (dx parts, grads, made)``: the
    parts of ``x``'s cotangent, which the form sums; ``made`` holds the held
    experts' leaves ``W1``, ``W3``, ``W2``, made in ``acc`` (the layer's
    accumulator, read as zero where ``fresh``) by ``ops.experts`` and so
    holding ``acc + gradient``; ``grads`` every other leaf's gradient alone."""
    x2 = x.reshape(-1, x.shape[-1])
    (weights, sel), route_back = ops.route(
        x2, p["W_r"], spec.num_experts_per_tok, spec.norm_topk_prob,
        spec.routed_scaling_factor,
    )
    routed, experts_back, rows = ops.experts(
        x2, sel, weights, spec.experts_held, p["W1"], p["W3"], p["W2"], precision,
        spec.moe_tile,
    )
    if census is not None:
        census.append(rows)
    shared, shared_back = _swiglu_ffn(p, x, spec, precision, names=("Ws1", "Ws3", "Ws2"))
    out = ops.fan_in(shared, routed.reshape(x.shape))

    def back(dout, acc=None, fresh=False):
        (dx_g, dx_u), grads, made = shared_back(dout)
        held_acc = None if acc is None else (acc["W1"], acc["W3"], acc["W2"])
        dx_e, dweights, made["W1"], made["W3"], made["W2"] = experts_back(
            dout.reshape(x2.shape), held_acc, fresh
        )
        dx_r, grads["W_r"] = route_back(dweights)
        return (dx_g, dx_u, (dx_e + dx_r).reshape(x.shape)), grads, made

    return out, back


def _norm_leaves(spec):
    d = spec.hidden_size
    return {"attn_norm": ((d,), "ones"), "mlp_norm": ((d,), "ones")}


def _branch_norm_layer(p, x, seg, mix, ffn, spec, precision, census):
    """``h = x + norm(mix(x))``, ``y = h + norm(ffn(h))``."""
    mixed, mix_back = mix(p, x, seg, spec, precision)
    hid, add1 = ops.residual_norm(x, mixed, p["attn_norm"], spec.rms_norm_eps)
    out, ffn_back = ffn(p, hid, spec, precision, census)
    y, add2 = ops.residual_norm(hid, out, p["mlp_norm"], spec.rms_norm_eps)

    def back(dy, acc=None, fresh=False):
        grads = {}
        dhid, dout, grads["mlp_norm"] = add2(dy)
        parts, ffn_grads, made = ffn_back(dout, acc, fresh)
        grads.update(ffn_grads)
        dx, dmixed, grads["attn_norm"] = add1(ops.fan_in(dhid, *parts))
        dx_mix, mix_grads = mix_back(dmixed)
        dx = ops.fan_in(dx, dx_mix)
        return dx, {**_summed(acc, {**grads, **mix_grads}, fresh), **made}

    return y, back


def _pre_norm_layer(p, x, seg, mix, ffn, spec, precision, census):
    """``h = x + mix(norm(x))``, ``y = h + ffn(norm(h))``."""
    normed, norm1 = ops.rms_norm(x, p["attn_norm"], spec.rms_norm_eps)
    mixed, mix_back = mix(p, normed, seg, spec, precision)
    hid = ops.fan_in(x, mixed)
    normed2, norm2 = ops.rms_norm(hid, p["mlp_norm"], spec.rms_norm_eps)
    out, ffn_back = ffn(p, normed2, spec, precision, census)
    y = ops.fan_in(hid, out)

    def back(dy, acc=None, fresh=False):
        parts, grads, made = ffn_back(dy, acc, fresh)
        dhid, grads["mlp_norm"] = norm2(ops.fan_in(*parts))
        dhid = ops.fan_in(dy, dhid)
        dnormed, mix_grads = mix_back(dhid)
        dx, grads["attn_norm"] = norm1(dnormed)
        grads = _summed(acc, {**grads, **mix_grads}, fresh)
        return ops.fan_in(dhid, dx), {**grads, **made}

    return y, back


class Part(NamedTuple):
    """A mixer, feed-forward or residual form: ``leaves(spec) -> {name:
    (shape, init kind)}`` and ``forward`` (``_linear_attention_mix``,
    ``_routed_ffn``, ``_pre_norm_layer`` show the three signatures); a
    mixer's FLOPs a token beside its products (``mix_flops(spec,
    pairs_per_token)``) and the rule that picks its scan's form
    (``scan(spec) -> (path, chunk)``), whose kernels' backward may read the
    pair matrices their forward kept (``reads_pairs``)."""

    leaves: Callable
    forward: Callable
    mix_flops: Callable = None
    scan: Callable = None
    reads_pairs: bool = False

    def flops(self, spec, pairs_per_token):
        """Training FLOPs a token: 6 a weight of its products (a held expert's times
        the share of experts a token takes) and ``mix_flops``; no norm or gate."""
        share = spec.num_experts_per_tok / (spec.n_routed_experts or 1)
        products = sum(
            math.prod(shape) * (share if len(shape) == 3 else 1)
            for shape, kind in self.leaves(spec).values() if kind == "weight"
        )
        return 6 * products + (self.mix_flops(spec, pairs_per_token) if self.mix_flops else 0)


class Family(NamedTuple):
    """A ``model_type``: the ``keys`` it reads, those it covers at one value
    only, ``spec_fields(config) -> (fields, widest)`` refusing the rest."""

    keys: tuple
    fixed: dict
    spec_fields: Callable
    form: Part
    ffn: Part


def _scan_flops(per_head):  # per_head d_k d_v a head forward, twice that backward
    return lambda s, _: 3 * per_head * s.linear_num_heads * (
        s.linear_key_head_dim * s.linear_value_head_dim
    )


MIXERS = {
    "linear_attention": Part(
        _linear_attention_leaves, _linear_attention_mix, _scan_flops(9), scan=_delta_scan
    ),
    "full_attention": Part(
        _full_attention_leaves, _full_attention_mix,
        lambda s, pairs: 3 * 4 * s.hidden_size * pairs,
    ),
    "kda": Part(_kda_leaves, _kda_mix, _scan_flops(7), scan=_channel_scan, reads_pairs=True),
    "gqa": Part(
        _gqa_leaves, _gqa_mix,
        lambda s, pairs: 3 * 4 * s.num_attention_heads * s.attn_head_dim * pairs,
    ),
}
SWIGLU, ROUTED = Part(_swiglu_leaves, _swiglu_ffn), Part(_routed_leaves, _routed_ffn)
BRANCH_NORM = Part(_norm_leaves, _branch_norm_layer)
PRE_NORM = Part(_norm_leaves, _pre_norm_layer)
FAMILIES = {
    "olmo_hybrid": Family(
        keys=(
            "vocab_size", "hidden_size", "intermediate_size", "num_hidden_layers",
            "num_attention_heads", "num_key_value_heads", "rms_norm_eps", "layer_types",
            "linear_num_key_heads", "linear_num_value_heads", "linear_key_head_dim",
            "linear_value_head_dim", "linear_conv_kernel_dim", "linear_allow_neg_eigval",
        ),
        fixed={"hidden_act": "silu", "attention_bias": False, "tie_word_embeddings": False},
        spec_fields=_olmo_spec_fields, form=BRANCH_NORM, ffn=SWIGLU,
    ),
    "solar_open2": Family(
        keys=(
            "vocab_size", "hidden_size", "num_hidden_layers", "num_attention_heads",
            "head_dim", "num_key_value_heads", "rms_norm_eps", "gqa_layers",
            "linear_attn_config", "kda_allow_neg_eigval", "n_routed_experts",
            "routed_experts_held", "n_shared_experts", "num_experts_per_tok",
            "moe_intermediate_size", "norm_topk_prob", "routed_scaling_factor",
        ),
        fixed={
            "tie_word_embeddings": False, "use_rope": False, "use_gqa_gate": True,
            "kda_use_full_proj": False, "first_k_dense_replace": 0,
        },
        spec_fields=_solar_spec_fields, form=PRE_NORM, ffn=ROUTED,
    ),
}


def token_layer(p, x, seg, kind, spec, precision, census=None):
    """One layer: the family's form around the kind's mixer and the
    family's feed-forward (``census``: see ``_routed_ffn``). -> ``(y,
    back)``; ``back(dy, acc=None, fresh=False) -> (dx, grads)``, ``grads``
    shaped like ``p``: with ``acc``, the layer's accumulator (read as zero
    where ``fresh``), every leaf holds ``acc + gradient``."""
    family = FAMILIES[spec.family]
    return family.form.forward(
        p, x, seg, MIXERS[kind].forward, family.ffn.forward, spec, precision, census
    )


def token_loss_and_grads(
    params, spec: TokenModelSpec, tokens, segments, precision, acc=None, census=None,
    fresh_weights=False, fresh=False,
):
    """One microbatch: ``tokens``, ``segments``: (rows, seq_len + 1) int32;
    inputs are ``[:, :-1]``, targets ``[:, 1:]``, every position a target.
    -> ``(loss, grads)``: this microbatch's share of the STEP's mean
    cross-entropy and its gradient, ``grads`` shaped like ``params``. With
    ``spec.recompute`` the forward keeps each layer's input only and a
    layer's forward runs again, behind an optimization barrier, when its
    backward is due; the last layer keeps its forward's residuals
    (``spec.recomputed``), since between that forward and its backward lies
    only the head. ``acc`` (shaped like ``params``): returned instead of
    the gradient is ``acc + gradient``, each layer's added as soon as its
    backward has made it, so that no second tree of gradients exists beside
    the accumulator (a model's worth of memory); the held experts'
    gradients are made in the accumulator itself (``_routed_ffn``).
    ``fresh`` (a bool, traced or not): ``acc`` is taken as zero, what it
    holds never read, and the gradient alone returned in it.
    ``fresh_weights``: a layer's recomputed forward reads its weights
    behind the barrier too, so nothing the first forward made of them alone
    is kept for it. ``census`` (a list): gains, from the first forward only,
    one (held,) int32 count a routed layer (``_routed_ffn``)."""
    embedding, *layers, head = params[0]

    def made(index, layer_grads):
        return _summed(None if acc is None else acc[0][index], layer_grads, fresh)

    with scope("batch"):
        inputs, targets, seg = tokens[:, :-1], tokens[:, 1:], segments[:, :-1]
    x, embed_back = ops.embed(embedding["E"], inputs)
    kept = []
    for p, kind, again in zip(layers, spec.layer_types, spec.recomputed):
        y, back = token_layer(p, x, seg, kind, spec, precision, census)
        kept.append(x if again else back)
        x = y
    normed, norm_back = ops.rms_norm(x, head["norm"], spec.rms_norm_eps)
    logits, logits_back = ops.dense(normed, head["W"], precision)
    loss, loss_back = ops.cross_entropy(logits, targets, spec.step_tokens)

    (dlogits,) = loss_back(jnp.ones((), logits.dtype))
    dnormed, d_head_w = logits_back(dlogits)
    dx, d_head_norm = norm_back(dnormed)
    grads = [made(len(layers) + 1, {"norm": d_head_norm, "W": d_head_w})]
    for index in reversed(range(len(layers))):
        p, kind, keep = layers[index], spec.layer_types[index], kept[index]
        if spec.recomputed[index]:
            # the barrier keeps the compiler from merging this forward with
            # the first one, which would keep every layer's residuals alive
            if fresh_weights:
                x_in, dx, p = lax.optimization_barrier((keep, dx, p))
            else:
                x_in, dx = lax.optimization_barrier((keep, dx))
            _, keep = token_layer(p, x_in, seg, kind, spec, precision)
        dx, layer_grads = keep(dx, None if acc is None else acc[0][index + 1], fresh)
        grads.append(layer_grads)
    grads.append(made(0, {"E": embed_back(dx)}))
    return loss, [grads[::-1]]
