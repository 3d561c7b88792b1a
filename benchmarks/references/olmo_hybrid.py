"""Plain reference for the hybrid token model (``olmo_hybrid``: Gated DeltaNet
layers and full-attention layers in the pattern ``layer_types`` gives, an
embedding, a final RMSNorm, an untied head, mean cross-entropy) trained by
SGD: forward, loss and gradients in straightforward ``jax.numpy`` and
``jax.grad``, float32, independent of ``shallowspeed_tpu``. No kernel, no
chunked form, no hand-written backward.

The equations, for one row of ``S`` tokens ``x_t`` in R^hidden whose documents
``segments`` gives (a layer is ``h = x + RMSNorm(mix(x))``, ``y = h +
RMSNorm(mlp(h))``: the OLMo 2/3 family's placement of the norms, assumed):

- linear attention (Gated DeltaNet): ``q, k, v = SiLU(conv4(x W_q)),
  SiLU(conv4(x W_k)), SiLU(conv4(x W_v))``; ``conv4`` is causal, depthwise,
  four taps a channel (tap 0 on the token itself, tap 3 on the one three
  back), and reads zero for a token of another document. Per head ``q^ =
  q / sqrt(|q|^2 + 1e-6) / sqrt(d_k)``, ``k^ = k / sqrt(|k|^2 + 1e-6)``;
  ``beta_t = 2 sigmoid(x W_b)``, ``alpha_t = exp(-exp(A_log) softplus(x W_a +
  dt_bias))``; ``S_t = alpha_t S_{t-1} (I - beta_t k^_t k^_t^T) + beta_t v_t
  k^_t^T`` with ``S_{t-1} := 0`` at a document's first token; ``o_t = S_t
  q^_t``; ``mix = W_o [RMSNorm_head(o_t) * SiLU(x W_g)]``.
- full attention: ``q, k = RMSNorm(x W_q), RMSNorm(x W_k)`` (over the whole
  projection), heads of ``head_dim``, ``softmax(q k^T / sqrt(head_dim) + M)
  v`` with ``M`` = causal and same document, ``W_o``; no rotary embedding.
- ``mlp = W_down(SiLU(x W_gate) * x W_up)``.
- loss: the sum over every position of ``logsumexp(logits) - logits[target]``
  over the step's tokens (all microbatches), so microbatch losses and
  gradients add up to the step's mean.

Departures that change no arithmetic, so that the cell's size fits the chip
after the session is dropped: each layer is a ``jax.checkpoint``; the token
scan is nested (blocks of ``SCAN_BLOCK`` tokens, each a checkpoint, so the
backward keeps a state per block and per token of one block, not per token);
attention is computed one block of ``QUERY_BLOCK`` queries at a time against
all keys (a checkpointed ``lax.map``); rows are taken one at a time and each
row's gradient is added into the running sum inside its own program.

The matmul policy is the configuration's, as in ``mlp_sgd.py``: ``highest``
is float32 at ``Precision.HIGHEST``; ``default`` is what the chip does with
most float32 matmuls left at their default, stated outright (operands rounded
to bfloat16, float32 accumulation, in the forward product and in the two of
its backward), on any backend, because the system's ``ops.dense`` states it
outright too; ``bfloat16``, one step below, rounds the results too and is the
control. The policy covers every matrix product with a
weight and attention's two; the recurrence is written element by element and
has none.

Also here: the operations and bytes the model's kernels need, for ``mfu`` and
the roofline shares, and the counts of a packed set (tokens, documents, the
pairs the mask admits).
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

SCAN_BLOCK = 128
QUERY_BLOCK = 256
NORM_EPS_L2 = 1e-6

MODEL_KEYS = (
    "vocab_size", "hidden_size", "intermediate_size", "num_hidden_layers",
    "num_attention_heads", "num_key_value_heads", "rms_norm_eps", "layer_types",
    "linear_num_key_heads", "linear_num_value_heads", "linear_key_head_dim",
    "linear_value_head_dim", "linear_conv_kernel_dim", "linear_allow_neg_eigval",
)


def model_config(config):
    """The model's keys: the ``session``'s ``model`` where it is given as a
    dictionary (a rehearsal, a test), else the configuration file's own
    top level, which is what the program loads under the model's name."""
    given = config.get("session", {}).get("model")
    source = given if isinstance(given, dict) else config
    return {k: source[k] for k in MODEL_KEYS}


# -- counts and costs ---------------------------------------------------------


def packed_counts(segments):
    """``{"tokens", "documents", "pairs"}`` of the inputs of a packed set:
    ``segments`` is ``(rows, seq_len + 1)``, a row's inputs are its first
    ``seq_len`` columns; a pair is a (query, key) the causal, same-document
    mask admits, the query itself included."""
    seg = np.asarray(segments)[:, :-1]
    rows, width = seg.shape
    first = np.ones((rows, width), bool)
    first[:, 1:] = seg[:, 1:] != seg[:, :-1]
    at = np.broadcast_to(np.arange(width), (rows, width))
    start = np.maximum.accumulate(np.where(first, at, 0), axis=1)
    return {
        "tokens": int(seg.size),
        "documents": int(first.sum()),
        "pairs": int((at - start + 1).sum()),
    }


def _linear_params(m):
    """(in x out summed) of one layer of each kind, and of the head."""
    d, ff = m["hidden_size"], m["intermediate_size"]
    hk, hv = m["linear_num_key_heads"], m["linear_num_value_heads"]
    dk, dv = m["linear_key_head_dim"], m["linear_value_head_dim"]
    mlp = 3 * d * ff
    full = 4 * d * d + mlp
    linear = d * (2 * hk * dk + 2 * hv * dv) + hv * dv * d + 2 * d * hv + mlp
    return {"full_attention": full, "linear_attention": linear, "head": d * m["vocab_size"]}


# benchmarks/datasets/packed_tokens.py's document lengths, copied: the cost of
# attention depends on the traffic, and the cost functions see no data
LENGTH_MEDIAN, LENGTH_SIGMA, LENGTH_MIN = 1024, 1.2, 16


@functools.lru_cache(maxsize=None)
def expected_pairs_per_token(seq_len):
    """The pairs the mask admits per token under ``packed_tokens``' lengths
    (lognormal, clipped to ``LENGTH_MIN`` ... ``seq_len``), where the cost
    functions are not handed a set's own count: ``E[L (L + 1) / 2] / E[L]``
    from a fixed draw. A document cut at a row's end admits fewer, so this
    is a little high (within 10% at 8,192); a traced run uses its set's own
    count instead (``attn_roofline``)."""
    drawn = np.random.default_rng(0).lognormal(np.log(LENGTH_MEDIAN), LENGTH_SIGMA, 1 << 18)
    lengths = np.clip(np.rint(drawn), min(LENGTH_MIN, seq_len), seq_len)
    return float((lengths * (lengths + 1) / 2).mean() / lengths.mean())


def attention_flops_per_pair(m):
    """Forward: ``q k^T`` and ``p v``, 2 x 2 x head_dim x heads a pair."""
    head_dim = m["hidden_size"] // m["num_attention_heads"]
    return 4 * head_dim * m["num_attention_heads"]


def attention_train_flops(m, pairs):
    """Forward and backward of one full-attention layer's core over
    ``pairs`` admitted pairs: the forward's two products and the backward's
    four (``dp``, ``dv``, ``dq``, ``dk``); the backward's recomputed scores
    are recomputation and are not counted."""
    return 3 * attention_flops_per_pair(m) * pairs


def attention_train_bytes(m, tokens):
    """HBM bytes one layer's core must move for ``tokens`` tokens, float32:
    ``q, k, v`` read and ``o`` written forward; ``q, k, v, o, do`` read and
    ``dq, dk, dv`` written backward."""
    return 4 * 12 * m["hidden_size"] * tokens


def scan_train_flops(m, tokens):
    """Forward and backward of one layer's delta rule in its recurrence
    form, per token and head: ``S k`` (2 d_k d_v), the rank-one correction
    and the decay (3 d_k d_v), the rank-one write (2 d_k d_v), ``S q``
    (2 d_k d_v): 9 d_k d_v forward, twice that backward."""
    per_head = 9 * m["linear_key_head_dim"] * m["linear_value_head_dim"]
    return 3 * per_head * m["linear_num_value_heads"] * tokens


def scan_train_bytes(m, tokens):
    """``q, k, v, beta, alpha`` read and ``o`` written forward; the same six
    and ``do`` read and five gradients written backward; float32, once a
    pass."""
    h, dk, dv = (
        m["linear_num_value_heads"], m["linear_key_head_dim"], m["linear_value_head_dim"]
    )
    q_k_v_b_a = 2 * h * dk + h * dv + 2 * h
    o = h * dv
    return 4 * ((q_k_v_b_a + o) + (q_k_v_b_a + 2 * o) + q_k_v_b_a) * tokens


def train_flops_per_sample(config, pairs_per_token=None):
    """What the equations require of one row of ``seq_len`` tokens, forward
    and backward (the recomputed forward is not counted): 6 FLOPs a weight
    of every matrix product and token, the delta rule in its recurrence
    form, attention over the pairs the mask admits (``pairs_per_token``;
    ``expected_pairs_per_token`` where it is not given). The embedding is a lookup; norms,
    gates and the convolution are noise beside these and are not counted."""
    m, seq_len = model_config(config), config["session"]["seq_len"]
    per = _linear_params(m)
    if pairs_per_token is None:
        pairs_per_token = expected_pairs_per_token(seq_len)
    weights = per["head"] + sum(per[kind] for kind in m["layer_types"])
    flops = 6 * weights * seq_len
    for kind in m["layer_types"]:
        if kind == "full_attention":
            flops += attention_train_flops(m, pairs_per_token * seq_len)
        else:
            flops += scan_train_flops(m, seq_len)
    return flops


def matmul_bytes_per_sample(config, rows):
    """HBM bytes the three matmuls of every matrix product with a weight
    must move for one microbatch of ``rows`` rows, per row, in float32: each
    reads two operands and writes one result (``mlp_sgd.py``'s count, with
    ``rows x seq_len`` tokens for rows). The first layer's products have an
    input gradient too (the embedding's)."""
    m, tokens = model_config(config), rows * config["session"]["seq_len"]
    d, ff = m["hidden_size"], m["intermediate_size"]
    hk, hv = m["linear_num_key_heads"], m["linear_num_value_heads"]
    dk, dv = m["linear_key_head_dim"], m["linear_value_head_dim"]
    mlp = [(d, ff), (d, ff), (ff, d)]
    products = {
        "full_attention": [(d, d)] * 4 + mlp,
        "linear_attention": [
            (d, hk * dk), (d, hk * dk), (d, hv * dv), (d, hv * dv),
            (d, hv), (d, hv), (hv * dv, d),
        ] + mlp,
    }
    total = 0
    for i, o in [p for kind in m["layer_types"] for p in products[kind]] + [
        (d, m["vocab_size"])
    ]:
        total += 4 * 3 * (tokens * i + i * o + tokens * o)
    return total / rows


# -- the model ----------------------------------------------------------------


def _rounded_matmul(round_result):
    """``a @ b`` with both operands rounded to bfloat16 and float32
    accumulation, and the same for the two products of its backward (``g
    b^T`` and ``a^T g``): what the chip does with a float32 matmul left at
    its default, stated outright for all three. (Left to ``jax.grad``, the
    cotangent of an operand cast to bfloat16 would itself be rounded to
    bfloat16, which no float32 matmul at its default does.) With
    ``round_result`` each of the three results is rounded to bfloat16 too:
    one precision step below. ``a``, ``b``: matrices, or stacks of them with
    the same leading axes."""

    def product(a, b):
        out = jnp.matmul(
            a.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
            preferred_element_type=jnp.float32,
        )
        return out.astype(jnp.bfloat16).astype(jnp.float32) if round_result else out

    @jax.custom_vjp
    def mm(a, b):
        return product(a, b)

    def forward(a, b):
        return product(a, b), (a, b)

    def backward(kept, g):
        a, b = kept
        return product(g, jnp.swapaxes(b, -1, -2)), product(jnp.swapaxes(a, -1, -2), g)

    mm.defvjp(forward, backward)
    return mm


def _matmul(policy):
    if policy == "highest":
        return lambda a, b: jnp.matmul(a, b, precision=lax.Precision.HIGHEST)
    if policy == "default":  # on any backend: the system states it outright too
        return _rounded_matmul(round_result=False)
    if policy == "bfloat16":  # one step below "default": the results rounded too
        return _rounded_matmul(round_result=True)
    raise ValueError(f"no reference matmul policy {policy!r}")


def rms_norm(x, w, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def silu(x):
    return x * jax.nn.sigmoid(x)


def conv4(x, taps, seg):
    """``x``: (S, C); ``taps``: (C, K); ``y_t = sum_j taps[:, j] x_{t-j}``
    over the tokens ``t - j`` of ``t``'s own document."""
    y = jnp.zeros_like(x)
    for j in range(taps.shape[1]):
        back = jnp.pad(x, ((j, 0), (0, 0)))[: x.shape[0]]
        seg_back = jnp.pad(seg, (j, 0), constant_values=-1)[: x.shape[0]]
        y = y + jnp.where((seg_back == seg)[:, None], back, 0.0) * taps[:, j]
    return y


def delta_rule(q, k, v, beta, alpha, first):
    """The recurrence, token by token. ``q, k``: (S, H, d_k), ``v``: (S, H,
    d_v), ``beta, alpha``: (S, H), ``first``: (S,) bool. -> ``o`` (S, H,
    d_v). Written element by element: no matrix product, so no policy."""
    S, H, dk = q.shape
    dv = v.shape[-1]

    def token(state, xs):
        q_t, k_t, v_t, b_t, a_t, f_t = xs
        state = jnp.where(f_t, 0.0, state)  # (H, d_v, d_k)
        sk = jnp.sum(state * k_t[:, None, :], axis=-1)  # S k: (H, d_v)
        state = a_t[:, None, None] * (
            state - b_t[:, None, None] * sk[:, :, None] * k_t[:, None, :]
        ) + b_t[:, None, None] * v_t[:, :, None] * k_t[:, None, :]
        return state, jnp.sum(state * q_t[:, None, :], axis=-1)

    @jax.checkpoint
    def block(state, xs):
        return lax.scan(token, state, xs)

    n = -(-S // SCAN_BLOCK)
    pad = n * SCAN_BLOCK - S

    def blocked(a):
        a = jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1))
        return a.reshape(n, SCAN_BLOCK, *a.shape[1:])

    # padding tokens are documents of their own: they reset a state nobody reads
    xs = tuple(map(blocked, (q, k, v, beta, alpha))) + (
        jnp.pad(first, (0, pad), constant_values=True).reshape(n, SCAN_BLOCK),
    )
    _, o = lax.scan(block, jnp.zeros((H, dv, dk), jnp.float32), xs)
    return o.reshape(n * SCAN_BLOCK, H, dv)[:S]


def linear_attention(p, x, seg, m, mm):
    hk, hv = m["linear_num_key_heads"], m["linear_num_value_heads"]
    dk, dv = m["linear_key_head_dim"], m["linear_value_head_dim"]
    if hk != hv:
        raise ValueError("the reference covers linear_num_key_heads == value_heads")
    S = x.shape[0]
    q = silu(conv4(mm(x, p["Wq"].T), p["conv_q"], seg)).reshape(S, hk, dk)
    k = silu(conv4(mm(x, p["Wk"].T), p["conv_k"], seg)).reshape(S, hk, dk)
    v = silu(conv4(mm(x, p["Wv"].T), p["conv_v"], seg)).reshape(S, hv, dv)
    q = q * lax.rsqrt(jnp.sum(q * q, -1, keepdims=True) + NORM_EPS_L2) * dk**-0.5
    k = k * lax.rsqrt(jnp.sum(k * k, -1, keepdims=True) + NORM_EPS_L2)
    beta = jax.nn.sigmoid(mm(x, p["Wb"].T))
    if m["linear_allow_neg_eigval"]:
        beta = 2.0 * beta
    alpha = jnp.exp(
        -jnp.exp(p["A_log"]) * jax.nn.softplus(mm(x, p["Wa"].T) + p["dt_bias"])
    )
    first = jnp.concatenate([jnp.ones((1,), bool), seg[1:] != seg[:-1]])
    o = delta_rule(q, k, v, beta, alpha, first)
    o = rms_norm(o, p["o_norm"], m["rms_norm_eps"])
    gate = silu(mm(x, p["Wg"].T)).reshape(S, hv, dv)
    return mm((o * gate).reshape(S, hv * dv), p["Wo"].T)


def full_attention(p, x, seg, m, mm):
    if m["num_key_value_heads"] != m["num_attention_heads"]:
        raise ValueError("the reference covers num_key_value_heads == num_attention_heads")
    S, H = x.shape[0], m["num_attention_heads"]
    hd = m["hidden_size"] // H
    q = rms_norm(mm(x, p["Wq"].T), p["q_norm"], m["rms_norm_eps"]).reshape(S, H, hd)
    k = rms_norm(mm(x, p["Wk"].T), p["k_norm"], m["rms_norm_eps"]).reshape(S, H, hd)
    v = mm(x, p["Wv"].T).reshape(S, H, hd)
    kT = k.transpose(1, 2, 0)  # (H, hd, S)
    vH = v.transpose(1, 0, 2)  # (H, S, hd)
    at = jnp.arange(S)
    block = min(QUERY_BLOCK, S)
    n = -(-S // block)
    pad = n * block - S

    @jax.checkpoint
    def queries(xs):
        q_b, seg_b, at_b = xs  # (block, H, hd), (block,), (block,)
        scores = mm(q_b.transpose(1, 0, 2), kT) * hd**-0.5  # (H, block, S)
        mask = (seg_b[:, None] == seg[None, :]) & (at_b[:, None] >= at[None, :])
        scores = jnp.where(mask[None], scores, -jnp.inf)
        return mm(jax.nn.softmax(scores, axis=-1), vH).transpose(1, 0, 2)

    # padding queries sit at the last position, in the last document
    q_p = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(n, block, H, hd)
    seg_p = jnp.pad(seg, (0, pad), mode="edge").reshape(n, block)
    at_p = jnp.pad(at, (0, pad), mode="edge").reshape(n, block)
    o = lax.map(queries, (q_p, seg_p, at_p)).reshape(n * block, H * hd)[:S]
    return mm(o, p["Wo"].T)


def layer(p, x, seg, kind, m, mm):
    mix = linear_attention if kind == "linear_attention" else full_attention
    h = x + rms_norm(mix(p, x, seg, m, mm), p["attn_norm"], m["rms_norm_eps"])
    mlp = mm(silu(mm(h, p["W_gate"].T)) * mm(h, p["W_up"].T), p["W_down"].T)
    return h + rms_norm(mlp, p["mlp_norm"], m["rms_norm_eps"])


def row_loss(params, tokens, segments, m, mm, step_tokens):
    """One row's share of the step's mean cross-entropy. ``params`` is the
    list of layers ``session.params()`` flattens to: the embedding, the
    layers, the final norm and head."""
    inputs, targets, seg = tokens[:-1], tokens[1:], segments[:-1]
    x = params[0]["E"][inputs]
    for p, kind in zip(params[1:-1], m["layer_types"]):
        x = jax.checkpoint(functools.partial(layer, kind=kind, m=m, mm=mm))(p, x, seg)
    logits = mm(rms_norm(x, params[-1]["norm"], m["rms_norm_eps"]), params[-1]["W"].T)
    picked = jnp.take_along_axis(logits, targets[:, None], axis=-1)[:, 0]
    return jnp.sum(jax.nn.logsumexp(logits, axis=-1) - picked) / step_tokens


def make_reference(config):
    """-> ``run(params, tokens, segments)``: train on ``tokens``/``segments``
    of shape ``(steps, mubatches, rows, seq_len + 1)`` from ``params`` (the
    layers of ``session.params()`` in model order); returns ``(params,
    step_losses)`` as host arrays."""
    session = config["session"]
    if session["optimizer"] != "sgd":
        raise ValueError("references/olmo_hybrid.py covers SGD only")
    m = model_config(config)
    if len(m["layer_types"]) != m["num_hidden_layers"]:
        raise ValueError("layer_types does not list num_hidden_layers layers")
    mm, lr = _matmul(session["precision"]), session["lr"]

    @jax.jit
    def zeros(params):
        return jax.tree.map(jnp.zeros_like, params)

    def add_row(params, grads, loss, tokens, segments, step_tokens):
        l, g = jax.value_and_grad(row_loss)(params, tokens, segments, m, mm, step_tokens)
        return jax.tree.map(jnp.add, grads, g), loss + l

    add_row = jax.jit(add_row, donate_argnums=(1, 2))

    def descend(params, grads):
        return jax.tree.map(lambda w, g: w - lr * g, params, grads)

    descend = jax.jit(descend, donate_argnums=(0, 1))

    def run(params, tokens, segments):
        params = jax.tree.map(jnp.asarray, params)
        step_tokens = float(tokens.shape[1] * tokens.shape[2] * (tokens.shape[3] - 1))
        losses = []
        for t_step, s_step in zip(tokens, segments):
            grads, loss = zeros(params), jnp.zeros(())
            for t_mb, s_mb in zip(t_step, s_step):
                for t_row, s_row in zip(t_mb, s_mb):
                    grads, loss = add_row(
                        params, grads, loss, jnp.asarray(t_row), jnp.asarray(s_row),
                        step_tokens,
                    )
            params = descend(params, grads)
            losses.append(float(loss))
        return jax.device_get(params), losses

    return run
