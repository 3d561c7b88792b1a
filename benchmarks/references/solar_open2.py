"""Plain reference for the second token family (``solar_open2``: layers of
Kimi-style delta attention with a decay per key channel, ``kda``, and of gated
grouped-query attention without a positional term, ``gqa``, in the pattern
``gqa_layers`` gives; pre-norm residuals; in every layer a routed mixture of
SwiGLU experts with a shared expert; an embedding, a final RMSNorm, an untied
head, mean cross-entropy) trained by SGD: forward, loss and gradients in
straightforward ``jax.numpy`` with ``jax.grad``/``jax.vjp``, float32,
independent of ``shallowspeed_tpu``. No kernel, no chunked form, no sort, no
hand-written backward.

The equations, for one row of ``S`` tokens ``x_t`` in R^hidden whose documents
``segments`` gives; all norms RMS with ``rms_norm_eps``; a layer is ``h = x +
mix(norm1(x))``, ``y = h + moe(norm2(h))`` (pre-norm: assumed):

- ``kda``, ``H`` heads of ``d``: ``q, k = l2norm(SiLU(conv4(x W_q))),
  l2norm(SiLU(conv4(x W_k)))`` per head (``a / sqrt(|a|^2 + 1e-6)``), ``q``
  scaled by ``d^-1/2``; ``v = SiLU(conv4(x W_v))``; ``conv4`` is causal,
  depthwise, four taps a channel, and reads zero for a token of another
  document; ``beta_t = 2 sigmoid(x W_b)``, one a head; log decay ``g_t =
  -exp(A_log_h) softplus(x W_fa W_fb + dt_bias)``, a VECTOR of ``d`` a head;
  ``S_t = (I - beta_t k_t k_t^T) Diag(exp g_t) S_{t-1} + beta_t k_t v_t^T``
  with ``S_{t-1} := 0`` at a document's first token; ``o_t = S_t^T q_t``;
  ``mix = W_o [RMSNorm_head(o_t) * sigmoid(x W_ga W_gb)]``.
- ``gqa``: ``Q = x W_q`` (heads), ``K, V = x W_k, x W_v`` (key/value heads,
  each read by ``heads / kv_heads`` consecutive query heads), ``softmax(Q K^T
  / sqrt(head_dim) + M) V`` with ``M`` = causal and same document, no
  positional term; ``mix = W_o [o * sigmoid(x W_z)]``.
- ``moe``: ``s = sigmoid(x W_r)`` over ALL ``n_routed_experts``, float32 at
  ``Precision.HIGHEST`` whatever the policy; the ``num_experts_per_tok``
  largest are selected (the family's selection bias is zero and untrained: a
  constant, left out; the selection carries no gradient); ``w_e = s_e / sum
  of the selected s`` (``norm_topk_prob``), times ``routed_scaling_factor``;
  ``moe(x) = sum over the selected e that are HELD of w_e W2_e(SiLU(x W1_e) *
  x W3_e) + shared(x)``. The sum in ``w`` runs over all selected, held or
  not; an expert held elsewhere adds nothing here (``routed_experts_held =
  [lo, hi)``: the chip's share of the deployment). Written as a dense loop:
  every held expert takes every token, times a weight that is zero where the
  token did not select it.
- loss: the sum over every position of ``logsumexp(logits) - logits[target]``
  over the step's tokens (all microbatches).

Departures that change no arithmetic, so that the cell's size fits the chip
after the session is dropped (5.2 GB of parameters: a second and a third tree
of that size, the gradient and its running sum, do not fit beside a row's
working set): the model is differentiated LAYER BY LAYER. Each row's forward
keeps every layer's input; then, from the head down, one layer at a time,
``jax.vjp`` of that layer pulls each row's cotangent back and the rows'
parameter gradients are added in row order; the layer's update is applied
and the next layer down follows. The token scan is nested (blocks of
``SCAN_BLOCK`` tokens, each a checkpoint); attention is computed one block of
``QUERY_BLOCK`` queries at a time against all keys.

The matmul policy is the configuration's, as in ``olmo_hybrid.py``:
``highest`` is float32 at ``Precision.HIGHEST``; ``default`` rounds the
operands of every matrix product with a weight, of attention's two and of
the three of each one's backward to bfloat16 and accumulates in float32,
stated outright on any backend, because the system's ``ops.dense`` and
``ops.experts`` state it outright too; ``bfloat16``, one step below, rounds
the results too and is the control. The router's product is float32 under
every policy; the recurrence is written element by element and has none.
Everything runs under ``jax.default_matmul_precision("highest")``.

Also here: the operations and bytes the model's kernels need, for ``mfu`` and
the roofline shares.
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
    "vocab_size", "hidden_size", "num_hidden_layers", "num_attention_heads",
    "head_dim", "num_key_value_heads", "rms_norm_eps", "gqa_layers",
    "linear_attn_config", "kda_allow_neg_eigval", "n_routed_experts",
    "routed_experts_held", "n_shared_experts", "num_experts_per_tok",
    "moe_intermediate_size", "norm_topk_prob", "routed_scaling_factor",
)


def model_config(config):
    """The model's keys: the ``session``'s ``model`` where it is given as a
    dictionary (a rehearsal, a test), else the configuration file's own top
    level; ``layer_types`` (``gqa``/``kda`` a layer) and ``gate_rank`` (the
    low-rank projections' rank: ``kda_gate_rank``, one head's width where
    not given) are worked out beside them."""
    given = config.get("session", {}).get("model")
    source = given if isinstance(given, dict) else config
    m = {k: source[k] for k in MODEL_KEYS}
    gqa = set(m["gqa_layers"])
    m["layer_types"] = [
        "gqa" if i in gqa else "kda" for i in range(m["num_hidden_layers"])
    ]
    m["gate_rank"] = source.get("kda_gate_rank", m["linear_attn_config"]["head_dim"])
    return m


def _held(m):
    lo, hi = m["routed_experts_held"]
    return hi - lo


# -- counts and costs ---------------------------------------------------------

# benchmarks/datasets/packed_tokens.py's document lengths, copied: the cost of
# attention depends on the traffic, and the cost functions see no data
LENGTH_MEDIAN, LENGTH_SIGMA, LENGTH_MIN = 1024, 1.2, 16


@functools.lru_cache(maxsize=None)
def expected_pairs_per_token(seq_len):
    """The (query, key) pairs the causal, same-document mask admits per token
    under the generators' document lengths (lognormal, clipped to
    ``LENGTH_MIN`` ... ``seq_len``): ``E[L (L + 1) / 2] / E[L]`` from a
    fixed draw. A document cut at a row's end admits fewer, so this is a
    little high."""
    drawn = np.random.default_rng(0).lognormal(np.log(LENGTH_MEDIAN), LENGTH_SIGMA, 1 << 18)
    lengths = np.clip(np.rint(drawn), min(LENGTH_MIN, seq_len), seq_len)
    return float((lengths * (lengths + 1) / 2).mean() / lengths.mean())


def _products(m):
    """(in, out) of every matrix product with a weight that EVERY token
    takes, by layer kind, and the head's; the routed experts' are counted
    from the rows routed (``moe_train_flops``)."""
    d, hd = m["hidden_size"], m["head_dim"]
    heads, kv = m["num_attention_heads"], m["num_key_value_heads"]
    lin = m["linear_attn_config"]
    width, rank = lin["num_heads"] * lin["head_dim"], m["gate_rank"]
    shared = m["moe_intermediate_size"] * m["n_shared_experts"]
    ffn = [(d, m["n_routed_experts"]), (d, shared), (d, shared), (shared, d)]
    return {
        "gqa": [(d, heads * hd), (d, kv * hd), (d, kv * hd), (d, heads * hd),
                (heads * hd, d)] + ffn,
        "kda": [(d, width)] * 3 + [(width, d), (d, lin["num_heads"]),
                (d, rank), (rank, width), (d, rank), (rank, width)] + ffn,
        "head": [(d, m["vocab_size"])],
    }


def attention_train_flops(m, pairs):
    """One ``gqa`` layer's core over ``pairs`` admitted pairs: two products
    forward and four backward, 2 x head_dim x heads each a pair."""
    return 3 * 4 * m["head_dim"] * m["num_attention_heads"] * pairs


def scan_train_flops(m, tokens):
    """Forward and backward of one ``kda`` layer's rule in its recurrence
    form, per token and head: the decay of the state's rows (d_k d_v), ``S^T
    k`` (2 d_k d_v), the rank-one write ``k (beta (v - S^T k))^T`` (2 d_k
    d_v), ``S^T q`` (2 d_k d_v): 7 d_k d_v forward, twice that backward."""
    lin = m["linear_attn_config"]
    return 3 * 7 * lin["head_dim"] ** 2 * lin["num_heads"] * tokens


def scan_train_bytes(m, tokens):
    """``q, k, v, g`` (heads x d each) and ``beta`` read and ``o`` written
    forward; the same and ``do`` read and five gradients written backward;
    float32, once a pass."""
    lin = m["linear_attn_config"]
    width = lin["num_heads"] * lin["head_dim"]
    inputs = 4 * width + lin["num_heads"]
    return 4 * ((inputs + width) + (inputs + 2 * width) + inputs) * tokens


def moe_train_flops(m, rows):
    """The held experts' three products over ``rows`` (token, slot) pairs
    routed to them: 2 a weight forward, 4 backward."""
    return 6 * 3 * m["hidden_size"] * m["moe_intermediate_size"] * rows


def moe_train_bytes(m, rows, visits):
    """HBM bytes the held experts' products must move, float32: a row's input
    read and output written forward, input and cotangent read and input
    gradient written backward; and for each of ``visits`` (a held expert
    taking one microbatch's rows) its three weights read forward, read again
    and their gradients written backward."""
    d, ff = m["hidden_size"], m["moe_intermediate_size"]
    return 4 * (5 * d * rows + 3 * 3 * d * ff * visits)


def expected_rows_per_token(m):
    """(token, slot) pairs a token sends to the experts held here, a layer,
    under even routing."""
    return m["num_experts_per_tok"] * _held(m) / m["n_routed_experts"]


def train_flops_per_sample(config, pairs_per_token=None, rows_per_token=None):
    """What the equations require of one row of ``seq_len`` tokens, forward
    and backward (recomputed forwards are not counted): 6 FLOPs a weight of
    every matrix product a token takes, the routed experts' over the rows
    routed to the experts held (``rows_per_token`` a layer; even routing
    where not given), the per-channel rule in its recurrence form, attention
    over the pairs the mask admits (``expected_pairs_per_token`` where not
    given). The embedding is a lookup; norms, gates, the convolution, the
    top-k and the sort are not counted."""
    m, seq_len = model_config(config), config["session"]["seq_len"]
    per = _products(m)
    if pairs_per_token is None:
        pairs_per_token = expected_pairs_per_token(seq_len)
    if rows_per_token is None:
        rows_per_token = expected_rows_per_token(m)
    weights = sum(i * o for kind in m["layer_types"] + ["head"] for i, o in per[kind])
    flops = 6 * weights * seq_len
    for kind in m["layer_types"]:
        flops += moe_train_flops(m, rows_per_token * seq_len)
        if kind == "gqa":
            flops += attention_train_flops(m, pairs_per_token * seq_len)
        else:
            flops += scan_train_flops(m, seq_len)
    return flops


def matmul_bytes_per_sample(config, rows):
    """HBM bytes the three matmuls of every matrix product with a weight
    must move for one microbatch of ``rows`` rows, per row, in float32: each
    reads two operands and writes one result; the held experts' over the rows
    even routing sends them, each expert's weights once a microbatch."""
    m, tokens = model_config(config), rows * config["session"]["seq_len"]
    per = _products(m)
    total = 0
    for i, o in [p for kind in m["layer_types"] + ["head"] for p in per[kind]]:
        total += 4 * 3 * (tokens * i + i * o + tokens * o)
    d, ff = m["hidden_size"], m["moe_intermediate_size"]
    routed = expected_rows_per_token(m) * tokens
    total += len(m["layer_types"]) * 4 * 3 * 3 * (routed * (d + ff) + _held(m) * d * ff)
    return total / rows


# -- the model ----------------------------------------------------------------


def _rounded_matmul(round_result):
    """``a @ b`` with both operands rounded to bfloat16 and float32
    accumulation, and the same for the two products of its backward (``g
    b^T`` and ``a^T g``); with ``round_result`` each of the three results is
    rounded to bfloat16 too: one precision step below."""

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


def channel_delta_rule(q, k, v, beta, log_decay, first):
    """The recurrence, token by token. ``q, k, log_decay``: (S, H, d_k),
    ``v``: (S, H, d_v), ``beta``: (S, H), ``first``: (S,) bool. -> ``o`` (S,
    H, d_v). Written element by element: no matrix product, so no policy."""
    S, H, dk = q.shape
    dv = v.shape[-1]

    def token(state, xs):
        q_t, k_t, v_t, b_t, g_t, f_t = xs
        state = jnp.where(f_t, 0.0, state) * jnp.exp(g_t)[:, :, None]  # (H, d_k, d_v)
        read = jnp.sum(state * k_t[:, :, None], axis=1)  # S^T k: (H, d_v)
        state = state + k_t[:, :, None] * (b_t[:, None] * (v_t - read))[:, None, :]
        return state, jnp.sum(state * q_t[:, :, None], axis=1)

    @jax.checkpoint
    def block(state, xs):
        return lax.scan(token, state, xs)

    n = -(-S // SCAN_BLOCK)
    pad = n * SCAN_BLOCK - S

    def blocked(a):
        a = jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1))
        return a.reshape(n, SCAN_BLOCK, *a.shape[1:])

    # padding tokens are documents of their own: they reset a state nobody reads
    xs = tuple(map(blocked, (q, k, v, beta, log_decay))) + (
        jnp.pad(first, (0, pad), constant_values=True).reshape(n, SCAN_BLOCK),
    )
    _, o = lax.scan(block, jnp.zeros((H, dk, dv), jnp.float32), xs)
    return o.reshape(n * SCAN_BLOCK, H, dv)[:S]


def kda(p, x, seg, m, mm):
    lin = m["linear_attn_config"]
    H, d = lin["num_heads"], lin["head_dim"]
    S = x.shape[0]
    q = silu(conv4(mm(x, p["Wq"].T), p["conv_q"], seg)).reshape(S, H, d)
    k = silu(conv4(mm(x, p["Wk"].T), p["conv_k"], seg)).reshape(S, H, d)
    v = silu(conv4(mm(x, p["Wv"].T), p["conv_v"], seg)).reshape(S, H, d)
    q = q * lax.rsqrt(jnp.sum(q * q, -1, keepdims=True) + NORM_EPS_L2) * d**-0.5
    k = k * lax.rsqrt(jnp.sum(k * k, -1, keepdims=True) + NORM_EPS_L2)
    beta = jax.nn.sigmoid(mm(x, p["Wb"].T))
    if m["kda_allow_neg_eigval"]:
        beta = 2.0 * beta
    decay = (mm(mm(x, p["W_fa"].T), p["W_fb"].T) + p["dt_bias"]).reshape(S, H, d)
    log_decay = -jnp.exp(p["A_log"])[:, None] * jax.nn.softplus(decay)
    first = jnp.concatenate([jnp.ones((1,), bool), seg[1:] != seg[:-1]])
    o = channel_delta_rule(q, k, v, beta, log_decay, first)
    o = rms_norm(o, p["o_norm"], m["rms_norm_eps"])
    gate = jax.nn.sigmoid(mm(mm(x, p["W_ga"].T), p["W_gb"].T)).reshape(S, H, d)
    return mm((o * gate).reshape(S, H * d), p["Wo"].T)


def gqa(p, x, seg, m, mm):
    S, H, KV, hd = x.shape[0], m["num_attention_heads"], m["num_key_value_heads"], m["head_dim"]
    q = mm(x, p["Wq"].T).reshape(S, H, hd)
    # query head h reads key/value head h // (H / KV): the heads, repeated
    k = jnp.repeat(mm(x, p["Wk"].T).reshape(S, KV, hd), H // KV, axis=1)
    v = jnp.repeat(mm(x, p["Wv"].T).reshape(S, KV, hd), H // KV, axis=1)
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
    return mm(o * jax.nn.sigmoid(mm(x, p["Wz"].T)), p["Wo"].T)


def routing(p, x, m):
    """-> ``(S, n_routed_experts)``: each token's weight on every published
    expert, zero where it did not select it."""
    scores = jax.nn.sigmoid(jnp.matmul(x, p["W_r"].T, precision=lax.Precision.HIGHEST))
    top = m["num_experts_per_tok"]
    # the ``top`` largest, ties to the lower index; no gradient through the choice
    sel = jnp.argsort(-lax.stop_gradient(scores), axis=-1, stable=True)[:, :top]
    chosen = jnp.zeros(scores.shape, bool).at[jnp.arange(x.shape[0])[:, None], sel].set(True)
    picked = jnp.where(chosen, scores, 0.0)
    if m["norm_topk_prob"]:
        picked = picked / jnp.sum(picked, axis=-1, keepdims=True)
    return picked * m["routed_scaling_factor"]


def moe(p, x, m, mm):
    lo, hi = m["routed_experts_held"]
    weights = routing(p, x, m)
    out = mm(silu(mm(x, p["Ws1"].T)) * mm(x, p["Ws3"].T), p["Ws2"].T)
    for e in range(hi - lo):  # a dense loop: every held expert takes every token
        expert = mm(silu(mm(x, p["W1"][e].T)) * mm(x, p["W3"][e].T), p["W2"][e].T)
        out = out + weights[:, lo + e, None] * expert
    return out


def layer(p, x, seg, kind, m, mm):
    mix = gqa if kind == "gqa" else kda
    h = x + mix(p, rms_norm(x, p["attn_norm"], m["rms_norm_eps"]), seg, m, mm)
    return h + moe(p, rms_norm(h, p["mlp_norm"], m["rms_norm_eps"]), m, mm)


def head_loss(head, x, targets, m, mm, step_tokens):
    logits = mm(rms_norm(x, head["norm"], m["rms_norm_eps"]), head["W"].T)
    picked = jnp.take_along_axis(logits, targets[:, None], axis=-1)[:, 0]
    return jnp.sum(jax.nn.logsumexp(logits, axis=-1) - picked) / step_tokens


def row_loss(params, tokens, segments, m, mm, step_tokens):
    """One row's share of the step's mean cross-entropy, whole (the tests'
    form; ``make_reference`` computes the same layer by layer). ``params``:
    the embedding, the layers, the final norm and head."""
    inputs, targets, seg = tokens[:-1], tokens[1:], segments[:-1]
    x = params[0]["E"][inputs]
    for p, kind in zip(params[1:-1], m["layer_types"]):
        x = layer(p, x, seg, kind, m, mm)
    return head_loss(params[-1], x, targets, m, mm, step_tokens)


def make_reference(config):
    """-> ``run(params, tokens, segments)``: train on ``tokens``/``segments``
    of shape ``(steps, mubatches, rows, seq_len + 1)`` from ``params`` (the
    layers of ``session.params()`` in model order); returns ``(params,
    step_losses)`` as host arrays."""
    session = config["session"]
    if session["optimizer"] != "sgd":
        raise ValueError("references/solar_open2.py covers SGD only")
    m = model_config(config)
    mm, lr = _matmul(session["precision"]), session["lr"]

    @functools.partial(jax.jit, static_argnames="kind")
    def forward(p, x, seg, kind):
        return layer(p, x, seg, kind, m, mm)

    @functools.partial(jax.jit, static_argnames="kind", donate_argnums=(0, 4))
    def backward(acc, p, x, seg, dy, kind):
        """One row through one layer, backwards: the layer's gradient added
        to ``acc``, and the cotangent of its input."""
        _, pull = jax.vjp(lambda p, x: layer(p, x, seg, kind, m, mm), p, x)
        dp, dx = pull(dy)
        return jax.tree.map(jnp.add, acc, dp), dx

    @functools.partial(jax.jit, donate_argnums=(0,))
    def head_backward(acc, head, x, targets, step_tokens):
        loss, (dhead, dx) = jax.value_and_grad(head_loss, argnums=(0, 1))(
            head, x, targets, m, mm, step_tokens
        )
        return jax.tree.map(jnp.add, acc, dhead), dx, loss

    @functools.partial(jax.jit, donate_argnums=(0,))
    def embed_backward(acc, inputs, dx):
        return {"E": acc["E"].at[inputs].add(dx)}

    @functools.partial(jax.jit, donate_argnums=(0,))
    def descend(p, grads):
        return jax.tree.map(lambda w, g: w - lr * g, p, grads)

    zeros = jax.jit(lambda p: jax.tree.map(jnp.zeros_like, p))

    def step(params, rows, step_tokens):
        """One optimizer step over ``rows`` (tokens, segments) pairs."""
        inputs = [jnp.asarray(t[:-1]) for t, _ in rows]
        targets = [jnp.asarray(t[1:]) for t, _ in rows]
        segs = [jnp.asarray(s[:-1]) for _, s in rows]
        kept = []  # per row: the input of every layer, and the head's
        for tok, seg in zip(inputs, segs):
            xs = [params[0]["E"][tok]]
            for p, kind in zip(params[1:-1], m["layer_types"]):
                xs.append(forward(p, xs[-1], seg, kind))
            kept.append(xs)
        acc, loss, cots = zeros(params[-1]), 0.0, []
        for xs, tgt in zip(kept, targets):
            acc, dx, row_loss_ = head_backward(acc, params[-1], xs.pop(), tgt, step_tokens)
            cots.append(dx)
            loss += float(row_loss_)
        params[-1] = descend(params[-1], acc)
        for index in reversed(range(1, len(params) - 1)):
            kind, acc = m["layer_types"][index - 1], zeros(params[index])
            for r, (xs, seg) in enumerate(zip(kept, segs)):
                acc, cots[r] = backward(acc, params[index], xs.pop(), seg, cots[r], kind)
            params[index] = descend(params[index], acc)
        acc = zeros(params[0])
        for tok, dx in zip(inputs, cots):
            acc = embed_backward(acc, tok, dx)
        params[0] = descend(params[0], acc)
        return loss

    def run(params, tokens, segments):
        with jax.default_matmul_precision("highest"):
            params = [jax.tree.map(jnp.asarray, layer_) for layer_ in params]
            step_tokens = float(tokens.shape[1] * tokens.shape[2] * (tokens.shape[3] - 1))
            losses = []
            for t_step, s_step in zip(tokens, segments):
                rows = [
                    (t_row, s_row)
                    for t_mb, s_mb in zip(t_step, s_step)
                    for t_row, s_row in zip(t_mb, s_mb)
                ]
                losses.append(step(params, rows, step_tokens))
            return jax.device_get(params), losses

    return run
