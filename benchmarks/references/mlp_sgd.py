"""Plain reference for an MLP trained by SGD on the upstream's loss: the
equations of ``tests/oracle_numpy.py`` (relu Linears, last Linear without
relu, softmax whose stability maximum is taken over the whole microbatch and
whose denominator carries +1e-7, MSE scaled once by the GLOBAL batch size,
gradients summed over microbatches, ``w -= lr * g``) in straightforward
``jax.numpy``: no kernels, no scan, no fusion hints, one jitted
forward-and-backward per microbatch and a Python loop around it, as the
upstream's worker loops.

The matmul policy is the configuration's: ``highest`` computes in float32
with ``Precision.HIGHEST``; ``default`` is what the chip does with a float32
matmul left at its default, stated outright: operands rounded to bfloat16,
products accumulated in float32. A CPU has no such default (it multiplies in
float32), so off the chip, in a rehearsal, ``default`` is float32 too.

Also here, because they belong to the model and not to a cell: the
operations and bytes its matmuls need, for ``mfu`` and the roofline share.
"""

import jax
import jax.numpy as jnp
from jax import lax


def train_flops_per_sample(config):
    """fwd 2P + dgrad 2P + wgrad 2P for P = sum(in x out); biases, relu and
    the 10-wide head are noise beside the matmuls and are not counted
    (``costmodel.mlp_train_flops_per_sample``, copied)."""
    sizes = config["session"]["sizes"]
    return 6 * sum(a * b for a, b in zip(sizes, sizes[1:]))


def matmul_bytes_per_sample(config, rows):
    """HBM bytes the three matmuls of every Linear must move for one
    microbatch of ``rows`` rows, per sample, in float32: each reads two
    operands and writes one result, none of it reused between matmuls. The
    first Linear has no input gradient."""
    sizes = config["session"]["sizes"]
    total = 0
    for layer, (i, o) in enumerate(zip(sizes, sizes[1:])):
        fwd = rows * i + i * o + rows * o
        wgrad = rows * o + rows * i + i * o
        dgrad = rows * o + i * o + rows * i if layer else 0
        total += 4 * (fwd + wgrad + dgrad)
    return total / rows


def _matmul(policy):
    if policy == "highest":
        return lambda a, b: jnp.matmul(a, b, precision=lax.Precision.HIGHEST)
    if policy == "default":
        if jax.default_backend() != "tpu":
            return jnp.matmul
        return lambda a, b: jnp.matmul(
            a.astype(jnp.bfloat16),
            b.astype(jnp.bfloat16),
            preferred_element_type=jnp.float32,
        )
    if policy == "bfloat16":  # one step below "default": the result rounded too
        return lambda a, b: jnp.matmul(
            a.astype(jnp.bfloat16), b.astype(jnp.bfloat16)
        ).astype(jnp.float32)
    raise ValueError(f"no reference matmul policy {policy!r}")


def make_reference(config):
    """-> ``run(params, xb, yb)``: train on ``xb``/``yb`` of shape
    ``(steps, mubatches, rows, dim)`` from ``params`` (a list of
    ``{"W": (out, in), "b": (1, out)}``); returns ``(params, step_losses)``
    as host arrays."""
    session = config["session"]
    if config["activation"] != "relu" or session["optimizer"] != "sgd":
        raise ValueError("references/mlp_sgd.py covers relu MLPs under SGD only")
    mm = _matmul(session["precision"])
    lr = session["lr"]

    @jax.jit
    def microbatch(params, x, y, global_batch):
        acts, masks = [x], []
        for layer in params[:-1]:
            z = mm(acts[-1], layer["W"].T) + layer["b"]
            masks.append(z > 0)
            acts.append(jnp.maximum(z, 0.0))
        z = mm(acts[-1], params[-1]["W"].T) + params[-1]["b"]
        e = jnp.exp(z - jnp.max(z))
        p = e / (e.sum(axis=1, keepdims=True) + 1e-7)
        loss = ((y - p) ** 2).sum() / global_batch
        g = p * (-2.0 * (y - p) / global_batch)
        g = g - p * g.sum(axis=1, keepdims=True)
        grads = [None] * len(params)
        for l in reversed(range(len(params))):
            if l < len(masks):
                g = g * masks[l]
            grads[l] = {
                "W": mm(g.T, acts[l]),
                "b": g.sum(axis=0, keepdims=True),
            }
            if l:
                g = mm(g, params[l]["W"])
        return grads, loss

    @jax.jit
    def add(a, b):
        return jax.tree.map(jnp.add, a, b)

    @jax.jit
    def descend(params, grads):
        return jax.tree.map(lambda w, g: w - lr * g, params, grads)

    def run(params, xb, yb):
        params = jax.tree.map(jnp.asarray, params)
        global_batch = float(xb.shape[1] * xb.shape[2])
        losses = []
        for x_step, y_step in zip(xb, yb):
            grads, loss = None, 0.0
            for x, y in zip(x_step, y_step):
                g, l = microbatch(params, jnp.asarray(x), jnp.asarray(y), global_batch)
                grads = g if grads is None else add(grads, g)
                loss = loss + l
            params = descend(params, grads)
            losses.append(float(loss))
        return jax.device_get(params), losses

    return run
