"""Optimizers over parameter pytrees, applied on-device inside the jitted step.

Capability parity: the reference ships plain stateless SGD
(/root/reference/shallowspeed/optimizer.py:4-13, ``param.data -= lr * grad``).
Here the update is a pytree map that XLA fuses into the training step — no
host round-trip per parameter — plus stateful optimizers (momentum, Adam)
the reference has no plumbing for.

State protocol: ``init(params)`` returns the state pytree (``()`` =
stateless); ``apply(params, grads, state) -> (new_params, new_state)`` must
be ELEMENTWISE over param leaves (that is what makes ZeRO-1 chunking and the
padded-stack executor exact); ``state_layout()`` names the state's parts for
layout-independent checkpointing — a dict mapping state key to kind:

    SGD      -> {}                                (no state)
    Momentum -> {"": "params"}                    (state IS one params mirror)
    Adam     -> {"m": "params", "v": "params", "t": "scalar"}

"params" parts mirror the param pytree (stored per logical layer, like the
weights); "scalar" parts are 0-d arrays (stored in checkpoint metadata,
replicated on every device).
"""

import dataclasses

import jax

from shallowspeed_tpu.observability.scopes import scoped


@dataclasses.dataclass(frozen=True)
class SGD:
    """Stateless SGD. ``apply`` returns new params; grads are SUMS over the
    global batch (the loss is pre-scaled by the global batch size), so no
    averaging happens here — same ledger as the reference.

    ``weight_decay``: decoupled (applied directly to params, not through the
    gradient), so it stays elementwise — exact under padding and ZeRO-1
    chunking like the update itself. Default 0 = reference parity.
    """

    lr: float
    weight_decay: float = 0.0

    def init(self, params):
        return ()  # no optimizer state

    def state_layout(self):
        return {}

    def _decay(self, p):
        return p * _decay_factor(self.lr, self.weight_decay) if self.weight_decay else p

    @scoped("update")
    def apply(self, params, grads, state=()):
        new = jax.tree.map(lambda p, g: self._decay(p) - self.lr * g, params, grads)
        return new, state


@dataclasses.dataclass(frozen=True)
class MomentumSGD:
    """Heavy-ball SGD: v <- mu*v + g; p <- p - lr*v.

    The reference ships only plain SGD; this exists to exercise (and prove)
    the optimizer-state plumbing: state is a pytree mirroring the params, it
    threads through the sequential trainer AND the pipeline executor
    identically, so stateful optimizers keep the distributed == sequential
    invariant (tests/test_optimizer_state.py)."""

    lr: float
    momentum: float = 0.9
    weight_decay: float = 0.0

    def init(self, params):
        import jax.numpy as jnp

        return jax.tree.map(lambda p: jnp.zeros(p.shape, p.dtype), params)

    def state_layout(self):
        return {"": "params"}

    def _decay(self, p):
        return p * _decay_factor(self.lr, self.weight_decay) if self.weight_decay else p

    @scoped("update")
    def apply(self, params, grads, state):
        velocity = jax.tree.map(lambda v, g: self.momentum * v + g, state, grads)
        new = jax.tree.map(lambda p, v: self._decay(p) - self.lr * v, params, velocity)
        return new, velocity


@dataclasses.dataclass(frozen=True)
class Adam:
    """Adam (Kingma & Ba 2014), elementwise over param leaves.

    Grads in this framework are SUMS over the global batch (the loss is
    pre-scaled by the global batch size), identical on every layout, so the
    moment estimates are layout-independent too. State is a dict
    {"m", "v", "t"}: two params mirrors plus one shared step counter — the
    multi-part state that exercises the full state_layout protocol
    (checkpoints, stacked pp sharding, ZeRO-1 chunking)."""

    lr: float
    b1: float = 0.9
    b2: float = 0.999
    eps: float = 1e-8
    weight_decay: float = 0.0  # decoupled (AdamW); 0 = plain Adam

    def init(self, params):
        import jax.numpy as jnp

        zeros = lambda: jax.tree.map(  # noqa: E731
            lambda p: jnp.zeros(p.shape, p.dtype), params
        )
        return {"m": zeros(), "v": zeros(), "t": jnp.zeros((), jnp.float32)}

    def state_layout(self):
        return {"m": "params", "v": "params", "t": "scalar"}

    @scoped("update")
    def apply(self, params, grads, state):
        import jax.numpy as jnp

        t = state["t"] + 1.0
        m = jax.tree.map(
            lambda m_, g: self.b1 * m_ + (1 - self.b1) * g, state["m"], grads
        )
        v = jax.tree.map(
            lambda v_, g: self.b2 * v_ + (1 - self.b2) * g * g, state["v"], grads
        )
        c1 = 1.0 - self.b1**t
        c2 = 1.0 - self.b2**t
        wd = _decay_factor(self.lr, self.weight_decay) if self.weight_decay else 1.0
        new = jax.tree.map(
            lambda p, m_, v_: p * wd
            - self.lr * (m_ / c1) / (jnp.sqrt(v_ / c2) + self.eps),
            params,
            m,
            v,
        )
        return new, {"m": m, "v": v, "t": t}


class WithGradScratch:
    """An optimizer whose state also keeps the step's accumulated gradient,
    ``{"opt": the wrapped optimizer's state, "grads": a tree like params}``:
    ``apply`` hands the gradient it was given back as ``"grads"``. The point
    is where the accumulator LIVES: a step that loops over its microbatches
    (``trainer._token_step_scanned``) starts its accumulator from the
    ``"grads"`` it was handed (a donated argument) and returns it there (the
    output that aliases it), so the compiler places it with the arguments;
    as a temporary inside the program it lay between the working set's
    buffers and the 4-layer expert model's step did not fit the chip
    (PERF.md section 6, PR 35). What ``"grads"`` holds between steps is never
    read: the first microbatch takes it as zero."""

    def __init__(self, inner):
        self.inner = inner

    def __getattr__(self, name):  # lr, momentum, weight_decay: the wrapped one's
        return getattr(self.inner, name)

    def init(self, params):
        import jax.numpy as jnp

        return {
            "opt": self.inner.init(params),
            "grads": jax.tree.map(lambda p: jnp.zeros(p.shape, p.dtype), params),
        }

    def state_layout(self):
        return {**{f"opt/{k}": v for k, v in self.inner.state_layout().items()},
                "grads": "params"}

    def apply(self, params, grads, state):
        new, inner_state = self.inner.apply(params, grads, state["opt"])
        return new, {"opt": inner_state, "grads": grads}


def is_stateless(opt) -> bool:
    """True iff the optimizer carries no state (e.g. SGD). Answered by the
    state_layout() protocol — the single source of truth every call site
    branches on."""
    return not opt.state_layout()


def make_optimizer(name: str, lr: float, momentum: float = 0.9, weight_decay: float = 0.0):
    """Optimizer registry for the CLI/API surface (reference hardwires SGD,
    train.py:107). ``weight_decay`` is decoupled and UNIFORM over every
    param element including biases — uniformity is what keeps the update
    exact under ZeRO-1's flat chunking."""
    if weight_decay:
        _decay_factor(lr, weight_decay)  # validate eagerly, not at trace time
    if name == "sgd":
        return SGD(lr, weight_decay=weight_decay)
    if name == "momentum":
        return MomentumSGD(lr, momentum, weight_decay=weight_decay)
    if name == "adam":
        return Adam(lr, weight_decay=weight_decay)
    raise ValueError(
        f"optimizer must be one of ['adam', 'momentum', 'sgd'], got {name!r}"
    )


def clip_scale(grads_sq_sum, clip_norm):
    """Global-norm clip factor: min(1, clip/||g||) from the SUM OF SQUARES of
    the full gradient (callers supply the cross-device total where grads are
    sharded). One definition shared by every execution path."""
    import jax.numpy as jnp

    norm = jnp.sqrt(grads_sq_sum)
    return jnp.minimum(1.0, clip_norm / jnp.maximum(norm, 1e-12))


def tree_sq_sum(tree, cross_device_sum=None):
    """Sum of squares over every leaf of a pytree, optionally reduced by
    ``cross_device_sum`` (a callable, e.g. a psum over the axes the tree is
    sharded across). The shared input of both the clip factor and the
    grad-norm telemetry (observability aux outputs), so the two always agree
    on what "the global norm" means."""
    import jax
    import jax.numpy as jnp

    sq = sum(jnp.sum(g * g) for g in jax.tree.leaves(tree))
    if cross_device_sum is not None:
        sq = cross_device_sum(sq)
    return sq


@scoped("update")
def global_norm(tree, cross_device_sum=None):
    """Global L2 norm over every leaf of a pytree (see ``tree_sq_sum``)."""
    import jax.numpy as jnp

    return jnp.sqrt(tree_sq_sum(tree, cross_device_sum))


@scoped("update")
def clip_tree(grads, clip_norm, cross_device_sum=None):
    """Scale a gradient pytree by the global-norm clip factor. The local
    sum-of-squares is optionally reduced by ``cross_device_sum`` (a callable,
    e.g. a psum over the axes the gradient is sharded across) before the
    factor is computed — the ONE implementation behind the sequential,
    pipeline and ZeRO-1 paths (which differ only in that reduction)."""
    import jax

    sq = tree_sq_sum(grads, cross_device_sum)
    s = clip_scale(sq, clip_norm)
    return jax.tree.map(lambda g: g * s, grads)


def _decay_factor(lr, weight_decay):
    """Decoupled weight decay multiplier (1 - lr*wd); validated once here —
    the single definition all optimizers share."""
    if weight_decay < 0:
        raise ValueError(f"weight_decay must be >= 0, got {weight_decay}")
    f = 1.0 - lr * weight_decay
    if f <= 0:
        raise ValueError(
            f"lr * weight_decay = {lr * weight_decay} >= 1 would flip the "
            "decay factor's sign"
        )
    return f


def split_state(opt, state):
    """State pytree -> ({key: params-mirroring subtree}, {key: scalar}),
    keyed per ``state_layout()``. The inverse is ``join_state``."""
    layout = opt.state_layout()
    parts, scalars = {}, {}
    for key, kind in layout.items():
        sub = state if key == "" else state[key]
        (parts if kind == "params" else scalars)[key] = sub
    return parts, scalars


def join_state(opt, parts, scalars):
    """({key: subtree}, {key: scalar}) -> the state pytree ``apply`` expects."""
    layout = opt.state_layout()
    if not layout:
        return ()
    if set(layout) == {""}:
        return parts[""]
    return {
        key: (parts[key] if kind == "params" else scalars[key])
        for key, kind in layout.items()
    }
