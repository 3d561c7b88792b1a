"""The names the device programs give their own work, and where to look a
compiled program's instructions up by them.

``SCOPES`` is the one list of ``jax.named_scope`` names the package uses
(``scope(name)`` / ``scoped(name)`` refuse any other). A scope is metadata:
it reaches ``Compiled.as_text()`` and the profiler as the instruction's
``op_name`` path (``jit(epoch_core)/while/body/tick/cond/branch_1_fun/
linear/fwd/dot_general``) and changes no operation, fusion or number. Each
scope belongs to a class, the unit the benchmark's class table
(``benchmarks/optable.py``) sums device time by:

    linear     linear/fwd, linear/dgrad, linear/wgrad   (ops.py)
    pointwise  act, softmax, loss, head_grad            (ops.py)
    stash      stash, unstash        (executor: activation/grad stashes)
    mailbox    mail                  (executor: relay mailboxes, payloads)
    relay      relay                 (executor: the ppermutes a tick has due)
    grad_acc   acc                   (microbatch gradient accumulation)
    sync       sync/dp, sync/pp      (gradsync, executor step tail)
    update     update                (optimizer.py: apply, clip, norms)
    batch      batch                 (one step's / microbatch's rows)
    gdn_scan   gdn/scan              (ops.py: the chunked gated delta rule)
    attn       attn/core             (ops.py: blocked attention under the
                                      causal, same-document mask)
    token_mix  gdn/conv, gdn/gate, norm, swiglu, fanin
                                     (ops.py: the token model's pointwise work;
                                      ``fanin`` sums the cotangents that meet
                                      at one value)
    head       embed, head/xent      (ops.py: embedding, cross-entropy)
    kda_scan   kda/scan              (ops.py: the chunked delta rule with a
                                      decay per key channel)
    moe_route  moe/route             (ops.py: the router's scores, the top-k,
                                      the sort of the (token, slot) pairs, each
                                      tile's gather and weighted scatter)
    moe_experts  moe/experts         (ops.py: the grouped products of the
                                      experts held, forward and backward)

The token model's ops trace forward and backward under the op's scope; where
a backward is ``jax.vjp``'s, the transform wraps what FOLLOWS the scope in
the path (``gdn/scan/transpose(jvp(...))/mul``), so the scope is still the
last well-formed one.

``tick`` (the executor's whole tick body) is a parent of the others and has
no class of its own. What no scope covers (compiler-inserted copies, loop
control) is classed by ``program_audit.op_index`` from the instruction
itself: ``control``, ``container``, or a carry leaf's class.

JAX's persistent compilation cache keys a program AFTER stripping this
metadata, so a tree with other scope names would load this tree's
executables and lose its own names: ``CACHE_TAG`` (derived from ``SCOPES``)
names the cache sub-directory, see ``compile_cache.enable_compile_cache``.

The registry (``register_program`` / ``program_index``) is how a trace
reader reaches the op index of the program a session ran without the
session: it keeps the jitted callable and the arguments' shapes, never an
array, and lowers, compiles (a persistent-cache hit) and parses only when
somebody asks.
"""

import functools
import zlib

import jax

# scope -> class; ``None``: a parent scope that decides no class
_CLASS_OF = {
    "linear/fwd": "linear",
    "linear/dgrad": "linear",
    "linear/wgrad": "linear",
    "act": "pointwise",
    "softmax": "pointwise",
    "loss": "pointwise",
    "head_grad": "pointwise",
    "stash": "stash",
    "unstash": "stash",
    "mail": "mailbox",
    "relay": "relay",
    "acc": "grad_acc",
    "sync/dp": "sync",
    "sync/pp": "sync",
    "update": "update",
    "batch": "batch",
    "tick": None,
    "gdn/scan": "gdn_scan",
    "attn/core": "attn",
    "gdn/conv": "token_mix",
    "gdn/gate": "token_mix",
    "norm": "token_mix",
    "swiglu": "token_mix",
    "fanin": "token_mix",
    "embed": "head",
    "head/xent": "head",
    "kda/scan": "kda_scan",
    "moe/route": "moe_route",
    "moe/experts": "moe_experts",
}
SCOPES = tuple(_CLASS_OF)

# Bump when a scope MOVES to other code without any name changing: the
# compile cache cannot tell such a tree from its parent (see CACHE_TAG).
_SALT = "2"  # PR 29: ``mail`` also names the mailboxes' allocation


def cache_tag(names=SCOPES, salt=_SALT):
    """A short constant naming this tree's scope set, the same in every
    process of one tree and different for any other set of names."""
    return f"s{zlib.crc32('|'.join((salt,) + tuple(names)).encode()):08x}"


CACHE_TAG = cache_tag()


def scope(name):
    """``with scope("stash"): ...`` — ``jax.named_scope`` for a listed name."""
    if name not in _CLASS_OF:
        raise ValueError(f"unknown scope {name!r}: list it in scopes.SCOPES")
    return jax.named_scope(name)


def scoped(name):
    """Decorator form: the whole function body traces under ``scope(name)``."""
    scope(name)  # refuse an unknown name where the function is defined

    def wrap(fn):
        @functools.wraps(fn)
        def inner(*args, **kwargs):
            with jax.named_scope(name):
                return fn(*args, **kwargs)

        return inner

    return wrap


def scope_of(op_name):
    """``(scope, class)`` of the LAST known scope in an ``op_name`` path,
    ``(None, None)`` where there is none. ``tick`` alone gives ``("tick",
    None)``: known, but it decides no class."""
    path = f"/{op_name}/"
    best, at = None, -1
    for name in SCOPES:
        i = path.rfind(f"/{name}/")
        if i > at or (i == at and i >= 0 and len(name) > len(best)):
            best, at = name, i
    return (best, _CLASS_OF[best]) if best else (None, None)


# -- the registry -------------------------------------------------------------

_programs = {}  # module name -> (jitted callable, abstract arguments)
_indexes = {}  # module name -> op index, built on demand
_counts = {}  # module name -> what the program's resident set holds


def _abstract(leaf):
    """A ``jax.Array`` as its shape, dtype and (where it was placed on
    purpose) sharding; anything else as it is."""
    if not isinstance(leaf, jax.Array):
        return leaf
    sharding = leaf.sharding if leaf.committed else None
    return jax.ShapeDtypeStruct(leaf.shape, leaf.dtype, sharding=sharding)


def register_program(jit_fn, args):
    """Remember how to rebuild the program ``jit_fn(*args)`` runs, under the
    name XLA gives its module (``"jit_" + jit_fn.__name__``): the callable
    and the arguments' abstract values. Holds no array, compiles nothing.
    Returns the name."""
    name = f"jit_{jit_fn.__name__}"
    _programs[name] = (jit_fn, jax.tree.map(_abstract, tuple(args)))
    _indexes.pop(name, None)
    return name


def record_counts(name, counts):
    """Remember what the resident set of the program called ``name`` holds
    (a token model's: ``tokens``, ``documents`` and ``pairs``, the (query,
    key) pairs the causal, same-document mask admits, per epoch). Plain
    numbers, readable after the session is gone, as the op index is."""
    _counts[name] = dict(counts)


def program_counts(name):
    """What ``record_counts`` was given for ``name``, or ``None``."""
    return _counts.get(name)


def registered(name):
    """``(jitted callable, abstract arguments)`` or ``None``."""
    return _programs.get(name)


def program_index(name):
    """The op index (``program_audit.op_index``) of the registered program
    called ``name``, or ``None`` where none is registered. The first call
    lowers and compiles from the stored shapes, a persistent-cache hit after
    the run that registered it, and keeps the parsed index; it works after
    ``jax.clear_caches()``."""
    if name not in _indexes:
        entry = _programs.get(name)
        if entry is None:
            return None
        from shallowspeed_tpu.observability.program_audit import op_index

        jit_fn, args = entry
        _indexes[name] = op_index(jit_fn.lower(*args).compile().as_text())
    return _indexes[name]
