"""Utilities: layout-independent model hashing + replica-sync verification.

Capability parity with /root/reference/shallowspeed/utils.py (rank-0 print,
SHA1-of-SHA1s model hash, cross-replica sync assert), strengthened for the
mesh world: the hash is computed over the *logical* per-layer (W, b) blocks in
global layer order, so a sequential run, a DP=4 run and a DP=2xPP=4 run of the
same model produce the SAME hash — the reference could only compare hashes
within one layout (utils.py:13-31).
"""

from hashlib import sha1

import jax
import numpy as np


def iter_param_blocks(params_list):
    """Yield ``(global_layer, key, float32_array)`` for every logical (W, b)
    block of a logical params tree, in global layer order.

    This is the ONE digest-block definition shared by ``model_hash``, the
    per-layer checksum stream (``layer_digests`` and the in-program scan
    aux that mirrors it) and the divergence comparator: the exact float32
    bytes each of them hashes/sums come from here, so the hash and the
    digest stream can never disagree about what a "block" is.

    ``params_list``: list (per stage) of lists of {"W","b"} arrays (jax or
    numpy).
    """
    gl = 0
    for stage in params_list:
        for layer in stage:
            for key in ("W", "b"):
                yield gl, key, np.ascontiguousarray(
                    jax.device_get(layer[key]), np.float32
                )
            gl += 1


def model_hash(params_list) -> str:
    """SHA1 over concatenated per-parameter SHA1s, in global layer order.

    Mirrors reference utils.py:13-24 (sha1 of each param's bytes,
    concatenated, re-hashed); the bytes hashed are exactly the
    ``iter_param_blocks`` blocks, so the hash and the divergence digest
    stream share one block definition (the hash value itself is pinned by
    tests/test_divergence.py).
    """
    acc = ""
    for _gl, _key, arr in iter_param_blocks(params_list):
        acc += sha1(arr.tobytes()).hexdigest()
    return sha1(acc.encode("utf-8")).hexdigest()


def tree_hash(params_list) -> str:
    """``model_hash`` for a parameter tree that is not ``{W, b}`` slots (a
    token model's): SHA1 over the per-leaf SHA1s of the float32 bytes, layers
    in order, a layer's leaves by sorted name."""
    acc = ""
    for stage in params_list:
        for layer in stage:
            for key in sorted(layer):
                arr = np.ascontiguousarray(jax.device_get(layer[key]), np.float32)
                acc += sha1(arr.tobytes()).hexdigest()
    return sha1(acc.encode("utf-8")).hexdigest()


def block_checksum(arr) -> int:
    """The host-side digest checksum of one logical block: the uint32
    wrap-around sum of the block's float32 bytes reinterpreted as uint32
    words — exactly what the fused scan aux computes in-program with
    ``jnp.sum(lax.bitcast_convert_type(x, jnp.uint32), dtype=jnp.uint32)``,
    so mesh-psum'd digests can be asserted equal to this logical value.
    """
    a = np.ascontiguousarray(np.asarray(jax.device_get(arr), np.float32))
    return int(a.view(np.uint32).sum(dtype=np.uint64) % (1 << 32))


def layer_digests(params_list):
    """Per-global-layer host digests of a logical params tree: a list of
    ``{"layer", "crc_w", "crc_b", "pnorm_w", "pnorm_b"}`` dicts over the
    ``iter_param_blocks`` blocks — the reference implementation the
    in-program digest stream is tested against (tests/test_divergence.py).
    """
    out = {}
    for gl, key, arr in iter_param_blocks(params_list):
        d = out.setdefault(gl, {"layer": gl})
        suffix = "w" if key == "W" else "b"
        d[f"crc_{suffix}"] = block_checksum(arr)
        d[f"pnorm_{suffix}"] = float(np.sqrt(np.sum(arr.astype(np.float64) ** 2)))
    return [out[gl] for gl in sorted(out)]


def assert_dp_replicas_in_sync(arr) -> None:
    """Verify every data-parallel replica holds bit-identical parameters.

    The reference gathers per-process hashes over the dp communicator and
    compares (utils.py:27-31, train.py:154-155). Here replication is a
    *sharding invariant* of the params jax.Array (replicated over the ``dp``
    mesh axis); we verify it physically by hashing every addressable shard
    per device-row and comparing. Works on any pytree of arrays.
    """
    mismatches = []

    def check(x):
        if not isinstance(x, jax.Array):
            return
        by_index = {}
        for shard in x.addressable_shards:
            h = sha1(np.ascontiguousarray(shard.data).tobytes()).hexdigest()
            # key by the index's string form: shard.index is a tuple of
            # slice objects, which are unhashable on Python < 3.12
            prev = by_index.setdefault(str(shard.index), h)
            if prev != h:
                mismatches.append((shard.device, shard.index))

    jax.tree.map(check, arr)
    if mismatches:
        raise ValueError(f"replica desync detected at shards: {mismatches}")


def assert_dp_replicas_in_sync_global(arr) -> None:
    """Multi-process extension of ``assert_dp_replicas_in_sync``.

    One process can only hash the shards it can address, so on a
    process-spanning mesh the local assert never compares the replicas that
    live on OTHER hosts. Here every process hashes its addressable shards
    (first 8 bytes of the SHA1, as two uint32 lanes — uint64 would be
    silently truncated under JAX's default x64-disabled mode), the
    per-device hash vectors are summed across processes with
    ``multihost_utils.process_allgather`` (each device slot is filled by
    exactly one process), and devices holding the same logical shard index
    are compared — the cross-host analogue of the reference's
    gather-hashes-over-the-dp-communicator check (utils.py:27-31). Raises
    on desync, on every process.
    """
    if jax.process_count() == 1:
        return assert_dp_replicas_in_sync(arr)
    from jax.experimental import multihost_utils

    leaves = [x for x in jax.tree.leaves(arr) if isinstance(x, jax.Array)]
    vecs, groups = [], []
    for li, x in enumerate(leaves):
        # identical on all processes: the full device->shard-index map
        dev_index = sorted(
            x.sharding.devices_indices_map(x.shape).items(),
            key=lambda kv: kv[0].id,
        )
        pos_of = {d.id: p for p, (d, _) in enumerate(dev_index)}
        v = np.zeros((len(dev_index), 2), np.uint32)
        for shard in x.addressable_shards:
            h = sha1(np.ascontiguousarray(shard.data).tobytes()).digest()
            # +1 so a real hash can't collide with the "not mine" sentinel 0
            v[pos_of[shard.device.id], 0] = np.uint32(
                int.from_bytes(h[:4], "big") % (2**32 - 1) + 1
            )
            v[pos_of[shard.device.id], 1] = np.uint32(int.from_bytes(h[4:8], "big"))
        vecs.append(v)
        by_index = {}
        for p, (d, idx) in enumerate(dev_index):
            by_index.setdefault(str(idx), []).append(p)
        groups.append((li, by_index))
    summed = [
        np.asarray(g).sum(axis=0, dtype=np.uint64)
        for g in multihost_utils.process_allgather(vecs)
    ]
    mismatches = []
    for (li, by_index), total in zip(groups, summed):
        for idx, positions in by_index.items():
            hashes = {(int(total[p, 0]), int(total[p, 1])) for p in positions}
            if len(hashes) > 1:
                mismatches.append((li, idx))
    if mismatches:
        raise ValueError(
            f"cross-process replica desync at (leaf, shard-index): {mismatches}"
        )
