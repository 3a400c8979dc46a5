"""Pallas TPU kernel: fused secure-aggregation masked client mean (eq. 7 + 23).

Server aggregation with Bonawitz-style pairwise masks, with the masks
generated IN-KERNEL from a counter-based integer hash (xorshift-mix of
(pair_id, feature_index, round_seed)) instead of being materialized in HBM.
For L clients the [L, D] mask tensor never exists: each grid step
regenerates its block of every pairwise stream in VMEM and accumulates

    out[:] = (1/L) sum_k (upd[k, :] + mask_k[:]),
    mask_k = sum_{j<k} -PRG(j,k) + sum_{j>k} +PRG(k,j)

Because each pair's stream enters the sum twice with opposite signs (once in
each endpoint's net mask), the kernel's output equals the plain client mean
bit-for-bit in exact arithmetic, and to float-add reordering in practice —
asserted against ref.py in tests.

The per-client net masks are accumulated by an O(L) ``fori_loop`` whose body
evaluates all of client k's pair streams at once (:func:`net_mask_stream`),
so trace/compile time and program size are FLAT in the cohort size L — the
previous unrolled double python loop emitted all L(L-1)/2 pair streams as
separate graph nodes (2016 streams at L=64), which made compile time
quadratic in L.  Runtime stream work is unchanged.

HBM traffic: L*D reads + D writes (the mask tensor would add 2*L*D).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _hash_u32(x: jax.Array) -> jax.Array:
    """xorshift-multiply mix (Murmur3 finalizer) on uint32."""
    x = x.astype(jnp.uint32)
    x = x ^ (x >> 16)
    x = x * jnp.uint32(0x7FEB352D)
    x = x ^ (x >> 15)
    x = x * jnp.uint32(0x846CA68B)
    x = x ^ (x >> 16)
    return x


def pair_stream(pair_id: jax.Array, idx: jax.Array, seed: jax.Array,
                scale: float) -> jax.Array:
    """Uniform(-scale, scale) stream for one client pair at feature idx.

    ``pair_id`` may be a scalar or an integer array (it broadcasts against
    ``idx``), which is what lets :func:`net_mask_stream` evaluate all of one
    client's pair streams in a single vectorized expression."""
    pid = jnp.asarray(pair_id).astype(jnp.uint32)
    h = _hash_u32(idx.astype(jnp.uint32)
                  ^ _hash_u32(pid * jnp.uint32(0x9E3779B9)
                              + jnp.uint32(seed)))
    # top 24 bits -> uniform in [0,1) with exact float32 representation
    # (through int32, which holds 24 bits exactly: Mosaic has no
    # uint32 -> float32 cast)
    u = ((h >> jnp.uint32(8)).astype(jnp.int32).astype(jnp.float32)
         * jnp.float32(1.0 / (1 << 24)))
    return (2.0 * u - 1.0) * scale


def net_mask_stream(k: jax.Array, idx: jax.Array, seed: jax.Array,
                    scale: float, L: int,
                    alive: jax.Array | None = None) -> jax.Array:
    """Client k's NET pairwise mask at feature block ``idx`` ([1, bd]).

    mask_k = sum_{j>k} +PRG(pair(k,j)) + sum_{j<k} -PRG(pair(j,k)),
    optionally restricted to pairs whose peer j is alive (``alive`` [L]
    or [L, 1] bool) — dead peers' streams never arrive, matching the
    Bonawitz orphan-repair semantics of the reference path.

    Vectorized over all L peers, so a ``fori_loop`` over k costs O(1) trace
    size; the pair enumeration matches the row-major (a < b) ordering of
    the reference double loop (pair (a, b) has id
    ``a*(2L-a-1)/2 + (b-a-1)``).
    """
    j = jax.lax.broadcasted_iota(jnp.int32, (L, 1), 0)
    k = jnp.asarray(k, jnp.int32)
    a = jnp.minimum(j, k)
    b = jnp.maximum(j, k)
    pid = (a * (2 * L - a - 1)) // 2 + (b - a - 1)            # [L, 1]
    s = pair_stream(pid, idx, seed, scale)                    # [L, bd]
    sgn = jnp.where(j > k, jnp.float32(1.0), jnp.float32(-1.0))
    m = jnp.where(j == k, 0.0, sgn) * s
    if alive is not None:
        m = jnp.where(alive.reshape(L, 1), m, 0.0)
    return jnp.sum(m, axis=0, keepdims=True)                  # [1, bd]


def _secure_agg_kernel(upd_ref, seed_ref, out_ref, *, L: int, scale: float,
                       block_d: int):
    j = pl.program_id(0)
    seed = seed_ref[0]
    idx = j * block_d + jax.lax.broadcasted_iota(jnp.uint32, (1, block_d), 1)
    acc = jnp.sum(upd_ref[...].astype(jnp.float32), axis=0, keepdims=True)
    # pairwise masks: each pair's stream enters the sum twice with opposite
    # signs (through both endpoints' net masks), so the net effect on the
    # SUM is zero — mirroring exactly what the distributed protocol
    # computes.  O(L) fori_loop over clients, each body vectorized over the
    # client's L-1 peer streams: trace/compile cost flat in L.
    def fold_client(k, a):
        return a + net_mask_stream(k, idx, seed, scale, L)

    acc = jax.lax.fori_loop(0, L, fold_client, acc)
    out_ref[...] = (acc / L).astype(out_ref.dtype)


def secure_agg_mean(updates: jax.Array, seed: jax.Array, *, scale: float = 1.0,
                    block_d: int = 512, interpret: bool = False) -> jax.Array:
    """updates: [L, D] -> masked mean [D]. seed: uint32 scalar array [1]."""
    L, D = updates.shape
    assert D % block_d == 0, (D, block_d)
    import functools
    kern = functools.partial(_secure_agg_kernel, L=L, scale=scale,
                             block_d=block_d)
    out = pl.pallas_call(
        kern,
        grid=(D // block_d,),
        in_specs=[
            pl.BlockSpec((L, block_d), lambda j: (0, j)),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, block_d), lambda j: (0, j)),
        out_shape=jax.ShapeDtypeStruct((1, D), updates.dtype),
        interpret=interpret,
    )(updates, seed)
    return out[0]
