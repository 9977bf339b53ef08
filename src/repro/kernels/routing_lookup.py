"""Mixed-routing dispatch kernel — F(k) on the per-token hot path (Eq. 1).

The override table (A_max entries) is pinned whole in VMEM for every program
(BlockSpec index_map is constant in the stream dimension), so each token block
pays one (BN x A) compare + reduce instead of a host-side dict probe. The
hash fallback is the murmur3 finalizer (fmix32) — TPUs have no 64-bit integer
units, so the 32-bit mix is the device-canonical hash shared bit-for-bit with
the host planner (balancer.hashing.Hash32) and the jnp oracle.

VMEM per program: BN*4 (keys) + 2*A*4 (table) + BN*A (match, promoted f32)
-> BN=1024, A=2048: ~8.5 MB peak with f32 match; we reduce with integer
max instead to stay ~2.5 MB.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu


def require_int32(kernel: str, name: str, arr) -> None:
    """Int32 contract check, outside the jit boundary.

    The kernels' integer lanes are 32-bit: a wider (or float) key array
    would be truncated inside the kernel and ids >= 2**31 would silently
    alias other keys instead of failing. Callers must validate the value
    range and cast explicitly (``KeyedStage._dest_batch`` does)."""
    dtype = np.dtype(getattr(arr, "dtype", np.asarray(arr).dtype))
    if dtype != np.dtype(np.int32):
        raise TypeError(
            f"{kernel} requires int32 {name} (got {dtype.name}): the kernel "
            "operates on 32-bit integer lanes, so wider ids would silently "
            "alias after truncation — validate ids are in [0, 2**31) and "
            "cast explicitly")


def _fmix32(h):
    h = h ^ (h >> jnp.uint32(16))
    h = h * jnp.uint32(0x85EBCA6B)
    h = h ^ (h >> jnp.uint32(13))
    h = h * jnp.uint32(0xC2B2AE35)
    h = h ^ (h >> jnp.uint32(16))
    return h


def _routing_kernel(keys_ref, tkeys_ref, tdests_ref, out_ref, *, n_dest: int,
                    seed: int):
    keys = keys_ref[...]                                   # (1, BN) int32
    h = _fmix32(keys.astype(jnp.uint32) ^ jnp.uint32(seed & 0xFFFFFFFF))
    base = (h % jnp.uint32(n_dest)).astype(jnp.int32)
    tkeys = tkeys_ref[...]                                 # (1, A)
    tdests = tdests_ref[...]                               # (1, A)
    # (BN, A) match; empty slots are -1 and keys are >= 0, so never match
    match = keys.reshape(-1, 1) == tkeys.reshape(1, -1)
    # integer-max reduction: dest+1 where matched, 0 where not; 0 -> miss
    hit_val = jnp.where(match, tdests.reshape(1, -1) + 1, 0)
    best = jnp.max(hit_val, axis=1).reshape(keys.shape)    # (1, BN)
    out_ref[...] = jnp.where(best > 0, best - 1, base)


@functools.partial(jax.jit,
                   static_argnames=("n_dest", "seed", "block_n", "interpret"))
def _routing_lookup(keys: jax.Array, table_keys: jax.Array,
                    table_dests: jax.Array, n_dest: int, seed: int = 0,
                    block_n: int = 1024,
                    interpret: Optional[bool] = None) -> jax.Array:
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    n = keys.shape[0]
    a = table_keys.shape[0]
    n_pad = pl.cdiv(n, block_n) * block_n - n
    a_pad = pl.cdiv(a, 128) * 128 - a
    keys_p = jnp.pad(keys.astype(jnp.int32), (0, n_pad),
                     constant_values=-1)[None, :]
    tkeys_p = jnp.pad(table_keys.astype(jnp.int32), (0, a_pad),
                      constant_values=-1)[None, :]
    tdests_p = jnp.pad(table_dests.astype(jnp.int32), (0, a_pad))[None, :]
    a_total = a + a_pad

    out = pl.pallas_call(
        functools.partial(_routing_kernel, n_dest=n_dest, seed=seed),
        grid=(keys_p.shape[1] // block_n,),
        in_specs=[
            pl.BlockSpec((1, block_n), lambda i: (0, i)),
            pl.BlockSpec((1, a_total), lambda i: (0, 0)),   # table: whole, VMEM
            pl.BlockSpec((1, a_total), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_n), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, keys_p.shape[1]), jnp.int32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(keys_p, tkeys_p, tdests_p)
    return out[0, :n]


def routing_lookup(keys: jax.Array, table_keys: jax.Array,
                   table_dests: jax.Array, n_dest: int, seed: int = 0,
                   block_n: int = 1024,
                   interpret: Optional[bool] = None) -> jax.Array:
    """Vectorized F(k) for a token/tuple block. -1 table slots = empty.

    ``interpret=None`` (default) auto-selects: compiled Mosaic on real TPU
    backends, interpret mode elsewhere (CPU/GPU have no lowering for this
    kernel). Both values are static, so the choice is baked per trace.

    All three arrays must already be int32 — this unjitted wrapper enforces
    the contract (raises TypeError) before any tracing happens, so a wrong
    dtype fails loudly instead of silently aliasing key ids >= 2**31.
    """
    require_int32("routing_lookup", "keys", keys)
    require_int32("routing_lookup", "table_keys", table_keys)
    require_int32("routing_lookup", "table_dests", table_dests)
    return _routing_lookup(keys, table_keys, table_dests, n_dest, seed=seed,
                           block_n=block_n, interpret=interpret)


if hasattr(_routing_lookup, "_cache_size"):      # retrace-counting test hook
    routing_lookup._cache_size = _routing_lookup._cache_size
