"""Fused per-key statistics histogram — the paper's monitoring hot path.

At every interval boundary each worker must produce g(k) (frequency) and c(k)
(computation cost) for its key slice (paper Fig. 5, step 1). On TPU the
natural formulation is a one-hot matmul: a (tokens x key-block) match matrix
contracted against ones / costs runs on the MXU, turning a scatter-add (bad
on TPU) into dense compute.

Tiling: grid (K/BK, N/BN); the stream axis (last grid dim) is sequential on
TPU, so each key-block accumulates partial sums across stream blocks in its
own VMEM output tile — no cross-program reduction needed.

VMEM budget per program: keys BN*4 + costs BN*4 + match BN*BK*4 + out 2*BK*4
bytes; BN=BK=512 -> ~1.1 MB, comfortably inside the ~16 MB/core VMEM.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

from .routing_lookup import require_int32


def _key_stats_kernel(keys_ref, costs_ref, freq_ref, cost_ref, *, block_k: int):
    n_idx = pl.program_id(1)

    @pl.when(n_idx == 0)
    def _init():
        freq_ref[...] = jnp.zeros_like(freq_ref)
        cost_ref[...] = jnp.zeros_like(cost_ref)

    k_idx = pl.program_id(0)
    keys = keys_ref[...]                                  # (1, BN) int32
    costs = costs_ref[...].astype(jnp.float32)            # (1, BN)
    key_base = k_idx * block_k
    key_ids = key_base + jax.lax.broadcasted_iota(jnp.int32, (1, block_k), 1)
    # (BN, BK) one-hot match matrix; padding keys (< 0) never match
    match = (keys.T == key_ids).astype(jnp.float32)       # (BN, BK)
    freq_ref[...] += jnp.sum(match, axis=0, keepdims=True)
    cost_ref[...] += jnp.dot(costs, match,                # MXU contraction
                             preferred_element_type=jnp.float32)


@functools.partial(jax.jit,
                   static_argnames=("num_keys", "block_n", "block_k",
                                    "interpret"))
def _key_stats(keys: jax.Array, costs: jax.Array, num_keys: int,
               block_n: int = 512, block_k: int = 512,
               interpret: Optional[bool] = None):
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    n = keys.shape[0]
    n_pad = pl.cdiv(n, block_n) * block_n - n
    k_pad = pl.cdiv(num_keys, block_k) * block_k - num_keys
    keys_p = jnp.pad(keys.astype(jnp.int32), (0, n_pad),
                     constant_values=-1)[None, :]
    costs_p = jnp.pad(costs.astype(jnp.float32), (0, n_pad))[None, :]
    padded_k = num_keys + k_pad

    grid = (padded_k // block_k, keys_p.shape[1] // block_n)
    freq, cost = pl.pallas_call(
        functools.partial(_key_stats_kernel, block_k=block_k),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_n), lambda i, j: (0, j)),
            pl.BlockSpec((1, block_n), lambda i, j: (0, j)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_k), lambda i, j: (0, i)),
            pl.BlockSpec((1, block_k), lambda i, j: (0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, padded_k), jnp.float32),
            jax.ShapeDtypeStruct((1, padded_k), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(keys_p, costs_p)
    return freq[0, :num_keys], cost[0, :num_keys]


def key_stats(keys: jax.Array, costs: jax.Array, num_keys: int,
              block_n: int = 512, block_k: int = 512,
              interpret: Optional[bool] = None):
    """Per-key frequency and cost over a tuple/token stream.

    keys: (N,) int32 in [0, num_keys), -1 = padding; costs: (N,) float.
    Returns (freq, cost) each (num_keys,) float32. ``interpret=None``
    auto-selects: compiled on real TPU backends, interpret mode elsewhere.

    ``keys`` must already be int32 — enforced outside the jit boundary so a
    wider dtype raises TypeError instead of aliasing ids >= 2**31 (costs may
    be any float dtype; they accumulate in float32 either way).
    """
    require_int32("key_stats", "keys", keys)
    return _key_stats(keys, costs, num_keys, block_n=block_n,
                      block_k=block_k, interpret=interpret)


if hasattr(_key_stats, "_cache_size"):           # retrace-counting test hook
    key_stats._cache_size = _key_stats._cache_size
