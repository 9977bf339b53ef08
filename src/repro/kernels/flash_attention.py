"""Blocked causal GQA flash attention (train/prefill compute hot spot).

Standard online-softmax formulation tiled for the MXU: grid
(B, Hq, T/BT, S/BS) with the key/value axis innermost — TPU grids execute
sequentially over the last dimension, so the (m, l, acc) running state lives
in VMEM scratch across S-blocks of the same query tile.

GQA is handled in the index_map (kv head = q head // group), sliding-window
masking covers the gemma3-style local layers. Query positions are
right-aligned against the KV sequence so the same kernel serves training
(T == S) and single-step/chunked decode (T << S against a KV cache).

VMEM per program (BT=BS=512, D=128, f32): q/k/v tiles 3*512*128*4 = 0.79 MB,
logits 512*512*4 = 1 MB, acc + stats ~0.33 MB -> ~2.2 MB.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu


_NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                  scale: float, causal: bool, window: int, block_t: int,
                  block_s: int, q_offset: int, s_real: int):
    s_idx = pl.program_id(3)
    t_idx = pl.program_id(2)

    @pl.when(s_idx == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0].astype(jnp.float32)                    # (BT, D)
    k = k_ref[0, 0].astype(jnp.float32)                    # (BS, D)
    v = v_ref[0, 0].astype(jnp.float32)                    # (BS, D)

    logits = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
    # true positions: q rows are front-padded by t_pad (q_offset = s - t -
    # t_pad restores right alignment); keys are end-padded past s_real.
    q_pos = q_offset + t_idx * block_t + \
        jax.lax.broadcasted_iota(jnp.int32, (block_t, block_s), 0)
    k_pos = s_idx * block_s + \
        jax.lax.broadcasted_iota(jnp.int32, (block_t, block_s), 1)
    mask = k_pos < s_real                                   # kill key padding
    if causal:
        mask = mask & (k_pos <= q_pos)
    if window > 0:
        mask = mask & (k_pos > q_pos - window)
    logits = jnp.where(mask, logits, _NEG_INF)

    m_prev = m_scr[...][:, :1]                             # (BT, 1)
    l_prev = l_scr[...][:, :1]
    m_cur = jnp.max(logits, axis=1, keepdims=True)         # (BT, 1)
    m_new = jnp.maximum(m_prev, m_cur)
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.where(mask, jnp.exp(logits - m_new), 0.0)      # (BT, BS)
    l_new = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
    acc_scr[...] = acc_scr[...] * alpha + jnp.dot(
        p, v, preferred_element_type=jnp.float32)
    m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
    l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(s_idx == pl.num_programs(3) - 1)
    def _finalize():
        l = l_scr[...][:, :1]
        o_ref[0, 0, ...] = (acc_scr[...] /
                            jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("causal", "window", "block_t", "block_s",
                                    "interpret"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    causal: bool = True, window: int = 0,
                    block_t: int = 512, block_s: int = 512,
                    interpret: bool = True) -> jax.Array:
    """q: (B, Hq, T, D); k, v: (B, Hkv, S, D); Hq % Hkv == 0. Causal and/or
    sliding-window masked, right-aligned positions (decode friendly)."""
    b, hq, t, d = q.shape
    hkv, s = k.shape[1], k.shape[2]
    assert hq % hkv == 0, "GQA requires Hq to be a multiple of Hkv"
    group = hq // hkv
    scale = d ** -0.5

    block_t = min(block_t, max(t, 8))
    block_s = min(block_s, max(s, 8))
    t_pad = pl.cdiv(t, block_t) * block_t - t
    s_pad = pl.cdiv(s, block_s) * block_s - s
    # pad queries at the FRONT (right alignment preserved), keys at the END
    # (end-padded keys sit above every real query's causal horizon).
    qp = jnp.pad(q, ((0, 0), (0, 0), (t_pad, 0), (0, 0)))
    kp = jnp.pad(k, ((0, 0), (0, 0), (0, s_pad), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, 0), (0, s_pad), (0, 0)))
    tp, sp = t + t_pad, s + s_pad

    out = pl.pallas_call(
        functools.partial(_flash_kernel, scale=scale, causal=causal,
                          window=window, block_t=block_t, block_s=block_s,
                          q_offset=s - t - t_pad, s_real=s),
        grid=(b, hq, tp // block_t, sp // block_s),
        in_specs=[
            pl.BlockSpec((1, 1, block_t, d), lambda b_, h, i, j: (b_, h, i, 0)),
            pl.BlockSpec((1, 1, block_s, d),
                         lambda b_, h, i, j, g=group: (b_, h // g, j, 0)),
            pl.BlockSpec((1, 1, block_s, d),
                         lambda b_, h, i, j, g=group: (b_, h // g, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_t, d),
                               lambda b_, h, i, j: (b_, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((b, hq, tp, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_t, 128), jnp.float32),      # m
            pltpu.VMEM((block_t, 128), jnp.float32),      # l
            pltpu.VMEM((block_t, d), jnp.float32),        # acc
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(qp, kp, vp)
    return out[:, :, t_pad:, :]
