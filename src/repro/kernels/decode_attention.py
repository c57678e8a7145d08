"""One-token GQA decode attention Pallas kernel.

Decode attention is memory-bound: arithmetic intensity ≈ 2·group FLOPs per
cache byte, so the kernel's job is to stream the KV cache through VMEM at
HBM line rate while the (group × head_dim) query tile stays resident.

Tiling: grid = (batch, kv_heads, T/block_k).  Each program owns one KV
head, processes the whole query *group* for that head (group = H/KH rows —
a skinny matmul that still feeds the MXU/VPU), and iterates KV blocks via
the sequential minor grid dimension, carrying online-softmax statistics in
VMEM scratch across grid steps.

The cache is viewed as (B, T, KH·D) — a free reshape, no copy — so a KV
head is a (block_k, D) column block: its last two dims meet the TPU's
(8, 128) tiling whenever D is a multiple of 128 (or KH = 1).

Invalid cache slots (≥ cache_len, ring-buffer tails) are masked with the
per-batch length passed as a scalar-prefetch operand.

VMEM per program (block_k = 512, hd = 128, f32): kv tiles 2×512×128×4 ≈
512 KB + scratch (G×128) — well under budget with double buffering.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _dec_kernel(
    len_ref,  # scalar prefetch: (B,) int32 in SMEM
    q_ref,    # (1, 1, G, D)
    k_ref,    # (1, block_k, D)
    v_ref,    # (1, block_k, D)
    o_ref,    # (1, 1, G, D)
    m_ref, l_ref, acc_ref,  # VMEM scratch: (G, 1), (G, 1), (G, D)
    *,
    block_k: int,
    kv_len: int,
    scale: float,
):
    bi = pl.program_id(0)
    ki = pl.program_id(2)
    n_k = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0].astype(jnp.float32) * scale     # (G, D)
    k = k_ref[0].astype(jnp.float32)                # (bk, D)
    v = v_ref[0].astype(jnp.float32)
    s = q @ k.T                                     # (G, bk)
    k_pos = ki * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (1, block_k), 1
    )
    valid = k_pos < jnp.minimum(len_ref[bi], kv_len)
    s = jnp.where(valid, s, NEG_INF)

    m_prev = m_ref[...]                             # (G, 1)
    l_prev = l_ref[...]
    acc_prev = acc_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m_prev - m_new)
    l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
    acc_new = acc_prev * alpha + p @ v
    m_ref[...] = m_new
    l_ref[...] = l_new
    acc_ref[...] = acc_new

    @pl.when(ki == n_k - 1)
    def _finish():
        l = jnp.where(l_new == 0.0, 1.0, l_new)
        o_ref[0, 0] = (acc_new / l).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_k", "interpret"))
def decode_attention(
    q: jax.Array,
    k_cache: jax.Array,
    v_cache: jax.Array,
    cache_len: jax.Array,
    *,
    block_k: int = 512,
    interpret: bool = False,
) -> jax.Array:
    """q: (B, H, D); k_cache/v_cache: (B, T, KH, D); cache_len: (B,) int32
    → (B, H, D)."""
    b, h, d = q.shape
    _, t, kh, _ = k_cache.shape
    group = h // kh
    scale = d ** -0.5
    block_k = min(block_k, t)

    # Pad the cache to a block multiple with zeros: ragged tail blocks are
    # masked by cache_len, and zero (not uninitialised) padding keeps the
    # 0-probability × value products finite.
    t_pad = -(-t // block_k) * block_k
    if t_pad != t:
        pad = ((0, 0), (0, t_pad - t), (0, 0), (0, 0))
        k_cache = jnp.pad(k_cache, pad)
        v_cache = jnp.pad(v_cache, pad)

    qg = q.reshape(b, kh, group, d)  # queries grouped per KV head
    kf = k_cache.reshape(b, t_pad, kh * d)  # KV head hi = column block hi
    vf = v_cache.reshape(b, t_pad, kh * d)
    grid = (b, kh, pl.cdiv(t, block_k))

    out = pl.pallas_call(
        functools.partial(
            _dec_kernel, block_k=block_k, kv_len=t, scale=scale
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec(
                    (1, 1, group, d), lambda bi, hi, ki, *_: (bi, hi, 0, 0)
                ),
                pl.BlockSpec(
                    (1, block_k, d), lambda bi, hi, ki, *_: (bi, ki, hi)
                ),
                pl.BlockSpec(
                    (1, block_k, d), lambda bi, hi, ki, *_: (bi, ki, hi)
                ),
            ],
            out_specs=pl.BlockSpec(
                (1, 1, group, d), lambda bi, hi, ki, *_: (bi, hi, 0, 0)
            ),
            scratch_shapes=[
                pltpu.VMEM((group, 1), jnp.float32),
                pltpu.VMEM((group, 1), jnp.float32),
                pltpu.VMEM((group, d), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, kh, group, d), q.dtype),
        interpret=interpret,
    )(cache_len.astype(jnp.int32), qg, kf, vf)
    return out.reshape(b, h, d)
