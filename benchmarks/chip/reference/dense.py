"""Plain float32 decoder-only transformer with grouped-query attention,
rotary positions and a SwiGLU MLP (the Mistral / Llama block: Touvron et
al. arXiv:2302.13971, Ainslie et al. arXiv:2305.13245, Su et al.
arXiv:2104.09864), full causal attention over the whole sequence, no
kernels and no cache.

Per layer, on the residual stream h (n, T, D):

    x = RMSNorm(h) * ln1
    q, k, v = x Wq, x Wk, x Wv               (H query, KV key/value heads)
    q, k = RoPE(q), RoPE(k)                   (rotate-half, base rope_theta)
    o = softmax(q k^T / sqrt(hd) + causal mask) v,  query head j reading
        key/value head j // (H / KV)
    h = h + o Wo
    h = h + (silu(x2 Wg) * (x2 Wu)) Wd,  x2 = RMSNorm(h) * ln2

then logits = RMSNorm(h) * final_norm @ lm_head.
"""

from __future__ import annotations

from typing import Callable, Dict, Optional

import jax
import jax.numpy as jnp

from chipbench.refmath import F32, matmul, rms_norm, stacked_layer

HIGHEST = jax.lax.Precision.HIGHEST


def _rope(x, theta: float):
    """x (n, T, heads, hd), positions 0 .. T-1."""
    t, hd = x.shape[1], x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=F32) / hd))
    ang = jnp.arange(t, dtype=F32)[:, None] * inv  # (T, hd/2)
    cos = jnp.cos(ang)[None, :, None, :]
    sin = jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _layer(h, p, m, quant):
    n, t, _ = h.shape
    hd = m["head_dim"]
    nq, nkv = m["num_attention_heads"], m["num_key_value_heads"]
    eps, theta = m["rms_norm_eps"], m["rope_theta"]
    x = rms_norm(h, p["ln1"], eps)
    q = _rope(matmul(x, p["wq"], quant).reshape(n, t, nq, hd), theta)
    k = _rope(matmul(x, p["wk"], quant).reshape(n, t, nkv, hd), theta)
    v = matmul(x, p["wv"], quant).reshape(n, t, nkv, hd)
    kv_of = jnp.arange(nq) // (nq // nkv)
    k, v = k[:, :, kv_of], v[:, :, kv_of]
    s = jnp.einsum("nqhd,nkhd->nhqk", q, k, precision=HIGHEST) / jnp.sqrt(
        jnp.asarray(hd, F32))
    causal = jnp.arange(t)[None, :] <= jnp.arange(t)[:, None]
    s = jnp.where(causal, s, -jnp.inf)
    o = jnp.einsum("nhqk,nkhd->nqhd", jax.nn.softmax(s, axis=-1), v,
                   precision=HIGHEST)
    h = h + matmul(o.reshape(n, t, nq * hd), p["wo"], quant)
    x2 = rms_norm(h, p["ln2"], eps)
    mlp = p["mlp"]
    gate = jax.nn.silu(matmul(x2, mlp["wg"], quant))
    return h + matmul(gate * matmul(x2, mlp["wu"], quant), mlp["wd"], quant)


def logits_fn(m: Dict, quant: Optional[str] = None) -> Callable:
    """``f(weights, tokens (n, T) int32) -> logits (n, T, V) float32``,
    one layer at a time, so that only one layer is held in float32."""
    eps = m["rms_norm_eps"]

    @jax.jit
    def embed(table, tokens):
        return table[tokens].astype(F32)

    @jax.jit
    def layer(h, stack, i):
        return _layer(h, stacked_layer(stack, i), m, quant)

    @jax.jit
    def head(h, norm, w):
        return matmul(rms_norm(h, norm, eps), w, quant)

    def f(weights, tokens):
        h = embed(weights["embed"], tokens)
        for i in range(m["num_hidden_layers"]):
            h = layer(h, weights["layers"], i)
        w = weights["embed"].T if m["tie_word_embeddings"] else weights["lm_head"]
        return head(h, weights["final_norm"], w)

    return f
