"""Plain float32 Mamba-2 language model, written from the published
equations (Dao and Gu, arXiv:2405.21060, section 7 and Listing 1), with
no kernels, no cache and no chunking: the SSD recurrence runs one token at
a time.

Per layer, on the residual stream h (n, T, D):

    x = RMSNorm(h) * ln
    [z | x | B | C | dt] = x @ W_in
    [x | B | C] = silu(causal depthwise conv over time, kernel K, + bias)
    dt = softplus(dt + dt_bias);  A = -exp(A_log)          (per head)
    S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T;  y_t = S_t C_t + D x_t
    h = h + (RMSNorm(y * silu(z)) * gate_norm) @ W_out

then logits = RMSNorm(h) * final_norm @ lm_head (the embedding table,
transposed, where the head is tied).  B and C are shared by the heads of
a group; the gated RMSNorm normalises each group's share of the inner
width (``RMSNormGated(d_inner, group_size=d_inner // ngroups,
norm_before_gate=False)`` in the published ``mamba_ssm`` block).

Weights are read by name from the benchmark's own tree (see
``chipbench/weights.py``); the conv weight ``w[k]`` multiplies the input
K-1-k positions back.
"""

from __future__ import annotations

from typing import Callable, Dict, Optional

import jax
import jax.numpy as jnp

from chipbench.refmath import F32, matmul, rms_norm, stacked_layer


def _layer(h, p, m, quant):
    n, t, d = h.shape
    di = m["expand"] * d
    hp = m["head_dim"]
    nh = di // hp
    g, ns = m["n_groups"], m["state_size"]
    eps = m["rms_norm_eps"]
    proj = matmul(rms_norm(h, p["ln"], eps), p["w_in"], quant)
    z = proj[..., :di]
    xbc = proj[..., di:2 * di + 2 * g * ns]
    dt = proj[..., 2 * di + 2 * g * ns:]
    k = p["conv_w"].shape[0]
    w = p["conv_w"].astype(F32)
    pad = jnp.pad(xbc, ((0, 0), (k - 1, 0), (0, 0)))
    conv = sum(pad[:, j:j + t] * w[j] for j in range(k)) + p["conv_b"].astype(F32)
    conv = jax.nn.silu(conv)
    x = conv[..., :di].reshape(n, t, nh, hp)
    bmat = conv[..., di:di + g * ns].reshape(n, t, g, ns)
    cmat = conv[..., di + g * ns:].reshape(n, t, g, ns)
    head_group = jnp.arange(nh) // (nh // g)
    bmat = bmat[:, :, head_group]  # (n, t, heads, N)
    cmat = cmat[:, :, head_group]
    dt = jax.nn.softplus(dt + p["dt_bias"].astype(F32))  # (n, t, heads)
    a = -jnp.exp(p["a_log"].astype(F32))

    def step(s, inp):
        xt, bt, ct, dtt = inp
        s = (jnp.exp(dtt * a)[..., None, None] * s
             + (dtt[..., None] * xt)[..., None] * bt[:, :, None, :])
        return s, jnp.einsum("nhpk,nhk->nhp", s, ct,
                             precision=jax.lax.Precision.HIGHEST)

    s0 = jnp.zeros((n, nh, hp, ns), F32)
    seq = [jnp.moveaxis(v, 1, 0) for v in (x, bmat, cmat, dt)]
    _, y = jax.lax.scan(step, s0, seq)
    y = jnp.moveaxis(y, 0, 1) + x * p["d_skip"].astype(F32)[:, None]
    y = (y.reshape(n, t, di) * jax.nn.silu(z)).reshape(n, t, g, di // g)
    y = rms_norm(y, p["gate_norm"].reshape(g, di // g), eps)
    return h + matmul(y.reshape(n, t, di), p["w_out"], quant)


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
