"""Arithmetic shared by the plain references: float32 RMSNorm, matrix
products at ``highest`` precision, and the control's lower precision."""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

F32 = jnp.float32
FP8_MAX = 448.0  # largest finite float8_e4m3fn


def rms_norm(x, w, eps: float):
    x = x.astype(F32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * w.astype(F32)


def fp8(x, axis: int):
    """Round ``x`` to float8_e4m3fn, scaled per slice along ``axis`` so
    that each slice's largest magnitude maps to the format's largest."""
    x = x.astype(F32)
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, amax / FP8_MAX, 1.0)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(F32) * scale


def matmul(x, w, quant: Optional[str] = None):
    """``x @ w`` in float32 at highest precision.  ``quant="fp8"`` (the
    control) first rounds each row of ``x`` and each output column of
    ``w`` to float8_e4m3fn, as an fp8 matmul path would."""
    x = x.astype(F32)
    w = w.astype(F32)
    if quant == "fp8":
        x = fp8(x, axis=-1)
        w = fp8(w, axis=0)
    elif quant is not None:
        raise ValueError(f"unknown precision {quant!r}")
    return jnp.matmul(x, w, precision=jax.lax.Precision.HIGHEST)


def stacked_layer(stack, i):
    """Layer ``i`` of a stacked weight tree, sliced inside a jitted call so
    that only that layer is read and upcast."""
    return jax.tree.map(
        lambda v: jax.lax.dynamic_index_in_dim(v, i, keepdims=False), stack)
