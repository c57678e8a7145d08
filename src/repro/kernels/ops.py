"""jit-ready wrappers that dispatch between the Pallas TPU kernels and the
pure-jnp references.

``impl`` semantics:
  "ref"     — pure jnp (XLA-native).  Default for dry-runs / GSPMD lowering
              and the CPU container.
  "pallas"  — the Pallas kernel, compiled for TPU.  (The kernel tests
              call the kernels directly in interpret mode.)
  "auto"    — pallas on TPU backends, ref elsewhere.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels import ref as _ref


def _resolve(impl: str) -> str:
    if impl == "auto":
        return "pallas" if jax.default_backend() == "tpu" else "ref"
    return impl


def _resolve_nonattn(impl: str) -> str:
    """Ops without a chunked/grouped-ref variant treat those as ref."""
    impl = _resolve(impl)
    return "ref" if impl in ("ref_chunked", "ref_grouped") else impl


# -- flash attention -----------------------------------------------------------
def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    window: Optional[int] = None,
    q_offset: int = 0,
    impl: str = "ref",
    unroll: bool = False,
) -> jax.Array:
    impl = _resolve(impl)
    if impl in ("ref", "ref_grouped"):
        return _ref.attention_ref(
            q, k, v, causal=causal, window=window, q_offset=q_offset
        )
    if impl == "ref_chunked":
        return _ref.attention_chunked_ref(
            q, k, v, causal=causal, window=window, q_offset=q_offset,
            unroll=unroll,
        )
    from repro.kernels import flash_attention as _fa

    return _fa.flash_attention(
        q,
        k,
        v,
        causal=causal,
        window=window,
        q_offset=q_offset,
    )


# -- decode attention -----------------------------------------------------------
def decode_attention(
    q: jax.Array,
    k_cache: jax.Array,
    v_cache: jax.Array,
    cache_len: jax.Array,
    *,
    impl: str = "ref",
) -> jax.Array:
    impl = _resolve(impl)
    if impl in ("ref", "ref_chunked"):
        return _ref.decode_attention_ref(q, k_cache, v_cache, cache_len)
    if impl == "ref_grouped":
        return _ref.decode_attention_grouped_ref(q, k_cache, v_cache, cache_len)
    from repro.kernels import decode_attention as _da

    return _da.decode_attention(q, k_cache, v_cache, cache_len)


# -- Mamba2 SSD scan ---------------------------------------------------------------
def ssd_scan(
    x: jax.Array,
    dt: jax.Array,
    a: jax.Array,
    b: jax.Array,
    c: jax.Array,
    *,
    initial_state: Optional[jax.Array] = None,
    chunk: int = 64,
    impl: str = "ref",
    unroll: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    impl = _resolve_nonattn(impl)
    if impl == "ref":
        # Chunked dual form (same math as the kernel): the production jnp
        # path.  ``ref_sequential`` is the simple per-step oracle.
        return _ref.ssd_chunked_ref(
            x, dt, a, b, c,
            chunk=chunk, initial_state=initial_state, unroll=unroll,
        )
    if impl == "ref_sequential":
        return _ref.ssd_ref(x, dt, a, b, c, initial_state=initial_state)
    del unroll  # pallas path: chunk loop is the sequential grid dim
    from repro.kernels import ssd_scan as _ssd

    return _ssd.ssd_scan(
        x, dt, a, b, c,
        initial_state=initial_state,
        chunk=chunk,
    )


def ssd_decode(
    x: jax.Array,
    dt: jax.Array,
    a: jax.Array,
    b: jax.Array,
    c: jax.Array,
    state: jax.Array,
) -> Tuple[jax.Array, jax.Array]:
    """Single-step SSD (no kernel needed: pure elementwise + small matvec)."""
    return _ref.ssd_decode_ref(x, dt, a, b, c, state)


# -- grouped expert matmul ------------------------------------------------------------
def moe_gmm(
    x: jax.Array,
    w: jax.Array,
    group_sizes: jax.Array,
    *,
    impl: str = "ref",
) -> jax.Array:
    impl = _resolve_nonattn(impl)
    if impl == "ref":
        return _ref.moe_gmm_ref(x, w, group_sizes)
    from repro.kernels import moe_gmm as _gmm

    return _gmm.moe_gmm(x, w, group_sizes)
