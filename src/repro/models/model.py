"""Model assembly: init, train/prefill forward, and one-token decode for
every architecture family.

Design points:
* homogeneous layer stacks carry a leading ``n_layers`` axis and run under
  ``jax.lax.scan`` — HLO size stays O(1) in depth, which keeps 126-layer
  dry-run compiles tractable.
* ``init_params(cfg, key)`` materializes weights; ``abstract_params(cfg)``
  returns the same pytree as ShapeDtypeStructs (via ``jax.eval_shape``) for
  allocation-free lowering.
* decode carries an explicit cache pytree (family-specific; see
  ``init_cache``) and supports sliding-window ring buffers for the
  ``long_500k`` shape.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models import mla as mla_mod
from repro.models import moe as moe_mod
from repro.models import ssm as ssm_mod
from repro.models.config import ModelConfig
from repro.models.layers import (
    cross_attention,
    gqa_attention,
    gqa_decode_attention,
    project_cross_kv,
    rms_norm,
    swiglu,
    text_mrope_positions,
)

Params = Dict[str, Any]
Cache = Dict[str, Any]

# Families whose cache ``prefill`` fills in one call; the others' caches (a
# final scan state, a latent cache) need a fill of their own, so the served
# path feeds their prompts through ``decode_step`` one position per call.
PREFILL_FAMILIES = frozenset({"dense"})


def _layer_scan(body, carry, xs, unroll: bool):
    """lax.scan over the layer stack, or a Python loop when ``unroll`` —
    used by the dry-run's cost-accounting variants (XLA counts while-loop
    bodies once, so per-layer HLO costs come from unrolled 1/2-layer
    lowers; see launch/dryrun.py)."""
    if not unroll:
        return jax.lax.scan(body, carry, xs)
    n = jax.tree.leaves(xs)[0].shape[0]
    ys = []
    for i in range(n):
        xi = jax.tree.map(lambda v: v[i], xs)
        carry, y = body(carry, xi)
        ys.append(y)
    if ys and ys[0] is not None:
        ys = jax.tree.map(lambda *a: jnp.stack(a), *ys)
    else:
        ys = None
    return carry, ys


def _dtype(cfg: ModelConfig):
    return jnp.dtype(cfg.dtype)


# ===========================================================================
# Parameter initialisation
# ===========================================================================
def _dense_layer_init(cfg: ModelConfig, key) -> Params:
    d, hd = cfg.d_model, cfg.hd
    h, kh, f = cfg.n_heads, cfg.n_kv_heads, cfg.d_ff
    ks = jax.random.split(key, 7)
    sc = 0.02
    dt = _dtype(cfg)
    return {
        "ln1": jnp.ones((d,), dt),
        "ln2": jnp.ones((d,), dt),
        "wq": jax.random.normal(ks[0], (d, h * hd), dt) * sc,
        "wk": jax.random.normal(ks[1], (d, kh * hd), dt) * sc,
        "wv": jax.random.normal(ks[2], (d, kh * hd), dt) * sc,
        "wo": jax.random.normal(ks[3], (h * hd, d), dt) * sc,
        "mlp": {
            "wg": jax.random.normal(ks[4], (d, f), dt) * sc,
            "wu": jax.random.normal(ks[5], (d, f), dt) * sc,
            "wd": jax.random.normal(ks[6], (f, d), dt) * sc,
        },
    }


def _moe_ffn_init(cfg: ModelConfig, key) -> Params:
    d, e, fe = cfg.d_model, cfg.n_experts, cfg.d_ff_expert
    ks = jax.random.split(key, 7)
    sc = 0.02
    dt = _dtype(cfg)
    p = {
        "router": jax.random.normal(ks[0], (d, e), jnp.float32) * sc,
        "wg": jax.random.normal(ks[1], (e, d, fe), dt) * sc,
        "wu": jax.random.normal(ks[2], (e, d, fe), dt) * sc,
        "wd": jax.random.normal(ks[3], (e, fe, d), dt) * sc,
    }
    if cfg.n_shared_experts:
        fs = fe * cfg.n_shared_experts
        p["shared_wg"] = jax.random.normal(ks[4], (d, fs), dt) * sc
        p["shared_wu"] = jax.random.normal(ks[5], (d, fs), dt) * sc
        p["shared_wd"] = jax.random.normal(ks[6], (fs, d), dt) * sc
    return p


def _moe_layer_init(cfg: ModelConfig, key) -> Params:
    k1, k2 = jax.random.split(key)
    base = _dense_layer_init(cfg, k1)
    del base["mlp"]
    base["moe"] = _moe_ffn_init(cfg, k2)
    return base


def _mla_layer_init(cfg: ModelConfig, key) -> Params:
    d, hd, rd = cfg.d_model, cfg.hd, cfg.rope_head_dim
    h = cfg.n_heads
    ql, kvl = cfg.q_lora_rank, cfg.kv_lora_rank
    ks = jax.random.split(key, 8)
    sc = 0.02
    dt = _dtype(cfg)
    layer = {
        "ln1": jnp.ones((d,), dt),
        "ln2": jnp.ones((d,), dt),
        "mla": {
            "wq_a": jax.random.normal(ks[0], (d, ql), dt) * sc,
            "q_norm": jnp.ones((ql,), dt),
            "wq_b": jax.random.normal(ks[1], (ql, h * (hd + rd)), dt) * sc,
            "wkv_a": jax.random.normal(ks[2], (d, kvl + rd), dt) * sc,
            "kv_norm": jnp.ones((kvl,), dt),
            "wkv_b": jax.random.normal(ks[3], (kvl, h * 2 * hd), dt) * sc,
            "wo": jax.random.normal(ks[4], (h * hd, d), dt) * sc,
        },
        "moe": _moe_ffn_init(cfg, ks[5]),
    }
    return layer


def _ssm_layer_init(cfg: ModelConfig, key) -> Params:
    d = cfg.d_model
    di = cfg.d_inner
    h, n = cfg.n_ssm_heads, cfg.ssm_state
    proj = 2 * di + 2 * cfg.ssm_groups * n + h
    c = ssm_mod.conv_channels(cfg)
    ks = jax.random.split(key, 4)
    sc = 0.02
    dt = _dtype(cfg)
    return {
        "ln": jnp.ones((d,), dt),
        "w_in": jax.random.normal(ks[0], (d, proj), dt) * sc,
        "conv_w": jax.random.normal(ks[1], (cfg.conv_kernel, c), dt) * sc,
        "conv_b": jnp.zeros((c,), dt),
        "dt_bias": jnp.zeros((h,), jnp.float32),
        "a_log": jnp.zeros((h,), jnp.float32),
        "d_skip": jnp.ones((h,), jnp.float32),
        "w_out": jax.random.normal(ks[2], (di, d), dt) * sc,
    }


def _stacked(layer_init, cfg: ModelConfig, key, n: int) -> Params:
    keys = jax.random.split(key, n)
    return jax.vmap(lambda k: layer_init(cfg, k))(keys)


def init_params(cfg: ModelConfig, key: jax.Array) -> Params:
    dt = _dtype(cfg)
    k_emb, k_layers, k_head, k_extra = jax.random.split(key, 4)
    params: Params = {
        "embed": jax.random.normal(k_emb, (cfg.vocab, cfg.d_model), dt) * 0.02,
        "final_norm": jnp.ones((cfg.d_model,), dt),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = (
            jax.random.normal(k_head, (cfg.d_model, cfg.vocab), dt) * 0.02
        )

    at = cfg.arch_type
    if at in ("dense", "vlm"):
        params["layers"] = _stacked(_dense_layer_init, cfg, k_layers, cfg.n_layers)
    elif at == "moe":
        init = _mla_layer_init if cfg.use_mla else _moe_layer_init
        params["layers"] = _stacked(init, cfg, k_layers, cfg.n_layers)
    elif at == "ssm":
        params["layers"] = _stacked(_ssm_layer_init, cfg, k_layers, cfg.n_layers)
    elif at == "hybrid":
        params["layers"] = _stacked(_ssm_layer_init, cfg, k_layers, cfg.n_layers)
        params["shared_block"] = _dense_layer_init(cfg, k_extra)
    elif at == "audio":
        params["layers"] = _stacked(
            _audio_decoder_layer_init, cfg, k_layers, cfg.n_layers
        )
        params["encoder"] = _stacked(
            _dense_layer_init, cfg, k_extra, cfg.n_encoder_layers
        )
        params["enc_final_norm"] = jnp.ones((cfg.d_model,), dt)
    else:  # pragma: no cover
        raise AssertionError(at)
    return params


def _audio_decoder_layer_init(cfg: ModelConfig, key) -> Params:
    k1, k2 = jax.random.split(key)
    layer = _dense_layer_init(cfg, k1)
    d, hd = cfg.d_model, cfg.hd
    h, kh = cfg.n_heads, cfg.n_kv_heads
    ks = jax.random.split(k2, 4)
    dt = _dtype(cfg)
    layer["ln_cross"] = jnp.ones((d,), dt)
    layer["cross"] = {
        "wq": jax.random.normal(ks[0], (d, h * hd), dt) * 0.02,
        "wk": jax.random.normal(ks[1], (d, kh * hd), dt) * 0.02,
        "wv": jax.random.normal(ks[2], (d, kh * hd), dt) * 0.02,
        "wo": jax.random.normal(ks[3], (h * hd, d), dt) * 0.02,
    }
    return layer


def abstract_params(cfg: ModelConfig) -> Params:
    """ShapeDtypeStruct pytree — no allocation (dry-run / sharding design)."""
    return jax.eval_shape(
        functools.partial(init_params, cfg), jax.random.key(0)
    )


# ===========================================================================
# Forward (train / prefill)
# ===========================================================================
def _attn_kwargs(cfg: ModelConfig) -> Dict[str, Any]:
    return dict(
        n_heads=cfg.n_heads,
        n_kv_heads=cfg.n_kv_heads,
        head_dim=cfg.hd,
        theta=cfg.rope_theta,
    )


def _dense_block(h, layer, positions, cfg, *, window, impl, mrope=False,
                 mrope_positions=None, unroll=False):
    attn_out, kv = gqa_attention(
        rms_norm(h, layer["ln1"], cfg.norm_eps),
        layer,
        positions,
        causal=True,
        window=window,
        mrope_sections=cfg.mrope_sections if mrope else None,
        mrope_positions=mrope_positions,
        impl=impl,
        unroll=unroll,
        **_attn_kwargs(cfg),
    )
    h = h + attn_out
    h = h + swiglu(rms_norm(h, layer["ln2"], cfg.norm_eps), layer["mlp"])
    return h, kv


def _dense_layers(params, h, positions, cfg, *, window, impl, kv,
                  mrope_positions=None, unroll=False):
    """The dense layer stack over ``h`` (B, S, D).  Returns ``(h, kvs)``:
    with ``kv`` each layer's rope-applied ``(k, v)``, stacked over layers,
    else None."""
    def body(hh, layer):
        hh, layer_kv = _dense_block(
            hh, layer, positions, cfg, window=window, impl=impl,
            mrope=cfg.use_mrope, mrope_positions=mrope_positions,
            unroll=unroll,
        )
        return hh, layer_kv if kv else None

    return _layer_scan(body, h, params["layers"], unroll)


def _embed(params, tokens):
    """Token embeddings (B, S, D) and positions 0..S-1 (B, S)."""
    bsz, s = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(s)[None, :], (bsz, s))
    return params["embed"][tokens], positions


def _logits(params, h, cfg):
    """Final norm and the output head (the embedding table when tied)."""
    h = rms_norm(h, params["final_norm"], cfg.norm_eps)
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    return h @ head


def _moe_block(h, layer, positions, cfg, *, window, impl, dispatch,
               mesh=None, unroll=False):
    if cfg.use_mla:
        attn_out, kv = mla_mod.mla_attention(
            rms_norm(h, layer["ln1"], cfg.norm_eps),
            layer["mla"],
            positions,
            n_heads=cfg.n_heads,
            head_dim=cfg.hd,
            rope_head_dim=cfg.rope_head_dim,
            theta=cfg.rope_theta,
            norm_eps=cfg.norm_eps,
            window=window,
            impl=impl,
            unroll=unroll,
        )
    else:
        attn_out, kv = gqa_attention(
            rms_norm(h, layer["ln1"], cfg.norm_eps),
            layer,
            positions,
            causal=True,
            window=window,
            impl=impl,
            unroll=unroll,
            **_attn_kwargs(cfg),
        )
    h = h + attn_out
    ffn_out, aux = moe_mod.moe_ffn(
        rms_norm(h, layer["ln2"], cfg.norm_eps),
        layer["moe"],
        top_k=cfg.top_k,
        dispatch=dispatch,
        impl=impl,
        mesh=mesh,
    )
    h = h + ffn_out
    return h, kv, aux


def _ssm_block(h, layer, cfg, *, impl, initial_state=None, unroll=False):
    y, state = ssm_mod.mamba2_block(
        rms_norm(h, layer["ln"], cfg.norm_eps), layer, cfg,
        initial_state=initial_state, impl=impl, unroll=unroll,
    )
    return h + y, state


def forward(
    params: Params,
    batch: Dict[str, jax.Array],
    cfg: ModelConfig,
    *,
    impl: str = "ref",
    moe_dispatch: str = "sorted",
    window: Optional[int] = None,
    unroll: bool = False,
    mesh=None,
) -> Tuple[jax.Array, jax.Array]:
    """Full-sequence forward.  ``batch``:
      tokens        : (B, S) int32                       (all archs)
      vision_embeds : (B, n_vis, D)                      (vlm)
      audio_frames  : (B, n_frames, D)                   (audio)
    Returns (logits (B, S, V), aux_loss scalar)."""
    at = cfg.arch_type
    tokens = batch["tokens"]
    bsz, s = tokens.shape
    h, positions = _embed(params, tokens)
    aux_total = jnp.zeros((), jnp.float32)
    mrope_positions = None

    if at == "vlm":
        vis = batch["vision_embeds"].astype(h.dtype)
        nv = vis.shape[1]
        h = jnp.concatenate([vis, h], axis=1)
        s_total = nv + s
        # M-RoPE grid positions for the vision prefix (t=0, h=row, w=col on
        # a square-ish grid), sequential text positions offset past it.
        side = max(1, int(nv ** 0.5))
        vis_idx = jnp.arange(nv)
        vis_pos = jnp.stack(
            [jnp.zeros((nv,), jnp.int32), vis_idx // side, vis_idx % side]
        )  # (3, nv)
        text_pos = jnp.arange(s) + nv
        text_pos3 = jnp.broadcast_to(text_pos[None], (3, s))
        pos3 = jnp.concatenate([vis_pos, text_pos3], axis=1)  # (3, S_total)
        mrope_positions = jnp.broadcast_to(
            pos3[:, None, :], (3, bsz, s_total)
        )
        positions = jnp.broadcast_to(
            jnp.arange(s_total)[None, :], (bsz, s_total)
        )

    if at in ("dense", "vlm"):
        h, _ = _dense_layers(
            params, h, positions, cfg, window=window, impl=impl, kv=False,
            mrope_positions=mrope_positions, unroll=unroll,
        )
        if at == "vlm":
            h = h[:, batch["vision_embeds"].shape[1]:]

    elif at == "moe":
        def body(carry, layer):
            hh, aux = carry
            hh, _, a = _moe_block(
                hh, layer, positions, cfg, window=window, impl=impl,
                dispatch=moe_dispatch, mesh=mesh, unroll=unroll,
            )
            return (hh, aux + a), None

        (h, aux_total), _ = _layer_scan(
            body, (h, aux_total), params["layers"], unroll
        )

    elif at == "ssm":
        def body(carry, layer):
            hh = carry
            hh, _ = _ssm_block(hh, layer, cfg, impl=impl, unroll=unroll)
            return hh, None

        h, _ = _layer_scan(body, h, params["layers"], unroll)

    elif at == "hybrid":
        shared = params["shared_block"]
        period = cfg.attn_period

        def body(carry, xs):
            hh = carry
            layer, idx = xs
            hh, _ = _ssm_block(hh, layer, cfg, impl=impl, unroll=unroll)

            def with_attn(hx):
                out, _ = _dense_block(
                    hx, shared, positions, cfg,
                    window=cfg.sliding_window, impl=impl, unroll=unroll,
                )
                return out

            if unroll:
                hh = with_attn(hh) if (int(idx) + 1) % period == 0 else hh
            else:
                hh = jax.lax.cond(
                    (idx + 1) % period == 0, with_attn, lambda hx: hx, hh
                )
            return hh, None

        if unroll:
            import numpy as _np
            idxs = _np.arange(cfg.n_layers)
        else:
            idxs = jnp.arange(cfg.n_layers)
        h, _ = _layer_scan(body, h, (params["layers"], idxs), unroll)

    elif at == "audio":
        enc = _encode_audio(
            params, batch["audio_frames"], cfg, impl=impl, unroll=unroll
        )

        def body(carry, layer):
            hh = carry
            hh, _ = _dense_block(
                hh, layer, positions, cfg, window=window, impl=impl,
                unroll=unroll,
            )
            cross_out = cross_attention(
                rms_norm(hh, layer["ln_cross"], cfg.norm_eps),
                layer["cross"],
                *project_cross_kv(
                    enc, layer["cross"],
                    n_kv_heads=cfg.n_kv_heads, head_dim=cfg.hd,
                ),
                n_heads=cfg.n_heads,
                head_dim=cfg.hd,
                impl=impl,
            )
            return hh + cross_out, None

        h, _ = _layer_scan(body, h, params["layers"], unroll)
    else:  # pragma: no cover
        raise AssertionError(at)

    return _logits(params, h, cfg), aux_total


def _sinusoidal(n: int, d: int) -> jax.Array:
    pos = jnp.arange(n)[:, None].astype(jnp.float32)
    i = jnp.arange(d // 2)[None, :].astype(jnp.float32)
    ang = pos / (10000.0 ** (2 * i / d))
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


def _encode_audio(params, frames, cfg, *, impl, unroll=False):
    """Whisper-style encoder over stub frame embeddings (the mel/conv
    frontend is a stub per the carve-out; frames arrive (B, T, D))."""
    bsz, t, _ = frames.shape
    h = frames + _sinusoidal(t, cfg.d_model).astype(frames.dtype)[None]
    positions = jnp.broadcast_to(jnp.arange(t)[None, :], (bsz, t))

    def body(carry, layer):
        hh = carry
        attn_out, _ = gqa_attention(
            rms_norm(hh, layer["ln1"], cfg.norm_eps),
            layer, positions, causal=False, impl=impl, **_attn_kwargs(cfg),
        )
        hh = hh + attn_out
        hh = hh + swiglu(rms_norm(hh, layer["ln2"], cfg.norm_eps), layer["mlp"])
        return hh, None

    h, _ = _layer_scan(body, h, params["encoder"], unroll)
    return rms_norm(h, params["enc_final_norm"], cfg.norm_eps)


# ===========================================================================
# Loss / train step core
# ===========================================================================
def next_token_loss(
    params: Params,
    batch: Dict[str, jax.Array],
    cfg: ModelConfig,
    *,
    impl: str = "ref",
    moe_dispatch: str = "sorted",
    aux_weight: float = 0.01,
    unroll: bool = False,
    mesh=None,
) -> jax.Array:
    logits, aux = forward(
        params, batch, cfg, impl=impl, moe_dispatch=moe_dispatch,
        unroll=unroll, mesh=mesh,
    )
    targets = batch["tokens"][:, 1:]
    logits = logits[:, :-1].astype(jnp.float32)
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    return jnp.mean(nll) + aux_weight * aux


# ===========================================================================
# Decode cache + one-token decode step
# ===========================================================================
def init_cache(
    cfg: ModelConfig,
    batch: int,
    capacity: int,
    *,
    dtype=None,
) -> Cache:
    """Family-specific decode cache.  ``capacity`` is the KV capacity —
    the sliding window size for windowed archs, the max sequence length
    otherwise.  SSM caches are O(1) in capacity."""
    dt = dtype or _dtype(cfg)
    l, kh, hd = cfg.n_layers, cfg.n_kv_heads, cfg.hd
    at = cfg.arch_type
    cache: Cache = {
        "pos": jnp.zeros((batch,), jnp.int32),
    }
    if at in ("dense", "vlm"):
        cache["k"] = jnp.zeros((l, batch, capacity, kh, hd), dt)
        cache["v"] = jnp.zeros((l, batch, capacity, kh, hd), dt)
    elif at == "moe":
        if cfg.use_mla:
            cache["ckv"] = jnp.zeros((l, batch, capacity, cfg.kv_lora_rank), dt)
            cache["krope"] = jnp.zeros(
                (l, batch, capacity, cfg.rope_head_dim), dt
            )
        else:
            cache["k"] = jnp.zeros((l, batch, capacity, kh, hd), dt)
            cache["v"] = jnp.zeros((l, batch, capacity, kh, hd), dt)
    elif at == "ssm":
        cache["conv"] = jnp.zeros(
            (l, batch, cfg.conv_kernel - 1, ssm_mod.conv_channels(cfg)), dt
        )
        cache["ssm"] = jnp.zeros(
            (l, batch, cfg.n_ssm_heads, cfg.ssm_head_dim, cfg.ssm_state),
            jnp.float32,
        )
    elif at == "hybrid":
        cache["conv"] = jnp.zeros(
            (l, batch, cfg.conv_kernel - 1, ssm_mod.conv_channels(cfg)), dt
        )
        cache["ssm"] = jnp.zeros(
            (l, batch, cfg.n_ssm_heads, cfg.ssm_head_dim, cfg.ssm_state),
            jnp.float32,
        )
        napp = cfg.n_layers // cfg.attn_period
        wcap = min(capacity, cfg.sliding_window or capacity)
        cache["shared_k"] = jnp.zeros((napp, batch, wcap, kh, hd), dt)
        cache["shared_v"] = jnp.zeros((napp, batch, wcap, kh, hd), dt)
    elif at == "audio":
        cache["k"] = jnp.zeros((l, batch, capacity, kh, hd), dt)
        cache["v"] = jnp.zeros((l, batch, capacity, kh, hd), dt)
        cache["cross_k"] = jnp.zeros(
            (l, batch, cfg.n_audio_frames, kh, hd), dt
        )
        cache["cross_v"] = jnp.zeros(
            (l, batch, cfg.n_audio_frames, kh, hd), dt
        )
    else:  # pragma: no cover
        raise AssertionError(at)
    return cache


def prefill(
    params: Params,
    cache: Cache,
    tokens: jax.Array,
    cfg: ModelConfig,
) -> Tuple[jax.Array, Cache]:
    """Whole-prompt prefill of a fresh cache (``pos`` 0): tokens (B, S)
    int32 → (logits of the last position (B, V), cache holding positions
    0..S-1 with ``pos`` S).  For the families in ``PREFILL_FAMILIES``; the
    layers run as in ``forward``, causal over the prompt."""
    if cfg.arch_type not in PREFILL_FAMILIES:
        raise ValueError(f"prefill serves {sorted(PREFILL_FAMILIES)}, not "
                         f"{cfg.arch_type!r}")
    s = tokens.shape[1]
    if s > cache["k"].shape[2]:
        raise ValueError(f"prompt of {s} exceeds cache capacity "
                         f"{cache['k'].shape[2]}")
    h, positions = _embed(params, tokens)
    h, (ks, vs) = _dense_layers(params, h, positions, cfg, window=None,
                                impl="ref", kv=True)
    new_cache = dict(cache)
    for name, new in (("k", ks), ("v", vs)):
        new_cache[name] = jax.lax.dynamic_update_slice(
            cache[name], new.astype(cache[name].dtype), (0,) * new.ndim)
    new_cache["pos"] = jnp.full_like(cache["pos"], s)
    return _logits(params, h[:, -1], cfg), new_cache


def _ring(pos: jax.Array, capacity: int, windowed: bool) -> Tuple[jax.Array, jax.Array]:
    """(write_index, cache_len) for ring-buffer vs linear caches."""
    if windowed:
        return pos % capacity, jnp.minimum(pos + 1, capacity)
    return pos, pos + 1


def decode_step(
    params: Params,
    cache: Cache,
    tokens: jax.Array,
    cfg: ModelConfig,
    *,
    impl: str = "ref",
    moe_dispatch: str = "sorted",
    unroll: bool = False,
    mesh=None,
    cache_update: str = "scatter",
) -> Tuple[jax.Array, Cache]:
    """One decode step: tokens (B,) int32 → (logits (B, V), new cache)."""
    at = cfg.arch_type
    bsz = tokens.shape[0]
    h = params["embed"][tokens]  # (B, D)
    pos = cache["pos"]
    new_cache = dict(cache)
    windowed = cfg.sliding_window is not None

    if at in ("dense", "vlm", "audio") or (at == "moe" and not cfg.use_mla):
        capacity = cache["k"].shape[2]
        write_idx, cache_len = _ring(pos, capacity, windowed)

        if at == "audio":
            def body(carry, xs):
                hh = carry
                layer, kc, vc, ck, cv = xs
                x = rms_norm(hh, layer["ln1"], cfg.norm_eps)
                attn_out, (kc, vc) = gqa_decode_attention(
                    x, layer, pos, kc, vc, cache_len, write_idx,
                    impl=impl, cache_update=cache_update,
                    **_attn_kwargs(cfg),
                )
                hh = hh + attn_out
                xq = rms_norm(hh, layer["ln_cross"], cfg.norm_eps)
                # Cross-attention over the (static) encoder KV.
                q = (xq @ layer["cross"]["wq"]).reshape(
                    bsz, cfg.n_heads, cfg.hd
                )
                enc_len = jnp.full((bsz,), ck.shape[1], jnp.int32)
                from repro.kernels import ops as kops

                cross = kops.decode_attention(q, ck, cv, enc_len, impl=impl)
                hh = hh + cross.reshape(bsz, -1) @ layer["cross"]["wo"]
                hh = hh + swiglu(
                    rms_norm(hh, layer["ln2"], cfg.norm_eps), layer["mlp"]
                )
                return hh, (kc, vc)

            h, (ks, vs) = _layer_scan(
                body,
                h,
                (
                    params["layers"], cache["k"], cache["v"],
                    cache["cross_k"], cache["cross_v"],
                ),
                unroll,
            )
            new_cache["k"], new_cache["v"] = ks, vs
        else:
            mrope = at == "vlm" and cfg.use_mrope

            def body(carry, xs):
                hh, aux = carry
                layer, kc, vc = xs
                x = rms_norm(hh, layer["ln1"], cfg.norm_eps)
                attn_out, (kc, vc) = gqa_decode_attention(
                    x, layer, pos, kc, vc, cache_len, write_idx,
                    mrope_sections=cfg.mrope_sections if mrope else None,
                    impl=impl, cache_update=cache_update,
                    **_attn_kwargs(cfg),
                )
                hh = hh + attn_out
                x2 = rms_norm(hh, layer["ln2"], cfg.norm_eps)
                if at == "moe":
                    ffn, a = moe_mod.moe_ffn(
                        x2[:, None, :], layer["moe"], top_k=cfg.top_k,
                        dispatch=moe_dispatch, impl=impl, mesh=mesh,
                    )
                    hh = hh + ffn[:, 0]
                    aux = aux + a
                else:
                    hh = hh + swiglu(x2, layer["mlp"])
                return (hh, aux), (kc, vc)

            (h, _), (ks, vs) = _layer_scan(
                body,
                (h, jnp.zeros((), jnp.float32)),
                (params["layers"], cache["k"], cache["v"]),
                unroll,
            )
            new_cache["k"], new_cache["v"] = ks, vs

    elif at == "moe" and cfg.use_mla:
        capacity = cache["ckv"].shape[2]
        write_idx, cache_len = _ring(pos, capacity, windowed)

        def body(carry, xs):
            hh, aux = carry
            layer, ckv, krope = xs
            x = rms_norm(hh, layer["ln1"], cfg.norm_eps)
            attn_out, (ckv, krope) = mla_mod.mla_decode_attention(
                x, layer["mla"], pos, ckv, krope, cache_len, write_idx,
                n_heads=cfg.n_heads, head_dim=cfg.hd,
                rope_head_dim=cfg.rope_head_dim, theta=cfg.rope_theta,
                norm_eps=cfg.norm_eps, impl=impl,
                cache_update=cache_update,
            )
            hh = hh + attn_out
            x2 = rms_norm(hh, layer["ln2"], cfg.norm_eps)
            ffn, a = moe_mod.moe_ffn(
                x2[:, None, :], layer["moe"], top_k=cfg.top_k,
                dispatch=moe_dispatch, impl=impl, mesh=mesh,
            )
            return (hh + ffn[:, 0], aux + a), (ckv, krope)

        (h, _), (ckvs, kropes) = _layer_scan(
            body,
            (h, jnp.zeros((), jnp.float32)),
            (params["layers"], cache["ckv"], cache["krope"]),
            unroll,
        )
        new_cache["ckv"], new_cache["krope"] = ckvs, kropes

    elif at == "ssm":
        def body(carry, xs):
            hh = carry
            layer, conv, st = xs
            y, conv, st = ssm_mod.mamba2_decode(
                rms_norm(hh, layer["ln"], cfg.norm_eps), layer, cfg, conv, st
            )
            return hh + y, (conv, st)

        h, (convs, sts) = _layer_scan(
            body, h, (params["layers"], cache["conv"], cache["ssm"]), unroll
        )
        new_cache["conv"], new_cache["ssm"] = convs, sts

    elif at == "hybrid":
        shared = params["shared_block"]
        period = cfg.attn_period
        wcap = cache["shared_k"].shape[2]
        write_idx, cache_len = _ring(pos, wcap, True)

        def body(carry, xs):
            hh, sk, sv = carry
            layer, conv, st, idx = xs
            y, conv, st = ssm_mod.mamba2_decode(
                rms_norm(hh, layer["ln"], cfg.norm_eps), layer, cfg, conv, st
            )
            hh = hh + y

            def with_attn(operand):
                hx, sk_, sv_ = operand
                app = (idx + 1) // period - 1
                kc = sk_[app]
                vc = sv_[app]
                x = rms_norm(hx, shared["ln1"], cfg.norm_eps)
                attn_out, (kc, vc) = gqa_decode_attention(
                    x, shared, pos, kc, vc, cache_len, write_idx,
                    impl=impl, cache_update=cache_update,
                    **_attn_kwargs(cfg),
                )
                hx = hx + attn_out
                hx = hx + swiglu(
                    rms_norm(hx, shared["ln2"], cfg.norm_eps), shared["mlp"]
                )
                sk_ = jax.lax.dynamic_update_index_in_dim(sk_, kc, app, 0)
                sv_ = jax.lax.dynamic_update_index_in_dim(sv_, vc, app, 0)
                return hx, sk_, sv_

            if unroll:
                if (int(idx) + 1) % period == 0:
                    hh, sk, sv = with_attn((hh, sk, sv))
            else:
                hh, sk, sv = jax.lax.cond(
                    (idx + 1) % period == 0,
                    with_attn,
                    lambda op: op,
                    (hh, sk, sv),
                )
            return (hh, sk, sv), (conv, st)

        if unroll:
            import numpy as _np
            idxs = _np.arange(cfg.n_layers)
        else:
            idxs = jnp.arange(cfg.n_layers)
        (h, sk, sv), (convs, sts) = _layer_scan(
            body,
            (h, cache["shared_k"], cache["shared_v"]),
            (params["layers"], cache["conv"], cache["ssm"], idxs),
            unroll,
        )
        new_cache.update(conv=convs, ssm=sts, shared_k=sk, shared_v=sv)
    else:  # pragma: no cover
        raise AssertionError(at)

    new_cache["pos"] = pos + 1
    return _logits(params, h, cfg), new_cache
