"""The dense family: decoder layers with grouped-query attention under
RoPE and a SwiGLU MLP (the Mistral / Llama block), served by the
program's ``dense`` architecture."""

import jax.numpy as jnp

from chipbench.work import itemsize, leaf_bytes

ARCH_TYPE = "dense"
PROGRAM_KEYS = {"num_attention_heads": "n_heads",
                "num_key_value_heads": "n_kv_heads", "head_dim": "head_dim",
                "intermediate_size": "d_ff", "rope_theta": "rope_theta"}


def _widths(m):
    """Model width, query and key/value widths, MLP width."""
    hd = m["head_dim"]
    return (m["hidden_size"], m["num_attention_heads"] * hd,
            m["num_key_value_heads"] * hd, m["intermediate_size"])


def leaves(m):
    l, wd = m["num_hidden_layers"], jnp.dtype(m["dtype"])
    d, q, kv, f = _widths(m)
    return {
        "layers/ln1": ((l, d), wd, "norm"),
        "layers/ln2": ((l, d), wd, "norm"),
        "layers/wq": ((l, d, q), wd, "normal"),
        "layers/wk": ((l, d, kv), wd, "normal"),
        "layers/wv": ((l, d, kv), wd, "normal"),
        "layers/wo": ((l, q, d), wd, "out"),
        "layers/mlp/wg": ((l, d, f), wd, "normal"),
        "layers/mlp/wu": ((l, d, f), wd, "normal"),
        "layers/mlp/wd": ((l, f, d), wd, "out"),
    }


def _layer_flops(m, context):
    d, q, kv, f = _widths(m)
    return (2 * (d * q + 2 * d * kv + q * d) + 6 * d * f
            + 4 * q * context)  # scores and weighted values


def _kv_bytes(m):
    """Cache bytes of one position, all layers: k and v."""
    return m["num_hidden_layers"] * _widths(m)[2] * itemsize(m) * 2


def decode_stack(m, context):
    """The valid prefix of the cache read and one position written."""
    return (m["num_hidden_layers"] * _layer_flops(m, context),
            leaf_bytes(leaves(m)) + _kv_bytes(m) * (context + 1))


def prefill_stack(m, prompt):
    """Position p attends causally over p + 1 positions; the prompt's keys
    and values are written once."""
    # _layer_flops is linear in the context: the mean context of a causal
    # prompt, (prompt + 1) / 2, times the prompt's positions
    flops = m["num_hidden_layers"] * prompt * _layer_flops(
        m, (prompt + 1) / 2.0)
    return flops, leaf_bytes(leaves(m)) + _kv_bytes(m) * prompt
