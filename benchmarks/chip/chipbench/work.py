"""Operations and HBM bytes of one decode step (batch 1), from a
configuration's published shapes.

Counted so that the result is the least the step has to do, whatever
implements it:

* every weight the step reads counts once; of the embedding table only the
  gathered row counts; an untied output head counts whole;
* the recurrent state (SSM) or the key/value cache (attention) counts for
  what the step reads and writes: the whole state both ways, or the valid
  prefix of the cache read and one position written;
* the logits are written once, in the served dtype.
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

import numpy as np


def _itemsize(m: Dict[str, Any]) -> int:
    return np.dtype({"bfloat16": "float16"}.get(m["dtype"], m["dtype"])).itemsize


def weight_bytes(m: Dict[str, Any], family: str) -> int:
    """Bytes of all weights, as the chip holds them."""
    w = _itemsize(m)
    d, l, v = m["hidden_size"], m["num_hidden_layers"], m["vocab_size"]
    emb = v * d * w * (1 if m["tie_word_embeddings"] else 2)
    return emb + d * w + l * _layer_bytes(m, family)


def _layer_bytes(m: Dict[str, Any], family: str) -> int:
    w = _itemsize(m)
    d = m["hidden_size"]
    if family == "ssm":
        di = m["expand"] * d
        h = di // m["head_dim"]
        gn = m["n_groups"] * m["state_size"]
        c = di + 2 * gn
        return (d * w  # norm
                + d * (2 * di + 2 * gn + h) * w  # in_proj
                + (m["conv_kernel"] + 1) * c * w  # conv weight and bias
                + 3 * h * 4  # dt_bias, A_log, D in float32
                + di * w  # gated norm
                + di * d * w)  # out_proj
    if family == "dense":
        hd = m["head_dim"]
        q, kv = m["num_attention_heads"] * hd, m["num_key_value_heads"] * hd
        f = m["intermediate_size"]
        return (2 * d * w  # two norms
                + (d * q + 2 * d * kv + q * d) * w  # attention
                + 3 * d * f * w)  # SwiGLU
    raise ValueError(f"unknown family {family!r}")


def _layer_flops(m: Dict[str, Any], family: str, context: float) -> float:
    d = m["hidden_size"]
    if family == "ssm":
        di = m["expand"] * d
        h = di // m["head_dim"]
        n = m["state_size"]
        gn = m["n_groups"] * n
        proj = 2 * di + 2 * gn + h
        return (2 * d * proj + 2 * di * d  # projections
                + 2 * m["conv_kernel"] * (di + 2 * gn)  # conv
                + 6 * di * n)  # state decay, update and read-out
    hd = m["head_dim"]
    q, kv = m["num_attention_heads"] * hd, m["num_key_value_heads"] * hd
    f = m["intermediate_size"]
    return (2 * (d * q + 2 * d * kv + q * d) + 6 * d * f
            + 4 * q * context)  # scores and weighted values


def _state_bytes(m: Dict[str, Any], family: str, context: float) -> float:
    """State or cache bytes one step reads and writes, all layers."""
    w = _itemsize(m)
    l = m["num_hidden_layers"]
    if family == "ssm":
        d = m["hidden_size"]
        di = m["expand"] * d
        c = di + 2 * m["n_groups"] * m["state_size"]
        ssm = di * m["state_size"] * 4  # float32 state
        conv = (m["conv_kernel"] - 1) * c * w
        return l * 2 * (ssm + conv)
    kv = m["num_key_value_heads"] * m["head_dim"] * w * 2  # k and v
    return l * kv * (context + 1)


def decode_step(m: Dict[str, Any], family: str,
                context: float) -> Tuple[float, float]:
    """(FLOPs, HBM bytes) of one decode step that attends over ``context``
    cached positions (the one written included)."""
    w = _itemsize(m)
    d, l, v = m["hidden_size"], m["num_hidden_layers"], m["vocab_size"]
    flops = l * _layer_flops(m, family, context) + 2 * d * v
    nbytes = (l * _layer_bytes(m, family)
              + d * w  # final norm
              + d * w  # the embedding row gathered
              + (0 if m["tie_word_embeddings"] else d * v * w)  # head
              + _state_bytes(m, family, context)
              + v * w)  # logits out
    if m["tie_word_embeddings"]:
        nbytes += d * v * w - d * w  # the head is the whole table
    return float(flops), float(nbytes)


def mean_context(mix: Dict[str, Any]) -> float:
    """Mean number of valid cache positions over the step calls of a mix:
    a stage of prompt S and D decode tokens calls the step at positions
    0 .. S+D-1, attending over pos+1 positions."""
    dec = mix["decode_tokens"]
    shape = mix["dfg"]["shape"]
    stages = mix["dfg"]["stages"]
    num = den = 0.0
    for s_str, p in mix["prompt_tokens"].items():
        s = int(s_str)
        lens = [s] * stages if shape == "fanout" else [s] + [dec] * (stages - 1)
        for sl in lens:
            n = sl + dec
            num += p * n * (n + 1) / 2.0
            den += p * n
    return num / den


def step_calls(mix: Dict[str, Any]) -> float:
    """Mean decode-step calls per request."""
    dec = mix["decode_tokens"]
    stages = mix["dfg"]["stages"]
    mean_s = sum(int(s) * p for s, p in mix["prompt_tokens"].items())
    if mix["dfg"]["shape"] == "fanout":
        return stages * (mean_s + dec)
    return mean_s + dec + (stages - 1) * 2 * dec
