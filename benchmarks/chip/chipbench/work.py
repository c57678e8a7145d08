"""Operations and HBM bytes of one decode step (batch 1), from a
configuration's published shapes.

Counted so that the result is the least the step has to do, whatever
implements it:

* every weight the step reads counts once; of the embedding table only the
  gathered row counts; an untied output head counts whole;
* the layer stack, its cache or recurrent state read and written, counts
  as its family (``families/<family>.py``) states it;
* the logits are written once, in the served dtype.
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

import numpy as np

from chipbench import catalog


def itemsize(m: Dict[str, Any]) -> int:
    return np.dtype({"bfloat16": "float16"}.get(m["dtype"], m["dtype"])).itemsize


def leaf_bytes(leaves: Dict[str, Any]) -> int:
    """Bytes of a family's ``leaves(m)``, as the chip holds them."""
    return sum(int(np.prod(s)) * np.dtype(t).itemsize
               for s, t, _ in leaves.values())


def weight_bytes(m: Dict[str, Any], family: str) -> int:
    """Bytes of all weights, as the chip holds them."""
    w = itemsize(m)
    d, v = m["hidden_size"], m["vocab_size"]
    emb = v * d * w * (1 if m["tie_word_embeddings"] else 2)
    return emb + d * w + leaf_bytes(catalog.family(family).leaves(m))


def decode_step(m: Dict[str, Any], family: str,
                context: float) -> Tuple[float, float]:
    """(FLOPs, HBM bytes) of one decode step that attends over ``context``
    cached positions (the one written included)."""
    w = itemsize(m)
    d, v = m["hidden_size"], m["vocab_size"]
    flops, nbytes = catalog.family(family).decode_stack(m, context)
    flops += 2 * d * v
    nbytes += (d * w  # final norm
               + d * w  # the embedding row gathered
               + (0 if m["tie_word_embeddings"] else d * v * w)  # head
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
