"""Operations and HBM bytes of one whole-prompt prefill call (batch 1),
counted as ``work.py`` counts a decode step: the least the call has to
do, whatever implements it.

* every layer weight is read once, whatever the prompt's length; of the
  embedding table only the prompt's rows count; an untied head counts
  whole (it runs at the last position only);
* the layer stack, its cache written, counts as its family's
  ``prefill_stack`` states it; a family without one has no such call;
* the last position's logits are written once, in the served dtype.
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

from chipbench import catalog, work

# The served path's prefill program (``ExecutionEngine.prefill_fn``), as the
# profiler names its executions.
PROGRAM = "jit_served_prefill"


def prefill_call(m: Dict[str, Any], family: str,
                 prompt: int) -> Tuple[float, float]:
    """(FLOPs, HBM bytes) of one prefill of ``prompt`` positions through a
    family's ``prefill_stack``; the head runs once."""
    fam = catalog.family(family)
    if not hasattr(fam, "prefill_stack"):
        raise ValueError(f"no whole-prompt prefill for family {family!r}")
    w = work.itemsize(m)
    d, v = m["hidden_size"], m["vocab_size"]
    flops, nbytes = fam.prefill_stack(m, prompt)
    flops += 2 * d * v
    nbytes += (d * w  # final norm
               + prompt * d * w  # the embedding rows gathered
               + d * v * w  # head, or the whole table when tied
               + v * w)  # logits out
    return float(flops), float(nbytes)


def prefill_prompts(mix: Dict[str, Any]) -> Dict[int, float]:
    """Share of a mix's prefill calls by prompt length: a chain's later
    stages are prompted with the stage before's decoded tokens."""
    dec = mix["decode_tokens"]
    stages = mix["dfg"]["stages"]
    out: Dict[int, float] = {}
    for s_str, p in mix["prompt_tokens"].items():
        lens = ([int(s_str)] * stages if mix["dfg"]["shape"] == "fanout"
                else [int(s_str)] + [dec] * (stages - 1))
        for s in lens:
            out[s] = out.get(s, 0.0) + p / stages
    return out


def least_s(m: Dict[str, Any], family: str, mix: Dict[str, Any],
            peaks: Dict[str, Any]) -> float:
    """Mean over a mix's prefill calls of the least time each takes:
    max(FLOPs / peak FLOP/s, bytes / peak HBM B/s)."""
    total = 0.0
    for s, share in prefill_prompts(mix).items():
        flops, nbytes = prefill_call(m, family, s)
        total += share * max(flops / peaks["bf16_flops_per_s"],
                             nbytes / peaks["hbm_bytes_per_s"])
    return total
