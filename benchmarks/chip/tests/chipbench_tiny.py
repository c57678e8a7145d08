"""Tiny configurations of both families for the CPU tests, with the
program's own sizes cut to match."""

import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent))
sys.path.insert(0, str(HERE.parents[2] / "src"))

from chipbench import catalog  # noqa: E402
from chipbench.cell import Spec  # noqa: E402

# Widest logit gap of served tokens allowed at these sizes: over three
# seeds each, bf16 programs read at most 0.0092 and the fp8 control at
# least 0.023 (CPU, tiny sizes below).
TINY_GAP_LIMIT = 0.015


def tiny_spec(family: str, shape: str = "chain", *, d: int = 256,
              vocab: int = 4096, decode: int = 16) -> Spec:
    """A tiny cell of ``family`` under the ``chat`` mix, its DFG a chain
    or a fan-out of two stages joined on the host."""
    common = dict(hidden_size=d, num_hidden_layers=2, vocab_size=vocab,
                  tie_word_embeddings=False, rms_norm_eps=1e-5,
                  dtype="bfloat16")
    if family == "ssm":
        model = dict(common, state_size=16, head_dim=16, expand=2,
                     conv_kernel=4, n_groups=1)
        prog = {"arch": "mamba2-780m", "overrides": dict(
            n_layers=2, d_model=d, vocab=vocab, ssm_state=16,
            ssm_head_dim=16, tie_embeddings=False)}
    else:
        model = dict(common, num_attention_heads=4, num_key_value_heads=2,
                     head_dim=16, intermediate_size=128, rope_theta=1e6)
        prog = {"arch": "mistral-nemo-12b", "overrides": dict(
            n_layers=2, d_model=d, vocab=vocab, n_heads=4, n_kv_heads=2,
            head_dim=16, d_ff=128)}
    conf = {
        "name": f"tiny-{family}", "family": family, "reference": family,
        "model": model, "program": prog,
        "deployment": {"chips": 1, "workers": 1, "scheduler": "navigator",
                       "cluster_profile": "TPU_V5E_CLUSTER"},
        "check": {"logit_gap_limit": TINY_GAP_LIMIT},
    }
    mix = catalog.traffic("chat")
    mix["dfg"] = {"shape": shape, "stages": 2}
    mix["arrival"] = dict(mix["arrival"], rate_per_s=4.0, schedule_seed=1201)
    mix["prompt_tokens"] = {"8": 0.5, "16": 0.5}
    mix["decode_tokens"] = decode
    return Spec(f"tiny.{shape}", conf, mix)
