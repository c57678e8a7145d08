"""Bytes and FLOPs per decode step against hand counts, and weight bytes
against the trees the harness makes."""

import jax.numpy as jnp
import pytest
from chipbench_tiny import catalog

from chipbench import weights, work


def conf(name):
    return catalog.load_json(catalog.BENCH_DIR / "configs" / f"{name}.json")


def tree_bytes(c):
    tree = weights.abstract(c["model"], c["family"])
    import jax
    return sum(x.size * jnp.dtype(x.dtype).itemsize
               for x in jax.tree.leaves(tree))


def test_mamba_weight_and_step_bytes():
    c = conf("mamba2-780m")
    m = c["model"]
    # 50288 x 1536 x 2 (embedding, tied head) + 1536 x 2 (final norm)
    # + 48 x 29288512 per layer (the gated norm's 3072 x 2 included)
    embed = 50288 * 1536 * 2
    assert work.weight_bytes(m, "ssm") == embed + 3072 + 48 * 29288512
    assert work.weight_bytes(m, "ssm") == 1560336384 == tree_bytes(c)
    flops, nbytes = work.decode_step(m, "ssm", context=50.0)
    state = 2 * 48 * (48 * 64 * 128 * 4)  # float32 SSM state, read + write
    conv = 2 * 48 * (3 * 3328 * 2)
    logits = 50288 * 2
    # every layer, the final norm, the whole table as the head (the row
    # gathered is inside it), state and logits
    assert nbytes == 48 * 29288512 + 3072 + embed + state + conv + logits
    assert nbytes == 1713348832
    # the step is HBM-bound: 2.09 ms at 819 GB/s against 8.5 us of FLOPs
    assert nbytes / 819e9 > 100 * flops / 197e12


def test_nemo_cut_weight_and_step_bytes():
    c = conf("nemo-12b-s10")
    m = c["model"]
    layer = 545280000
    head = 131072 * 5120 * 2
    # 10 layers + embedding + head (8137154560 B) + the final norm (10240 B)
    assert work.weight_bytes(m, "dense") == 10 * layer + 2 * head + 10240
    assert work.weight_bytes(m, "dense") == 8137164800 == tree_bytes(c)
    flops, nbytes = work.decode_step(m, "dense", context=34.0)
    kv = 10 * 8 * 128 * 2 * 2 * 35  # k and v, 34 positions read + 1 written
    assert nbytes == 10 * layer + head + 10240 + 10240 + kv + 131072 * 2
    assert 6.79e9 < nbytes < 6.81e9  # 8.30 ms at 819 GB/s
    assert flops == 10 * (2 * (5120 * 4096 + 2 * 5120 * 1024 + 4096 * 5120)
                          + 6 * 5120 * 14336 + 4 * 4096 * 34) + 2 * 5120 * 131072


def test_mix_step_calls_and_context():
    chat = catalog.traffic("chat")
    # mean prompt 0.15 x 16 + 0.215 x 32 + 0.27 x 64 + 0.215 x 128
    # + 0.15 x 256 = 92.48, then 8 out; stage 2 is fed those 8, 8 out
    assert work.step_calls(chat) == pytest.approx(92.48 + 8 + 16)
    fan = dict(chat, dfg={"shape": "fanout", "stages": 2})
    assert work.step_calls(fan) == pytest.approx(2 * (92.48 + 8))
    # one stage of prompt S and D decode tokens: mean context (S+D+1)/2
    g = dict(fan, prompt_tokens={"8": 1.0}, decode_tokens=48)
    assert work.mean_context(g) == (8 + 48 + 1) / 2
