"""A model family is a file: the weight trees of the dense and ssm families
are pinned bit for bit, and a new family added to a copy of the harness as
``families/<name>.py`` alone is taken by every entry point that depends on
the family."""

import hashlib
import json
import os
import shutil
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest
from chipbench_tiny import HERE, catalog, tiny_spec

from chipbench import weights

# sha256 over (path, bytes) of every leaf of weights.make's tree for
# tiny_spec(family) at seed 2**33 + 5, taken before the families moved
# out of weights.py.
TREE_SHA256 = {
    "dense": "13fa53d0555973e5748c3c4cabda368aaa889cec8cf8f40f71bcfcd58ae02871",
    "ssm": "58b6053d71b03543ad963909a6edfd5648aa63b4619e0b69c37598de89f50402",
}


@pytest.mark.parametrize("family", sorted(TREE_SHA256))
def test_weight_tree_is_pinned(family):
    w = weights.make(tiny_spec(family).conf["model"], family, seed=2**33 + 5)
    h = hashlib.sha256()
    for p, x in jax.tree_util.tree_leaves_with_path(w):
        h.update(jax.tree_util.keystr(p).encode())
        h.update(np.asarray(x).tobytes())
    assert h.hexdigest() == TREE_SHA256[family]


# Latent attention (MLA) and routed experts after a leading dense block,
# as DeepSeek-V2 (arXiv:2405.04434) states them; ``experts_held`` of the
# ``n_routed_experts`` the router scores live on this chip.
LATENT_MOE = '''
import jax
import jax.numpy as jnp

from chipbench.work import itemsize, leaf_bytes

ARCH_TYPE = "moe"
PROGRAM_KEYS = {
    "num_attention_heads": "n_heads", "qk_nope_head_dim": "head_dim",
    "qk_rope_head_dim": "rope_head_dim", "kv_lora_rank": "kv_lora_rank",
    "q_lora_rank": "q_lora_rank", "n_routed_experts": "n_experts",
    "num_experts_per_tok": "top_k", "n_shared_experts": "n_shared_experts",
    "moe_intermediate_size": "d_ff_expert"}


def _blocks(m):
    k = m["first_k_dense_replace"]
    return {"dense_layers": k, "layers": m["num_hidden_layers"] - k}


def leaves(m):
    d, h, wd = m["hidden_size"], m["num_attention_heads"], jnp.dtype(m["dtype"])
    nope, rope, v = (m["qk_nope_head_dim"], m["qk_rope_head_dim"],
                     m["v_head_dim"])
    ql, kvl = m["q_lora_rank"], m["kv_lora_rank"]
    f, fe = m["intermediate_size"], m["moe_intermediate_size"]
    e, fs = m["experts_held"], m["n_shared_experts"] * fe
    out = {}
    for blk, n in _blocks(m).items():
        out.update({f"{blk}/{k}": ((n,) + s, t, kind) for k, (s, t, kind) in {
            "ln1": ((d,), wd, "norm"), "ln2": ((d,), wd, "norm"),
            "mla/wq_a": ((d, ql), wd, "normal"),
            "mla/q_norm": ((ql,), wd, "norm"),
            "mla/wq_b": ((ql, h * (nope + rope)), wd, "normal"),
            "mla/wkv_a": ((d, kvl + rope), wd, "normal"),
            "mla/kv_norm": ((kvl,), wd, "norm"),
            "mla/wkv_b": ((kvl, h * (nope + v)), wd, "normal"),
            "mla/wo": ((h * v, d), wd, "out"),
        }.items()})
    out.update({f"dense_layers/mlp/{k}": ((_blocks(m)["dense_layers"],) + s,
                                          wd, kind)
                for k, s, kind in (("wg", (d, f), "normal"),
                                   ("wu", (d, f), "normal"),
                                   ("wd", (f, d), "out"))})
    n = _blocks(m)["layers"]
    out.update({f"layers/moe/{k}": ((n,) + s, t, kind) for k, s, t, kind in (
        ("router", (d, m["n_routed_experts"]), jnp.float32, "router"),
        ("wg", (e, d, fe), wd, "normal"), ("wu", (e, d, fe), wd, "normal"),
        ("wd", (e, fe, d), wd, "out"), ("shared_wg", (d, fs), wd, "normal"),
        ("shared_wu", (d, fs), wd, "normal"),
        ("shared_wd", (fs, d), wd, "out"))})
    return out


def init(kind, key, shape, dtype):
    if kind == "router":
        return jax.random.normal(key, shape, jnp.float32) * 0.006
    raise ValueError(kind)


def _token(m, context):
    """(FLOPs, weight bytes) of one token through every layer, attending
    over ``context`` latent positions; of the routed experts it reads the
    top-k share of those held."""
    w, d, h = itemsize(m), m["hidden_size"], m["num_attention_heads"]
    kvl, rope = m["kv_lora_rank"], m["qk_rope_head_dim"]
    fe = m["moe_intermediate_size"]
    unread = (m["experts_held"] * (1 - m["num_experts_per_tok"]
                                   / m["n_routed_experts"]) * 3 * d * fe * w)
    nbytes = leaf_bytes(leaves(m)) - _blocks(m)["layers"] * unread
    attn = m["num_hidden_layers"] * 2 * h * (2 * kvl + rope) * context
    return 2 * nbytes / w + attn, nbytes


def _latent(m):
    """Latent cache bytes of one position, all layers."""
    return (m["num_hidden_layers"] * (m["kv_lora_rank"] + m["qk_rope_head_dim"])
            * itemsize(m))


def decode_stack(m, context):
    flops, nbytes = _token(m, context)
    return flops, nbytes + _latent(m) * (context + 1)


def prefill_stack(m, prompt):
    flops, _ = _token(m, (prompt + 1) / 2.0)
    return prompt * flops, leaf_bytes(leaves(m)) + _latent(m) * prompt
'''

# Runs in a fresh interpreter whose ``chipbench`` is the copy's, so that
# catalog.BENCH_DIR is the copy and the family is found there by name.
SCRIPT = textwrap.dedent('''
    import json
    import jax, jax.numpy as jnp
    from chipbench import catalog, cell, prefill_work, weights, work
    from repro.configs import get_config

    red = get_config("deepseek-v2-236b").reduced()
    model = dict(
        hidden_size=red.d_model, num_hidden_layers=red.n_layers,
        vocab_size=red.vocab, tie_word_embeddings=red.tie_embeddings,
        rms_norm_eps=red.norm_eps, dtype="bfloat16",
        num_attention_heads=red.n_heads, qk_nope_head_dim=red.head_dim,
        qk_rope_head_dim=red.rope_head_dim, v_head_dim=red.head_dim,
        kv_lora_rank=red.kv_lora_rank, q_lora_rank=red.q_lora_rank,
        n_routed_experts=red.n_experts, experts_held=red.n_experts,
        num_experts_per_tok=red.top_k, n_shared_experts=red.n_shared_experts,
        moe_intermediate_size=red.d_ff_expert, first_k_dense_replace=1,
        intermediate_size=512)
    fields = ("n_layers", "d_model", "n_heads", "n_kv_heads", "head_dim",
              "vocab", "n_experts", "top_k", "n_shared_experts",
              "d_ff_expert", "kv_lora_rank", "q_lora_rank", "rope_head_dim")
    conf = {"name": "tiny-latent-moe", "family": "latent_moe", "model": model,
            "program": {"arch": "deepseek-v2-236b", "overrides": {
                f: getattr(red, f) for f in fields}}}
    out = {"arch_type": cell.program_config(conf).arch_type}
    try:
        cell.program_config(dict(conf, program={"arch": "mistral-nemo-12b"}))
    except ValueError as e:
        out["refused"] = str(e)

    def nbytes(tree):
        return sum(x.size * jnp.dtype(x.dtype).itemsize
                   for x in jax.tree.leaves(tree))

    tree = weights.make(model, "latent_moe", seed=2**33 + 5)
    out["shapes"] = {jax.tree_util.keystr(p): [list(x.shape), str(x.dtype)]
                     for p, x in jax.tree_util.tree_leaves_with_path(tree)}
    out["router_std"] = float(jnp.std(tree["layers"]["moe"]["router"]))
    out["tree_bytes"] = nbytes(tree)
    out["abstract_bytes"] = nbytes(weights.abstract(model, "latent_moe"))
    out["weight_bytes"] = work.weight_bytes(model, "latent_moe")
    out["decode"] = [work.decode_step(model, "latent_moe", c) for c in (1, 9)]
    out["prefill"] = [prefill_work.prefill_call(model, "latent_moe", s)
                      for s in (1, 8)]
    fam = catalog.family("latent_moe")
    out["stack"] = [fam.decode_stack(model, 1), fam.prefill_stack(model, 1)]
    print(json.dumps(out))
''')


@pytest.fixture(scope="module")
def new_family(tmp_path_factory):
    """What every family-dependent entry point of a harness copy returns
    for a family that only a new file there defines."""
    root = tmp_path_factory.mktemp("checkout")
    bench_dir = root / "benchmarks" / "chip"
    shutil.copytree(catalog.BENCH_DIR, bench_dir,
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {p.relative_to(bench_dir): p.read_bytes()
              for p in bench_dir.rglob("*") if p.is_file()}
    (bench_dir / "families" / "latent_moe.py").write_text(LATENT_MOE)
    after = {p.relative_to(bench_dir): p.read_bytes()
             for p in bench_dir.rglob("*") if p.is_file()
             and "__pycache__" not in p.parts}
    assert {k: v for k, v in after.items() if k in before} == before
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=os.pathsep.join(
        [str(bench_dir), str(HERE.parents[2] / "src")]))
    run = subprocess.run([sys.executable, "-c", SCRIPT], cwd=root, env=env,
                         capture_output=True, text=True, timeout=240)
    assert run.returncode == 0, run.stderr[-4000:]
    return json.loads(run.stdout.strip().splitlines()[-1])


def test_new_family_program_config(new_family):
    """Its keys map onto the program's reduced DeepSeek-V2, whose
    ``arch_type`` is its ``ARCH_TYPE``; a program of another arch_type is
    refused."""
    assert new_family["arch_type"] == "moe"
    assert "'arch_type': ('moe', 'dense')" in new_family["refused"]


def test_new_family_weights(new_family):
    """Both blocks of the stack, each with its own widths, the family's own
    router initialiser, and ``abstract`` and ``weight_bytes`` agreeing with
    the tree ``make`` built."""
    shapes = new_family["shapes"]
    assert shapes["['dense_layers']['mlp']['wg']"] == [[1, 256, 512],
                                                      "bfloat16"]
    assert shapes["['layers']['moe']['wg']"] == [[1, 4, 256, 128], "bfloat16"]
    assert shapes["['layers']['moe']['router']"] == [[1, 256, 4], "float32"]
    assert shapes["['layers']['mla']['wkv_a']"] == [[1, 256, 48], "bfloat16"]
    assert 0.005 < new_family["router_std"] < 0.007
    assert (new_family["tree_bytes"] == new_family["abstract_bytes"]
            == new_family["weight_bytes"])


def test_new_family_counts(new_family):
    """Decode and prefill counts are the family's stack plus what the
    harness adds: the embedding rows, final norm, head and logits."""
    (f1, b1), (f9, b9) = new_family["decode"]
    (p1, q1), (p8, q8) = new_family["prefill"]
    (sf, sb), (pf, pb) = new_family["stack"]
    assert f9 > f1 and b9 > b1 and p8 > p1 and q8 > q1
    d, v, w = 256, 512, 2
    assert (f1 - sf, p1 - pf) == (2 * d * v, 2 * d * v)
    assert b1 - sb == d * w + d * w + d * v * w + v * w
    assert q1 - pb == d * w + 1 * d * w + d * v * w + v * w

