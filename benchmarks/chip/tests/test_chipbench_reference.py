"""Each plain reference at reduced sizes in float32: against the
published block as ``transformers`` implements it (Mamba2ForCausalLM,
MistralForCausalLM) on the same weights, and, where the program
implements the published block, against the program's ``forward``."""

import jax
import numpy as np
import pytest
from chipbench_tiny import catalog, tiny_spec

from chipbench import weights
from chipbench.cell import program_config

torch = pytest.importorskip("torch")
transformers = pytest.importorskip("transformers")

TOKENS = np.random.default_rng(0).integers(0, 256, (2, 24), dtype=np.int32)


def f32_spec(family):
    spec = tiny_spec(family, d=64, vocab=256)
    spec.conf["model"]["dtype"] = "float32"
    return spec


def reference_logits(spec, w):
    with jax.default_matmul_precision("highest"):
        ref = catalog.reference(spec.conf["reference"])
        return np.asarray(ref.logits_fn(spec.conf["model"])(w, TOKENS))


def t(x):
    return torch.from_numpy(np.array(x, np.float32))


def mamba2_witness(m, w):
    di = m["expand"] * m["hidden_size"]
    cfg = transformers.Mamba2Config(
        num_heads=di // m["head_dim"], head_dim=m["head_dim"],
        vocab_size=m["vocab_size"], hidden_size=m["hidden_size"],
        state_size=m["state_size"], num_hidden_layers=m["num_hidden_layers"],
        layer_norm_epsilon=m["rms_norm_eps"], expand=m["expand"],
        conv_kernel=m["conv_kernel"], n_groups=m["n_groups"],
        use_bias=False, use_conv_bias=True, chunk_size=8,
        tie_word_embeddings=m["tie_word_embeddings"])
    model = transformers.Mamba2ForCausalLM(cfg).eval()
    sd = {"backbone.embeddings.weight": t(w["embed"]),
          "backbone.norm_f.weight": t(w["final_norm"])}
    lw = w["layers"]
    for i in range(m["num_hidden_layers"]):
        pre = f"backbone.layers.{i}."
        sd.update({
            pre + "norm.weight": t(lw["ln"][i]),
            pre + "mixer.in_proj.weight": t(lw["w_in"][i]).T,
            pre + "mixer.conv1d.weight": t(lw["conv_w"][i]).T[:, None, :],
            pre + "mixer.conv1d.bias": t(lw["conv_b"][i]),
            pre + "mixer.dt_bias": t(lw["dt_bias"][i]),
            pre + "mixer.A_log": t(lw["a_log"][i]),
            pre + "mixer.D": t(lw["d_skip"][i]),
            pre + "mixer.norm.weight": t(lw["gate_norm"][i]),
            pre + "mixer.out_proj.weight": t(lw["w_out"][i]).T,
        })
    sd["lm_head.weight"] = (sd["backbone.embeddings.weight"]
                            if m["tie_word_embeddings"] else t(w["lm_head"]).T)
    return model, sd


def mistral_witness(m, w):
    cfg = transformers.MistralConfig(
        vocab_size=m["vocab_size"], hidden_size=m["hidden_size"],
        intermediate_size=m["intermediate_size"],
        num_hidden_layers=m["num_hidden_layers"],
        num_attention_heads=m["num_attention_heads"],
        num_key_value_heads=m["num_key_value_heads"],
        head_dim=m["head_dim"], rope_theta=m["rope_theta"],
        rms_norm_eps=m["rms_norm_eps"], sliding_window=None,
        tie_word_embeddings=m["tie_word_embeddings"])
    model = transformers.MistralForCausalLM(cfg).eval()
    sd = {"model.embed_tokens.weight": t(w["embed"]),
          "model.norm.weight": t(w["final_norm"]),
          "lm_head.weight": t(w["lm_head"]).T}
    lw = w["layers"]
    names = {"self_attn.q_proj": "wq", "self_attn.k_proj": "wk",
             "self_attn.v_proj": "wv", "self_attn.o_proj": "wo"}
    for i in range(m["num_hidden_layers"]):
        pre = f"model.layers.{i}."
        sd[pre + "input_layernorm.weight"] = t(lw["ln1"][i])
        sd[pre + "post_attention_layernorm.weight"] = t(lw["ln2"][i])
        for hf, ours in names.items():
            sd[pre + hf + ".weight"] = t(lw[ours][i]).T
        for hf, ours in (("gate", "wg"), ("up", "wu"), ("down", "wd")):
            sd[pre + f"mlp.{hf}_proj.weight"] = t(lw["mlp"][ours][i]).T
    return model, sd


WITNESS = {"ssm": mamba2_witness, "dense": mistral_witness}


@pytest.mark.parametrize("family,tied", [("ssm", True), ("ssm", False),
                                         ("dense", False)])
def test_reference_matches_published_block(family, tied):
    spec = f32_spec(family)
    m = spec.conf["model"]
    m["tie_word_embeddings"] = tied
    w = weights.make(m, family, seed=2**33 + 5)
    model, sd = WITNESS[family](m, w)
    missing, unexpected = model.load_state_dict(sd, strict=False)
    assert not unexpected and set(missing) <= {"lm_head.weight"}, (
        missing, unexpected)
    with torch.no_grad():
        want = model(torch.from_numpy(TOKENS.astype(np.int64))).logits
    np.testing.assert_allclose(reference_logits(spec, w), want.numpy(),
                               rtol=2e-4, atol=2e-4)


def test_dense_reference_matches_program_forward():
    from repro.models import forward

    spec = f32_spec("dense")
    cfg = program_config(spec.conf)
    w = weights.make(spec.conf["model"], "dense", seed=2**33 + 5)
    with jax.default_matmul_precision("highest"):
        want = forward(w, {"tokens": TOKENS}, cfg, impl="ref")[0]
    np.testing.assert_allclose(reference_logits(spec, w), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


def test_program_view_names_what_the_program_lacks():
    """The program's Mamba-2 block has no gated RMSNorm: its tree has no
    leaf for the published ``gate_norm`` weight, and nothing else differs."""
    from repro.models import abstract_params

    for family, lacks in (("ssm", ["['layers']['gate_norm']"]),
                          ("dense", [])):
        spec = f32_spec(family)
        w = weights.make(spec.conf["model"], family, seed=1)
        view, missing = weights.program_view(
            w, abstract_params(program_config(spec.conf)))
        assert missing == lacks
        assert weights.nbytes(view) == weights.nbytes(w) - sum(
            x.nbytes for x in ([w["layers"]["gate_norm"]] if lacks else []))


@pytest.mark.parametrize("family", ["ssm", "dense"])
def test_control_precision_departs(family):
    spec = tiny_spec(family, d=64, vocab=256)
    w = weights.make(spec.conf["model"], family, seed=3)
    toks = np.random.default_rng(1).integers(0, 256, (1, 16), dtype=np.int32)
    ref = catalog.reference(family)
    with jax.default_matmul_precision("highest"):
        hi = np.asarray(ref.logits_fn(spec.conf["model"])(w, toks))
        lo = np.asarray(ref.logits_fn(spec.conf["model"], "fp8")(w, toks))
    rel = np.linalg.norm(lo - hi) / np.linalg.norm(hi)
    assert 1e-3 < rel < 0.5
