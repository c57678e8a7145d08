"""Bytes and FLOPs of one whole-prompt prefill call against hand counts,
the mix's prefill calls by prompt length, and the two prefill readers on
a synthetic trace (nothing read where the program never ran)."""

from types import SimpleNamespace

import pytest
from chipbench_tiny import catalog

from chipbench import prefill_work, trace
from chipbench.trace import Span, Trace

DEV = "/device:TPU:0"
NEMO = catalog.load_json(catalog.BENCH_DIR / "configs" / "nemo-12b-s10.json")


def test_nemo_prefill_bytes_and_flops():
    m = NEMO["model"]
    layer = 545280000
    head = 131072 * 5120 * 2
    for s in (1, 8, 256):
        flops, nbytes = prefill_work.prefill_call(m, "dense", s)
        kv = 10 * 8 * 128 * 2 * 2 * s  # k and v of the prompt, written once
        # 10 layers, the final norm, the prompt's embedding rows, the head,
        # the cache written and one position's logits
        assert nbytes == (10 * layer + 10240 + s * 5120 * 2 + head + kv
                          + 131072 * 2)
        matmul = 2 * (5120 * 4096 + 2 * 5120 * 1024 + 4096 * 5120) \
            + 6 * 5120 * 14336
        # position p attends over p + 1 positions: sum (p + 1) = s(s+1)/2
        assert flops == pytest.approx(
            10 * (s * matmul + 4 * 4096 * s * (s + 1) / 2) + 2 * 5120 * 131072)
    assert prefill_work.prefill_call(m, "dense", 8)[1] == 6795659264
    # HBM-bound at every prompt of the mix: at 256 the FLOPs take 7.1 ms at
    # 197 TFLOP/s, the bytes 8.3 ms at 819 GB/s
    flops, nbytes = prefill_work.prefill_call(m, "dense", 256)
    assert 7.0e-3 < flops / 197e12 < nbytes / 819e9 < 8.4e-3


def test_no_prefill_for_other_families():
    with pytest.raises(ValueError, match="ssm"):
        prefill_work.prefill_call(NEMO["model"], "ssm", 8)


def test_mix_prefill_prompts():
    chat = catalog.traffic("chat")
    # a two-stage chain: stage 1 the mix's prompt, stage 2 the 8 tokens
    # stage 1 decoded
    got = prefill_work.prefill_prompts(chat)
    assert got == pytest.approx({16: 0.075, 32: 0.1075, 64: 0.135,
                                 128: 0.1075, 256: 0.075, 8: 0.5})
    fan = dict(chat, dfg={"shape": "fanout", "stages": 2})
    assert prefill_work.prefill_prompts(fan) == pytest.approx(
        {int(s): p for s, p in chat["prompt_tokens"].items()})


def test_least_time_is_the_weight_read():
    chat = catalog.traffic("chat")
    peaks = catalog.peaks("TPU v5 lite")
    least = prefill_work.least_s(NEMO["model"], "dense", chat, peaks)
    want = sum(p * prefill_work.prefill_call(NEMO["model"], "dense", s)[1]
               for s, p in prefill_work.prefill_prompts(chat).items()) / 819e9
    assert least == pytest.approx(want)
    assert 8.29e-3 < least < 8.31e-3


def _ctx(modules):
    spec = SimpleNamespace(conf=NEMO, mix=catalog.traffic("chat"))
    tr = Trace(ops={DEV: list(modules)}, modules={DEV: list(modules)},
               host=[])
    return SimpleNamespace(trace=tr, spec=spec, step_module=None,
                           peaks=catalog.peaks("TPU v5 lite"))


def test_prefill_readers():
    ms = 1e6
    ctx = _ctx([Span("jit_served_prefill(3)", 0, 9 * ms),
                Span("jit_served_decode_step(4)", 9 * ms, 18 * ms),
                Span("jit_served_prefill(3)", 20 * ms, 31 * ms)])
    assert trace.module_matches("jit_served_prefill(3)", prefill_work.PROGRAM)
    for suffix in ("", ".over"):
        call = catalog.metric_reader("prefill_call_ms" + suffix).read(ctx)
        assert call == pytest.approx(10.0)
        share = catalog.metric_reader("prefill_roofline" + suffix).read(ctx)
        least = prefill_work.least_s(NEMO["model"], "dense", ctx.spec.mix,
                                     ctx.peaks)
        assert share == pytest.approx(100 * least / 10e-3)


@pytest.mark.parametrize("name", ["prefill_call_ms", "prefill_roofline"])
def test_prefill_readers_read_nothing_without_the_program(name):
    """A program that prefills one position per step call (the served path
    before the whole-prompt prefill) has no such executions."""
    reader = catalog.metric_reader(name)
    assert reader.read(SimpleNamespace(trace=None)) is None
    ctx = _ctx([Span("jit_served_decode_step(4)", 0, 9e6)])
    assert reader.read(ctx) is None
