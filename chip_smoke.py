"""Chip smoke test: the served path, end to end, on TPU.

    python chip_smoke.py              # one chip
    python chip_smoke.py --four-chip  # one host with four chips

One chip: mamba2-780m at its published widths (48 layers, d_model 1536,
vocab 50280, bf16, random weights from ``--seed``) is hosted on a
two-worker ``ServingCluster`` with the v5e worker profile; both workers
share the one chip.  A few requests of a two-stage pipeline (both stages
on the same model, so the second is a model-cache hit) are served, and the
first generated token and its logits are checked against ``forward`` over
the same prompt.

``--four-chip`` runs only the sharded path: the one-token serve step of
mistral-nemo-12b at full width (about 24 GB of bf16 weights, more than one
chip holds) on a (data=1, model=4) mesh; the same widths cut to 2 layers
on one device and on the mesh, whose logits must agree; and the SST
all-gather across the four devices, which must reproduce the host-packed
table.

Everything runs in this one process.  With no TPU, or when any phase
fails, the script exits non-zero and prints no result.  The last line of
stdout is one JSON object naming the device.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import math
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.core import TPU_V5E_CLUSTER  # noqa: E402
from repro.core.sst_exchange import make_sst_allgather, pack_row  # noqa: E402
from repro.core.state import SSTRow  # noqa: E402
from repro.core.types import DFG, MB, TaskSpec  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.mesh import make_mesh  # noqa: E402
from repro.models import (  # noqa: E402
    abstract_params,
    forward,
    init_cache,
    init_params,
)
from repro.models.config import ModelConfig  # noqa: E402
from repro.models.sharding import (  # noqa: E402
    cache_pspecs,
    param_pspecs,
    to_named,
)
from repro.serving import HostedModel, ServingCluster  # noqa: E402
from repro.training import make_serve_step  # noqa: E402


def rel_tol(n_layers: int) -> float:
    """Bound on the relative L2 error ‖got − want‖ / ‖want‖ between two
    orders of the same bf16 computation.  The residual stream is rounded
    to bf16 (unit roundoff 2**-8) once per layer and independent roundings
    add in quadrature, so drift grows as sqrt(n_layers)·2**-8; the bound is
    twice that (0.054 at 48 layers, 0.011 at 2)."""
    return 2 * math.sqrt(n_layers) * 2**-8


# Sharded vs one-device logits in f32 at full precision: a change of
# summation order over contractions of up to 14336 terms moves a result by
# about sqrt(14336)·2**-24 ≈ 7e-6 relative; the bound leaves 10× above that.
SHARDED_F32_TOL = 1e-4


def rel_err(got, want) -> float:
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


def check(ok: bool, what: str) -> None:
    if not ok:
        raise RuntimeError(f"check failed: {what}")


def _init(cfg: ModelConfig, seed: int, out_shardings=None):
    init = jax.jit(init_params, static_argnums=0, out_shardings=out_shardings)
    return jax.block_until_ready(init(cfg, jax.random.key(seed)))


def _nbytes(tree) -> int:
    return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(tree))


# --------------------------------------------------------------------------
# One chip: ServingCluster over a full-width model
# --------------------------------------------------------------------------
def serve_phase(
    cfg: ModelConfig,
    *,
    seed: int,
    n_requests: int = 4,
    batch: int = 2,
    prompt_len: int = 64,
    decode_tokens: int = 8,
) -> dict:
    """Serve ``n_requests`` two-stage requests and check the first
    generated token and its logits against ``forward``."""
    t0 = time.perf_counter()
    params = _init(cfg, seed)
    init_s = time.perf_counter() - t0

    cluster = dataclasses.replace(TPU_V5E_CLUSTER, n_workers=2)
    sc = ServingCluster(cluster, [HostedModel(0, cfg, params)],
                        decode_tokens=decode_tokens)
    dfg = DFG(
        "two_stage",
        tasks=[
            TaskSpec("draft", 0.05, model_id=0, output_bytes=0.01 * MB,
                     input_bytes=0.01 * MB),
            TaskSpec("refine", 0.05, model_id=0, output_bytes=0.01 * MB),
        ],
        edges=[("draft", "refine")],
    )
    sc.register_pipeline(dfg)
    rng = np.random.default_rng(seed)
    prompts = [
        rng.integers(0, cfg.vocab, size=(batch, prompt_len), dtype=np.int32)
        for _ in range(n_requests)
    ]

    # Compile the engine's own step on the request shapes (first call).
    step = sc.engine.decode_fn(0)
    cache = init_cache(cfg, batch, capacity=prompt_len + decode_tokens + 1)
    t0 = time.perf_counter()
    jax.block_until_ready(step(params, cache, jnp.asarray(prompts[0][:, 0])))
    compile_s = time.perf_counter() - t0

    results = [
        sc.submit(dfg, {"draft": p}, origin=i % cluster.n_workers)
        for i, p in enumerate(prompts)
    ]
    n_tokens = sum(int(o.size) for r in results for o in r.outputs.values())
    check(n_tokens == n_requests * 2 * batch * decode_tokens,
          f"generated {n_tokens} tokens")

    # Reference: forward (impl="ref") over the first prompt, same params.
    fwd = jax.jit(lambda p, t: forward(p, {"tokens": t}, cfg, impl="ref")[0])
    t0 = time.perf_counter()
    ref = np.asarray(fwd(params, prompts[0])[:, -1], np.float32)
    ref_compile_s = time.perf_counter() - t0
    # The served path's logits: the engine's step, teacher-forced over the
    # same prompt exactly as ``run_task`` prefills a model of a family other
    # than dense (mamba2-780m here; a dense model prefills in one call).
    toks = jnp.asarray(prompts[0])
    cache = init_cache(cfg, batch, capacity=prompt_len + decode_tokens + 1)
    for i in range(prompt_len):
        logits, cache = step(params, cache, toks[:, i])
    served = np.asarray(logits, np.float32)
    first = results[0].outputs["draft"][:, 0]
    check(bool(np.all(np.isfinite(served))), "served logits are finite")
    check(served.shape == ref.shape == (batch, cfg.vocab),
          f"logits shape {served.shape} vs {ref.shape}")
    check(np.array_equal(first, served.argmax(-1)),
          "served first token is the argmax of the served logits")
    err = rel_err(served, ref)
    check(err <= rel_tol(cfg.n_layers),
          f"logits rel err {err} > {rel_tol(cfg.n_layers)}")
    # With logits within ``gap`` of each other, the greedy token can differ
    # from the reference argmax only among tokens within 2·gap of the max.
    gap = float(np.max(np.abs(served - ref)))
    ref_at_first = np.take_along_axis(ref, first[:, None], axis=1)[:, 0]
    check(bool(np.all(ref_at_first >= ref.max(-1) - 2 * gap)),
          "served first token is a reference argmax within bf16 error")
    return dict(
        params_bytes=_nbytes(params),
        init_s=init_s,
        compile_s=compile_s,
        ref_compile_s=ref_compile_s,
        latency_s=[r.latency_s for r in results],
        assignments=[r.assignment for r in results],
        tokens=n_tokens,
        cache_hit_rate=sc.cache_hit_rate(),
        rel_err=err,
        max_abs_err=gap,
        first_token_matches_ref_argmax=bool(
            np.array_equal(first, ref.argmax(-1))
        ),
    )


# --------------------------------------------------------------------------
# Four chips: sharded serve step and the SST all-gather
# --------------------------------------------------------------------------
def _serve_mesh(devices):
    return make_mesh((1, len(devices)), ("data", "model"), devices=devices)


def sharded_decode(cfg: ModelConfig, mesh, tokens: np.ndarray, *,
                   seed: int, params=None) -> np.ndarray:
    """Feed ``tokens`` (B, S) through ``make_serve_step`` on ``mesh`` one
    position at a time; returns the logits of every step (S, B, V).
    Params are generated already sharded unless given."""
    b, s = tokens.shape
    _, jit_step = make_serve_step(cfg, mesh)
    cache = init_cache(cfg, b, capacity=s + (-s) % mesh.shape["model"])
    if params is None:
        params = _init(cfg, seed, to_named(
            mesh, param_pspecs(mesh, abstract_params(cfg), cfg)))
    else:
        params = jax.device_put(
            params, to_named(mesh, param_pspecs(mesh, params, cfg)))
    cache = jax.device_put(cache, to_named(mesh, cache_pspecs(mesh, cache)))
    step = jit_step(params, cache, tokens[:, 0])
    out = []
    for i in range(s):
        logits, cache = step(params, cache, tokens[:, i])
        out.append(logits)
    return np.asarray(jnp.stack(out), np.float32)


def sst_allgather_check(mesh, axis: str = "model") -> float:
    """All-gather one packed SST row per device; every device must end
    with exactly the host-packed table.  Returns the exchange's seconds."""
    n = mesh.shape[axis]
    rows = [
        SSTRow(ft_estimate_s=0.25 * w, cache_bitmap=(1 << w) | (1 << 40),
               free_cache_bytes=(w + 1) * 1024.0**3, version=w + 1,
               intent_bitmap=1 << (w + 8), heartbeat_s=1.5 * w, epoch=w)
        for w in range(n)
    ]
    host = np.stack([pack_row(r, queue_len=w) for w, r in enumerate(rows)])
    exchange = make_sst_allgather(mesh, axis=axis)
    local = jax.device_put(host, NamedSharding(mesh, P(axis, None)))
    jax.block_until_ready(exchange(local))
    t0 = time.perf_counter()
    table = jax.block_until_ready(exchange(local))
    exchange_s = time.perf_counter() - t0
    shards = table.addressable_shards
    check(len(shards) == n, f"{len(shards)} result shards")
    for sh in shards:
        check(np.array_equal(np.asarray(sh.data), host),
              f"SST table on {sh.device} equals the host-packed table")
    return exchange_s


def four_chip_phase(cfg: ModelConfig, devices, *, seed: int,
                    batch: int = 2, steps: int = 8) -> dict:
    mesh = _serve_mesh(devices)
    rng = np.random.default_rng(seed)
    tokens = rng.integers(0, cfg.vocab, size=(batch, steps), dtype=np.int32)
    full_bytes = _nbytes(abstract_params(cfg))

    t0 = time.perf_counter()
    full = sharded_decode(cfg, mesh, tokens, seed=seed)
    full_s = time.perf_counter() - t0
    check(full.shape == (steps, batch, cfg.vocab), f"logits {full.shape}")
    check(bool(np.all(np.isfinite(full))), "full-depth logits are finite")
    stats = [d.memory_stats() for d in devices]
    peaks = [s["peak_bytes_in_use"] if s else None for s in stats]

    # The sharded and the one-device program may differ only in summation
    # order, so compare them in f32 at full matmul precision, where that
    # difference is far below any misplaced shard's.
    cut = dataclasses.replace(cfg, n_layers=2, dtype="float32")
    with jax.default_matmul_precision("highest"):
        # Generated sharded (threefry is partitionable, so the values do
        # not depend on the layout), then gathered for the one-device run.
        params = _init(cut, seed, to_named(
            mesh, param_pspecs(mesh, abstract_params(cut), cut)))
        one = sharded_decode(cut, _serve_mesh(devices[:1]), tokens,
                             seed=seed, params=params)
        many = sharded_decode(cut, mesh, tokens, seed=seed, params=params)
    err = rel_err(many, one)
    check(err <= SHARDED_F32_TOL,
          f"{len(devices)}-way vs 1-device rel err {err}")

    return dict(
        full_weight_bytes=full_bytes,
        full_s=full_s,
        peak_bytes=peaks,
        cut_rel_err=err,
        sst_exchange_s=sst_allgather_check(mesh),
    )


# --------------------------------------------------------------------------
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chip", action="store_true",
                    help="run only the four-chip sharded phase")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform {dev.platform!r})",
              file=sys.stderr)
        return 1
    n_chips = 4 if args.four_chip else 1
    if len(devices) < n_chips:
        print(f"chip_smoke: needs {n_chips} chips, found {len(devices)}",
              file=sys.stderr)
        return 1
    cache_dir = enable_compile_cache()
    hits = []
    jax.monitoring.register_event_listener(
        lambda name, **kw: hits.append(name)
        if name == "/jax/compilation_cache/cache_hits" else None
    )
    print(f"device: {dev.platform} {dev.device_kind} x{len(devices)}; "
          f"compile cache: {cache_dir}")

    if args.four_chip:
        r = four_chip_phase(get_config("mistral-nemo-12b"), devices[:4],
                            seed=args.seed)
        print(f"nemo-12b full width on 4 chips: weights "
              f"{r['full_weight_bytes']} B, 8 steps incl. init+compile "
              f"{r['full_s']} s")
        for d, peak in zip(devices[:4], r["peak_bytes"]):
            print(f"  {d}: peak_bytes_in_use {peak} "
                  f"({peak / r['full_weight_bytes']} of the weights)")
            check(r["full_weight_bytes"] / 4 <= peak
                  < r["full_weight_bytes"] / 2,
                  f"{d} holds about a quarter of the weights")
        print(f"2-layer cut in f32, 4-way mesh vs 1 device: rel err "
              f"{r['cut_rel_err']} (tol {SHARDED_F32_TOL})")
        print(f"SST all-gather over 4 devices: equals host table; "
              f"{r['sst_exchange_s']} s")
    else:
        cfg = get_config("mamba2-780m")
        r = serve_phase(cfg, seed=args.seed)
        print(f"{cfg.name}: weights {r['params_bytes']} B, init {r['init_s']} s")
        print(f"compile: decode step {r['compile_s']} s (first call), "
              f"reference forward {r['ref_compile_s']} s")
        for i, (lat, asg) in enumerate(zip(r["latency_s"], r["assignments"])):
            print(f"request {i}: wall {lat} s, assignment {asg}")
        print(f"generated tokens: {r['tokens']}; "
              f"cache hit rate: {r['cache_hit_rate']}")
        print(f"peak_bytes_in_use: {dev.memory_stats()['peak_bytes_in_use']}")
        print(f"reference check: rel err {r['rel_err']} "
              f"(tol {rel_tol(cfg.n_layers)}), "
              f"max abs err {r['max_abs_err']}, first token = ref argmax: "
              f"{r['first_token_matches_ref_argmax']}; passed")
    print(f"persistent compile cache hits: {len(hits)}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
