"""Serving launcher: ``python -m repro.launch.serve --arch <id> [...]``.

Builds the sharded one-token serve step for the requested architecture
(reduced config by default), runs batched greedy decode against the
synthetic prompt source and reports tokens/s.  ``--optimized`` turns on
the §Perf serving path (grouped-GQA decode + one-hot cache writes; EP
dispatch for MoE archs).
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_debug_mesh, make_production_mesh
from repro.models import init_cache, init_params
from repro.training import make_serve_step


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--capacity", type=int, default=256)
    ap.add_argument("--tokens", type=int, default=64)
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--production-mesh", action="store_true")
    ap.add_argument("--optimized", action="store_true",
                    help="§Perf serving path (grouped decode, onehot writes)")
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_config(args.arch)
    if not args.full:
        cfg = cfg.reduced(dtype="float32")
    if cfg.arch_type == "audio":
        raise SystemExit("use examples/serve_cluster.py for enc-dec serving")
    mesh = (
        make_production_mesh() if args.production_mesh else make_debug_mesh()
    )
    moe_dispatch = (
        "ep" if (args.optimized and cfg.n_experts) else
        ("sorted" if cfg.n_experts else "sorted")
    )
    _, jit_factory = make_serve_step(
        cfg, mesh,
        impl="ref_grouped" if args.optimized else "ref",
        cache_update="onehot" if args.optimized else "scatter",
        moe_dispatch=moe_dispatch,
        donate=False,
    )
    params = init_params(cfg, jax.random.key(0))
    cache = init_cache(cfg, args.batch, args.capacity)
    tokens0 = jnp.ones((args.batch,), jnp.int32)
    step = jit_factory(params, cache, tokens0)

    logits, cache = step(params, cache, tokens0)  # compile + first token
    jax.block_until_ready(logits)
    t0 = time.time()
    tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    for _ in range(args.tokens - 1):
        logits, cache = step(params, cache, tok)
        tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    jax.block_until_ready(logits)
    dt = time.time() - t0
    rate = (args.tokens - 1) * args.batch / dt
    print(f"{cfg.name}: {rate:,.0f} tokens/s "
          f"({dt/(args.tokens-1)*1e3:.1f} ms/step, batch {args.batch}, "
          f"{'optimized' if args.optimized else 'baseline'} path)")


if __name__ == "__main__":
    main()
