"""Find the pieces of a cell by name: BENCHMARK.json, configurations,
traffic mixes, per-layer metric readers, plain references, model families
and peaks."""

from __future__ import annotations

import importlib.util
import json
from pathlib import Path
from types import ModuleType
from typing import Any, Dict

BENCH_DIR = Path(__file__).resolve().parents[1]  # benchmarks/chip
ROOT = BENCH_DIR.parents[1]  # the checkout


def load_json(path: Path) -> Any:
    with open(path) as f:
        return json.load(f)


def benchmark(root: Path = ROOT) -> Dict[str, Any]:
    return load_json(root / "BENCHMARK.json")


def workload(name: str, bench: Dict[str, Any]) -> Dict[str, Any]:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json")


def config(name: str, bench: Dict[str, Any], root: Path = ROOT) -> Dict[str, Any]:
    """The configuration file named by BENCHMARK.json's entry ``name``;
    one that BENCHMARK.json does not list is ``configs/<name>.json``."""
    files = {c["name"]: root / c["file"] for c in bench["configs"]}
    path = files.get(name, root / BENCH_DIR.relative_to(ROOT) / "configs"
                     / f"{name}.json")
    if not path.is_file():
        raise KeyError(f"no config {name!r} in BENCHMARK.json or {path}")
    conf = load_json(path)
    conf["name"] = name
    return conf


def traffic(name: str, bench_dir: Path = BENCH_DIR) -> Dict[str, Any]:
    mix = load_json(bench_dir / "traffic" / f"{name}.json")
    mix["name"] = name
    return mix


def _module(path: Path, modname: str) -> ModuleType:
    if not path.is_file():
        raise FileNotFoundError(path)
    spec = importlib.util.spec_from_file_location(modname, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_reader(name: str, bench_dir: Path = BENCH_DIR) -> ModuleType:
    """``metrics/<base>.py`` for metric ``<base>`` or ``<base>.<suffix>``:
    a suffix names the same quantity in another set of cells."""
    base = name.split(".", 1)[0]
    return _module(bench_dir / "metrics" / f"{base}.py",
                   f"chipbench_metric_{base}")


def reference(family: str, bench_dir: Path = BENCH_DIR) -> ModuleType:
    return _module(bench_dir / "reference" / f"{family}.py",
                   f"chipbench_reference_{family}")


def family(name: str, bench_dir: Path = BENCH_DIR) -> ModuleType:
    """``families/<name>.py``: one model family's ``ARCH_TYPE`` (the
    program's ``arch_type``), ``PROGRAM_KEYS`` (its configuration keys ->
    ``ModelConfig`` fields), ``leaves(m)`` ({path: (shape, dtype, kind)} of
    the layer stack, whose bytes ``work.leaf_bytes`` counts),
    ``decode_stack(m, context)`` and, optional, ``init(kind, key, shape,
    dtype)`` for its own weight kinds and ``prefill_stack(m, prompt)``.
    Counts are (FLOPs, bytes) at batch 1."""
    return _module(bench_dir / "families" / f"{name}.py",
                   f"chipbench_family_{name}")


def peaks(device_kind: str, bench_dir: Path = BENCH_DIR) -> Dict[str, Any]:
    """Published peaks of ``device_kind``; an unknown kind is an error."""
    table = load_json(bench_dir / "peaks.json")
    if device_kind not in table:
        raise KeyError(
            f"no peaks for device kind {device_kind!r} in peaks.json "
            f"(known: {sorted(table)})"
        )
    return table[device_kind]
