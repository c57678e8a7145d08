"""The harness finds every piece by name, a new piece is picked up with no
edit to an existing file, and BENCHMARK.json keeps the contract's shape."""

import json
import re
import shutil

import pytest
from chipbench_tiny import catalog

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def test_new_files_are_found_by_name(tmp_path):
    bench_dir = tmp_path / "benchmarks" / "chip"
    shutil.copytree(catalog.BENCH_DIR, bench_dir,
                    ignore=shutil.ignore_patterns("__pycache__"))
    (bench_dir / "metrics" / "queue_wait_ms.py").write_text(
        "def read(ctx):\n    return 4.5\n")
    (bench_dir / "traffic" / "chat-burst.json").write_text(json.dumps(
        dict(catalog.traffic("chat"), what="bursty")))
    (bench_dir / "configs" / "other.json").write_text(json.dumps(
        {"model": {"hidden_size": 8}}))
    (bench_dir / "reference" / "hybrid.py").write_text("FAMILY = 'hybrid'\n")
    (bench_dir / "families" / "hybrid.py").write_text("ARCH_TYPE = 'hybrid'\n")
    bench = catalog.benchmark()
    bench["configs"].append({"name": "other",
                             "file": "benchmarks/chip/configs/other.json"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    assert catalog.metric_reader("queue_wait_ms", bench_dir).read(None) == 4.5
    assert catalog.metric_reader("queue_wait_ms.over", bench_dir).read(None) == 4.5
    assert catalog.traffic("chat-burst", bench_dir)["what"] == "bursty"
    new = catalog.benchmark(tmp_path)
    assert catalog.config("other", new, tmp_path)["model"]["hidden_size"] == 8
    assert catalog.reference("hybrid", bench_dir).FAMILY == "hybrid"
    assert catalog.family("hybrid", bench_dir).ARCH_TYPE == "hybrid"


def test_peaks_by_device_kind():
    p = catalog.peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    assert "TPU v5e" in p["source"]
    with pytest.raises(KeyError):
        catalog.peaks("TPU v9 imaginary")


def test_benchmark_json_shape():
    bench = catalog.benchmark()
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert bench["paths"] == ["benchmarks/chip"]
    assert 1 <= bench["run_seconds"] <= 51
    cells = {w["name"]: w for w in bench["workloads"]}
    configs = {c["name"] for c in bench["configs"]}
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert (catalog.ROOT / c["file"]).is_file()
        assert c["file"].startswith("benchmarks/chip/")
        conf = catalog.config(c["name"], bench)
        assert c["reduced"] == conf["reduced"]
        assert conf["check"]["logit_gap_limit"] is not None
        catalog.reference(conf["reference"])
    for w in cells.values():
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert len(w["why"]) <= 200
        mix = catalog.traffic(w["traffic"])
        assert mix["arrival"]["rate_per_s"] > 0
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        catalog.metric_reader(m["name"])
        for w in m.get("workloads", []):
            assert w in cells
    for m in bench["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in bench["per_layer"]:
        moved = e2e[m["moves"]]
        # every cell a per-layer metric is read in reports what it moves
        for w in m["workloads"]:
            assert w in moved.get("workloads", cells)
    for name in cells:
        reported = [m for m in bench["end_to_end"]
                    if name in m.get("workloads", cells)]
        assert any(m["name"] == "setup_s" for m in reported)
        assert any(m["name"] != "setup_s" for m in reported)
        assert any(name in m["workloads"] for m in bench["per_layer"])
