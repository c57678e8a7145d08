"""``chip_smoke.py`` on the CPU: it refuses to report without a TPU, and
its phases run end to end at reduced widths (control flow, checks and
bookkeeping; no timing)."""

import importlib.util
from pathlib import Path

import jax
import pytest

from repro.configs import get_config

_PATH = Path(__file__).resolve().parents[1] / "chip_smoke.py"
_spec = importlib.util.spec_from_file_location("chip_smoke", _PATH)
chip_smoke = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(chip_smoke)


@pytest.mark.parametrize("argv", [[], ["--four-chip"]])
def test_refuses_without_tpu(argv, capsys):
    assert jax.devices()[0].platform != "tpu"
    assert chip_smoke.main(argv) != 0
    assert capsys.readouterr().out == ""  # no result line


def test_serve_phase_reduced():
    r = chip_smoke.serve_phase(
        get_config("mamba2-780m").reduced(), seed=0, prompt_len=12
    )
    assert r["tokens"] == 4 * 2 * 2 * 8
    # One miss (first request's first stage), every later stage a hit.
    assert r["cache_hit_rate"] == pytest.approx(7 / 8)
    assert all(a["draft"] == a["refine"] for a in r["assignments"])
    assert r["rel_err"] <= chip_smoke.rel_tol(2)


def test_four_chip_phase_on_available_devices():
    r = chip_smoke.four_chip_phase(
        get_config("mistral-nemo-12b").reduced(), jax.devices()[:1], seed=0
    )
    assert r["cut_rel_err"] <= chip_smoke.SHARDED_F32_TOL


def test_rel_tol_grows_with_depth():
    assert chip_smoke.rel_tol(2) < chip_smoke.rel_tol(48) < 0.06
