"""A whole run at tiny sizes on the CPU: the look for a chip skipped, the
rest of ``cli.run`` driven.  A sound run is correct; the control in the
program's place and each fault a served cell can have (a step that returns
its state unchanged, a token altered where it is produced, a stage fed the
wrong input, a join that drops a branch) come out not correct.

The cells are of the dense family: the program's Mamba-2 block lacks the
published gated RMSNorm, so no ssm run is correct against its reference."""

import time

import jax
import numpy as np
import pytest
from chipbench_tiny import TINY_GAP_LIMIT, catalog, tiny_spec

from chipbench import arrivals, cli, faults
from chipbench.cell import Cell, Window, logit_gaps

SEEDS = (1, 2, 3)


def run(spec, seed, fault=None, monkeypatch=None):
    if fault is not None:
        setup = Cell.setup

        def broken_setup(self):
            setup(self)
            fault(self)

        monkeypatch.setattr(Cell, "setup", broken_setup)
    return cli.run(spec, seed, 2.0, False, jax.devices(),
                   catalog.peaks("TPU v5 lite"), catalog.benchmark(),
                   time.perf_counter())


@pytest.mark.parametrize("shape", ["chain", "fanout"])
def test_sound_run_is_correct(shape):
    out = run(tiny_spec("dense", shape), 2**31 + 7)
    assert out["correct"], out["checks"]
    assert list(out)[-1] == "checks"
    assert out["attempted"] >= 4 and out["failed"] == 0
    assert set(out["metrics"]) >= {"setup_s"}
    assert out["device"]["platform"] == "cpu"


@pytest.mark.parametrize("shape,fault,check", [
    ("chain", faults.stale_state, "logit_gap"),
    ("fanout", faults.stale_state, "logit_gap"),
    ("chain", faults.altered_token, "logit_gap"),
    ("fanout", faults.altered_token, "logit_gap"),
    ("chain", faults.wrong_handover, "logit_gap"),
    ("fanout", faults.dropped_branch, "join_mismatches"),
    ("chain", faults.control, "logit_gap"),
    ("fanout", faults.control, "logit_gap"),
])
def test_fault_is_not_correct(shape, fault, check, monkeypatch):
    out = run(tiny_spec("dense", shape), 11, fault, monkeypatch)
    assert not out["correct"]
    c = out["checks"][check]
    assert c["value"] > c["limit"], out["checks"]


@pytest.mark.parametrize("shape", ["chain", "fanout"])
def test_control_fails_and_program_passes(shape):
    """Over three seeds the bf16 program's widest gap stays under the limit
    and the fp8 control's goes over it, at the tiny sizes."""
    served, control = [], []
    for seed in SEEDS:
        spec = tiny_spec("dense", shape)
        cell = Cell(spec, seed)
        cell.setup()
        win = cell.serve(3.0)
        seqs = cell.sequences(win, cell.sample(win))
        cell.free_program()
        g = logit_gaps(spec.conf, cell.weights, seqs, control=True)
        served.append(g["served"])
        control.append(g["control"])
    assert max(served) <= TINY_GAP_LIMIT < min(control), (served, control)


def test_window_replays_schedule_and_counts_tokens():
    spec = tiny_spec("dense", "fanout", decode=4)
    cell = Cell(spec, 5)
    cell.setup()
    win = cell.serve(2.0)
    assert win.compiles == 0
    assert win.attempted == len(arrivals.schedule(spec.mix, 2.0))
    assert all(d is not None for d in win.done)
    assert win.tokens_in_window() <= 2 * 4 * win.attempted
    assert all(j > 0 for j in win.jcts())
    assert np.isfinite(win.jcts()).all()


def test_tokens_of_a_task_the_close_cuts():
    """A task of prompt 6 and 4 tokens makes 10 equal calls over 10 s; its
    first token comes out of call 6.  Closed at 7.5 s, 7 calls are done
    and 2 tokens counted; tasks ended inside count whole, those started
    after the close not at all."""
    win = Window(seconds=7.5, opened=0.0)
    win.tasks = [(0.0, 10.0, 6, 4), (-3.0, 1.0, 2, 3), (8.0, 9.0, 1, 5)]
    assert win.tokens_in_window() == 2 + 3
    win.tasks = [(0.0, 10.0, 6, 4)]
    win.seconds = 5.5  # 5 calls: the first token is not out yet
    assert win.tokens_in_window() == 0
    win.seconds = 6.0
    assert win.tokens_in_window() == 1
