"""Faults planted under a cell after its set-up, and the control put in
the program's place: each must make a run come out not correct.

Each is a function of the set-up ``Cell`` that replaces a piece of its
served path.  ``tools/faults.py`` drives a whole run with one of them on
the chip; the CPU tests drive the same at tiny sizes.
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import catalog


def stale_state(cell) -> None:
    """The step returns the cache it was given, unchanged."""
    step = cell.sc.engine.decode_fn(0)

    def stale(params, cache, tok):
        logits, _ = step(params, cache, tok)
        return logits, cache

    cell.sc.engine.decode_fn = lambda mid: stale


def altered_token(cell) -> None:
    """Each task's last generated token is altered where it is produced."""
    run_task = cell.sc.engine.run_task

    def altered(mid, prompt):
        out, wall = run_task(mid, prompt)
        out = out.copy()
        out[0, -1] = (out[0, -1] + 1) % cell.model["vocab_size"]
        return out, wall

    cell.sc.engine.run_task = altered


def wrong_handover(cell) -> None:
    """Later stages of a chain are fed the request's prompt, not the
    previous stage's output."""
    task_input = cell.sc._task_input
    cell.sc._task_input = lambda tid, dfg, inputs, outputs: (
        inputs[cell.stages[0]] if tid != cell.stages[0]
        else task_input(tid, dfg, inputs, outputs))


def dropped_branch(cell) -> None:
    """The join returns its first branch alone."""
    submit = cell.sc.submit

    def dropping(dfg, inputs, origin=0):
        r = submit(dfg, inputs, origin)
        r.outputs["join"] = r.outputs[cell.stages[0]]
        return r

    cell.sc.submit = dropping


def control(cell) -> None:
    """The control in the program's place: every task decodes greedily
    through the plain reference computed at fp8.  Each call runs the
    reference over the prompt and the tokens so far, padded to one width
    per task (a causal model ignores the padding at the end)."""
    ref = catalog.reference(cell.conf["reference"]).logits_fn(
        cell.model, quant="fp8")
    dec = cell.mix["decode_tokens"]
    w = cell.weights

    def run_task(mid, prompt):
        t0 = time.perf_counter()
        b, s = prompt.shape
        seq = np.zeros((b, s + dec), np.int32)
        seq[:, :s] = prompt
        with jax.default_matmul_precision("highest"):
            for j in range(dec):
                logits = ref(w, jnp.asarray(seq))
                seq[:, s + j] = np.asarray(jnp.argmax(logits[:, s + j - 1], -1))
        return seq[:, s:], time.perf_counter() - t0

    cell.sc.engine.run_task = run_task


FAULTS = {f.__name__: f for f in (stale_state, altered_token, wrong_handover,
                                  dropped_branch, control)}
