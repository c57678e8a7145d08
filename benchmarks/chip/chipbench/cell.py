"""One benchmark cell: a configuration served through ``ServingCluster``
under one traffic mix.

``Cell.setup`` makes the weights, builds the cluster and warms up every
shape the mix uses; ``Cell.serve`` drives the open-loop window;
``Cell.compare`` checks what the window served against the plain
reference.  The program is imported here and nowhere else in the harness.
"""

from __future__ import annotations

import contextlib
import dataclasses
import gc
import math
import random
import time
import sys
import traceback
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import arrivals, catalog, weights

# Traced runs measure at most this long: a 50 s device trace of one-token
# steps holds millions of op events and takes minutes to read.
TRACE_SECONDS = 6.0
# A window that drains keeps serving requests due inside it for at most
# this long after it closes; what is left then never completed.
DRAIN_LIMIT_S = 60.0
# Host spans the breakdown attributes idle device time to, innermost first.
HOST_LABELS = ("plan", "task", "idle", "submit")

# Configuration key -> ModelConfig field, for every family; each family
# adds its own (``families/<family>.py``).
_PROGRAM_KEYS = {"hidden_size": "d_model", "num_hidden_layers": "n_layers",
                 "vocab_size": "vocab", "tie_word_embeddings": "tie_embeddings",
                 "rms_norm_eps": "norm_eps", "dtype": "dtype"}


@dataclasses.dataclass
class Spec:
    """What a cell is: its names, configuration file and traffic mix."""

    name: str
    conf: Dict[str, Any]
    mix: Dict[str, Any]
    chips: int = 1

    @classmethod
    def from_benchmark(cls, workload: str, bench=None) -> "Spec":
        """The cell BENCHMARK.json names ``workload``; a name it does not
        list, ``<config>.<traffic>``, is read from those two files."""
        bench = bench or catalog.benchmark()
        try:
            w = catalog.workload(workload, bench)
        except KeyError:
            conf_name, _, mix = workload.rpartition(".")
            w = {"name": workload, "config": conf_name, "traffic": mix,
                 "chips": 1}
        return cls(w["name"], catalog.config(w["config"], bench),
                   catalog.traffic(w["traffic"]), int(w["chips"]))


def program_config(conf: Dict[str, Any]):
    """The program's ``ModelConfig`` for a configuration file, checked
    key by key against the file's sizes."""
    from repro.configs import get_config

    prog = conf["program"]
    cfg = dataclasses.replace(get_config(prog["arch"]),
                              dtype=conf["model"]["dtype"],
                              **prog.get("overrides", {}))
    fam = catalog.family(conf["family"])
    keys = dict(_PROGRAM_KEYS, **fam.PROGRAM_KEYS)
    wrong = {k: (v, getattr(cfg, keys[k])) for k, v in conf["model"].items()
             if k in keys and getattr(cfg, keys[k]) != v}
    if cfg.arch_type != fam.ARCH_TYPE:
        wrong["arch_type"] = (fam.ARCH_TYPE, cfg.arch_type)
    if wrong:
        raise ValueError(f"program config differs from {conf['name']}: {wrong}")
    return cfg


def build_dfg(mix: Dict[str, Any]):
    """The mix's DFG and the ids of its model stages (in order) and of
    its host join vertex (or None)."""
    from repro.core.types import DFG, MB, TaskSpec

    shape, n = mix["dfg"]["shape"], mix["dfg"]["stages"]
    stages = [f"s{i}" for i in range(n)]
    tasks = [TaskSpec(s, 0.1, model_id=0, output_bytes=0.01 * MB,
                      input_bytes=0.01 * MB) for s in stages]
    if shape == "chain":
        return (DFG(f"chain{n}", tasks, list(zip(stages, stages[1:]))),
                stages, None)
    if shape == "fanout":
        tasks.append(TaskSpec("join", 0.0, model_id=None,
                              output_bytes=0.01 * MB))
        return (DFG(f"fanout{n}", tasks, [(s, "join") for s in stages]),
                stages, "join")
    raise ValueError(f"unknown DFG shape {shape!r}")


@dataclasses.dataclass
class Window:
    """What one open-loop window did, on the host clock (perf_counter)."""

    seconds: float
    opened: float
    due: List[float] = dataclasses.field(default_factory=list)
    started: List[Optional[float]] = dataclasses.field(default_factory=list)
    done: List[Optional[float]] = dataclasses.field(default_factory=list)
    results: List[Any] = dataclasses.field(default_factory=list)
    prompts: List[np.ndarray] = dataclasses.field(default_factory=list)
    failed: int = 0
    idle_lateness: List[float] = dataclasses.field(default_factory=list)
    tasks: List[Tuple[float, float, int, int]] = dataclasses.field(
        default_factory=list)  # (start, end, prompt tokens, tokens generated)
    plans: List[float] = dataclasses.field(default_factory=list)
    compiles: int = 0

    @property
    def attempted(self) -> int:
        return len(self.due)

    def jcts(self) -> List[float]:
        """Due-to-done seconds of every request due in the window; a
        request that never completed counts as infinitely late."""
        return [(d - q) if d is not None else float("inf")
                for q, d in zip(self.due, self.done)]

    def tokens_in_window(self) -> int:
        """Generated tokens produced inside the window.  A task of prompt
        S and D generated tokens calls the step S + D times, each call as
        long as the others (one cache capacity per task), and its j-th
        token comes out of call S + j - 1.  Of a task that the close cuts,
        the tokens counted are those its calls before the close produced,
        the calls spread evenly over the task's span on the host clock."""
        end = self.opened + self.seconds
        n = 0
        for t0, t1, s, d in self.tasks:
            if t1 <= end:
                n += d
            elif t0 < end:
                calls = math.floor((end - t0) / (t1 - t0) * (s + d))
                n += min(d, max(0, calls - s + 1))
        return n


class _CompileCounter:
    """Counts programs lowered in this process (one per fresh compile or
    persistent-cache load).  One listener serves every cell."""

    EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
    n = 0
    _listening = False

    @classmethod
    def start(cls) -> None:
        if not cls._listening:
            jax.monitoring.register_event_duration_secs_listener(cls._on)
            cls._listening = True

    @classmethod
    def _on(cls, event, duration, **kw):
        if event == cls.EVENT:
            cls.n += 1


class Cell:
    def __init__(self, spec: Spec, seed: int):
        self.spec = spec
        self.seed = seed
        self.conf = spec.conf
        self.model = spec.conf["model"]
        self.mix = spec.mix
        self.dfg, self.stages, self.join = build_dfg(spec.mix)
        self.sc = None
        self.weights = None
        _CompileCounter.start()
        self._win: Optional[Window] = None
        self._annotate = False

    # -- set-up ------------------------------------------------------------
    def setup(self) -> None:
        from repro import core
        from repro.models import abstract_params
        from repro.serving import HostedModel, ServingCluster

        cfg = program_config(self.conf)
        self.weights = weights.make(self.model, self.conf["family"], self.seed)
        served, missing = weights.program_view(self.weights,
                                               abstract_params(cfg))
        if missing:
            print(f"chipbench: the program has no parameter for {missing}: "
                  "it serves without them", file=sys.stderr)
        dep = self.conf["deployment"]
        cluster = dataclasses.replace(getattr(core, dep["cluster_profile"]),
                                      n_workers=dep["workers"])
        self.sc = ServingCluster(
            cluster, [HostedModel(0, cfg, served)],
            scheduler=dep["scheduler"],
            decode_tokens=self.mix["decode_tokens"],
        )
        self.sc.register_pipeline(self.dfg)
        self._hook()
        # Warm up: one request per prompt length serves every shape the
        # window will use (step programs per cache capacity, prompt slices).
        lens, _ = arrivals.prompt_lengths(self.mix)
        rng = np.random.default_rng(weights.seed_words(self.seed, 2))
        for s in lens:
            p = rng.integers(0, self.model["vocab_size"], size=(1, s),
                             dtype=np.int32)
            self.sc.submit(self.dfg, self._inputs(p))
        self.sc.results.clear()

    def _inputs(self, prompt: np.ndarray) -> Dict[str, np.ndarray]:
        if self.join is None:
            return {self.stages[0]: prompt}
        return {s: prompt for s in self.stages}

    def _span(self, name: str):
        if self._annotate:
            return jax.profiler.TraceAnnotation("chipbench." + name)
        return contextlib.nullcontext()

    def _hook(self) -> None:
        """Record spans around the calls into the scheduler and the engine."""
        sched, engine = self.sc.scheduler, self.sc.engine
        plan, run_task = sched.plan, engine.run_task

        def timed_plan(*a, **kw):
            with self._span("plan"):
                t0 = time.perf_counter()
                out = plan(*a, **kw)
                t1 = time.perf_counter()
            if self._win is not None:
                self._win.plans.append(t1 - t0)
            return out

        def timed_run_task(mid, prompt):
            with self._span("task"):
                t0 = time.perf_counter()
                out, wall = run_task(mid, prompt)
                t1 = time.perf_counter()
            if self._win is not None:
                self._win.tasks.append((t0, t1, prompt.shape[1],
                                        out.shape[1]))
            return out, wall

        sched.plan = timed_plan
        engine.run_task = timed_run_task

    def step_module(self) -> str:
        """Name of the engine's compiled step program, as the profiler
        names its executions."""
        from repro.models import init_cache

        cfg = self.sc.hosted[0].cfg
        s = min(arrivals.prompt_lengths(self.mix)[0])
        cache = init_cache(cfg, 1, capacity=s + self.mix["decode_tokens"] + 1)
        tok = jnp.zeros((1,), jnp.int32)
        text = self.sc.engine.decode_fn(0).lower(
            self.sc.hosted[0].params, cache, tok).as_text()
        head = text.split("{", 1)[0]
        return head.split("@", 1)[1].split()[0].strip('"')

    # -- the window --------------------------------------------------------
    def serve(self, seconds: float, *, annotate: bool = False,
              rate_per_s: Optional[float] = None) -> Window:
        mix = self.mix
        if rate_per_s is not None:
            mix = dict(mix, arrival=dict(mix["arrival"], rate_per_s=rate_per_s))
        sched = arrivals.schedule(mix, seconds)
        prompts = arrivals.prompts([s for _, s in sched],
                                   self.model["vocab_size"], self.seed)
        drain = mix["after_window"] == "drain"
        self._annotate = annotate
        win = Window(seconds=seconds, opened=0.0, prompts=prompts)
        self._win = win
        n = len(sched)
        win.started = [None] * n
        win.done = [None] * n
        win.results = [None] * n
        c0 = _CompileCounter.n
        gc.collect()
        gc.disable()  # no collector pauses inside the window
        try:
            with self._span("window"):
                win.opened = t_open = time.perf_counter()
                win.due = [t_open + t for t, _ in sched]
                end = t_open + seconds
                stop = end + DRAIN_LIMIT_S if drain else end
                for i in range(n):
                    now = time.perf_counter()
                    if now >= stop or win.due[i] >= end:
                        break
                    if now < win.due[i]:
                        with self._span("idle"):
                            time.sleep(win.due[i] - now)
                            while time.perf_counter() < win.due[i]:
                                pass
                        now = time.perf_counter()
                        win.idle_lateness.append(now - win.due[i])
                    win.started[i] = now
                    try:
                        with self._span("submit"):
                            win.results[i] = self.sc.submit(
                                self.dfg, self._inputs(prompts[i]))
                    except Exception:  # a request that raises has failed
                        win.failed += 1
                        traceback.print_exc(file=sys.stderr)
                        continue
                    win.done[i] = time.perf_counter()
        finally:
            gc.enable()
            self._annotate = False
            self._win = None
        # Requests due after the window closes were never offered.
        win.due = [d for d in win.due if d < win.opened + seconds]
        k = len(win.due)
        win.started, win.done, win.results = (
            win.started[:k], win.done[:k], win.results[:k])
        win.prompts = prompts[:k]
        win.compiles = _CompileCounter.n - c0
        return win

    # -- correctness ---------------------------------------------------------
    def exact_checks(self, win: Window) -> Dict[str, int]:
        """Placement, data flow and output shapes of every completed
        request; each count must be 0."""
        n_workers = self.sc.cluster.n_workers
        vocab = self.model["vocab_size"]
        dec = self.mix["decode_tokens"]
        tasks = set(self.dfg.tasks)
        placement = shape = join = 0
        for r in win.results:
            if r is None:
                continue
            a = r.assignment
            placement += int(set(a) != tasks
                             or any(not 0 <= w < n_workers for w in a.values()))
            for s in self.stages:
                o = np.asarray(r.outputs.get(s))
                shape += int(o.shape != (1, dec) or o.min() < 0
                             or o.max() >= vocab)
            if self.join is not None:
                want = np.concatenate([r.outputs[s] for s in self.stages], -1)
                join += int(not np.array_equal(r.outputs.get(self.join), want))
        out = {"placement_faults": placement, "output_shape_faults": shape}
        if self.join is not None:
            out["join_mismatches"] = join
        return out

    def sample(self, win: Window) -> List[int]:
        """Completed requests to compare: drawn from the seed, the one with
        the longest prompt always among them."""
        done = [i for i, r in enumerate(win.results) if r is not None]
        k = min(self.mix["check_requests"], len(done))
        if not k:
            return []
        longest = max(done, key=lambda i: (win.prompts[i].shape[1], -i))
        rest = [i for i in done if i != longest]
        rng = random.Random(self.seed % (1 << 64))
        return sorted([longest] + rng.sample(rest, k - 1))

    def sequences(self, win: Window, idx: List[int]):
        """Per compared stage: (tokens it was fed, tokens it served).  A
        stage is fed what the mix says (the prompt, or in a chain the
        previous stage's output), so a wrong hand-over shows up as served
        tokens that the reference rejects."""
        out = []
        for i in idx:
            fed = win.prompts[i][0]
            for s in self.stages:
                served = np.asarray(win.results[i].outputs[s])[0]
                out.append((fed, served))
                if self.join is None:
                    fed = served
        return out

    def free_program(self) -> None:
        """Drop the cluster and everything it made, keeping the weights."""
        self.sc = None
        gc.collect()

    def close(self) -> None:
        """Drop the cluster and the weights, freeing the device."""
        self.weights = None
        self.free_program()


def _pad(seqs) -> Tuple[np.ndarray, np.ndarray]:
    """Feed ``prompt + served[:-1]`` per sequence, padded to one length;
    targets hold the served token at each position that chose one and -1
    elsewhere.  Causal models ignore the padding at the end."""
    width = max(len(f) + len(s) - 1 for f, s in seqs)
    toks = np.zeros((len(seqs), width), np.int32)
    tgt = np.full((len(seqs), width), -1, np.int32)
    for j, (fed, served) in enumerate(seqs):
        seq = np.concatenate([fed, served[:-1]])
        toks[j, :len(seq)] = seq
        tgt[j, len(fed) - 1:len(fed) - 1 + len(served)] = served
    return toks, tgt


@jax.jit
def _gap(ref_logits, tokens):
    """Widest gap by which ``tokens``' logits lie below the reference's best,
    over positions whose token is >= 0."""
    best = jnp.max(ref_logits, axis=-1)
    at = jnp.take_along_axis(ref_logits, jnp.maximum(tokens, 0)[..., None],
                             axis=-1)[..., 0]
    return jnp.max(jnp.where(tokens >= 0, best - at, -jnp.inf))


def logit_gaps(conf: Dict[str, Any], weights_tree, seqs,
               control: bool = False) -> Dict[str, float]:
    """``served``: the widest gap of the served tokens under the float32
    reference.  With ``control``, also ``control``: the widest gap of the
    tokens the reference at fp8 ranks first at the same positions."""
    ref = catalog.reference(conf["reference"])
    toks, tgt = _pad(seqs)
    toks_d, tgt_d = jnp.asarray(toks), jnp.asarray(tgt)
    out = {}
    with jax.default_matmul_precision("highest"):
        if control:
            low = ref.logits_fn(conf["model"], quant="fp8")(weights_tree, toks_d)
            low_first = jnp.where(tgt_d >= 0, jnp.argmax(low, -1), -1)
            del low
        logits = ref.logits_fn(conf["model"])(weights_tree, toks_d)
        out["served"] = float(_gap(logits, tgt_d))
        if control:
            out["control"] = float(_gap(logits, low_first))
        out["tokens"] = int((tgt >= 0).sum())
    return out
