"""Serving cluster engine: Navigator-scheduled ML pipelines over real
jitted JAX models.

This is the execution-engine layer of the paper's system (§3): each
*worker* hosts an accelerator-memory model cache (``GpuMemoryManager``)
and an execution queue; the scheduler from ``make_scheduler`` (Alg. 1
planning on the host; the served path runs no Alg. 2 adjustment) places
pipeline tasks; the Execution Engine performs real teacher-forced prefill
+ autoregressive ``decode_step`` calls on the zoo models.

Every worker shares one device — ``jax.devices()[0]``, a CPU in the tests
and one TPU chip under ``chip_smoke.py``.  Transfer and fetch *costs*
therefore advance a virtual clock from the profiled cost model (exactly
the simulator's), while the ML compute itself is real: real outputs,
wall-clock measured.

``ServedSpans`` times the served path on the wall clock: request, plan,
state, task and the task's phases, each a ``compass.<name>`` profiler
annotation (on the clock of the device trace) and, with ``trace`` on, a
``WallSpan`` in the flight recorder.
"""

from __future__ import annotations

import contextlib
import dataclasses
import time
from typing import (Any, Callable, Dict, List, Optional, Sequence, Set, Tuple,
                    Union)

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import (
    ClusterSpec,
    GpuMemoryManager,
    Job,
    NavigatorConfig,
    PrefetchConfig,
    PrefetchPlane,
    ProfileRepository,
    SharedStateTable,
)
from repro.core.healthplane import HealthConfig, HealthMonitor
from repro.core.scheduler import Scheduler, make_scheduler
from repro.core.sst_exchange import GossipConfig, GossipPlane
from repro.core.telemetry import FlightRecorder, TraceConfig
from repro.core.types import DFG, MLModel, TaskSpec
from repro.models import decode_step, forward, init_cache, init_params
from repro.models.config import ModelConfig


@dataclasses.dataclass
class HostedModel:
    """A zoo model registered with the serving cluster."""

    model_id: int
    cfg: ModelConfig
    params: Any

    @property
    def size_bytes(self) -> float:
        return float(
            sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(self.params))
        )


class ServedSpans:
    """Wall-clock spans of the served path.

    ``spans(name, **stats)`` opens ``compass.<name>``: always as a
    ``jax.profiler.TraceAnnotation`` (about a microsecond with no profiler
    running), so the span lies on the device trace's clock; with a
    recorder (``ServingCluster(trace=...)``) also as a ``WallSpan`` in
    ``recorder.wall_spans``.  ``labels`` (the request's job id, and the
    task and worker while one runs) go into every span's stats.
    """

    def __init__(self, recorder: Optional[FlightRecorder] = None) -> None:
        self.recorder = recorder
        self.labels: Dict[str, Any] = {}

    @contextlib.contextmanager
    def __call__(self, name: str, **stats):
        """Yields ``[t0, t1]``, the span's ``time.perf_counter`` reads;
        ``t1`` is filled in when it closes."""
        stats = {**self.labels, **stats}
        rec = self.recorder
        with jax.profiler.TraceAnnotation("compass." + name, **stats):
            clock = [time.perf_counter(), 0.0]
            if rec is not None:
                rec.begin_span(name, clock[0], stats)
            try:
                yield clock
            finally:
                clock[1] = time.perf_counter()
                if rec is not None:
                    rec.end_span(clock[1])


class ExecutionEngine:
    """Per-framework plug-in layer (§3): here, one plug-in — JAX."""

    def __init__(self, models: Dict[int, HostedModel], decode_tokens: int = 8,
                 spans: Optional[ServedSpans] = None):
        self.models = models
        self.decode_tokens = decode_tokens
        self.spans = spans if spans is not None else ServedSpans()
        self._steps: Dict[int, Callable] = {}
        # (model, batch, cache capacity) of every step shape run so far
        self._shapes_run: Set[Tuple[int, int, int]] = set()

    def decode_fn(self, mid: int) -> Callable:
        """The jitted one-token step ``(params, cache, tokens) -> (logits,
        cache)`` this engine runs for model ``mid``.  Its program is named
        ``jit_served_decode_step`` in compiled text and device traces."""
        if mid not in self._steps:
            cfg = self.models[mid].cfg

            def served_decode_step(params, cache, tokens):
                return decode_step(params, cache, tokens, cfg,
                                   moe_dispatch="scan")

            self._steps[mid] = jax.jit(served_decode_step)
        return self._steps[mid]

    def _first_call(self, mid: int, batch: int, capacity: int):
        """A span around the first step call of a shape this engine has
        not run before (the call that compiles), else nothing."""
        key = (mid, batch, capacity)
        if key in self._shapes_run:
            return contextlib.nullcontext()
        self._shapes_run.add(key)
        return self.spans("first_call", capacity=capacity)

    def run_task(self, mid: int, prompt: np.ndarray) -> Tuple[np.ndarray, float]:
        """Prefill ``prompt`` then greedily decode a few tokens.  Returns
        (generated token ids, wall seconds of the task span)."""
        hosted = self.models[mid]
        params = hosted.params
        b, s = prompt.shape
        d = self.decode_tokens
        cap = s + d + 1
        sp = self.spans
        step = self.decode_fn(mid)
        with sp("task", model=mid, prompt=s, decode=d) as clock:
            with sp("task_setup", capacity=cap):
                cache = init_cache(hosted.cfg, b, capacity=cap)
                toks = jnp.asarray(prompt)
            # teacher-forced prefill through the decode path (seeds the
            # cache); the spans time the dispatch, the device runs later
            with sp("prefill", calls=s):
                with self._first_call(mid, b, cap):
                    logits, cache = step(params, cache, toks[:, 0])
                for i in range(1, s):
                    logits, cache = step(params, cache, toks[:, i])
            out = []
            with sp("decode", calls=d):
                nxt = jnp.argmax(logits, axis=-1)
                for _ in range(d):
                    out.append(nxt)
                    logits, cache = step(params, cache, nxt)
                    nxt = jnp.argmax(logits, axis=-1)
            with sp("sync"):
                jax.block_until_ready(logits)
            with sp("readback", copies=len(out)):
                tokens = np.stack([np.asarray(o) for o in out], axis=1)
        return tokens, clock[1] - clock[0]


@dataclasses.dataclass
class RequestResult:
    job_id: int
    dfg_name: str
    latency_s: float
    virtual_latency_s: float
    outputs: Dict[str, np.ndarray]
    assignment: Dict[str, int]


class ServingCluster:
    """N Navigator workers serving pipeline requests over hosted models."""

    def __init__(
        self,
        cluster: ClusterSpec,
        hosted: Sequence[HostedModel],
        scheduler: str = "navigator",
        navigator_config: Optional[NavigatorConfig] = None,
        decode_tokens: int = 8,
        gossip: Optional[GossipConfig] = None,
        prefetch: Optional[PrefetchConfig] = None,
        trace: Union[bool, TraceConfig] = False,
        health: Union[bool, HealthConfig] = False,
    ) -> None:
        self.cluster = cluster
        self.hosted = {h.model_id: h for h in hosted}
        self.catalog = {
            mid: MLModel(mid, h.cfg.name, h.size_bytes)
            for mid, h in self.hosted.items()
        }
        self.profiles = ProfileRepository(cluster, self.catalog)
        self.scheduler: Scheduler = make_scheduler(
            scheduler, self.profiles, navigator_config
        )
        # Flight recorder (core/telemetry.py): events land on the virtual
        # clock, so serving traces line up with simulator traces of the
        # same workload; placement provenance comes from the scheduler.
        self.recorder: Optional[FlightRecorder] = None
        if trace:
            self.recorder = FlightRecorder(
                cluster.n_workers,
                trace if isinstance(trace, TraceConfig) else None,
            )
            self.scheduler.recorder = self.recorder
        # Health plane (core/healthplane.py) on the virtual clock: same
        # zero-overhead-when-off ``is not None`` guard as the recorder.
        self.health: Optional[HealthMonitor] = None
        if health:
            self.health = HealthMonitor(
                cluster.n_workers,
                health if isinstance(health, HealthConfig) else None,
                recorder=self.recorder,
            )
        # ``gossip`` swaps the single-snapshot table for the decentralized
        # per-worker view plane: the planner then reads the *origin
        # worker's* replica, which lags peers by up to a gossip period.
        self.gossip = gossip
        if gossip is not None:
            self.sst = GossipPlane(cluster.n_workers, gossip)
        else:
            self.sst = SharedStateTable(cluster.n_workers)
        self.memories = [
            GpuMemoryManager(
                cluster.gpu_capacity(w),
                self.catalog,
                cluster.link,
                compression_ratio=cluster.compression_ratio,
            )
            for w in cluster.workers()
        ]
        # Wall-clock spans of the served path: always profiler
        # annotations, kept in the recorder too when tracing is on.
        self.spans = ServedSpans(self.recorder)
        self.engine = ExecutionEngine(self.hosted, decode_tokens, self.spans)
        self._vclock = [0.0] * cluster.n_workers  # per-worker virtual time
        # Predictive prefetch plane (core/prefetch.py) on the virtual
        # clock: planned intents stage models through the per-worker fetch
        # pipe *before* their tasks reach the front of the queue.
        self.prefetch_plane: Optional[PrefetchPlane] = None
        if prefetch is not None:
            self.prefetch_plane = PrefetchPlane(
                cluster.n_workers, prefetch,
                fetch_time_fn=self.profiles.td_model,
            )
        self._pipe_free_at = [0.0] * cluster.n_workers
        # worker -> {model_id: virtual time the speculative transfer lands}
        self._prefetch_ready_at: List[Dict[int, float]] = [
            {} for _ in cluster.workers()
        ]
        self._jobid = 0
        for w in cluster.workers():
            self.sst.update_cache(w, 0, cluster.gpu_capacity(w), 0.0)
            self.sst.push(w, 0.0)
        self.results: List[RequestResult] = []

    # -- pipeline registration --------------------------------------------------
    def register_pipeline(self, dfg: DFG) -> None:
        self.profiles.register(dfg)

    # -- request handling ----------------------------------------------------------
    def submit(
        self, dfg: DFG, inputs: Dict[str, np.ndarray], origin: int = 0
    ) -> RequestResult:
        """Schedule + execute one pipeline request synchronously.

        ``inputs`` maps entry-task ids → prompt token arrays (B, S)."""
        now = max(self._vclock)
        job = Job(self._jobid, dfg, arrival_time=now)
        self._jobid += 1
        sp = self.spans
        sp.labels = {"job": job.job_id}
        try:
            with sp("request", dfg=dfg.name,
                    tasks=len(dfg.tasks)) as clock:
                outputs, adfg, finish = self._serve(job, dfg, inputs, origin,
                                                    now)
        finally:
            sp.labels = {}
        result = RequestResult(
            job_id=job.job_id,
            dfg_name=dfg.name,
            latency_s=clock[1] - clock[0],
            virtual_latency_s=max(finish.values()) - now,
            outputs=outputs,
            assignment=dict(adfg.assignment),
        )
        self.results.append(result)
        return result

    def _serve(self, job: Job, dfg: DFG, inputs: Dict[str, np.ndarray],
               origin: int, now: float):
        """``submit``'s body: plan ``job`` at virtual time ``now`` and run
        its tasks.  Returns (outputs, assignment, virtual finish times)."""
        sp = self.spans
        if self.gossip is not None:
            # Run the gossip rounds due up to the request's arrival; the
            # origin worker then plans from its own (possibly stale) view.
            self.sst.advance(now)
        with sp("plan"):
            adfg = self.scheduler.plan(job, now, origin, self.sst.view(origin))
        if adfg is None:
            raise NotImplementedError("serving engine drives planned schedulers")
        if self.prefetch_plane is not None:
            self._issue_prefetches(job, adfg, now)
        rec = self.recorder
        if rec is not None:
            # Cluster-scope lifecycle events ride the GLOBAL ring, same
            # as the simulator (parity-tested: identical taxonomy).
            rec.emit(now, "job.arrive", job=job.job_id,
                     dfg=dfg.name, origin=origin, n_tasks=len(dfg.tasks))

        outputs: Dict[str, np.ndarray] = {}
        finish: Dict[str, float] = {}
        for ti, tid in enumerate(dfg.topo_order):
            task = dfg.tasks[tid]
            w = adfg[tid]
            mem = self.memories[w]
            sp.labels = {"job": job.job_id, "task": tid, "worker": w}
            start = max(
                self._vclock[w],
                max((finish[p] for p in dfg.preds[tid]), default=now),
            )
            # transfer delay for remote inputs
            for p in dfg.preds[tid]:
                if adfg[p] != w:
                    dur = self.cluster.network.transfer_time(
                        dfg.tasks[p].output_bytes
                    )
                    start += dur
                    if rec is not None:
                        rec.emit(finish[p], "net.xfer", worker=adfg[p],
                                 dst=w, bytes=dfg.tasks[p].output_bytes,
                                 dur=dur, scope="flat", share=1.0)
                    if self.health is not None:
                        self.health.on_transfer(
                            finish[p], "flat", dfg.tasks[p].output_bytes,
                            1.0, cross=False,
                        )
            if rec is not None:
                if not dfg.preds[tid]:
                    rec.emit(now, "task.input", worker=w, job=job.job_id,
                             task=tid, gen=0, src="", frm=origin, to=w,
                             arrive=now)
                else:
                    for p in dfg.preds[tid]:
                        arrive = finish[p] if adfg[p] == w else start
                        rec.emit(arrive, "task.input", worker=w,
                                 job=job.job_id, task=tid, gen=0, src=p,
                                 frm=adfg[p], to=w, arrive=arrive)
            was_miss = False
            if task.model_id is not None:
                with sp("state"):
                    upcoming = [task.model_id]
                    res = mem.ensure(task.model_id, upcoming)
                    ready = (
                        self._prefetch_ready_at[w].pop(task.model_id, None)
                        if self.prefetch_plane is not None
                        else None
                    )
                    if res is not None:
                        fetch_s, _ = res
                        was_miss = fetch_s > 0.0
                        if rec is not None and fetch_s > 0.0:
                            rec.emit(start, "fetch.start", worker=w,
                                     fetch_kind="demand", model=task.model_id,
                                     bytes=mem.cached_size(task.model_id),
                                     dur=fetch_s, job=job.job_id, task=tid)
                            rec.emit(start + fetch_s, "fetch.done", worker=w,
                                     model=task.model_id, spec=False)
                        if self.health is not None and fetch_s > 0.0:
                            self.health.fetch_state(w, start, True)
                            self.health.fetch_state(w, start + fetch_s,
                                                    False)
                        if fetch_s > 0.0 and self.prefetch_plane is not None:
                            # Demand miss: demand preempts speculation on the
                            # single fetch pipe — the transfer starts now, and
                            # every speculative transfer still in flight is
                            # pushed back behind it.
                            t0 = start
                            start += fetch_s
                            self._pipe_free_at[w] = max(
                                self._pipe_free_at[w] + fetch_s, start
                            )
                            for m, t in self._prefetch_ready_at[w].items():
                                if t > t0:
                                    self._prefetch_ready_at[w][m] = t + fetch_s
                        elif fetch_s > 0.0:
                            start += fetch_s
                        elif ready is not None:
                            # Cache hit thanks to a speculative transfer that
                            # may still be in flight on the virtual clock.
                            start = max(start, ready)
                            if rec is not None:
                                rec.emit(start, "fetch.promote", worker=w,
                                         model=task.model_id, job=job.job_id,
                                         task=tid)
                    self.sst.update_cache(w, mem.bitmap, mem.free_bytes, start)
                    if self.health is not None:
                        self.health.sample_memory(
                            w, start,
                            (mem.used_bytes + mem.exec_reserved_bytes)
                            / mem.capacity_bytes
                            if mem.capacity_bytes > 0 else 0.0,
                            mem.stats.evictions,
                        )
                    if self.prefetch_plane is not None:
                        self.sst.update_intent(
                            w,
                            mem.bitmap
                            | self.prefetch_plane.advertised_bits(w),
                            start,
                        )
                prompt = self._task_input(tid, dfg, inputs, outputs)
                out, wall = self.engine.run_task(task.model_id, prompt)
                outputs[tid] = out
                runtime = wall
            else:
                # host-side aggregation vertex
                preds = dfg.preds[tid]
                outputs[tid] = np.concatenate(
                    [outputs[p] for p in preds], axis=-1
                ) if preds else np.zeros((1, 0), np.int32)
                runtime = 1e-4
            finish[tid] = start + runtime
            with sp("state"):
                if rec is not None:
                    rec.emit(start, "task.start", worker=w, job=job.job_id,
                             task=tid, gen=0,
                             model=-1 if task.model_id is None
                             else task.model_id,
                             miss=was_miss)
                    rec.emit(finish[tid], "task.done", worker=w,
                             job=job.job_id, task=tid, gen=0)
                self._vclock[w] = finish[tid]
                self.sst.update_load(w, self._vclock[w], finish[tid])
                if self.health is not None:
                    # Virtual-queue depth: this job's tasks still bound to
                    # w (including the one just finished draining to 0
                    # marks the backlog the next probe would see).
                    depth = sum(
                        1 for t2 in dfg.topo_order[ti + 1:] if adfg[t2] == w
                    )
                    self.health.sample_queue(w, finish[tid], depth)
                    self.health.task_done(
                        w, finish[tid], runtime,
                        self.profiles.runtime(task, w),
                    )
                    # Digest refresh rides the publication, same as the sim.
                    d = self.health.digest(w, finish[tid])
                    self.sst.update_health(
                        w, d.queue_depth, d.mem_occupancy, d.fetch_util,
                        d.p99_latency_s, finish[tid],
                    )
                if self.gossip is not None:
                    self.sst.advance(finish[tid])
                else:
                    self.sst.push(w, finish[tid])
            sp.labels = {"job": job.job_id}
        t_end = max(finish.values())
        if rec is not None:
            rec.emit(t_end, "job.done", job=job.job_id,
                     latency=t_end - now)
        if self.health is not None:
            self.health.job_done(t_end, t_end - now)
        return outputs, adfg, finish

    def _issue_prefetches(self, job: Job, adfg, now: float) -> None:
        """Virtual-clock analogue of the simulator's speculative fetch
        path: every intended model is staged through the worker's fetch
        pipe at plan time, so by the time its task reaches the front of
        the queue the transfer has (partially) overlapped queue wait."""
        plane = self.prefetch_plane
        assert plane is not None
        per = plane.plan_intents(job, adfg, self.profiles, now)
        for w, intents in per.items():
            plane.admit(w, intents, now)
            mem = self.memories[w]
            t_pipe = max(now, self._pipe_free_at[w])
            while True:
                intent, _ = plane.next_intent(w, now, mem.has, 0)
                if intent is None:
                    break
                res = mem.begin_prefetch(
                    intent.model_id,
                    allow_evict=plane.config.evict_for_prefetch,
                )
                if res is None:
                    # No room: fall back to demand fetching at task start.
                    plane.stall_inflight(w, now)
                    break
                fetch_s, _ = res
                if self.recorder is not None:
                    # Same key set as the simulator's speculative
                    # fetch.start (no job/task: nothing demanded it yet).
                    self.recorder.emit(
                        t_pipe, "fetch.start", worker=w,
                        fetch_kind="prefetch", model=intent.model_id,
                        bytes=mem.cached_size(intent.model_id),
                        dur=fetch_s,
                    )
                if self.health is not None:
                    self.health.fetch_state(w, t_pipe, True)
                t_pipe += fetch_s
                if self.recorder is not None:
                    self.recorder.emit(t_pipe, "fetch.done", worker=w,
                                       model=intent.model_id, spec=True)
                if self.health is not None:
                    self.health.fetch_state(w, t_pipe, False)
                mem.complete_prefetch(intent.model_id)
                plane.complete_inflight(w)
                self._prefetch_ready_at[w][intent.model_id] = t_pipe
            self._pipe_free_at[w] = t_pipe
            self.sst.update_cache(w, mem.bitmap, mem.available_bytes, now)
            self.sst.update_intent(
                w, mem.bitmap | plane.advertised_bits(w), now
            )

    def _task_input(self, tid, dfg, inputs, outputs) -> np.ndarray:
        if not dfg.preds[tid]:
            return inputs[tid]
        parts = [outputs[p] for p in dfg.preds[tid]]
        return np.concatenate(parts, axis=1)

    # -- metrics ---------------------------------------------------------------------
    def cache_hit_rate(self) -> float:
        hits = sum(m.stats.hits for m in self.memories)
        total = hits + sum(m.stats.misses for m in self.memories)
        return hits / total if total else 1.0

    def workers_used(self) -> List[int]:
        return [
            w
            for w in self.cluster.workers()
            if self.memories[w].stats.hits + self.memories[w].stats.misses > 0
        ]
