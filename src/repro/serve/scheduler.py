"""Job queue + micro-batch scheduler for multi-tenant LiFE solves.

The serving problem (DESIGN.md §8): SBBNNLS solves run for hundreds of
iterations, subjects arrive continuously, and the hardware is best used
batched — so the scheduler must (a) group compatible subjects into one
vmapped computation, (b) admit late arrivals without restarting anyone, and
(c) share the device fairly between tenants with different priorities and
deadlines.  All three reduce to the stepped solver API
(:func:`repro.core.sbbnnls.sbbnnls_steps`): state in -> k iterations ->
state out, with the Barzilai-Borwein parity riding in the state, so slicing
and re-batching never change the trajectory.

Bucketing policy
----------------
A job lands in the bucket keyed by its *batch-compatibility class*:

  (Nv, Nf, Ntheta, dictionary digest, format, tune mode, compute dtype)

Tuning settings are part of the class (DESIGN.md §10.4): jobs tuned
differently must not share a micro-batch — a bf16-storage job stacked with
an fp32 job would silently run one of them under the other's numerics, and
a tune="full" job batched with tune="off" would either skip a requested
search or impose an unrequested one.

Jobs in one bucket can be stacked into a single
:class:`~repro.core.batched.BatchedLifeEngine` (same geometry, same shared
dictionary; coefficient counts may differ — the engine pads).  The key uses
the *requested* format: jobs asking for the same vmappable format
(``BATCHABLE_FORMATS``: coo, alto, or "auto" — which resolves inside the
batched engine) share one bucket engine, while an "auto" job and an
explicit "coo" job stay in separate buckets even when selection would pick
coo (resolving at submit would mean running format selection on the intake
path).  SELL's per-subject static slot shapes cannot stack, so
``format="sell"`` jobs get solo buckets running a
:class:`~repro.core.life.LifeEngine` behind the same stepped interface;
``format="fcoo"`` is solo for the same reason (per-subject static chunk
and segment-map shapes).

Continuous batching
-------------------
Bucket membership is re-evaluated every tick: queued arrivals are admitted,
finished jobs leave, and the bucket engine is rebuilt only when the member
set changed.  Rebuilds are cheap by construction — every inspector product
(FormatPlan, autotune choice, tile plan) is content-addressed in the shared
:class:`~repro.core.plan_cache.PlanCache`, so re-batching the same datasets
hits the cache rather than re-running selection.  Solver states are carried
over verbatim: a subject that already ran 80 iterations keeps its weights
and parity when a newcomer joins the stack.

Time-slicing
------------
Each ``tick()`` serves the most urgent bucket for at most ``slice_iters``
iterations: earliest deadline first, then highest priority, then the bucket
that has been served least (so starvation is bounded by the slice length).

Mesh slices
-----------
A job may request a device-mesh slice (``Job.mesh = (R, C)``): its solve
runs on the sharded executor for its format — resolved from the registry's
``mesh=``/``consumes=`` metadata (``shard`` for coo, ``shard-sell`` for
sell).  Mesh jobs name their cell format explicitly: ``format="auto"``
would make the executed topology depend on a selection the intake path
never ran, so it is rejected at submit rather than resolved inconsistently.
Mesh jobs get solo buckets keyed by their topology: the mesh is a per-job
placement, and the sharded operand layouts are per-subject static shapes
that cannot stack under vmap.  ``submit`` validates the slice fits the
available devices, and the per-bucket engine config threads
``shard_rows``/``shard_cols`` through so plan-cache keys (which include the
mesh shape and device count) hit on re-buckets of the same topology.
"""
from __future__ import annotations

import dataclasses
import hashlib
import itertools
import time
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core.batched import BatchedLifeEngine
from repro.core.life import LifeConfig, LifeEngine
from repro.core.registry import REGISTRY
from repro.core.plan_cache import PlanCache
from repro.core.sbbnnls import SbbnnlsState, sbbnnls_init
from repro.data.dmri import LifeProblem

#: formats whose stacked operands run under vmap — eligible for shared
#: micro-batch buckets ("auto" restricts itself to the vmappable subset
#: inside BatchedLifeEngine; SELL widths are per-subject static shapes)
BATCHABLE_FORMATS = ("auto", "coo", "alto")

_SOLO_FORMATS = ("sell", "fcoo")

#: statuses a job never leaves (failure isolation, DESIGN.md §13.3)
TERMINAL_STATUSES = ("done", "failed", "cancelled")


class JobFailedError(RuntimeError):
    """Raised when a result is read off a job whose solve failed.

    The executor's original exception is both chained (``__cause__``) and
    carried on ``.error`` so clients on the async front line can retrieve
    it from the handle without parsing the message."""

    def __init__(self, job_id: str, error: BaseException):
        super().__init__(f"job {job_id!r} failed: {error!r}")
        self.job_id = job_id
        self.error = error


class JobCancelledError(RuntimeError):
    """Raised when a result is read off a cancelled job."""

    def __init__(self, job_id: str):
        super().__init__(f"job {job_id!r} was cancelled")
        self.job_id = job_id


def _is_solo(fmt: str, mesh: Optional[Tuple[int, int]]) -> bool:
    """Solo-bucket predicate: SELL operands cannot stack under vmap, and a
    mesh slice is a per-job placement — either way the job never shares an
    engine.  Single definition for both the bucket key and the bucket."""
    return fmt in _SOLO_FORMATS or mesh is not None


def dataset_key(problem: LifeProblem) -> str:
    """Content digest of one subject's full dataset (Phi + signal + dict).

    Two submissions with byte-identical data share the digest; any change —
    different seed, compaction, new acquisition — misses cleanly.  The
    service uses it to (a) verify a resumed job is being re-attached to the
    same data and (b) key FormatPlan/plan-cache reuse across requests.
    """
    h = hashlib.sha256()
    phi = problem.phi
    h.update(np.int64([phi.n_atoms, phi.n_voxels, phi.n_fibers]).tobytes())
    for arr in (phi.atoms, phi.voxels, phi.fibers):
        h.update(np.ascontiguousarray(np.asarray(arr), np.int64).tobytes())
    for arr in (phi.values, problem.b, problem.dictionary):
        h.update(np.ascontiguousarray(np.asarray(arr), np.float64).tobytes())
    return h.hexdigest()[:16]


def _dict_digest(problem: LifeProblem) -> str:
    return hashlib.sha256(
        np.ascontiguousarray(np.asarray(problem.dictionary),
                             np.float64).tobytes()).hexdigest()[:16]


@dataclasses.dataclass
class Job:
    """One tenant's solve request plus its in-flight progress."""

    job_id: str
    problem: LifeProblem
    n_iters: int
    priority: int = 0                     # higher runs sooner (tie-break)
    deadline: Optional[float] = None      # absolute time.monotonic() seconds
    format: str = "auto"
    # (R, C) device-mesh slice request; None = single-device engines.
    # Mesh jobs run the sharded executor for their format in a solo bucket.
    mesh: Optional[Tuple[int, int]] = None
    # kernel-autotuning knobs (None = inherit the scheduler config at
    # submit); both are part of the batch-compatibility class — jobs tuned
    # differently never share a micro-batch (DESIGN.md §10.4)
    tune: Optional[str] = None            # "off" | "cached" | "full"
    compute_dtype: Optional[str] = None   # "fp32" | "bf16" | "auto"
    # warm-start weights (Nf,): the solver starts from sbbnnls_init(w0)
    # instead of all-ones — the repeat-visit path for Phi-delta
    # resubmissions and virtual lesions (DESIGN.md §15.3).  Not part of
    # the batch-compatibility class: states are initialized per job, so
    # warm and cold jobs share a micro-batch freely.
    w0: Optional[np.ndarray] = None
    # None = unset (stamped at submit); 0.0 is a legitimate monotonic time
    submitted_at: Optional[float] = None
    # -- progress (owned by the scheduler) --------------------------------
    state: Optional[SbbnnlsState] = None
    done: int = 0                         # iterations completed
    losses: List[np.ndarray] = dataclasses.field(default_factory=list)
    status: str = "queued"    # queued | running | done | failed | cancelled
    dataset: str = ""                     # content digest, set on submit
    dict_digest: str = ""                 # dictionary digest (bucket key part)
    finished_at: Optional[float] = None
    # seconds spent in previous service incarnations (restored on resume);
    # end-to-end latency = prior_elapsed + (finished_at - submitted_at)
    prior_elapsed: float = 0.0
    # the exception that failed this job (status == "failed")
    error: Optional[BaseException] = None

    @property
    def remaining(self) -> int:
        return max(0, self.n_iters - self.done)

    def result(self) -> Tuple[jnp.ndarray, np.ndarray]:
        """(final weights (Nf,), per-iteration loss trace)."""
        if self.status == "failed":
            assert self.error is not None
            raise JobFailedError(self.job_id, self.error) from self.error
        if self.status == "cancelled":
            raise JobCancelledError(self.job_id)
        if self.state is None:
            raise RuntimeError(f"job {self.job_id!r} has not run yet")
        losses = (np.concatenate(self.losses) if self.losses
                  else np.zeros((0,)))
        return self.state.w, losses


class _Bucket:
    """Jobs sharing one batch-compatibility class + their cached engine."""

    def __init__(self, key: Tuple, fmt: str, arrival: int,
                 mesh: Optional[Tuple[int, int]] = None,
                 tune: str = "off", compute_dtype: str = "fp32"):
        self.key = key
        self.format = fmt
        self.mesh = mesh
        self.tune = tune
        self.compute_dtype = compute_dtype
        self.solo = _is_solo(fmt, mesh)
        self.jobs: List[Job] = []
        self.iters_served = 0             # virtual time for fairness
        self.arrival = arrival
        self._engine = None
        self._engine_sig: Optional[Tuple[str, ...]] = None

    # -- urgency ordering --------------------------------------------------
    def urgency(self) -> Tuple:
        deadline = min((j.deadline for j in self.jobs
                        if j.deadline is not None), default=float("inf"))
        priority = max(j.priority for j in self.jobs)
        return (deadline, -priority, self.iters_served, self.arrival)

    # -- engine construction (memoized on the member set) ------------------
    def _config(self, base: LifeConfig) -> LifeConfig:
        cfg = dataclasses.replace(base, format=self.format, tune=self.tune,
                                  compute_dtype=self.compute_dtype)
        if self.mesh is not None:
            R, C = self.mesh
            # submit validated the format has a mesh executor
            cfg = dataclasses.replace(
                cfg, shard_rows=R, shard_cols=C,
                executor=REGISTRY.mesh_executor_for(self.format))
        return cfg

    def engine(self, base: LifeConfig, cache: PlanCache):
        sig = tuple(j.job_id for j in self.jobs)
        if self._engine is None or self._engine_sig != sig:
            cfg = self._config(base)
            if self.solo:
                self._engine = LifeEngine(self.jobs[0].problem, cfg, cache,
                                          jobs=sig)
            else:
                self._engine = BatchedLifeEngine(
                    [j.problem for j in self.jobs], cfg, cache, jobs=sig)
            self._engine_sig = sig
        # pin the searched dtype the moment it resolves: engine rebuilds
        # (member churn) and checkpoint manifests must see the numerics
        # that actually ran, not the open "auto" request — a re-search
        # after plan-cache eviction could otherwise flip the dtype
        # mid-trajectory.  Late arrivals into an already-pinned bucket are
        # pinned here too (they keyed on "auto" but run the bucket engine).
        if self.compute_dtype == "auto":
            self.compute_dtype = self._engine.resolved_compute_dtype
        for j in self.jobs:
            if j.compute_dtype == "auto":
                j.compute_dtype = self.compute_dtype
        return self._engine

    # -- the time slice ----------------------------------------------------
    def run_slice(self, base: LifeConfig, cache: PlanCache,
                  slice_iters: int) -> List[Job]:
        """Advance every member by k <= slice_iters iterations; a member
        whose remaining budget is below k bounds the whole slice, so no job
        ever overruns its requested n_iters.  Returns members that finished.
        """
        engine = self.engine(base, cache)
        k = min([slice_iters] + [j.remaining for j in self.jobs])
        # warm starts: a job carrying w0 gets its state from
        # sbbnnls_init(w0) instead of the engine's all-ones default —
        # per job, so one micro-batch can mix warm and cold members
        for j in self.jobs:
            if j.state is None and j.w0 is not None:
                j.state = sbbnnls_init(
                    jnp.asarray(j.w0, j.problem.dictionary.dtype))
        if self.solo:
            job = self.jobs[0]
            if job.state is None:
                job.state = engine.init_state()
            if k:
                job.state, ls = engine.step(job.state, k)
                job.losses.append(ls)
                job.done += k
        else:
            if any(j.state is None for j in self.jobs):
                fresh = engine.init_states()
                for i, j in enumerate(self.jobs):
                    if j.state is None:
                        j.state = SbbnnlsState(w=fresh.w[i], it=fresh.it[i],
                                               loss=fresh.loss[i])
            states = SbbnnlsState(
                w=jnp.stack([j.state.w for j in self.jobs]),
                it=jnp.stack([j.state.it for j in self.jobs]),
                loss=jnp.stack([j.state.loss for j in self.jobs]))
            if k:
                states, losses = engine.step(states, k)
            for i, job in enumerate(self.jobs):
                job.state = SbbnnlsState(w=states.w[i], it=states.it[i],
                                         loss=states.loss[i])
                if k:
                    job.losses.append(losses[i])
                    job.done += k
        self.iters_served += k * len(self.jobs)
        finished = [j for j in self.jobs if j.remaining == 0]
        for job in finished:
            job.status = "done"
            job.finished_at = time.monotonic()
        self.jobs = [j for j in self.jobs if j.remaining > 0]
        return finished


class Scheduler:
    """Continuous-batching micro-batch scheduler over stepped solves."""

    def __init__(self, config: Optional[LifeConfig] = None, *,
                 slice_iters: int = 16, cache: Optional[PlanCache] = None):
        self.config = config if config is not None else LifeConfig()
        if getattr(self.config, "compact_every", 0) > 0:
            # silently never compacting would be worse than refusing: the
            # stepped path drives engines directly and bypasses the
            # compaction loop in LifeEngine.run()
            raise ValueError(
                "weight compaction (compact_every > 0) is not supported by "
                "the serving scheduler; run those solves through LifeEngine")
        self.cache = cache if cache is not None else PlanCache(
            self.config.plan_cache_dir, self.config.plan_cache_max_bytes)
        self.slice_iters = slice_iters
        self._queue: List[Job] = []
        self._buckets: Dict[Tuple, _Bucket] = {}
        self._jobs: Dict[str, Job] = {}
        self._arrivals = itertools.count()
        self._last_served: Optional[Tuple] = None
        # obs instruments, fetched once and held (DESIGN.md §12.2) — every
        # call below is an allocation-free no-op while obs is disabled.
        # Counter invariant, maintained across submit()/tick()/cancel():
        #   serve.jobs.admitted == serve.jobs.completed + serve.jobs.failed
        #                          + serve.jobs.cancelled
        #                          + serve.queue.depth + serve.jobs.running
        self._m_admitted = obs.counter("serve.jobs.admitted")
        self._m_completed = obs.counter("serve.jobs.completed")
        self._m_failed = obs.counter("serve.jobs.failed")
        self._m_cancelled = obs.counter("serve.jobs.cancelled")
        self._m_preempted = obs.counter("serve.preemptions")
        self._g_queue = obs.gauge("serve.queue.depth")
        self._g_running = obs.gauge("serve.jobs.running")
        self._g_buckets = obs.gauge("serve.buckets.live")
        self._h_queue = obs.histogram("serve.queue.depth")
        self._h_occupancy = obs.histogram("serve.bucket.occupancy")
        self._h_slice = obs.histogram("serve.slice.seconds")

    # -- intake ------------------------------------------------------------
    def submit(self, job: Job) -> Job:
        if job.job_id in self._jobs:
            raise ValueError(f"job id {job.job_id!r} already submitted")
        if "/" in job.job_id:
            raise ValueError("job ids must not contain '/' "
                             "(they key checkpoint array paths)")
        if job.format not in BATCHABLE_FORMATS + _SOLO_FORMATS:
            raise ValueError(
                f"format must be one of "
                f"{BATCHABLE_FORMATS + _SOLO_FORMATS}, got {job.format!r}")
        # tuning knobs: inherit the scheduler config when unset, then
        # validate eagerly (intake is the last place a bad value fails
        # cheaply).  validate_config reads .tune/.compute_dtype, so the
        # Job itself is the config it validates — one rule set with the
        # engines, not a hand-kept copy.
        if job.tune is None:
            job.tune = getattr(self.config, "tune", "off")
        if job.compute_dtype is None:
            job.compute_dtype = getattr(self.config, "compute_dtype", "fp32")
        from repro.tune.tuner import validate_config
        validate_config(job)
        if job.mesh is not None:
            R, C = job.mesh
            if R < 1 or C < 1:
                raise ValueError(f"mesh shape must be positive, "
                                 f"got {job.mesh}")
            if R * C > len(jax.devices()):
                raise ValueError(
                    f"mesh slice ({R}, {C}) needs {R * C} devices, "
                    f"have {len(jax.devices())}")
            if REGISTRY.mesh_executor_for(job.format) is None:
                meshable = tuple(
                    f for f in BATCHABLE_FORMATS + _SOLO_FORMATS
                    if REGISTRY.mesh_executor_for(f))
                raise ValueError(
                    f"format {job.format!r} has no mesh executor; mesh "
                    f"jobs must name an explicit cell format from "
                    f"{meshable}")
        if job.w0 is not None:
            w0 = np.asarray(job.w0)
            nf = job.problem.phi.n_fibers
            if w0.shape != (nf,):
                raise ValueError(f"w0 has shape {w0.shape}, expected "
                                 f"({nf},) for this problem")
            if not np.all(np.isfinite(w0)) or bool((w0 < 0).any()):
                raise ValueError("w0 must be finite and nonnegative "
                                 "(SBBNNLS iterates live in the "
                                 "nonnegative orthant)")
            job.w0 = w0
        if not job.dataset:
            job.dataset = dataset_key(job.problem)
        if not job.dict_digest:
            job.dict_digest = _dict_digest(job.problem)
        if job.submitted_at is None:      # 0.0 is a valid monotonic stamp
            job.submitted_at = time.monotonic()
        self._jobs[job.job_id] = job
        self._queue.append(job)
        self._m_admitted.inc()
        self._g_queue.set(float(len(self._queue)))
        return job

    def _bucket_key(self, job: Job) -> Tuple:
        phi = job.problem.phi
        return (phi.n_voxels, phi.n_fibers, job.problem.dictionary.shape[1],
                job.dict_digest, job.format, job.mesh,
                job.tune, job.compute_dtype,
                job.job_id if _is_solo(job.format, job.mesh) else "")

    def _admit(self) -> None:
        """Move queued jobs into buckets — the continuous-batching step:
        arrivals join their bucket's *next* micro-batch; nothing in flight
        restarts (states persist across the engine rebuild)."""
        for job in self._queue:
            key = self._bucket_key(job)
            if key not in self._buckets:
                self._buckets[key] = _Bucket(key, job.format,
                                             next(self._arrivals),
                                             mesh=job.mesh, tune=job.tune,
                                             compute_dtype=job.compute_dtype)
            self._buckets[key].jobs.append(job)
            job.status = "running"
        self._queue.clear()

    # -- the loop ----------------------------------------------------------
    def tick(self) -> List[Job]:
        """Admit arrivals, serve the most urgent bucket one time slice.

        Returns the jobs that reached a terminal state during this tick
        (``status`` is "done" or "failed").  An executor exception never
        propagates: the poisoned bucket is quarantined — each member is
        retried in a single-job probe so one bad tenant cannot condemn its
        batch-mates — and only the jobs that fail alone are marked
        ``failed`` with the exception captured (DESIGN.md §13.3).  Every
        other bucket stays servable."""
        with obs.span("scheduler.tick"):
            self._h_queue.observe(float(len(self._queue)))
            self._admit()
            self._g_queue.set(0.0)         # _admit drained the queue
            live = [b for b in self._buckets.values() if b.jobs]
            self._g_buckets.set(float(len(live)))
            self._g_running.set(float(sum(len(b.jobs) for b in live)))
            if not live:
                return []
            bucket = min(live, key=_Bucket.urgency)
            # a preemption = the most urgent bucket displaced the one served
            # last tick while that one still had members waiting to run
            last = self._last_served
            if (last is not None and last != bucket.key
                    and last in self._buckets and self._buckets[last].jobs):
                self._m_preempted.inc()
            self._last_served = bucket.key
            self._h_occupancy.observe(float(len(bucket.jobs)))
            timed = obs.SWITCH.on          # guard the clock reads, not just
            t0 = time.monotonic() if timed else 0.0   # the observe() call
            try:
                with obs.span("scheduler.slice",
                              {"format": bucket.format,
                               "jobs": tuple(j.job_id for j in bucket.jobs)}):
                    finished = bucket.run_slice(self.config, self.cache,
                                                self.slice_iters)
            except Exception as exc:
                finished = self._quarantine(bucket, exc)
            if timed:
                self._h_slice.observe(time.monotonic() - t0)
            done = [j for j in finished if j.status == "done"]
            if done:
                self._m_completed.inc(float(len(done)))
            if finished:
                self._g_running.dec(float(len(finished)))
            cur = self._buckets.get(bucket.key)
            if cur is not None and not cur.jobs:
                del self._buckets[bucket.key]
            return finished

    # -- failure isolation (DESIGN.md §13.3) -------------------------------
    def _fail(self, job: Job, exc: BaseException) -> None:
        job.status = "failed"
        job.error = exc
        job.finished_at = time.monotonic()
        self._m_failed.inc()

    def _quarantine(self, bucket: _Bucket, exc: Exception) -> List[Job]:
        """A slice raised: evict the poisoned bucket and bisect to the bad
        tenant(s).  Single-member buckets fail outright; multi-member
        buckets retry each job through a one-job probe bucket of the same
        compatibility class — members that succeed alone keep their
        advanced state and re-bucket together (micro-batching resumes next
        tick), members that fail alone are the poisoned ones.  Returns the
        jobs that reached a terminal state (failed, plus any that finished
        during their probe)."""
        jobs = list(bucket.jobs)
        self._buckets.pop(bucket.key, None)
        if len(jobs) == 1:
            self._fail(jobs[0], exc)
            return jobs
        terminal: List[Job] = []
        survivors: List[Job] = []
        with obs.span("scheduler.quarantine",
                      {"format": bucket.format, "jobs": len(jobs)}):
            for job in jobs:
                probe = _Bucket(bucket.key, bucket.format, bucket.arrival,
                                mesh=bucket.mesh, tune=bucket.tune,
                                compute_dtype=bucket.compute_dtype)
                probe.jobs = [job]
                try:
                    terminal.extend(probe.run_slice(self.config, self.cache,
                                                    self.slice_iters))
                except Exception as probe_exc:
                    self._fail(job, probe_exc)
                    terminal.append(job)
                else:
                    if job.remaining > 0:
                        survivors.append(job)
        if survivors:
            fresh = _Bucket(bucket.key, bucket.format,
                            next(self._arrivals), mesh=bucket.mesh,
                            tune=bucket.tune,
                            compute_dtype=bucket.compute_dtype)
            fresh.iters_served = bucket.iters_served   # fairness carries over
            fresh.jobs = survivors
            self._buckets[bucket.key] = fresh
        return terminal

    # -- cancellation ------------------------------------------------------
    def cancel(self, job_id: str) -> bool:
        """Cancel a queued or running job; returns False when the job is
        already terminal.  A running job leaves its bucket immediately (the
        engine signature invalidates, so batch-mates re-batch without it);
        its partial state stays readable on the Job for post-mortems but
        ``result()`` raises :class:`JobCancelledError`."""
        job = self._jobs[job_id]
        if job.status in TERMINAL_STATUSES:
            return False
        if job in self._queue:
            self._queue.remove(job)
            self._g_queue.set(float(len(self._queue)))
        else:
            bucket = next((b for b in self._buckets.values()
                           if job in b.jobs), None)
            if bucket is not None:
                bucket.jobs.remove(job)
                if not bucket.jobs:
                    del self._buckets[bucket.key]
                self._g_running.dec()
        job.status = "cancelled"
        job.finished_at = time.monotonic()
        self._m_cancelled.inc()
        return True

    def active(self) -> bool:
        return bool(self._queue) or any(b.jobs
                                        for b in self._buckets.values())

    def run_until_idle(self, max_ticks: Optional[int] = None) -> List[Job]:
        """Drive tick() until every submitted job completed."""
        finished: List[Job] = []
        ticks = 0
        while self.active():
            if max_ticks is not None and ticks >= max_ticks:
                break
            finished.extend(self.tick())
            ticks += 1
        return finished

    # -- introspection -----------------------------------------------------
    def job(self, job_id: str) -> Job:
        return self._jobs[job_id]

    def jobs(self) -> Sequence[Job]:
        return list(self._jobs.values())

    def in_flight(self) -> List[Job]:
        """Jobs admitted or queued but not terminal (checkpoint targets)."""
        return [j for j in self._jobs.values()
                if j.status not in TERMINAL_STATUSES]
