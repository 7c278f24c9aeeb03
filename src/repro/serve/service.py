"""LifeService: the serving front — submit / drive / checkpoint / resume.

Wraps :class:`~repro.serve.scheduler.Scheduler` with the durability story
(DESIGN.md §8.3): every ``checkpoint_every`` ticks the service snapshots all
in-flight solver states through :mod:`repro.checkpoint.manager` (atomic
rename, retention, the same machinery training jobs use).  A killed service
restarts, probes its checkpoint directory, and re-adopts each solve at the
exact iteration it left off — bit-compatibly, because a
:class:`~repro.core.sbbnnls.SbbnnlsState` is the *complete* solver state
(weights + iteration parity + last loss) and float arrays round-trip ``.npz``
losslessly.

Resume protocol: solve *data* is not checkpointed (at scale it lives in the
dataset store; here the client resubmits it).  The checkpoint manifest
records each job's dataset digest; on resubmission with a known ``job_id``
the service verifies the digest matches before re-attaching the restored
state, so a resumed job can never silently continue on different data.

Plan reuse across restarts is free: the scheduler's engines share one
persistent :class:`~repro.core.plan_cache.PlanCache`, keyed by dataset
content — a restarted service rebuilds its engines from cached FormatPlans /
autotune choices / tile plans instead of re-measuring.
"""
from __future__ import annotations

import time
from typing import Dict, List, Optional, Tuple

import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.checkpoint import manager as ckpt
from repro.core.life import LifeConfig
from repro.core.plan_cache import PlanCache
from repro.core.sbbnnls import SbbnnlsState
from repro.data.dmri import LifeProblem
from repro.serve.scheduler import Job, Scheduler, dataset_key


class LifeService:
    """Multi-tenant solve service with checkpointed, resumable jobs."""

    def __init__(self, config: Optional[LifeConfig] = None, *,
                 ckpt_dir: Optional[str] = None, checkpoint_every: int = 4,
                 slice_iters: int = 16, keep: int = 3,
                 cache: Optional[PlanCache] = None):
        self.config = config if config is not None else LifeConfig()
        self.scheduler = Scheduler(self.config, slice_iters=slice_iters,
                                   cache=cache)
        self.ckpt_dir = ckpt_dir
        self.checkpoint_every = checkpoint_every
        self.keep = keep
        self._tick = 0
        self._completed: Dict[str, Job] = {}
        self._failed: Dict[str, Job] = {}
        # job_id -> (restored arrays, manifest meta) awaiting resubmission
        self._resumable: Dict[str, Tuple[dict, dict]] = {}
        # obs instruments (no-ops while disabled, DESIGN.md §12.2)
        self._h_latency = obs.histogram("serve.job.latency.seconds")
        self._m_checkpoints = obs.counter("serve.checkpoints")
        self._m_ckpt_jobs = obs.counter("serve.jobs.checkpointed")
        self._m_resumed = obs.counter("serve.jobs.resumed")
        if ckpt_dir:
            self._load_resumable(ckpt_dir)

    # -- resume ------------------------------------------------------------
    def _load_resumable(self, ckpt_dir: str) -> None:
        latest = ckpt.load_latest(ckpt_dir)
        if latest is None:
            return
        step, flat, manifest = latest
        self._tick = step
        for job_id, meta in manifest.get("jobs", {}).items():
            arrays = {k.split(ckpt.SEP, 1)[1]: v for k, v in flat.items()
                      if k.split(ckpt.SEP, 1)[0] == job_id}
            if {"w", "it", "loss"} <= set(arrays):
                self._resumable[job_id] = (arrays, meta)

    @property
    def resumable_jobs(self) -> Tuple[str, ...]:
        """Job ids waiting to be re-adopted by a matching resubmission."""
        return tuple(sorted(self._resumable))

    # -- intake ------------------------------------------------------------
    def submit(self, problem: LifeProblem, *, job_id: Optional[str] = None,
               n_iters: Optional[int] = None, priority: Optional[int] = None,
               deadline: Optional[float] = None,
               format: Optional[str] = None,
               mesh: Optional[Tuple[int, int]] = None,
               tune: Optional[str] = None,
               compute_dtype: Optional[str] = None,
               w0: Optional[np.ndarray] = None) -> str:
        """Queue one solve; returns its job id.

        ``w0`` warm-starts the solver from the given weights instead of
        the all-ones default (shape ``(n_fibers,)``, finite,
        nonnegative) — the repeat-visit path for Phi-delta resubmission
        and virtual lesions (DESIGN.md §15.3).  It applies to *fresh*
        jobs only: on a checkpoint resume the restored state is the warm
        start, so passing ``w0`` alongside one is rejected rather than
        silently picking a winner.

        ``deadline`` is seconds from now (converted to an absolute monotonic
        time for EDF ordering).  If ``job_id`` names a checkpointed solve,
        the restored state is re-attached — after verifying the resubmitted
        data's digest matches the one recorded at checkpoint time.  On
        resume, arguments the caller passes explicitly win over the
        checkpointed values (extend a job with a larger ``n_iters``, bump
        its ``priority``, set a fresh ``deadline``); omitted ones are
        restored from the checkpoint, including the deadline's remaining
        budget.  The format, the mesh slice, and the compute dtype are the
        exceptions: the state's trajectory is only reproducible under the
        format, mesh topology, *and numerics* it ran on, so a conflicting
        explicit ``format``, ``mesh``, or ``compute_dtype`` is an error
        rather than a silent override.  ``tune`` may change freely on
        resume — tile choice affects speed, not the solution.

        ``mesh=(R, C)`` admits the job onto a device-mesh slice: its solve
        runs the sharded executor for its format (DESIGN.md §9)."""
        if job_id is None:
            taken = ({j.job_id for j in self.scheduler.jobs()}
                     | set(self._completed) | set(self._resumable))
            n = len(taken)
            while f"job-{n}" in taken:
                n += 1
            job_id = f"job-{n}"
        with obs.span("service.submit", {"job": job_id}):
            now = time.monotonic()
            job = Job(job_id=job_id, problem=problem,
                      n_iters=(self.config.n_iters if n_iters is None
                               else n_iters),
                      priority=0 if priority is None else priority,
                      deadline=None if deadline is None else now + deadline,
                      format=self.config.format if format is None else format,
                      mesh=None if mesh is None else tuple(mesh),
                      tune=tune, compute_dtype=compute_dtype, w0=w0,
                      submitted_at=now, dataset=dataset_key(problem))
            if job_id in self._resumable:
                if w0 is not None:
                    raise ValueError(
                        f"resume of job {job_id!r} rejected: a checkpointed "
                        f"state exists and is the warm start; w0 would "
                        f"silently discard it")
                arrays, meta = self._resumable[job_id]
                if meta.get("dataset") != job.dataset:
                    raise ValueError(
                        f"resume of job {job_id!r} rejected: resubmitted data "
                        f"digest {job.dataset} != checkpointed "
                        f"{meta.get('dataset')}")
                ck_format = str(meta.get("format", job.format))
                if format is not None and format != ck_format:
                    raise ValueError(
                        f"resume of job {job_id!r} rejected: checkpointed "
                        f"state ran under format {ck_format!r}, "
                        f"resubmitted with {format!r}")
                ck_mesh = meta.get("mesh")
                ck_mesh = None if ck_mesh is None else tuple(int(x)
                                                             for x in ck_mesh)
                if mesh is not None and tuple(mesh) != ck_mesh:
                    raise ValueError(
                        f"resume of job {job_id!r} rejected: checkpointed "
                        f"state ran on mesh {ck_mesh}, resubmitted with "
                        f"{tuple(mesh)}")
                ck_dtype = meta.get("compute_dtype")
                if (compute_dtype is not None and ck_dtype is not None
                        and compute_dtype != ck_dtype):
                    raise ValueError(
                        f"resume of job {job_id!r} rejected: checkpointed "
                        f"state ran under compute_dtype {ck_dtype!r}, "
                        f"resubmitted with {compute_dtype!r}")
                # validation passed — adopt the state (the entry is consumed
                # only once scheduler.submit accepts the job: its own
                # validation, e.g. the restored mesh not fitting this host's
                # devices, must leave the checkpointed state re-adoptable)
                job.format = ck_format
                job.mesh = ck_mesh
                if compute_dtype is None and ck_dtype is not None:
                    job.compute_dtype = str(ck_dtype)
                if tune is None and meta.get("tune") is not None:
                    job.tune = str(meta["tune"])
                job.state = SbbnnlsState(w=jnp.asarray(arrays["w"]),
                                         it=jnp.asarray(arrays["it"]),
                                         loss=jnp.asarray(arrays["loss"]))
                job.done = int(meta["done"])
                # the resume leg restarts submitted_at; the time the job spent
                # in earlier incarnations is restored so latency is end-to-end
                job.prior_elapsed = float(meta.get("elapsed", 0.0) or 0.0)
                # explicit caller arguments win over checkpointed values
                if n_iters is None:
                    job.n_iters = int(meta.get("n_iters", job.n_iters))
                if priority is None:
                    job.priority = int(meta.get("priority", 0))
                remaining = meta.get("deadline_remaining")
                if deadline is None and remaining is not None:
                    job.deadline = now + float(remaining)
                if "losses" in arrays:
                    job.losses = [np.asarray(arrays["losses"])]
                self._m_resumed.inc()
            self.scheduler.submit(job)
            self._resumable.pop(job_id, None)
            return job_id

    # -- driving -----------------------------------------------------------
    def step(self) -> List[Job]:
        """One scheduler tick + periodic checkpoint; returns the jobs that
        reached a terminal state (done or failed) this tick."""
        finished = self.scheduler.tick()
        self._tick += 1
        for job in finished:
            if job.status == "failed":
                self._failed[job.job_id] = job
                continue
            self._completed[job.job_id] = job
            if job.finished_at is not None:
                # end-to-end latency: legs run before a kill-and-resume are
                # restored into prior_elapsed, so a resumed job reports its
                # true submit→finish time, not just the final leg
                self._h_latency.observe(job.prior_elapsed
                                        + job.finished_at - job.submitted_at)
        if (self.ckpt_dir and self.checkpoint_every > 0
                and self._tick % self.checkpoint_every == 0):
            self.checkpoint()
        return finished

    def run(self, max_ticks: Optional[int] = None
            ) -> Dict[str, Tuple[jnp.ndarray, np.ndarray]]:
        """Drive until every job completed (or ``max_ticks`` elapsed);
        returns {job_id: (weights, loss trace)} for all completed jobs."""
        ticks = 0
        while self.scheduler.active():
            if max_ticks is not None and ticks >= max_ticks:
                break
            self.step()
            ticks += 1
        if self.ckpt_dir:
            self.checkpoint()                 # never exit with unsaved state
        return {jid: job.result() for jid, job in self._completed.items()}

    # -- durability --------------------------------------------------------
    def checkpoint(self) -> Optional[str]:
        """Snapshot every solver state: in-flight *and* completed (atomic,
        retained).  Completed jobs stay in the snapshot so a kill between a
        job finishing and the client reading its result loses nothing — a
        resubmission re-adopts the final state and completes instantly
        instead of re-running the whole solve."""
        if not self.ckpt_dir:
            return None
        with obs.span("service.checkpoint"):
            return self._checkpoint()

    def _checkpoint(self) -> Optional[str]:
        tree: Dict[str, Dict[str, np.ndarray]] = {}
        meta: Dict[str, dict] = {}
        now = time.monotonic()
        # failed jobs ride along with their last good state: resubmitting a
        # failed job's data re-adopts it and retries the remaining
        # iterations from where the solve was last healthy (DESIGN.md §13.3)
        for job in (self.scheduler.in_flight()
                    + list(self._completed.values())
                    + list(self._failed.values())):
            if job.state is None:
                continue                      # queued, never ran: nothing yet
            entry = {"w": np.asarray(job.state.w),
                     "it": np.asarray(job.state.it),
                     "loss": np.asarray(job.state.loss)}
            if job.losses:
                entry["losses"] = np.concatenate(job.losses)
            tree[job.job_id] = entry
            end = job.finished_at if job.finished_at is not None else now
            meta[job.job_id] = dict(
                done=job.done, n_iters=job.n_iters, priority=job.priority,
                format=job.format, dataset=job.dataset,
                mesh=None if job.mesh is None else list(job.mesh),
                tune=job.tune, compute_dtype=job.compute_dtype,
                # cumulative wall time across service incarnations, so a
                # resumed job's latency covers every leg (restored into
                # Job.prior_elapsed on resume)
                elapsed=job.prior_elapsed + max(0.0, end - job.submitted_at),
                # deadlines are monotonic-clock absolutes that don't survive
                # a restart; persist the remaining budget instead
                deadline_remaining=(None if job.deadline is None
                                    else job.deadline - now))
            if job.status == "failed" and job.error is not None:
                meta[job.job_id]["error"] = repr(job.error)
        # carry restored-but-unclaimed states forward: without this, a job
        # nobody has resubmitted yet would fall out of retention once other
        # jobs rotate `keep` fresh snapshots past its last one.  Deliberate
        # trade-off: abandoned tenants ride along in every snapshot (a few
        # arrays each) until operators clear the checkpoint dir — durability
        # over disk economy; revisit with a TTL if snapshots grow hot
        for job_id, (arrays, m) in self._resumable.items():
            if job_id not in tree:
                tree[job_id] = {k: np.asarray(v) for k, v in arrays.items()}
                meta[job_id] = m
        self._m_checkpoints.inc()
        self._m_ckpt_jobs.inc(float(len(tree)))
        return ckpt.save(self.ckpt_dir, self._tick, tree,
                         meta={"jobs": meta}, keep=self.keep)

    # -- introspection -----------------------------------------------------
    def job(self, job_id: str) -> Job:
        """The Job record whatever its state — queued, running, done,
        failed, or cancelled (the front line's status/result source)."""
        if job_id in self._completed:
            return self._completed[job_id]
        if job_id in self._failed:
            return self._failed[job_id]
        return self.scheduler.job(job_id)

    def result(self, job_id: str) -> Tuple[jnp.ndarray, np.ndarray]:
        """(weights, loss trace); raises
        :class:`~repro.serve.scheduler.JobFailedError` (chaining the
        captured executor exception) when the job failed."""
        return self.job(job_id).result()

    def status(self, job_id: str) -> str:
        return self.job(job_id).status

    def error(self, job_id: str) -> Optional[BaseException]:
        """The captured exception of a failed job (None otherwise)."""
        return self.job(job_id).error

    @property
    def failed_jobs(self) -> Tuple[str, ...]:
        return tuple(sorted(self._failed))

    def cancel(self, job_id: str) -> bool:
        """Cancel a queued or running job; False once it is terminal."""
        if job_id in self._completed or job_id in self._failed:
            return False
        return self.scheduler.cancel(job_id)

    @property
    def cache_stats(self):
        return self.scheduler.cache.stats

    def metrics_snapshot(self) -> dict:
        """The obs snapshot with the service's plan-cache stats mirrored in
        as authoritative gauges (``plan_cache.hits`` / ``.misses`` /
        ``.hit_rate`` — counted since the cache was built, including
        lookups made while obs was disabled).  This is the serving metric
        surface the ROADMAP names: queue depth, latency quantiles,
        completion counters, and plan-cache hit rate, one JSON-ready
        dict."""
        obs.record_cache_stats(self.scheduler.cache.stats)
        return obs.snapshot()
