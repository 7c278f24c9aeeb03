"""One run of one cell: set-up, the measured window, the check, the result.

Everything a cell needs is found by name: the cell in ``BENCHMARK.json``,
its configuration (``bench/configs/<config>.json``), its traffic mix
(``bench/traffic/<traffic>.json``), its limits
(``bench/limits/<cell>.json``) and one reader per metric family
(``bench/metrics/<family>.py``, a function ``read(run)``; the metric
``idle_share.solve`` is read by ``idle_share.py`` unless a file of its
full name exists).  The program is driven only through its front end:
``LifeFrontend.submit_async``, as a user gets it: with the front end's
default configuration, or where the mix names a ``format`` or a ``mesh``
that configuration with the mix's layout, which every job also asks for;
and for a virtual lesion the program's own edit (``repro.science.lesion``),
timed with the query.  Each run starts with an empty plan cache of its
own (:func:`fresh_plan_cache`), as it starts with cold compiles.

Set-up makes the subjects from the seed (the benchmark's own programs
compile with the persistent cache on), starts the front end, turns the
persistent cache off and runs one job of the mix's kind on a subject or
bundle the window never sees; an open mix instead runs its own schedule
for ``warmup_s`` on ``warmup_subjects`` the window never draws.  So every
compile the program does, in the warm-up and in the window, is equally
cold in every run and for every job: with the warm-up's programs read
from the cache instead, the compiler itself would first run inside the
window, and a seed's second run would read its first solve about 5 s
slower than its first run did.  Then one client sends jobs until the
window's seconds have passed: in a closed loop each when the last answer
is in, in an open loop each at its due time (:func:`open_window`).  A
traced run turns the program's own tracing on for the window.  After the
window the program is shut down, the device's peak memory is read, the
persistent cache is turned on again for the benchmark's own programs, and
the reference solves the answers the window compares.
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import gc
import glob
import importlib.util
import json
import os
import shutil
import tempfile
import threading
import time
from pathlib import Path
from typing import Dict, List, Optional, Tuple

import numpy as np

from bench import check, gen, reference, traffic, work

ROOT = Path(__file__).resolve().parents[1]
#: the benchmark's own persistent compile cache: a fixed path in the checkout
CACHE = ROOT / ".bench_cache"
#: longest wait for one answer
RESULT_TIMEOUT_S = 300.0
#: how often the open loop's sender looks for answers
POLL_S = 0.002
#: isolated DSC / WC calls per traced run
ISOLATED_CALLS = 10
PLAN_CACHE_ENV = "REPRO_PLAN_CACHE"
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
CACHE_HIT = "/jax/compilation_cache/cache_hits"


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    mix: dict
    limits: Dict[str, float]
    metrics: List[dict]          # end-to-end metrics this cell reports
    per_layer: List[dict]        # per-layer metrics this cell reports


def load_cell(workload: str, root: Path = ROOT) -> Cell:
    spec = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} (have {sorted(cells)})")
    return make_cell(cells[workload], spec, root)


def cell_from_files(workload: str, root: Path = ROOT) -> Cell:
    """The cell ``<config>.<traffic>`` from its files alone, where
    ``BENCHMARK.json`` does not list it yet: what a sweep for its rate,
    and the tests, run before the cell is added.  It takes as many chips
    as its mix's mesh holds."""
    spec = json.loads((root / "BENCHMARK.json").read_text())
    config, traffic_name = workload.split(".", 1)
    mix = traffic.load(root / "bench" / "traffic" / f"{traffic_name}.json")
    return make_cell({"name": workload, "config": config,
                      "traffic": traffic_name,
                      "chips": traffic.chips(mix)}, spec, root)


def make_cell(w: dict, spec: dict, root: Path = ROOT) -> Cell:
    """The cell of workload entry ``w`` under the benchmark ``spec``."""
    workload = w["name"]
    e2e = [m for m in spec["end_to_end"]
           if workload in m.get("workloads", [workload])]
    reported = {m["name"] for m in e2e}
    layer = [m for m in spec["per_layer"]
             if (workload in m["workloads"] if "workloads" in m
                 else m["moves"] in reported)]
    return Cell(
        name=workload, chips=int(w["chips"]),
        config=json.loads((root / "bench" / "configs"
                           / f"{w['config']}.json").read_text()),
        mix=traffic.load(root / "bench" / "traffic" / f"{w['traffic']}.json"),
        limits=check.load_limits(root / "bench" / "limits"
                                 / f"{workload}.json"),
        metrics=e2e, per_layer=layer)


# -- compile cache ------------------------------------------------------------

def enable_compile_cache() -> str:
    """Persistent compile cache for set-up: ``$JAX_COMPILATION_CACHE_DIR``
    when set, else a fixed directory in the checkout."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(CACHE / "jax")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_enable_compilation_cache", True)
    return path


@contextlib.contextmanager
def fresh_plan_cache():
    """The program's plan cache (``$REPRO_PLAN_CACHE``) in a new empty
    directory for the block, removed after it: format selection starts
    equally cold in every run, as compiles do, however many runs one
    process makes.  The program reads the variable when it builds its
    cache, which the front end does inside the block."""
    path = tempfile.mkdtemp(prefix="bench-plans-")
    old = os.environ.get(PLAN_CACHE_ENV)
    os.environ[PLAN_CACHE_ENV] = path
    try:
        yield path
    finally:
        if old is None:
            os.environ.pop(PLAN_CACHE_ENV, None)
        else:
            os.environ[PLAN_CACHE_ENV] = old
        shutil.rmtree(path, ignore_errors=True)


def disable_compile_cache() -> None:
    """No compile from here on reads or writes the persistent cache."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()


class CompileCounter:
    """Counts the program's XLA compiles and persistent-cache hits while
    ``on`` (a ``jax.monitoring`` listener; a cache hit also passes the
    compile wrapper, so compiles = wrapper events - hits)."""

    def __init__(self):
        import jax.monitoring as mon
        self.on = False
        self.wrapped = 0
        self.cache_hits = 0
        self._lock = threading.Lock()
        mon.register_event_time_span_listener(self._span)
        mon.register_event_listener(self._event)

    def _span(self, event, start, end, **_):
        if self.on and event == BACKEND_COMPILE:
            with self._lock:
                self.wrapped += 1

    def _event(self, event, **_):
        if self.on and event == CACHE_HIT:
            with self._lock:
                self.cache_hits += 1

    @property
    def compiles(self) -> int:
        return self.wrapped - self.cache_hits

    def close(self) -> None:
        import jax.monitoring as mon
        mon.unregister_event_time_span_listener(self._span)
        mon.unregister_event_listener(self._event)


# -- the run record the metric readers read -----------------------------------

@dataclasses.dataclass
class Job:
    """One job of the window; times in seconds from the window start.  An
    open loop's job has its due time and the job id it was sent under."""

    req: traffic.Request
    sent: float
    finished: Optional[float] = None
    error: Optional[str] = None
    w: object = None
    losses: Optional[np.ndarray] = None
    due: Optional[float] = None
    job_id: Optional[str] = None

    @property
    def latency(self) -> Optional[float]:
        """Answer time less the due time (the send time in a closed loop),
        so a late sender does not hide queueing; None until answered."""
        if self.finished is None or self.error is not None:
            return None
        return self.finished - (self.sent if self.due is None else self.due)


@dataclasses.dataclass
class Run:
    cell: Cell
    seconds: float
    setup_s: float
    jobs: List[Job]
    compiles_in_window: int
    cache_hits_in_window: int
    trace: Optional[dict] = None      # window, busy_s, idle_share
    isolated: Dict[str, dict] = dataclasses.field(default_factory=dict)
    peak: Optional[dict] = None       # peaks of the device kind

    def done(self) -> List[Job]:
        return [j for j in self.jobs if j.finished is not None
                and j.error is None]

    def seconds_per_job(self) -> Optional[float]:
        """Closed loop: (last completion - first submit) / completions."""
        done = self.done()
        if not done:
            return None
        return (max(j.finished for j in done)
                - min(j.sent for j in self.jobs)) / len(done)


def reader_path(name: str, root: Path = ROOT) -> Path:
    """The reader file of metric ``name``: ``metrics/<name>.py`` where it
    exists, else the family's, ``metrics/<name up to its first dot>.py``."""
    metrics = root / "bench" / "metrics"
    exact = metrics / f"{name}.py"
    return exact if exact.is_file() else metrics / f"{name.split('.')[0]}.py"


def reader(name: str, root: Path = ROOT):
    """The reader module of metric ``name``: ``read(run)``, and
    ``NEEDS_ISOLATED = True`` where it reads the isolated SpMV calls."""
    path = reader_path(name, root)
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# -- the program's side ---------------------------------------------------------

def to_problem(s: gen.Subject, d: np.ndarray):
    """The program's input type for one subject."""
    import jax.numpy as jnp
    from repro.core.std import PhiTensor
    from repro.data.dmri import LifeProblem
    phi = PhiTensor(atoms=jnp.asarray(s.atoms), voxels=jnp.asarray(s.voxels),
                    fibers=jnp.asarray(s.fibers), values=jnp.asarray(s.values),
                    n_atoms=s.n_atoms, n_voxels=s.n_voxels,
                    n_fibers=s.n_fibers)
    return LifeProblem(phi=phi, dictionary=jnp.asarray(d),
                       b=jnp.asarray(s.b), w_true=jnp.asarray(s.w_true),
                       stats={}, grid=s.grid)


def life_config(control: bool, mix: dict):
    """The front end's configuration for the mix: its defaults (executor
    ``opt``, format ``coo``, one device) with the mix's ``format`` and
    ``mesh`` where it names them.  The control is the same layout on the
    program's own bf16-storage path, the nearest precision below fp32."""
    from repro.core.life import LifeConfig
    rows, cols = mix.get("mesh", (1, 1))
    return LifeConfig(format=mix.get("format", "coo"), shard_rows=rows,
                      shard_cols=cols,
                      compute_dtype="bf16" if control else "fp32")


def _annotate(name: str):
    import jax
    return jax.profiler.TraceAnnotation(name)


class Workload:
    """The subjects and requests of one cell under one seed."""

    def __init__(self, cell: Cell, seed: int, seconds: float):
        cfg, mix = cell.config, cell.mix
        self.cell, self.seed, self.seconds = cell, seed, seconds
        self.options = traffic.job_options(mix)
        self.d = gen.dictionary(cfg["n_atoms"], cfg["n_theta"])
        lesion = "lesion" in mix
        # a solve mix gets one subject more than the window cycles
        # through, the warm-up's, so no window job finds its programs; an
        # open mix gets its warm-up subjects after the window's
        extra = (0 if lesion else int(mix.get("warmup_subjects", 1)))
        self.subjects = gen.subjects(cfg, int(mix["subjects"]) + extra, seed)
        self.problems = [to_problem(s, self.d) for s in self.subjects]
        self.bundles = []
        if lesion:
            les = mix["lesion"]
            # the last bundle is the warm-up's, never one of the window's
            self.bundles = traffic.bundles(
                self.subjects[0], size=int(les["bundle_fibers"]),
                count=int(les["bundles"]) + 1, seed=seed)
        self.w_full = None            # the set-up solve (lesion mixes)
        self.w_full_losses = None

    def submit(self, fe, req: traffic.Request,
               job_id: Optional[str] = None):
        if req.bundle is None:
            return fe.submit_async(self.problems[req.subject],
                                   n_iters=req.n_iters, job_id=job_id,
                                   **self.options)
        # a virtual lesion as a user makes it: the program's edit of the
        # full subject, then a warm-started re-solve
        from repro.science.lesion import lesion_problem, warm_start_weights
        ids = self.bundles[req.bundle]
        return fe.submit_async(lesion_problem(self.problems[req.subject], ids),
                               n_iters=req.n_iters,
                               w0=warm_start_weights(self.w_full, ids),
                               **self.options)

    def warm_up(self, fe) -> None:
        """One job of the mix's kind on what the window never sees: the
        last subject, or for a lesion mix the last bundle, after the full
        solve that the queries warm-start from."""
        mix = self.cell.mix
        if mix["loop"] == "open":
            seconds = float(mix["warmup_s"])
            jobs = open_loop(
                lambda req, job_id: self.submit(fe, req, job_id),
                traffic.open_requests(mix, self.seed, seconds, warm_up=True),
                seconds, RESULT_TIMEOUT_S, prefix="w")
            bad = [j for j in jobs if j.latency is None]
            if bad:
                raise RuntimeError(f"warm-up: {len(bad)} of {len(jobs)} "
                                   f"jobs not answered ({bad[0].error})")
            return
        if "lesion" in mix:
            n = int(mix["lesion"]["warm_start_iters"])
            w, losses = fe.submit_async(self.problems[0], n_iters=n,
                                        **self.options).result(
                timeout=RESULT_TIMEOUT_S)
            self.w_full, self.w_full_losses = np.asarray(w), losses
            req = traffic.Request(subject=0, n_iters=int(mix["n_iters"]),
                                  bundle=len(self.bundles) - 1)
        else:
            req = traffic.Request(subject=len(self.subjects) - 1,
                                  n_iters=int(mix["n_iters"]))
        self.submit(fe, req).result(timeout=RESULT_TIMEOUT_S)


def _collect(job: Job, handle, t0: float) -> None:
    job.finished = time.perf_counter() - t0
    try:
        job.w, job.losses = handle.result(timeout=0)
    except Exception as exc:            # a failed job is an answer too
        job.error = repr(exc)


def closed_window(fe, wl: Workload) -> List[Job]:
    """One client: the next request goes out when the last answer is in,
    until the window's seconds have passed; the window ends on an
    answer."""
    jobs: List[Job] = []
    t0 = time.perf_counter()
    for req in traffic.closed_requests(wl.cell.mix, wl.seed):
        sent = time.perf_counter() - t0
        job = Job(req=req, sent=sent)
        jobs.append(job)
        with _annotate("bench.submit"):
            handle = wl.submit(fe, req)
        with _annotate("bench.wait"):
            try:
                handle.result(timeout=RESULT_TIMEOUT_S)
            except Exception:           # recorded by _collect
                pass
        _collect(job, handle, t0)
        if job.finished >= wl.seconds:
            break
    return jobs


def open_window(fe, wl: Workload) -> List[Job]:
    """Requests sent at their due times for the window's seconds, never
    waiting for answers; then in-flight jobs drain for at most the mix's
    ``drain_s``."""
    mix = wl.cell.mix
    return open_loop(lambda req, job_id: wl.submit(fe, req, job_id),
                     traffic.open_requests(mix, wl.seed, wl.seconds),
                     wl.seconds, float(mix["drain_s"]))


def open_loop(submit, schedule: List[traffic.Request], seconds: float,
              drain_s: float, prefix: str = "") -> List[Job]:
    """Send each request of ``schedule`` at its due time, as job
    ``<prefix><index>``, through ``submit(req, job_id) -> handle``, until
    ``seconds`` have passed; collect answers as they resolve, and after
    ``seconds`` for at most ``drain_s`` more.  One thread sends and
    collects, looking for answers every :data:`POLL_S`.  A job's ``sent``
    less its ``due`` is how late the sender ran."""
    jobs: List[Job] = []
    live = []
    due = collections.deque(schedule)
    t0 = time.perf_counter()
    while True:
        now = time.perf_counter() - t0
        while due and due[0].at <= now < seconds:
            req = due.popleft()
            job = Job(req=req, sent=now, due=req.at,
                      job_id=f"{prefix}{len(jobs)}")
            jobs.append(job)
            with _annotate("bench.submit"):
                try:
                    live.append((job, submit(req, job.job_id)))
                except Exception as exc:   # a refused submission is an answer
                    job.finished = time.perf_counter() - t0
                    job.error = repr(exc)
            now = time.perf_counter() - t0
        waiting = []
        for job, handle in live:
            if handle.done():
                _collect(job, handle, t0)
            else:
                waiting.append((job, handle))
        live = waiting
        if now >= seconds and (not live or now >= seconds + drain_s):
            return jobs
        wake = due[0].at - now if due and now < seconds else POLL_S
        time.sleep(min(POLL_S, max(wake, 0.0)))


def open_summary(jobs: List[Job], seconds: float) -> dict:
    """What an open window did: jobs sent and answered, how late the sender
    ran, and latency (median and 95th percentile, over the answered) by
    thirds of the send window, by due time."""
    late = [j.sent - j.due for j in jobs if j.due is not None]
    thirds = []
    for k in range(3):
        part = [j for j in jobs
                if k * seconds / 3 <= (j.due or 0.0) < (k + 1) * seconds / 3]
        lat = [j.latency for j in part if j.latency is not None]
        thirds.append({
            "jobs": len(part), "answered": len(lat),
            "p50_s": float(np.percentile(lat, 50)) if lat else None,
            "p95_s": float(np.percentile(lat, 95)) if lat else None})
    return {"sent": len(jobs),
            "answered": sum(j.latency is not None for j in jobs),
            "late_max_s": max(late, default=0.0),
            "late_mean_s": float(np.mean(late)) if late else 0.0,
            "thirds": thirds}


#: a window is steady where its first third's median latency is at most
#: this many unloaded jobs: an M/D/1 queue at 90% load waits 4.5 service
#: times on average, so a queue the rate can hold reads under 5, and a
#: window that opens on a backlog (a storm of compiles) reads far above
STEADY_FACTOR = 5.0


def sustained(summary: dict, unloaded_s: float) -> bool:
    """Every job answered within the drain; the window opens steady (the
    first third's median latency at most :data:`STEADY_FACTOR` times
    ``unloaded_s``, an unloaded job's); and latency does not grow (the last
    third's median within 1.5 times the first third's)."""
    first, last = summary["thirds"][0], summary["thirds"][2]
    return (summary["answered"] == summary["sent"] and
            first["p50_s"] is not None and last["p50_s"] is not None and
            first["p50_s"] <= STEADY_FACTOR * unloaded_s and
            last["p50_s"] <= 1.5 * first["p50_s"])


WINDOWS = {"closed": closed_window, "open": open_window}


# -- isolated SpMV calls (traced runs) -------------------------------------------

def bench_dsc(fn, x):
    return fn(x)


def bench_wc(fn, x):
    return fn(x)


#: the formats the batched engine chooses among for a job that asks for
#: ``format="auto"`` (``repro.core.batched``: only these stack under vmap)
BATCHED_AUTO_FORMATS = ("coo", "alto")


def served_format(problem, config, cache) -> str:
    """The format the served path ran ``problem`` in under ``config``: the
    configured one, or for ``"auto"`` the plan the batched engine chose,
    which the front end's plan ``cache`` holds once a job on the problem
    ran (resolved as the engine resolves it, so a cached plan is read back
    and no fresh selection runs)."""
    if config.format != "auto":
        return config.format
    from repro.formats import select as fsel
    return fsel.resolve_format(problem.phi, problem, config, cache,
                               allowed=BATCHED_AUTO_FORMATS,
                               mesh_aware=False).format


class Isolated:
    """The executor that subject 0's jobs ran (:func:`served_format`, on the
    mix's mesh), called alone on subject 0 under jits the benchmark names
    (``jit_bench_dsc``, ``jit_bench_wc``), built and compiled after the
    window, so that it reads what the window's jobs chose and leaves them
    nothing in the plan cache."""

    def __init__(self, wl: Workload, config, cache):
        import jax
        import jax.numpy as jnp
        from repro.core.registry import create_for_format
        s = wl.subjects[0]
        rng = traffic.rng(wl.seed, 3)
        self.w = jnp.asarray(rng.uniform(size=s.n_fibers), jnp.float32)
        self.y = jnp.asarray(rng.standard_normal((s.n_voxels, s.b.shape[1])),
                             jnp.float32)
        self.format = served_format(wl.problems[0], config, cache)
        ex = create_for_format(wl.problems[0].phi, wl.problems[0],
                               dataclasses.replace(config, format=self.format))
        self.executor = ex.name
        self.calls = {
            "dsc": (jax.jit(bench_dsc).lower(ex.matvec, self.w).compile(),
                    ex.matvec, self.w),
            "wc": (jax.jit(bench_wc).lower(ex.rmatvec, self.y).compile(),
                   ex.rmatvec, self.y)}
        shape = (s.n_coeffs, s.n_voxels, s.n_fibers, s.n_atoms,
                 s.b.shape[1])
        self.work = {"dsc": work.dsc(*shape), "wc": work.wc(*shape)}
        self.out: Dict[str, object] = {}

    def run(self) -> None:
        for op, (compiled, fn, x) in self.calls.items():
            with _annotate(f"bench.isolated.{op}"):
                for _ in range(ISOLATED_CALLS):
                    self.out[op] = compiled(fn, x).block_until_ready()

    def gaps(self, wl: Workload) -> Dict[str, float]:
        phi = reference.blocked(wl.subjects[0])
        return {
            "dsc_gap": check.rel_max(self.out["dsc"],
                                     reference.dsc(phi, wl.d, self.w)),
            "wc_gap": check.rel_max(self.out["wc"],
                                    reference.wc(phi, wl.d, self.y))}


# -- the check --------------------------------------------------------------------

def checked(wl: Workload, jobs: List[Job]) -> List[Job]:
    """The answers the check compares: all of them, or where the mix names
    ``checked``, that many drawn from the seed."""
    answered = [j for j in jobs if j.finished is not None and j.error is None]
    k = wl.cell.mix.get("checked")
    if k is None or int(k) >= len(answered):
        return answered
    pick = traffic.rng(wl.seed, 4).choice(len(answered), size=int(k),
                                          replace=False)
    return [answered[i] for i in sorted(pick)]


def compare(wl: Workload, jobs: List[Job]
            ) -> Tuple[Dict[str, float], Dict[str, float]]:
    """The checked answers against the reference: one reference solve per
    distinct subject (or bundle), shared by its jobs.  Returns the numbers
    compared and those only shown (``fit_gap``: ``bench/check.py``)."""
    mix = wl.cell.mix
    worst_obj, worst_loss, worst_fit = 0.0, 0.0, 0.0
    ref_cache: Dict[object, tuple] = {}
    ones = np.ones(wl.subjects[0].n_fibers, np.float32)
    w_full_ref = None

    def gaps(s, phi, w, losses, ref_w, ref_losses):
        nonlocal worst_obj, worst_loss, worst_fit
        worst_obj = max(worst_obj, check.obj_gap(
            reference.objective(s, wl.d, w),
            reference.objective(s, wl.d, ref_w)))
        worst_loss = max(worst_loss, check.loss_gap(losses, ref_losses))
        worst_fit = max(worst_fit, check.fit_gap(
            reference.dsc(phi, wl.d, w), reference.dsc(phi, wl.d, ref_w)))

    if "lesion" in mix:
        n = int(mix["lesion"]["warm_start_iters"])
        s = wl.subjects[0]
        phi = reference.blocked(s)
        w_full_ref, l_full_ref = reference.sbbnnls(phi, wl.d, s.b, ones, n)
        w_full_ref = np.asarray(w_full_ref)
        gaps(s, phi, wl.w_full, wl.w_full_losses, w_full_ref, l_full_ref)
    picked = checked(wl, jobs)
    if not picked:                      # no answer shows nothing correct
        worst_obj = float("inf")
    for job in picked:
        key = (job.req.subject, job.req.bundle, job.req.n_iters)
        if key not in ref_cache:
            s, w0 = wl.subjects[job.req.subject], ones
            if job.req.bundle is not None:
                ids = wl.bundles[job.req.bundle]
                s = reference.lesion(s, ids)
                w0 = w_full_ref.copy()
                w0[ids] = 0.0
            phi = reference.blocked(s)
            ref_w, ref_losses = reference.sbbnnls(phi, wl.d, s.b, w0,
                                                  job.req.n_iters)
            ref_cache[key] = (s, phi, np.asarray(ref_w), ref_losses)
        gaps(*ref_cache[key][:2], job.w, job.losses, *ref_cache[key][2:])
    return ({"loss_gap": worst_loss, "obj_gap": worst_obj,
             "missing": float(missing(jobs))}, {"fit_gap": worst_fit})


def missing(jobs: List[Job]) -> int:
    """Jobs that failed, were refused, or were left unanswered when the
    window (an open loop's with its drain) closed."""
    return sum(j.error is not None or j.finished is None for j in jobs)


def peak_bytes(devices) -> Optional[int]:
    """Peak bytes in use on the fullest device (None where the backend
    keeps no statistics)."""
    stats = [d.memory_stats() for d in devices]
    peaks = [s["peak_bytes_in_use"] for s in stats
             if s and "peak_bytes_in_use" in s]
    return max(peaks) if peaks else None


# -- the run -----------------------------------------------------------------------

def run(cell: Cell, seed: int, seconds: float, traced: bool, t_start: float,
        control: bool = False) -> dict:
    """Run the cell once and return its result object (see run.py)."""
    with fresh_plan_cache():
        return _run(cell, seed, seconds, traced, t_start, control)


def _run(cell: Cell, seed: int, seconds: float, traced: bool, t_start: float,
         control: bool) -> dict:
    import jax
    from repro.serve.frontend import LifeFrontend
    from bench import peaks, trace

    devices = jax.devices()[:cell.chips]
    readers = {m["name"]: reader(m["name"])
               for m in (cell.per_layer if traced else cell.metrics)}
    needs_isolated = any(getattr(r, "NEEDS_ISOLATED", False)
                         for r in readers.values())
    wl = Workload(cell, seed, seconds)
    config = life_config(control, cell.mix)
    fe = LifeFrontend(config)
    cache = fe.service.scheduler.cache
    isolated = None
    disable_compile_cache()
    wl.warm_up(fe)
    counter = CompileCounter()
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if traced else None
    setup_s = time.perf_counter() - t_start
    try:
        if traced:
            jax.profiler.start_trace(trace_dir, profiler_options=_trace_options())
        counter.on = True
        with program_tracing(traced), _annotate("bench.window"):
            jobs = WINDOWS[cell.mix["loop"]](fe, wl)
        counter.on = False
        if needs_isolated:
            isolated = Isolated(wl, config, cache)
            isolated.run()
        if traced:
            jax.profiler.stop_trace()
    finally:
        counter.on = False
        counter.close()
        fe.shutdown(drain=False)
    chosen = {}
    if config.format == "auto":
        for j in jobs:
            if j.latency is not None and j.req.bundle is None:
                chosen.setdefault(str(j.req.subject), served_format(
                    wl.problems[j.req.subject], config, cache))
    peak = peak_bytes(devices)
    del fe
    gc.collect()
    enable_compile_cache()

    kind = devices[0].device_kind
    record = Run(cell=cell, seconds=seconds, setup_s=setup_s, jobs=jobs,
                 compiles_in_window=counter.compiles,
                 cache_hits_in_window=counter.cache_hits)
    result_trace = None
    if traced:
        record.peak = peaks.peaks(kind, len(devices))
        tr = trace.load(_xplane(trace_dir))
        record.trace = trace.summary(tr)
        for op in ("dsc", "wc") if isolated is not None else ():
            secs = trace.module_seconds(
                tr, *trace.span(tr, f"bench.isolated.{op}"))
            if secs:
                record.isolated[op] = {"device_s": float(np.median(secs)),
                                       "work": isolated.work[op]}
        result_trace = (trace.breakdown(tr, record.trace["lo"],
                                        record.trace["hi"]),
                        record.trace)
        del tr
        shutil.rmtree(trace_dir, ignore_errors=True)

    readings, shown = compare(wl, jobs)
    if isolated is not None:
        readings.update(isolated.gaps(wl))
    correct, lines = check.verdict(readings, cell.limits)

    metrics = {}
    for m in (cell.per_layer if traced else cell.metrics):
        value = readers[m["name"]].read(record)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    info = {"jobs": len(jobs), "done": len(record.done()),
            "compiles_in_window": counter.compiles,
            "cache_hits_in_window": counter.cache_hits,
            "window_end_s": max((j.finished or 0.0 for j in jobs),
                                default=0.0),
            "memory_peak_bytes": peak, "seed": seed, "shown": shown,
            "n_coeffs": [s.n_coeffs for s in wl.subjects][:8],
            "sent_and_latency_s": [[j.sent, j.latency] for j in jobs]}
    if chosen:
        info["formats"] = chosen
    if isolated is not None:
        info["isolated"] = {"format": isolated.format,
                            "executor": isolated.executor}
    if cell.mix["loop"] == "open":
        info["open"] = open_summary(jobs, seconds)
    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(jax.devices()), "memory_peak_bytes": peak}
    out = {"correct": correct, "attempted": len(jobs),
           "failed": int(readings["missing"]), "metrics": metrics,
           "device": device}
    if result_trace is not None:
        bd, summ = result_trace
        device["busy_s"] = summ["busy_s"]
        device["window_s"] = summ["window_s"]
        out["breakdown"] = bd
    out["checks"] = {k: {"value": v, "limit": cell.limits.get(k)}
                     for k, v in readings.items()}
    return {"result": out, "info": info, "check_lines": lines}


@contextlib.contextmanager
def program_tracing(on: bool):
    """The program's own tracing (``repro.obs``) reset and on inside the
    block where ``on`` (a traced window), untouched otherwise."""
    if not on:
        yield
        return
    from repro import obs
    obs.reset()
    obs.enable()
    try:
        yield
    finally:
        obs.disable()


def _trace_options():
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    return opts


def _xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]
