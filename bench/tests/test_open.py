"""The open loop: its schedule from the seed, latency from the due time,
and the accounting of missing, refused and cut-off jobs."""
import json
import time

import numpy as np
import pytest

from bench import harness, traffic

MIX = {"loop": "open", "rate_per_s": 40.0, "tenants_zipf": 1.1,
       "subjects": 32, "warmup_subjects": 4, "warmup_s": 10.0,
       "n_iters": 50, "checked": 8, "drain_s": 30.0}
SEED = 2 ** 31 + 4099


def test_schedule_repeats_per_seed_and_differs_between_seeds():
    a = traffic.open_requests(MIX, SEED, 51.0)
    b = traffic.open_requests(MIX, SEED, 51.0)
    c = traffic.open_requests(MIX, SEED + 1, 51.0)
    assert a == b
    assert [r.at for r in a] != [r.at for r in c]
    assert [r.subject for r in a] != [r.subject for r in c]
    assert all(r.n_iters == 50 and r.bundle is None for r in a)
    warm = traffic.open_requests(MIX, SEED, 10.0, warm_up=True)
    assert {r.subject for r in warm} <= set(range(32, 36))
    assert {r.subject for r in a} <= set(range(32))


@pytest.mark.parametrize("seed", [SEED, 7, 2 ** 33 + 5])
def test_every_seed_sends_the_same_load(seed):
    """The same number of requests and the same total of gaps for every
    seed; the gaps' mean is the rate's, and they spread as exponential
    gaps do (standard deviation about the mean)."""
    rate, seconds = MIX["rate_per_s"], 51.0
    times = traffic.arrivals(rate, seconds, traffic.rng(seed, 5))
    assert times.size == round(rate * seconds)
    assert np.all(np.diff(times) > 0) and 0 < times[0] and times[-1] < seconds
    gaps = np.diff(np.concatenate([[0.0], times]))
    assert gaps.mean() == pytest.approx(1 / rate, rel=0.02)
    assert gaps.std() == pytest.approx(1 / rate, rel=0.15)
    ref = traffic.arrivals(rate, seconds, traffic.rng(SEED, 5))
    assert np.sort(np.diff(np.concatenate([[0.0], ref]))) == pytest.approx(
        np.sort(gaps))


def test_zipf_shares_within_sampling_error():
    n, k, s = 20000, 32, 1.1
    draws = traffic.zipf_subjects(n, k, s, traffic.rng(SEED, 6))
    p = (np.arange(k) + 1.0) ** -s
    p /= p.sum()
    share = np.bincount(draws, minlength=k) / n
    sigma = np.sqrt(p * (1 - p) / n)
    assert np.all(np.abs(share - p) < 5 * sigma)
    assert share[0] > share[1] > share[4] > share[16]


@pytest.mark.parametrize("bad", [
    {"rate_per_s": 0}, {"rate_per_s": None}, {"drain_s": -1},
    {"drain_s": 0},
    {"tenants_zipf": -0.5}, {"warmup_subjects": 0},
    {"lesion": {"bundle_fibers": 5, "bundles": 1, "warm_start_iters": 1}},
    {"loop": "poisson"}])
def test_load_refuses_a_bad_open_mix(tmp_path, bad):
    mix = {k: v for k, v in dict(MIX, **bad).items() if v is not None}
    path = tmp_path / "mix.json"
    path.write_text(json.dumps(mix))
    with pytest.raises(ValueError):
        traffic.load(path)


def test_load_accepts_open_mixes(tmp_path):
    path = tmp_path / "mix.json"
    path.write_text(json.dumps(dict(MIX, tenants_zipf=0)))
    assert traffic.load(path)["loop"] == "open"


class Handle:
    """A handle that resolves at a time, with an answer or a failure, or
    never (``at`` None)."""

    def __init__(self, at, error=None):
        self.at, self.error = at, error

    def done(self):
        return self.at is not None and time.perf_counter() >= self.at

    def result(self, timeout=None):
        if self.error is not None:
            raise self.error
        return np.zeros(3), np.zeros(2)


def _schedule(*ats):
    return [traffic.Request(subject=0, n_iters=1, at=a) for a in ats]


def test_latency_counts_from_the_due_time():
    """A sender held back charges the delay to the jobs it held: the
    second job, due at 0.05 s, goes out at about 0.3 s, after the first
    submission's sleep, and its latency counts from 0.05 s."""
    sent = []

    def submit(req, job_id):
        sent.append(job_id)
        if job_id == "0":
            time.sleep(0.3)
        return Handle(time.perf_counter() + 0.1)

    jobs = harness.open_loop(submit, _schedule(0.0, 0.05, 0.6), 1.0, 5.0)
    assert sent == ["0", "1", "2"]
    first, held, free = jobs
    assert held.due == 0.05 and held.sent - held.due > 0.2
    assert held.latency == pytest.approx(held.finished - 0.05)
    assert held.latency > 0.3
    assert free.sent - free.due < 0.05 and free.latency < 0.2
    summary = harness.open_summary(jobs, 1.0)
    assert summary["late_max_s"] == pytest.approx(held.sent - held.due)
    assert summary["answered"] == 3 and summary["sent"] == 3


def test_sending_stops_at_the_window_and_drain_waits():
    def submit(req, job_id):
        return Handle(time.perf_counter() + 0.4)

    t0 = time.perf_counter()
    jobs = harness.open_loop(submit, _schedule(0.0, 0.1, 0.35, 0.5), 0.3,
                             2.0)
    assert [j.job_id for j in jobs] == ["0", "1"]     # 0.35, 0.5 unsent
    assert all(j.latency is not None for j in jobs)   # drained
    assert time.perf_counter() - t0 < 1.0             # not the whole drain


def test_missing_counts_failed_refused_and_undrained():
    """A failed job, a refused submission and a job still unanswered when
    the drain ends are all missing."""
    handles = {"0": Handle(0.0, error=RuntimeError("solver failed")),
               "2": Handle(None),
               "3": Handle(0.0)}

    def submit(req, job_id):
        if job_id == "1":
            raise RuntimeError("admission queue full")
        return handles[job_id]

    jobs = harness.open_loop(submit, _schedule(0.0, 0.01, 0.02, 0.03), 0.1,
                             0.2)
    assert [j.latency is None for j in jobs] == [True, True, True, False]
    assert "solver failed" in jobs[0].error
    assert "admission" in jobs[1].error
    assert jobs[2].finished is None and jobs[2].error is None
    assert harness.missing(jobs) == 3


def test_sustained_reads_the_thirds():
    def jobs(latencies):
        out = []
        for i, lat in enumerate(latencies):
            due = 3.0 * i / len(latencies)
            out.append(harness.Job(req=None, sent=due, due=due,
                                   finished=None if lat is None
                                   else due + lat))
        return out

    steady = harness.open_summary(jobs([1.0, 1.1, 1.2, 1.0, 1.3, 1.1]), 3.0)
    growing = harness.open_summary(jobs([1.0, 1.1, 1.2, 1.6, 1.8, 2.0]),
                                   3.0)
    lost = harness.open_summary(jobs([1.0, 1.1, 1.2, 1.0, None, 1.1]), 3.0)
    # a window that opens on a backlog and then drains it: latency falls,
    # but the first third reads far above an unloaded job
    storm = harness.open_summary(jobs([13.0, 11.0, 9.0, 5.0, 1.2, 1.0]),
                                 3.0)
    assert [t["jobs"] for t in steady["thirds"]] == [2, 2, 2]
    assert harness.sustained(steady, 0.5)
    assert not harness.sustained(growing, 0.5)
    assert not harness.sustained(lost, 0.5)
    assert not harness.sustained(storm, 0.5)
    # the same steady window is a backlog against a faster unloaded job
    assert not harness.sustained(steady, 0.2)
