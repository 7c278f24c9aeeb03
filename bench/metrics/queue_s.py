"""Median over the open window's jobs of the seconds from a job's due time
to the first ``engine.step`` whose ``jobs`` names it (the program's spans,
bench/spans.py), in the traced window: time in the front end's and the
scheduler's queues, and in the engine build before its first step."""
from bench import spans

spans.install()


def read(run):
    return spans.queue_seconds(run)
