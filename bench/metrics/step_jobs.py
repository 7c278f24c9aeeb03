"""Mean number of jobs an ``engine.step`` advanced in the traced window
(the ``jobs`` of the program's spans, bench/spans.py): how much the
scheduler's continuous batching shares each solver call."""
from bench import spans

spans.install()


def read(run):
    return spans.step_jobs()
