"""Idle device seconds per answer inside the program's ``service.submit``
or ``lesion.edit`` span, neither compiling nor building an engine
(bench/spans.py), in the traced window."""
from bench import spans

spans.install()


def read(run):
    return spans.idle_per_answer(run, "intake")
