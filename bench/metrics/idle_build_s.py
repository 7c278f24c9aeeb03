"""Idle device seconds per answer inside the program's ``engine.build``
span and not compiling (bench/spans.py), in the traced window."""
from bench import spans

spans.install()


def read(run):
    return spans.idle_per_answer(run, "build")
