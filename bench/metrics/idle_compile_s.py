"""Idle device seconds per answer while the host lowered or compiled a
program (JAX's lowering and compile events, and the ``PjitFunction``
calls that hold them: bench/spans.py), in the traced window."""
from bench import spans

spans.install()


def read(run):
    return spans.idle_per_answer(run, "compile")
