"""Device seconds per answer of the ops under the solver's
``sbbnnls.wc`` scope (their ``tf_op``), in the traced window
(bench/spans.py)."""
from bench import spans

spans.install()


def read(run):
    return spans.scope_per_answer(run, "wc")
