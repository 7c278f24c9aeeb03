"""SBBNNLS — Subspace Barzilai-Borwein non-negative least squares.

Algorithm 1 of the paper (Kim, Sra & Dhillon 2013), the optimizer that LiFE
runs for 500+ iterations and whose two SpMV ops (DSC: ``M w``; WC: ``M^T y``)
this framework optimizes.  The solver is written against abstract
``matvec``/``rmatvec`` closures so the same loop runs on:

  * the naive executors               (CPU-naive analogue)
  * the restructured executors        (CPU/GPU-opt analogue)
  * Pallas kernel executors           (TPU target)
  * shard_map 2-D mesh executors      (multi-pod)

Per average iteration the loop issues 2 x matvec and 1.5 x rmatvec, matching
the paper's accounting (§2.2).
"""
from __future__ import annotations

from functools import partial
from typing import Callable, NamedTuple, Tuple

import jax
import jax.numpy as jnp
from jax.tree_util import Partial

Array = jax.Array
# fp32 dot products at full precision: the TPU default rounds operands to bf16
HIGHEST = jax.lax.Precision.HIGHEST
MatVec = Callable[[Array], Array]


class SbbnnlsState(NamedTuple):
    w: Array          # current weights (Nf,), nonnegative
    it: Array         # iteration counter (int32)
    loss: Array       # 0.5 * ||Mw - b||^2 at last step


def projected_gradient(w: Array, g: Array) -> Array:
    """Subspace projection: zero the gradient on the active set.

    Components with w == 0 and g > 0 would push w negative; they are frozen
    (the paper's "gradient projected to the positive space").
    """
    return jnp.where((w > 0) | (g < 0), g, 0.0)


def sbbnnls_step(matvec: MatVec, rmatvec: MatVec, b: Array,
                 state: SbbnnlsState) -> SbbnnlsState:
    """One SBBNNLS iteration (Algorithm 1)."""
    w, it = state.w, state.it
    y = matvec(w) - b                       # DSC (+ residual)
    g = rmatvec(y)                          # WC
    gt = projected_gradient(w, g)
    v = matvec(gt)                          # DSC

    def odd_alpha(_):
        return _safe_div(_dot(gt, gt), _dot(v, v))

    def even_alpha(_):
        vv = rmatvec(v)                     # WC (every other iteration)
        vv = projected_gradient(w, vv)
        return _safe_div(_dot(v, v), _dot(vv, vv))

    alpha = jax.lax.cond(it % 2 == 1, odd_alpha, even_alpha, operand=None)
    w_new = jnp.maximum(w - alpha * gt, 0.0)
    loss = 0.5 * _dot(y, y)
    return SbbnnlsState(w=w_new, it=it + 1, loss=loss)


def _dot(a: Array, b: Array) -> Array:
    return jnp.vdot(a.reshape(-1), b.reshape(-1), precision=HIGHEST)


def _safe_div(num: Array, den: Array) -> Array:
    return jnp.where(den > 0, num / jnp.where(den > 0, den, 1.0), 0.0)


def sbbnnls_init(w0: Array) -> SbbnnlsState:
    """Fresh solver state at iteration 0 (the stepped-API entry point)."""
    return SbbnnlsState(w=w0, it=jnp.asarray(0, jnp.int32),
                        loss=jnp.asarray(0.0, w0.dtype))


def sbbnnls_steps(matvec: MatVec, rmatvec: MatVec, b: Array,
                  state: SbbnnlsState, n_iters: int
                  ) -> Tuple[SbbnnlsState, Array]:
    """Advance an existing state by n_iters iterations (state in -> k iters
    -> state out).  Because ``state.it`` rides along, the Barzilai-Borwein
    odd/even alternation continues where it left off: composing
    ``k x (n/k)`` calls is exactly one ``n``-iteration run, which is what
    makes time-sliced and checkpoint-resumed solves bit-compatible with
    uninterrupted ones (serve/ relies on this).

    ``matvec``/``rmatvec`` that are :class:`jax.tree_util.Partial` objects
    (every registry executor's are) pass their bound arrays — Phi, tile
    plans, the dictionary — as arguments of the compiled program, so they
    keep their device placement and are not baked into it as constants.
    Any other callable is compiled as a static function."""
    return _steps(_as_partial(matvec), _as_partial(rmatvec), b, state,
                  n_iters)


def _as_partial(fn: MatVec) -> Partial:
    return fn if isinstance(fn, Partial) else Partial(fn)


@partial(jax.jit, static_argnames=("n_iters",))
def _steps(matvec: Partial, rmatvec: Partial, b: Array, state: SbbnnlsState,
           n_iters: int) -> Tuple[SbbnnlsState, Array]:
    def body(s, _):
        new = sbbnnls_step(matvec, rmatvec, b, s)
        return new, new.loss

    final, losses = jax.lax.scan(body, state, xs=None, length=n_iters)
    return final, losses


def sbbnnls_run(matvec: MatVec, rmatvec: MatVec, b: Array, w0: Array,
                n_iters: int) -> Tuple[SbbnnlsState, Array]:
    """Run n_iters iterations under lax.scan; returns (final state, losses)."""
    return sbbnnls_steps(matvec, rmatvec, b, sbbnnls_init(w0), n_iters)


def nnls_loss(matvec: MatVec, b: Array, w: Array) -> Array:
    r = matvec(w) - b
    return 0.5 * _dot(r, r)
