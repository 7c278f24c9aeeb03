"""Paper Table 5: scaling with worker count (threads -> mesh devices).

Runs the 2-D shard_map SBBNNLS on 1/2/4/8-device meshes in this process,
over the devices that exist (one process owns the chip, so topologies
cannot be spread over child processes).  A topology that needs more devices
than exist is skipped with a printed line; one that fails fails the run.
The derived column reports the per-device coefficient share — the quantity
the paper's sync-free mapping balances — alongside the time.
"""
import time

import jax
import jax.numpy as jnp

from benchmarks.common import emit, fitting_meshes, problem
from repro.distributed import life_shard as LS


def run():
    p = problem()
    for n, (R, C), mesh in fitting_meshes("table5"):
        shards = LS.build_life_shards(p.phi, 96, R=R, C=C)
        step = LS.make_sharded_step(mesh, dict(nv_local=shards.nv_local,
                                               nf_local=shards.nf_local,
                                               n_theta=96))
        args = LS.sharded_state(mesh, shards, p)
        jstep = jax.jit(step)
        cells = [args[k] for k in ("da", "dv", "df", "dw",
                                   "wa", "wv", "wf", "ww", "d", "b")]
        w = args["w"]
        for it in range(3):   # warmup/compile
            w, loss = jstep(*cells, w, jnp.asarray(it, jnp.int32))
        loss.block_until_ready()
        t0 = time.perf_counter()
        for it in range(10):
            w, loss = jstep(*cells, w, jnp.asarray(it, jnp.int32))
        loss.block_until_ready()
        us = (time.perf_counter() - t0) / 10 * 1e6
        emit(f"table5.devices{n}", us,
             f"nnz_per_cell={int(shards.dsc_values.shape[-1])}")


if __name__ == "__main__":
    run()
