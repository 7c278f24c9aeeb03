"""Multi-device integration tests.

Run in subprocesses with XLA_FLAGS=--xla_force_host_platform_device_count=8
so the main pytest process keeps its single real device.
"""
import os
import subprocess
import sys
import textwrap

import pytest

pytestmark = pytest.mark.slow        # 8-device subprocesses, fresh compiles

REPO_SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _run(code: str) -> str:
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               PYTHONPATH=REPO_SRC)
    proc = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                          capture_output=True, text=True, env=env,
                          timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return proc.stdout


def test_sharded_sbbnnls_matches_single_device():
    out = _run("""
        import numpy as np, jax, jax.numpy as jnp
        from repro.data.dmri import synth_connectome
        from repro.core.life import LifeEngine, LifeConfig
        from repro.distributed import life_shard as LS

        p = synth_connectome(n_fibers=96, n_theta=16, n_atoms=24,
                             grid=(10,10,10), seed=3)
        from jax.sharding import AxisType
        mesh = jax.make_mesh((4, 2), ("data", "model"),
                             axis_types=(AxisType.Auto,) * 2)
        shards = LS.build_life_shards(p.phi, 16, R=4, C=2)
        step = LS.make_sharded_step(mesh, dict(nv_local=shards.nv_local,
                                               nf_local=shards.nf_local,
                                               n_theta=16))
        args = LS.sharded_state(mesh, shards, p)
        jstep = jax.jit(step)
        w = args["w"]
        with mesh:
            for it in range(10):
                w, loss = jstep(args["da"],args["dv"],args["df"],args["dw"],
                                args["wa"],args["wv"],args["wf"],args["ww"],
                                args["d"], args["b"], w,
                                jnp.asarray(it, jnp.int32))
        w_full = LS.unshard_w(shards, np.asarray(w))
        eng = LifeEngine(p, LifeConfig(executor="opt", n_iters=10))
        w_ref, _ = eng.run()
        np.testing.assert_allclose(w_full, np.asarray(w_ref),
                                   rtol=1e-3, atol=1e-4)
        print("MATCH")
    """)
    assert "MATCH" in out


def test_train_step_on_mesh_and_elastic_restart():
    """Train 3 steps on a (4,2) mesh, checkpoint, restore onto a (2,4) mesh
    (elastic resize), continue — loss trajectory must continue finitely and
    params must be bit-identical after reshard."""
    out = _run("""
        import numpy as np, jax, jax.numpy as jnp, tempfile, dataclasses
        from repro.configs.base import get_config, reduced
        from repro.distributed import sharding as SH, hints
        from repro.launch import steps as ST
        from repro.checkpoint import manager as CK
        from repro.data.tokens import DataConfig, synth_batch_for
        from repro.optim.adamw import OptConfig

        cfg = dataclasses.replace(reduced(get_config("deepseek-7b")),
                                  remat=False)
        opt = OptConfig(lr=3e-3)          # 8 total steps must visibly descend
        data = DataConfig(seed=0, seq_len=32, global_batch=8)

        from jax.sharding import AxisType
        def build(mesh_shape):
            mesh = jax.make_mesh(mesh_shape, ("data", "model"),
                                 axis_types=(AxisType.Auto,) * 2)
            hints.activate(mesh)
            pspecs = lambda tree: SH.logical_to_shardings(
                mesh, SH.param_specs(cfg, mesh, tree))
            return mesh, pspecs

        mesh, mk = build((4, 2))
        params, opt_state = ST.init_all(cfg, opt, jax.random.PRNGKey(0))
        step_fn = jax.jit(ST.make_train_step(cfg, opt))
        losses = []
        with mesh:
            psh = mk(params)
            params = CK.place(params, psh)
            for s in range(3):
                batch = synth_batch_for(cfg, data, s)
                params, opt_state, m = step_fn(params, opt_state, batch)
                losses.append(float(m["loss"]))
        ckdir = tempfile.mkdtemp()
        CK.save(ckdir, 3, {"params": params, "opt": opt_state})

        # elastic restart on a different mesh
        mesh2, mk2 = build((2, 4))
        _, flat, _ = CK.restore(ckdir)
        template = jax.eval_shape(lambda: {"params": params, "opt": opt_state})
        host_tree = CK.unflatten_like(template, flat)
        with mesh2:
            psh2 = mk2(host_tree["params"])
            params2 = CK.place(host_tree["params"], psh2)
            opt2 = jax.tree.map(jnp.asarray, host_tree["opt"])
            # bit-identical across the reshard
            for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(params2)):
                np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
            for s in range(3, 8):
                batch = synth_batch_for(cfg, data, s)
                params2, opt2, m = step_fn(params2, opt2, batch)
                losses.append(float(m["loss"]))
        assert all(np.isfinite(losses)), losses
        assert losses[-1] < losses[0]
        print("ELASTIC_OK", losses)
    """)
    assert "ELASTIC_OK" in out


def test_moe_ep_train_step_on_mesh():
    out = _run("""
        import numpy as np, jax, dataclasses
        from repro.configs.base import get_config, reduced
        from repro.distributed import sharding as SH, hints
        from repro.launch import steps as ST
        from repro.data.tokens import DataConfig, synth_batch_for
        from repro.optim.adamw import OptConfig

        cfg = dataclasses.replace(reduced(get_config("phi3.5-moe-42b-a6.6b")),
                                  remat=False)
        opt = OptConfig(lr=1e-3)
        from jax.sharding import AxisType
        mesh = jax.make_mesh((2, 4), ("data", "model"),
                             axis_types=(AxisType.Auto,) * 2)
        hints.activate(mesh)
        params, opt_state = ST.init_all(cfg, opt, jax.random.PRNGKey(0))
        step_fn = jax.jit(ST.make_train_step(cfg, opt))
        data = DataConfig(seed=0, seq_len=32, global_batch=4)
        with mesh:
            psh = SH.logical_to_shardings(mesh, SH.param_specs(cfg, mesh, params))
            from repro.checkpoint import manager as CK
            params = CK.place(params, psh)
            for s in range(2):
                batch = synth_batch_for(cfg, data, s)
                params, opt_state, m = step_fn(params, opt_state, batch)
                assert np.isfinite(float(m["loss"]))
        print("MOE_EP_OK")
    """)
    assert "MOE_EP_OK" in out


def test_batched_engine_mesh_placement_matches_unplaced():
    """BatchedLifeEngine under a (4, 2) mesh — subjects over `data`, stacked
    Phi slots over `model` — reproduces the unplaced cohort solve."""
    out = _run("""
        import dataclasses
        import numpy as np, jax
        assert len(jax.devices()) == 8
        from repro.core.batched import BatchedLifeEngine
        from repro.core.life import LifeConfig
        from repro.data.dmri import synth_cohort
        cohort = synth_cohort(4, base_seed=10, n_fibers=64, n_theta=16,
                              n_atoms=24, grid=(10, 10, 10))
        base = LifeConfig(executor="opt", n_iters=10, plan_cache_dir="")
        W0, L0 = BatchedLifeEngine(cohort, base).run()
        eng = BatchedLifeEngine(
            cohort, dataclasses.replace(base, shard_rows=4, shard_cols=2))
        assert eng.mesh is not None
        sh = eng.phi_dsc.values.sharding
        assert "data" in str(sh.spec) and "model" in str(sh.spec), sh
        W1, L1 = eng.run()
        np.testing.assert_allclose(np.asarray(W1), np.asarray(W0),
                                   rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(L1, L0, rtol=1e-4)
        print("BATCH_MESH_OK")
    """)
    assert "BATCH_MESH_OK" in out
