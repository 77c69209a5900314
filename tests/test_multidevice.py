"""Multi-device behaviour tests (8 forced host devices in a subprocess so
the main test process keeps its single-device view)."""
import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(code: str):
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=8")
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    proc = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                          capture_output=True, text=True, env=env,
                          timeout=900)
    assert proc.returncode == 0, proc.stderr[-4000:]
    return proc.stdout


def test_sharded_train_step_matches_single_device():
    """The 2×4 (data×model) pjit'd train step must produce the same loss
    trajectory as unsharded execution — sharding is semantics-free."""
    out = _run("""
    import jax, jax.numpy as jnp, numpy as np, dataclasses
    from repro.configs import get_config
    from repro.distributed import sharding as sh
    from repro.launch.mesh import make_host_mesh
    from repro.models import model as M
    from repro.optim.optimizer import AdamWConfig, init_opt_state
    from repro.training.trainer import make_train_step

    cfg = get_config('gemma_2b').reduced()
    cfg = dataclasses.replace(cfg, n_layers=2, d_model=64, d_ff=128,
                              vocab=256, n_heads=4, n_kv_heads=1, head_dim=16)
    key = jax.random.PRNGKey(0)
    batch = {'tokens': jax.random.randint(key, (8, 32), 0, cfg.vocab)}
    opt_cfg = AdamWConfig(lr=1e-3)
    step = make_train_step(cfg, opt_cfg)

    # single device
    params = M.init_params(key, cfg)
    opt = init_opt_state(params)
    _, _, m1 = jax.jit(step)(params, opt, batch)

    # 2x4 mesh, full sharding stack
    mesh = make_host_mesh(2, 4)
    with jax.set_mesh(mesh):
        pshape = jax.eval_shape(lambda: M.init_params(key, cfg))
        pspec = sh.param_specs(cfg, pshape, mesh)
        pshard = sh.named_shardings(mesh, pspec)
        params2 = jax.jit(lambda k: M.init_params(k, cfg),
                          out_shardings=pshard)(key)
        opt2 = jax.jit(init_opt_state)(params2)
        bshard = sh.named_shardings(mesh, sh.batch_specs(mesh, batch))
        batch2 = jax.device_put(batch, bshard)
        _, _, m2 = jax.jit(step)(params2, opt2, batch2)

    l1, l2 = float(m1['loss']), float(m2['loss'])
    # f32 reduction order differs between the sharded and unsharded
    # graphs (GSPMD reduce-scatter vs single-device sums); observed
    # drift on jax 0.4.x CPU is ~7e-3 at loss ~5.5, so bound at 1e-2 —
    # still catches real semantic divergence (>0.2%), not bitwise.
    assert abs(l1 - l2) < 1e-2, (l1, l2)
    print('OK', l1, l2)
    """)
    assert "OK" in out


def test_moe_a2a_matches_scatter_path():
    """The explicit all-to-all expert-parallel MoE must agree with the
    GSPMD scatter path (ample capacity, 4-way EP)."""
    out = _run("""
    import jax, jax.numpy as jnp, numpy as np, dataclasses
    from repro.configs import get_config
    from repro.launch.mesh import make_host_mesh
    from repro.models import moe as moe_mod

    cfg = get_config('qwen3_moe_235b').reduced()
    cfg = dataclasses.replace(
        cfg, moe=dataclasses.replace(cfg.moe, capacity_factor=8.0))
    key = jax.random.PRNGKey(0)
    p = moe_mod.init_moe(key, cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 16, cfg.d_model)) * 0.3

    want, aux1 = moe_mod.apply_moe(x, p, cfg)

    mesh = make_host_mesh(2, 4)
    with jax.set_mesh(mesh):
        got, aux2 = jax.jit(
            lambda x, p: moe_mod.apply_moe_a2a(x, p, cfg, mesh=mesh,
                                               token_axes=('data',)))(x, p)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)
    print('OK')
    """)
    assert "OK" in out


def test_elastic_restore_reshards():
    """Checkpoint saved from a 1×8 mesh restores onto a 4×2 mesh (device
    loss / elastic rescale) with identical values."""
    out = _run("""
    import jax, jax.numpy as jnp, numpy as np, tempfile, dataclasses
    from repro.checkpoint.manager import CheckpointManager
    from repro.configs import get_config
    from repro.distributed import sharding as sh
    from repro.launch.mesh import make_host_mesh
    from repro.models import model as M
    from repro.optim.optimizer import init_opt_state

    cfg = get_config('gemma_2b').reduced()
    cfg = dataclasses.replace(cfg, n_layers=2, d_model=64, d_ff=128,
                              vocab=256, n_heads=4, n_kv_heads=1, head_dim=16)
    key = jax.random.PRNGKey(0)
    tmp = tempfile.mkdtemp()

    mesh1 = make_host_mesh(1, 8)
    with jax.set_mesh(mesh1):
        pshape = jax.eval_shape(lambda: M.init_params(key, cfg))
        shard1 = sh.named_shardings(mesh1, sh.param_specs(cfg, pshape, mesh1))
        params = jax.jit(lambda k: M.init_params(k, cfg),
                         out_shardings=shard1)(key)
        opt = jax.jit(init_opt_state)(params)
        mgr = CheckpointManager(tmp)
        mgr.save(5, params, opt)

    mesh2 = make_host_mesh(4, 2)
    with jax.set_mesh(mesh2):
        oshape = jax.eval_shape(init_opt_state, pshape)
        shard2p = sh.named_shardings(mesh2, sh.param_specs(cfg, pshape, mesh2))
        shard2o = {'m': shard2p, 'v': shard2p,
                   'step': jax.sharding.NamedSharding(
                       mesh2, jax.sharding.PartitionSpec())}
        p2, o2, man = CheckpointManager(tmp).restore(
            None, (pshape, oshape), (shard2p, shard2o))
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(p2)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert man['step'] == 5
    print('OK')
    """)
    assert "OK" in out


def test_multipod_mesh_axes():
    out = _run("""
    import jax
    from repro.launch.mesh import make_elastic_mesh
    mesh = make_elastic_mesh(8, model_parallel=4)
    assert dict(zip(mesh.axis_names, mesh.devices.shape)) == \
        {'data': 2, 'model': 4}
    mesh2 = make_elastic_mesh(6, model_parallel=4)  # degraded fleet
    assert mesh2.devices.size == 6
    print('OK')
    """)
    assert "OK" in out


def test_train_loop_shards_optimizer_state():
    """Adam's moments are laid out like the parameters they track: on a
    2×4 mesh no moment of the embedding table sits whole on one device
    (at gemma-2b width a replicated moment does not fit a chip)."""
    out = _run("""
    import jax, dataclasses
    from repro.configs import get_config
    from repro.launch import train
    from repro.launch.mesh import make_host_mesh

    cfg = get_config('gemma_2b').reduced()
    cfg = dataclasses.replace(cfg, n_layers=2, d_model=64, d_ff=128,
                              vocab=256, n_heads=4, n_kv_heads=1, head_dim=16)
    table = (cfg.vocab, cfg.d_model)
    seen = []
    make_step = train.make_train_step

    def recording_step(*args):
        step = make_step(*args)

        def traced(params, opt_state, batch):
            # Traced on the first call, while the initial parameters and
            # both moments are live.
            seen.extend(a.sharding for a in jax.live_arrays()
                        if a.shape == table)
            return step(params, opt_state, batch)
        return traced

    train.make_train_step = recording_step
    train.train_loop(cfg, steps=1, batch=2, seq=16,
                     mesh=make_host_mesh(2, 4), log=lambda msg: None)
    assert len(seen) == 3, seen
    assert not any(s.is_fully_replicated for s in seen), seen
    print('OK')
    """)
    assert "OK" in out
