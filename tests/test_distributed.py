"""Multi-device (8 forced host CPUs, subprocess) pjit/shard_map tests.

Each test spawns a fresh interpreter with XLA_FLAGS so the main pytest
process keeps its single real device (the assignment's constraint).
"""
import os
import subprocess
import sys
import textwrap

import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _run(body: str, timeout=560):
    code = "import os\nos.environ['XLA_FLAGS'] = '--xla_force_host_platform_device_count=8'\n"
    code += "import sys\nsys.path.insert(0, %r)\n" % SRC
    code += textwrap.dedent(body)
    p = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=timeout)
    assert p.returncode == 0, f"STDOUT:\n{p.stdout}\nSTDERR:\n{p.stderr[-3000:]}"
    return p.stdout


@pytest.mark.slow
def test_pjit_train_step_matches_single_device():
    _run("""
    import jax, jax.numpy as jnp, numpy as np
    from repro.models import registry, lm
    from repro.nn.module import materialize
    from repro.launch import specs, steps
    from repro.launch.mesh import make_mesh, param_pspecs, sharding_rules
    from repro.optim.adamw import AdamWConfig, adamw_init
    from repro.configs.base import ShapeConfig
    from jax.sharding import NamedSharding, PartitionSpec as P

    cfg = registry.get_smoke("phi4-mini-3.8b")
    params = materialize(lm.param_spec(cfg), jax.random.PRNGKey(0))
    opt_cfg = AdamWConfig(moment_dtype="float32")
    opt = adamw_init(params, opt_cfg)
    shape = ShapeConfig("t", 32, 8, "train")
    batch = specs.concrete_batch(cfg, shape, 0, 0)
    step = steps.make_train_step(cfg, opt_cfg)

    # single device
    p1, o1, m1 = jax.jit(step)(params, opt, batch)

    # 4x2 mesh with full sharding rules
    mesh = make_mesh((4, 2), ("data", "model"))
    rules = sharding_rules(mesh, "train")
    pps = param_pspecs(lm.param_spec(cfg), rules, mesh)
    psh = jax.tree.map(lambda s: NamedSharding(mesh, s), pps,
                       is_leaf=lambda x: isinstance(x, P))
    osh = steps.optimizer_pspecs(pps, opt_cfg)
    osh = jax.tree.map(lambda s: NamedSharding(mesh, s), osh,
                       is_leaf=lambda x: isinstance(x, P))
    bsh = jax.tree.map(lambda _: NamedSharding(mesh, P(("data",))), batch)
    params_s = jax.device_put(params, psh)
    opt_s = jax.device_put(opt, osh)
    batch_s = jax.device_put(batch, bsh)
    from repro.nn.pcontext import logical_sharding
    with mesh, logical_sharding(mesh, rules):
        p2, o2, m2 = jax.jit(step, in_shardings=(psh, osh, bsh),
                             out_shardings=(psh, osh, None))(params_s, opt_s, batch_s)
    np.testing.assert_allclose(float(m1["loss"]), float(m2["loss"]), rtol=2e-3)
    # params identical up to collective reduction order
    for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)):
        np.testing.assert_allclose(np.asarray(a, np.float32), np.asarray(b, np.float32),
                                   atol=3e-2)
    print("pjit parity OK", float(m1["loss"]), float(m2["loss"]))
    """)


@pytest.mark.slow
def test_dp_compressed_training_converges():
    _run("""
    import jax, jax.numpy as jnp, numpy as np
    from repro.models import registry
    from repro.launch.train import train_loop
    from repro.launch.mesh import make_mesh
    from repro.optim.adamw import AdamWConfig
    import tempfile

    cfg = registry.get_smoke("phi4-mini-3.8b")
    mesh = make_mesh((8,), ("data",))
    with tempfile.TemporaryDirectory() as d:
        _, losses = train_loop(cfg, steps=20, batch=8, seq=64, ckpt_dir=d,
                               grad_compress=True, mesh=mesh,
                               opt_cfg=AdamWConfig(moment_dtype="float32"),
                               base_lr=1e-3)
    assert losses[-1] < losses[0], (losses[0], losses[-1])
    print("compressed DP OK", losses[0], "->", losses[-1])
    """)


@pytest.mark.slow
def test_compressed_psum_in_hlo():
    """The int8 payload must actually appear in the compiled collective."""
    _run("""
    import jax, jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from repro.launch.mesh import make_mesh
    from repro.optim.compress import compressed_psum

    mesh = make_mesh((8,), ("data",))
    def f(g, k):
        return compressed_psum(g, ("data",), k)
    sm = jax.shard_map(f, mesh=mesh, in_specs=(P("data"), P()),
                       out_specs=P("data"), check_vma=False)
    g = jnp.zeros((8, 1, 4096), jnp.float32)
    k = jax.random.PRNGKey(0)
    hlo = jax.jit(sm).lower(g, k).compile().as_text()
    assert "all-reduce" in hlo
    assert "s32[" in hlo  # widened int payload visible in the reduction
    print("compressed psum HLO OK")
    """)


@pytest.mark.slow
def test_elastic_restore_across_meshes():
    """Checkpoint saved unsharded restores onto a (2,2,2) pod mesh."""
    _run("""
    import jax, jax.numpy as jnp, numpy as np, tempfile
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.checkpoint import ckpt
    from repro.launch.mesh import make_mesh

    tree = {"w": jnp.arange(64.0).reshape(8, 8), "step": jnp.int32(5)}
    with tempfile.TemporaryDirectory() as d:
        ckpt.save(d, 5, tree)
        tpl = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), tree)
        mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
        sh = {"w": NamedSharding(mesh, P(("pod", "data"), "model")),
              "step": NamedSharding(mesh, P())}
        out, step = ckpt.restore(d, 5, tpl, shardings=sh)
        assert out["w"].sharding.spec == P(("pod", "data"), "model")
        np.testing.assert_array_equal(np.asarray(out["w"]), np.arange(64.0).reshape(8, 8))
    print("elastic restore OK")
    """)


@pytest.mark.slow
def test_dryrun_smoke_cell_on_8_devices():
    """The dry-run machinery itself on a small mesh (fast compile)."""
    _run("""
    import jax
    from repro.launch.mesh import make_mesh
    from repro.launch.dryrun import run_cell
    mesh = make_mesh((2, 2), ("data", "model"))
    rec = run_cell("xlstm-350m", "train_4k", mesh=mesh, smoke=True)
    assert rec["status"] == "OK", rec
    assert rec["cost"]["flops"] > 0
    assert rec["memory"]["total_bytes"] > 0
    print("dryrun smoke OK", rec["roofline"]["bottleneck"])
    """)
