"""shard_map all-to-all MoE: correctness on a real multi-device mesh.

Runs in a subprocess because the 8-device host override must be set
before jax initializes (the main pytest process keeps 1 device).
"""
import os
import subprocess
import sys
import textwrap

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import sys; sys.path.insert(0, "src")
    import jax, jax.numpy as jnp
    from repro.models.moe import moe_init, moe_apply
    from repro.parallel import sharding as shd

    mesh = jax.make_mesh((2, 4), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    shd.set_activation_mesh(mesh)
    key = jax.random.PRNGKey(0)

    # E = 8 = 2*4 (full expert axes) and E = 4 (model-only)
    for e, shared in ((8, 1), (4, 0)):
        p = moe_init(key, 32, e, 64, shared, 48, jnp.float32)
        x = jax.random.normal(jax.random.PRNGKey(1), (4, 8, 32))
        with jax.set_mesh(mesh):
            oa, _ = jax.jit(lambda p, x: moe_apply(
                p, x, top_k=2, capacity_factor=16.0, dispatch="a2a"))(p, x)
        od, _ = moe_apply(p, x, top_k=2, capacity_factor=16.0, dispatch="dense")
        err = float(jnp.abs(oa - od).max())
        assert err < 1e-4, (e, err)

        # The loss touches BOTH outputs, so the gradient flows through the
        # aux pmean as well as the token exchange.
        def loss(p):
            out, aux = jax.jit(lambda p, x: moe_apply(
                p, x, top_k=2, capacity_factor=16.0, dispatch="a2a"))(p, x)
            return jnp.sum(out ** 2) + 0.0 * aux
        with jax.set_mesh(mesh):
            g = jax.grad(loss)(p)
        assert all(bool(jnp.isfinite(v).all()) for v in jax.tree.leaves(g)), e
    print("A2A_OK")
""")


def test_a2a_matches_dense_on_8_device_mesh():
    proc = subprocess.run(
        [sys.executable, "-c", SCRIPT],
        capture_output=True, text=True, timeout=600,
        cwd=os.path.join(os.path.dirname(__file__), ".."),
        env=dict(os.environ, PYTHONPATH="src"),
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "A2A_OK" in proc.stdout
