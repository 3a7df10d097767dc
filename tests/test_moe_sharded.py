"""shard_map expert-parallel dispatch == local scatter dispatch.

Needs >1 device, so it runs in a subprocess with forced host devices (tests
themselves must keep the 1-device view; see conftest)."""
import os
import subprocess
import sys
import textwrap


_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, numpy as np
    from repro.configs.registry import ARCHS
    from repro.models import moe, model_zoo

    cfg = ARCHS["deepseek-moe-16b"].reduced()      # 4 experts, top-2
    mesh = jax.make_mesh((2, 4), ("data", "model"))
    rng = jax.random.PRNGKey(0)
    params = model_zoo.init_params(rng, cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 8, cfg.d_model))
    lp = jax.tree.map(lambda a: a[0], params["layers"])   # layer 0

    nodrop = float(cfg.num_experts / cfg.experts_per_token)
    y_local, aux_local = jax.jit(
        lambda lp, x: moe.moe_ffn(lp, cfg, x, nodrop))(lp, x)
    with jax.set_mesh(mesh):
        y_shard, aux_shard = jax.jit(
            lambda lp, x: moe.moe_ffn_sharded(lp, cfg, x, nodrop, mesh))(lp, x)
    np.testing.assert_allclose(np.asarray(y_local), np.asarray(y_shard),
                               rtol=2e-4, atol=2e-4)
    # aux differs by design: per-data-shard load-balance stats averaged via
    # pmean vs one global statistic (Jensen gap) — still O(1) and same scale
    np.testing.assert_allclose(float(aux_local), float(aux_shard),
                               rtol=0.05, atol=0.05)
    print("MOE_SHARDED_OK")
""")


def test_moe_sharded_matches_local():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    out = subprocess.run([sys.executable, "-c", _SCRIPT], env=env,
                         capture_output=True, text=True, timeout=420)
    assert "MOE_SHARDED_OK" in out.stdout, out.stdout + out.stderr
