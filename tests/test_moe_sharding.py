"""The MoE layer on a device mesh: the expert FFN runs in a shard_map
(a Pallas kernel is not partitioned by the compiler), split over
``model`` as ``cfg.moe_shard`` lays the experts out, and gives the loss
and gradients of one device.

The mesh needs several devices, and a process's device count is fixed
at its first JAX call, so each case runs this file in a child process
with 8 host devices (a 2 x 4 ``data`` x ``model`` mesh)."""
import os
import subprocess
import sys

import pytest

CASES = {
    # 6 experts of width 96 on 4 model shards: split by ffn column
    "qwen2-moe-a2.7b": "ffn",
    # 4 experts on 4 model shards: one expert each
    "dbrx-132b": "expert",
}


@pytest.mark.parametrize("arch", sorted(CASES))
def test_sharded_moe_matches_one_device(arch):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=8")
    out = subprocess.run([sys.executable, __file__, arch], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stdout + out.stderr
    assert f"ok {arch} {CASES[arch]}" in out.stdout, out.stdout


def _child(arch: str) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs import SMOKES
    from repro.distributed.sharding import named, param_specs
    from repro.models import build_model

    assert len(jax.devices()) == 8
    cfg = SMOKES[arch]
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0), jnp.float32)
    ks = jax.random.split(jax.random.PRNGKey(1), 2)
    # 4 x 64 tokens: more than 128, and each data shard sorts its own
    batch = {k: jax.random.randint(key, (4, 64), 0, cfg.vocab_size)
             for k, key in zip(("tokens", "targets"), ks)}
    grad = jax.jit(jax.value_and_grad(lambda p: model.loss(p, batch)[0]))
    loss1, g1 = grad(params)

    mesh = jax.make_mesh((2, 4), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    specs = param_specs(cfg, jax.eval_shape(lambda: params), mesh)
    moe = specs["layers"]["moe"]
    axis = {"expert": 1, "ffn": 3}[CASES[arch]]
    assert moe["w_gate"][axis] == "model", moe
    placed = jax.device_put(params, named(mesh, specs))
    with mesh:
        text = grad.lower(placed).as_text()
        loss2, g2 = grad(placed)
    assert "shard_map" in text or "manual_computation" in text
    np.testing.assert_allclose(float(loss2), float(loss1), rtol=1e-5)
    # float32 sums in another order across shards: 1e-4 of the largest
    for a, b in zip(jax.tree_util.tree_leaves(g2), jax.tree_util.tree_leaves(g1)):
        scale = float(jnp.max(jnp.abs(b))) or 1.0
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-4 * scale)
    print("ok", arch, CASES[arch])


if __name__ == "__main__":
    _child(sys.argv[1])
