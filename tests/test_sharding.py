"""Sharding policy unit tests + an actual 8-device SPMD execution
(subprocess so the host-device-count flag doesn't leak into other tests)."""
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import pytest

from jax.sharding import PartitionSpec as P

from repro.sharding import MeshCtx


def test_dim_axis_divisibility():
    mc = MeshCtx.single_device()
    assert mc.dim_axis(100, "model") is None  # size-1 axis → replicate


def test_spec_drops_nondivisible():
    # fake 4-device mesh via host platform is heavy; use the rule math with
    # a mesh dict stub through MeshCtx on 1 device (extent 1 → None) plus
    # direct unit check of the guard logic
    mc = MeshCtx.single_device()
    spec = mc.spec((10, 7), ("data", "model"))
    assert spec == P(None, None)


SUBPROC = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp
    import numpy as np
    from jax.sharding import PartitionSpec as P
    from repro.configs import get_config
    from repro.models import Model
    from repro.sharding import (MeshCtx, auto_mesh, batch_specs, param_specs,
                                with_specs)
    from repro import trees

    mesh = auto_mesh((2, 4), ("data", "model"))
    mc = MeshCtx(mesh=mesh, batch_axes=("data",))
    cfg = get_config("{arch}").reduced(d_model=256, repeats=2)
    model = Model(cfg, meshctx=mc)
    params = model.init(jax.random.PRNGKey(0))
    pspecs = param_specs(mc, jax.eval_shape(lambda: params), cfg)
    params = jax.device_put(params, jax.tree.map(
        lambda s: jax.NamedSharding(mesh, s), pspecs,
        is_leaf=lambda x: isinstance(x, P)))
    B, S = 8, 64
    tokens = jnp.ones((B, S), jnp.int32)
    batch = dict(tokens=tokens, labels=tokens,
                 mask=jnp.ones((B, S)))
    bspecs = batch_specs(mc, jax.eval_shape(lambda: batch))
    batch = jax.device_put(batch, jax.tree.map(
        lambda s: jax.NamedSharding(mesh, s), bspecs,
        is_leaf=lambda x: isinstance(x, P)))

    @jax.jit
    def loss_fn(p, b):
        return model.lm_loss(p, b)

    with jax.set_mesh(mesh):
        l = loss_fn(params, batch)
    assert np.isfinite(float(l)), l
    # sharded value == single-device value
    mc1 = MeshCtx.single_device()
    model1 = Model(cfg, meshctx=mc1)
    l1 = model1.lm_loss(jax.device_get(params), jax.device_get(batch))
    np.testing.assert_allclose(float(l), float(l1), rtol=2e-4)
    print("SHARDED_OK", float(l))
""")


@pytest.mark.multidevice
@pytest.mark.slow
@pytest.mark.parametrize("arch", ["tinyllama-1.1b", "dbrx-132b",
                                  "mamba2-1.3b"])
def test_sharded_execution_matches_single_device(arch):
    """Run a real 8-device SPMD forward/loss and compare numerics against
    the single-device model — catches wrong psum/partial-softmax wiring."""
    import os
    code = SUBPROC.format(arch=arch)
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, timeout=1800,
                          env={**os.environ, "PYTHONPATH": "src"})
    assert "SHARDED_OK" in proc.stdout, proc.stderr[-3000:]


def test_param_specs_expert_sharding():
    import numpy as np
    from repro.configs import get_config
    from repro.sharding import param_specs
    from repro import trees as T

    cfg = get_config("dbrx-132b")
    mc = MeshCtx.single_device()  # axes size 1 → everything None, but rule
    shapes = {"stages": [{"layers": [{"ff": {
        "wg": jax.ShapeDtypeStruct((40, 16, 6144, 10752), jnp.bfloat16),
        "router": jax.ShapeDtypeStruct((6144, 16), jnp.float32)}}]}]}
    specs = param_specs(mc, shapes, cfg)
    # on a 1-device mesh all axes drop — just verify structure is preserved
    flat = T.flatten(specs)
    assert "stages/0/layers/0/ff/wg" in flat
