"""Sharded cohort engine tests.

Fast tier-1 part: the ``shard_map``/psum round on a 1-device ("pod","data")
mesh must match the unsharded engine (the collective math collapses to the
single-device math), ghost clients (zero aggregation weight) must be
invariant for the real clients, and the psum'd masked aggregation must
reproduce the plain stacked operator including all-outage keep-global.

Multi-device part (marked ``multidevice``/``slow``, subprocess so the
forced host-device-count flag doesn't leak): one fused PFTT and PFIT round
under ``shard_map`` spanning 8 host-platform devices, parity against the
single-device engine — including non-divisible (ghost-padded) cohorts and
forced all-outage rounds."""
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro import trees
from repro.core.aggregation import masked_fedavg_stacked
from repro.core.cohort import build_supervised_round
from repro.optim import sgd
from repro.sharding import (CohortSharding, auto_mesh, client_shard_axes,
                            cohort_sharding)


# ---------------------------------------------------------------------------
# cohort sharding policy (pure math)
# ---------------------------------------------------------------------------


def _mesh11():
    return auto_mesh((1, 1), ("pod", "data"))


def test_client_shard_axes_excludes_model():
    mesh = auto_mesh((1, 1), ("data", "model"))
    assert client_shard_axes(mesh) == ("data",)
    assert client_shard_axes(_mesh11()) == ("pod", "data")
    assert client_shard_axes(_mesh11(), ("data",)) == ("data",)


def test_cohort_sharding_ghost_padding_math():
    cs = cohort_sharding(_mesh11(), 3)
    assert (cs.n_shards, cs.total, cs.n_pad) == (1, 3, 0)
    # fake a 4-shard layout to exercise the padding arithmetic
    cs4 = CohortSharding(mesh=_mesh11(), axes=("pod", "data"), n_clients=3,
                         total=4)
    assert cs4.n_pad == 1
    assert cs4.pad([10, 11, 12]) == [10, 11, 12, 10]
    np.testing.assert_array_equal(cs4.pad_weights([1.0, 0.5, 2.0]),
                                  [1.0, 0.5, 2.0, 0.0])


# ---------------------------------------------------------------------------
# sharded round on a 1-device mesh == unsharded engine
# ---------------------------------------------------------------------------


def _toy_round(mesh=None, n_clients=2):
    opt = sgd(0.25)

    def local_step(tr, op, batch):
        loss, g = jax.value_and_grad(
            lambda t: jnp.sum((t["shared"]["w"] - batch["tgt"]) ** 2)
            + jnp.sum((t["local"]["v"] - batch["tgt"]) ** 2))(tr)
        upd, op = opt.update(g, op, tr)
        return trees.tree_add(tr, upd), op, loss

    tr = {"shared": {"w": jnp.zeros(2)}, "local": {"v": jnp.zeros(2)}}
    st_tr = trees.stack([tr] * n_clients)
    st_op = trees.stack([opt.init(tr)] * n_clients)
    tgts = np.stack([np.full((3, 2), 1.0 + 2.0 * ci, np.float32)
                     for ci in range(n_clients)])
    batches = {"tgt": jnp.asarray(tgts)}
    step = build_supervised_round(local_step,
                                  lambda p: p.startswith("shared"),
                                  donate=False, mesh=mesh)
    return step, st_tr, st_op, batches


def test_sharded_round_one_device_mesh_matches_unsharded():
    plain, st_tr, st_op, batches = _toy_round(mesh=None)
    sharded, *_ = _toy_round(mesh=_mesh11())
    w = jnp.asarray([1.0, 0.0])        # client 1 in outage
    ref = plain(st_tr, st_op, batches, w)
    got = sharded(st_tr, st_op, batches, w)
    for r, g in zip(trees.flatten(ref).values(), trees.flatten(got).values()):
        np.testing.assert_allclose(np.asarray(r), np.asarray(g), atol=1e-6)


def test_sharded_round_all_outage_keeps_local():
    sharded, st_tr, st_op, batches = _toy_round(mesh=_mesh11())
    out, _, _ = sharded(st_tr, st_op, batches, jnp.zeros(2))
    w = np.asarray(trees.flatten(out)["shared/w"])
    assert not np.allclose(w[0], w[1])     # gate: no agg, no broadcast


def test_ghost_clients_do_not_change_real_clients():
    """Zero-weight ghost padding (copies of client 0) must leave the real
    clients' round output bitwise unchanged — the invariant the sharded
    engine's non-divisible-cohort padding relies on."""
    step2, st_tr2, st_op2, batches2 = _toy_round(n_clients=2)
    step4, st_tr4, st_op4, _ = _toy_round(n_clients=4)
    # ghosts = copies of client 0, zero weight
    pad = lambda t: jax.tree_util.tree_map(
        lambda l: jnp.concatenate([l, l[:1], l[:1]]), t)
    batches4 = pad(batches2)
    st_tr4 = pad(st_tr2)
    st_op4 = pad(st_op2)
    w2, w4 = jnp.asarray([1.0, 1.0]), jnp.asarray([1.0, 1.0, 0.0, 0.0])
    ref, _, losses2 = step2(st_tr2, st_op2, batches2, w2)
    got, _, losses4 = step4(st_tr4, st_op4, batches4, w4)
    for k, r in trees.flatten(ref).items():
        np.testing.assert_array_equal(np.asarray(r),
                                      np.asarray(trees.flatten(got)[k])[:2],
                                      err_msg=k)
    np.testing.assert_array_equal(np.asarray(losses2),
                                  np.asarray(losses4)[:2])


def test_masked_fedavg_axis_names_matches_plain():
    """The psum'd masked aggregation under shard_map (1-device mesh) must
    reproduce the plain stacked operator — including all-outage (den 0
    everywhere → global kept)."""
    r = np.random.RandomState(0)
    g = {"w": jnp.asarray(r.randn(3, 4), jnp.float32)}
    st = {"w": jnp.asarray(r.randn(5, 3, 4), jnp.float32)}
    ms = {"w": jnp.asarray(r.randint(0, 2, (5, 3, 4)), jnp.float32)}
    mesh = _mesh11()
    axes = ("pod", "data")

    def agg(g, t, m, w):
        return masked_fedavg_stacked(g, t, m, w, axis_names=axes)

    f = jax.shard_map(agg, mesh=mesh,
                  in_specs=(P(), P(axes), P(axes), P(axes)),
                  out_specs=P(), check_vma=False)
    for w in ([1.0, 0.0, 1.0, 0.5, 0.0], [0.0] * 5):
        wv = jnp.asarray(w)
        ref = masked_fedavg_stacked(g, st, ms, wv)
        got = f(g, st, ms, wv)
        np.testing.assert_allclose(np.asarray(ref["w"]),
                                   np.asarray(got["w"]), atol=1e-6)


# ---------------------------------------------------------------------------
# 8-device SPMD execution (subprocess; marked multidevice + slow)
# ---------------------------------------------------------------------------

_PREAMBLE = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp
    import numpy as np
    from repro.sharding import auto_mesh
    mesh = auto_mesh((2, 4), ("pod", "data"))
    assert len(jax.devices()) == 8
""")

PFTT_SUBPROC = _PREAMBLE + textwrap.dedent("""
    from repro.core.pftt import PFTTConfig, run_pftt

    # the fused round really spans all 8 devices
    from repro import trees
    from repro.core.cohort import build_supervised_round
    from repro.optim import sgd
    from repro.sharding import cohort_sharding
    opt = sgd(0.1)
    def local_step(tr, op, b):
        loss, grad = jax.value_and_grad(
            lambda t: jnp.sum((t["w"] - b["tgt"]) ** 2))(tr)
        upd, op = opt.update(grad, op, tr)
        return trees.tree_add(tr, upd), op, loss
    cs = cohort_sharding(mesh, 8)
    tr = {"w": jnp.zeros(2)}
    st_tr = jax.device_put(trees.stack([tr] * 8), cs.named)
    st_op = jax.device_put(trees.stack([opt.init(tr)] * 8), cs.named)
    bt = jax.device_put({"tgt": jnp.ones((8, 3, 2))}, cs.named)
    w = jax.device_put(jnp.ones(8), cs.named)
    step = build_supervised_round(local_step, donate=False, mesh=mesh)
    out, _, _ = step(st_tr, st_op, bt, w)
    assert len(out["w"].sharding.device_set) == 8, out["w"].sharding
    print("SPAN8_OK")

    # engine parity: sharded vs single-device, divisible cohort (8 over 8)
    kw = dict(rounds=2, local_steps=2, pretrain_steps=5,
              samples_per_client=120, d_model=32, seed=0)
    for n, tag in ((8, "DIV"), (3, "GHOST")):
        base = run_pftt(PFTTConfig(n_clients=n, **kw))
        shard = run_pftt(PFTTConfig(n_clients=n, **kw), mesh=mesh)
        np.testing.assert_allclose(base["acc_per_round"],
                                   shard["acc_per_round"], atol=1e-6)
        assert base["mean_round_bytes"] == shard["mean_round_bytes"]
        print(tag + "_OK", base["acc_per_round"])

    # forced all-outage rounds (snr -> -inf): gate parity
    kw_out = dict(kw, snr_db=-30.0)
    base = run_pftt(PFTTConfig(n_clients=3, **kw_out))
    shard = run_pftt(PFTTConfig(n_clients=3, **kw_out), mesh=mesh)
    np.testing.assert_allclose(base["acc_per_round"],
                               shard["acc_per_round"], atol=1e-6)
    print("OUTAGE_OK")
""")

_PFIT_KW = textwrap.dedent("""
    from repro.core.pfit import PFITConfig, run_pfit
    kw = dict(n_clients=2, rounds=2, rollout_batch=4, pretrain_steps=10,
              rm_steps=10, d_model=48, n_layers=2, gen_len=8, prompt_len=6,
              seed=0)
""")

PFIT_PPO_SUBPROC = _PREAMBLE + _PFIT_KW + textwrap.dedent("""
    base = run_pfit(PFITConfig(**kw))
    shard = run_pfit(PFITConfig(**kw), mesh=mesh)
    np.testing.assert_allclose(base["reward_per_round"],
                               shard["reward_per_round"], atol=1e-3)
    assert base["mean_round_bytes"] == shard["mean_round_bytes"]
    print("PPO_OK", base["reward_per_round"])
""")

PFIT_SHEPHERD_SUBPROC = _PREAMBLE + _PFIT_KW + textwrap.dedent("""
    kw2 = dict(kw, method="shepherd", shepherd_steps=2)
    base = run_pfit(PFITConfig(**kw2))
    shard = run_pfit(PFITConfig(**kw2), mesh=mesh)
    np.testing.assert_allclose(base["reward_per_round"],
                               shard["reward_per_round"], atol=1e-3)
    print("SHEPHERD_OK", base["reward_per_round"])
""")


def _run_subproc(code: str, timeout: int = 1800):
    # generous timeout: 8 forced host-platform devices multiply compile
    # time, and CI/sandbox hosts are often oversubscribed.  Inherit the
    # environment (HOME/PATH differ across CI runners); the subprocess sets
    # its own XLA_FLAGS before importing jax.
    import os
    return subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, timeout=timeout,
                          env={**os.environ, "PYTHONPATH": "src"})


@pytest.mark.multidevice
@pytest.mark.slow
def test_pftt_sharded_8dev_matches_single_device():
    proc = _run_subproc(PFTT_SUBPROC)
    for marker in ("SPAN8_OK", "DIV_OK", "GHOST_OK", "OUTAGE_OK"):
        assert marker in proc.stdout, (marker, proc.stdout,
                                       proc.stderr[-3000:])


@pytest.mark.multidevice
@pytest.mark.slow
def test_pfit_ppo_sharded_8dev_matches_single_device():
    proc = _run_subproc(PFIT_PPO_SUBPROC)
    assert "PPO_OK" in proc.stdout, (proc.stdout, proc.stderr[-3000:])


@pytest.mark.multidevice
@pytest.mark.slow
def test_pfit_shepherd_sharded_8dev_matches_single_device():
    proc = _run_subproc(PFIT_SHEPHERD_SUBPROC)
    assert "SHEPHERD_OK" in proc.stdout, (proc.stdout, proc.stderr[-3000:])
