"""Parity tests for the vmapped federated cohort engine: the fused round
step (vmap over clients x scan over local steps + stacked aggregation +
broadcast) must reproduce the legacy per-client loop, and the stacked
aggregation operators must match the list-based API bit-for-bit on float32
inputs — including the all-clients-in-outage round (weights sum to zero →
global kept)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import trees
from repro.core.aggregation import (fedavg, fedavg_stacked, masked_fedavg,
                                    masked_fedavg_stacked, partial_fedavg,
                                    partial_fedavg_stacked)


def _tree(seed):
    r = np.random.RandomState(seed)
    return {"x": {"w": jnp.asarray(r.randn(3, 4), jnp.float32)},
            "y": jnp.asarray(r.randn(5), jnp.float32),
            "s": jnp.asarray(r.randn(), jnp.float32)}


def _mask(seed):
    r = np.random.RandomState(seed)
    return {"x": {"w": jnp.asarray(r.randint(0, 2, (3, 4)), jnp.float32)},
            "y": jnp.asarray(r.randint(0, 2, (5,)), jnp.float32),
            "s": jnp.ones((), jnp.float32)}


def _assert_trees_equal(a, b, exact=True):
    fa, fb = trees.flatten(a), trees.flatten(b)
    assert fa.keys() == fb.keys()
    for k in fa:
        if exact:
            np.testing.assert_array_equal(np.asarray(fa[k]),
                                          np.asarray(fb[k]), err_msg=k)
        else:
            np.testing.assert_allclose(np.asarray(fa[k]), np.asarray(fb[k]),
                                       atol=1e-5, err_msg=k)


# ---------------------------------------------------------------------------
# trees.stack / unstack
# ---------------------------------------------------------------------------


def test_stack_unstack_roundtrip():
    ts = [_tree(i) for i in range(3)]
    st = trees.stack(ts)
    assert trees.flatten(st)["x/w"].shape == (3, 3, 4)
    for orig, back in zip(ts, trees.unstack(st)):
        _assert_trees_equal(orig, back)


def test_stack_preserves_none_leaves():
    sel = [trees.select(_tree(i), lambda p: p.startswith("x"))
           for i in range(2)]
    st = trees.stack(sel)
    flat = trees.flatten(st)
    assert set(flat) == {"x/w"}
    assert flat["x/w"].shape == (2, 3, 4)


# ---------------------------------------------------------------------------
# stacked aggregation vs legacy list API (bit-for-bit on float32)
# ---------------------------------------------------------------------------


def test_fedavg_stacked_matches_list_bitwise():
    ts = [_tree(i) for i in range(4)]
    _assert_trees_equal(fedavg(ts), fedavg_stacked(trees.stack(ts)))
    w = [0.1, 0.4, 0.2, 0.3]
    _assert_trees_equal(fedavg(ts, w),
                        fedavg_stacked(trees.stack(ts), jnp.asarray(w)))


def test_masked_fedavg_stacked_matches_list_bitwise():
    g, ts = _tree(99), [_tree(i) for i in range(3)]
    ms = [_mask(10 + i) for i in range(3)]
    _assert_trees_equal(masked_fedavg(g, ts, ms),
                        masked_fedavg_stacked(g, trees.stack(ts),
                                              trees.stack(ms)))


def test_masked_fedavg_outage_vector_matches_alive_subset():
    """Zero-weight (outage) clients must drop out exactly as if they had
    been Python-filtered from the client list."""
    g, ts = _tree(99), [_tree(i) for i in range(4)]
    ms = [_mask(10 + i) for i in range(4)]
    legacy = masked_fedavg(g, [ts[0], ts[2]], [ms[0], ms[2]])
    stacked = masked_fedavg_stacked(g, trees.stack(ts), trees.stack(ms),
                                    weights=jnp.asarray([1., 0., 1., 0.]))
    _assert_trees_equal(legacy, stacked)


def test_masked_fedavg_all_outage_keeps_global():
    g, ts = _tree(99), [_tree(i) for i in range(3)]
    ms = [_mask(10 + i) for i in range(3)]
    out = masked_fedavg_stacked(g, trees.stack(ts), trees.stack(ms),
                                weights=jnp.zeros(3))
    _assert_trees_equal(out, g)


def test_masked_fedavg_zero_mask_keeps_global():
    g, ts = _tree(99), [_tree(i) for i in range(2)]
    zeros = [jax.tree_util.tree_map(jnp.zeros_like, m)
             for m in [_mask(0), _mask(1)]]
    out = masked_fedavg_stacked(g, trees.stack(ts), trees.stack(zeros))
    _assert_trees_equal(out, g)


def test_partial_fedavg_stacked_matches_list_bitwise():
    g, ts = _tree(99), [_tree(i) for i in range(3)]
    pred = lambda p: p.startswith("x")
    _assert_trees_equal(partial_fedavg(g, ts, pred),
                        partial_fedavg_stacked(g, trees.stack(ts), pred))


# ---------------------------------------------------------------------------
# fused supervised round step semantics (direct engine unit test)
# ---------------------------------------------------------------------------


def _toy_round_step():
    from repro.core.cohort import build_supervised_round
    from repro.optim import sgd
    opt = sgd(0.25)

    def local_step(tr, op, batch):
        loss, g = jax.value_and_grad(
            lambda t: jnp.sum((t["shared"]["w"] - batch["tgt"]) ** 2)
            + jnp.sum((t["local"]["v"] - batch["tgt"]) ** 2))(tr)
        upd, op = opt.update(g, op, tr)
        return trees.tree_add(tr, upd), op, loss

    tr = {"shared": {"w": jnp.zeros(2)}, "local": {"v": jnp.zeros(2)}}
    st_tr = trees.stack([tr, tr])
    st_op = trees.stack([opt.init(tr), opt.init(tr)])
    batches = {"tgt": jnp.asarray([[[1.0, 1.0]] * 3, [[3.0, 3.0]] * 3])}
    step = build_supervised_round(local_step,
                                  lambda p: p.startswith("shared"),
                                  donate=False)
    return step, st_tr, st_op, batches


def test_supervised_round_aggregates_shared_keeps_local():
    step, st_tr, st_op, batches = _toy_round_step()
    out, _, losses = step(st_tr, st_op, batches, jnp.asarray([1.0, 1.0]))
    w = np.asarray(trees.flatten(out)["shared/w"])
    v = np.asarray(trees.flatten(out)["local/v"])
    np.testing.assert_allclose(w[0], w[1])          # shared: broadcast agg
    assert not np.allclose(v[0], v[1])              # local: personalized
    assert losses.shape == (2, 3)
    assert float(losses[0, 0]) > float(losses[0, -1])  # scan actually trains


def test_supervised_round_all_outage_keeps_local():
    step, st_tr, st_op, batches = _toy_round_step()
    out, _, _ = step(st_tr, st_op, batches, jnp.zeros(2))
    w = np.asarray(trees.flatten(out)["shared/w"])
    assert not np.allclose(w[0], w[1])              # no agg, no broadcast


# ---------------------------------------------------------------------------
# engine vs legacy loop, end-to-end (per-round metrics parity)
# ---------------------------------------------------------------------------


def test_pftt_engine_matches_loop():
    from repro.core.pftt import PFTTConfig, run_pftt
    kw = dict(n_clients=2, rounds=3, local_steps=3, pretrain_steps=20,
              samples_per_client=200, seed=0)
    legacy = run_pftt(PFTTConfig(engine=False, **kw))
    fused = run_pftt(PFTTConfig(engine=True, **kw))
    np.testing.assert_allclose(legacy["acc_per_round"],
                               fused["acc_per_round"], atol=1e-5)
    np.testing.assert_allclose(legacy["loss_per_round"],
                               fused["loss_per_round"], rtol=1e-5)
    assert fused["dispatches_per_round"] == 1.0
    assert legacy["mean_round_bytes"] == fused["mean_round_bytes"]
    assert legacy["mean_round_delay_s"] == fused["mean_round_delay_s"]


def test_pfit_engine_matches_loop():
    from repro.core.pfit import PFITConfig, run_pfit
    kw = dict(n_clients=2, rounds=2, rollout_batch=4, pretrain_steps=15,
              rm_steps=15, d_model=48, n_layers=2, gen_len=8, prompt_len=6,
              seed=0)
    legacy = run_pfit(PFITConfig(engine=False, **kw))
    fused = run_pfit(PFITConfig(engine=True, **kw))
    np.testing.assert_allclose(legacy["reward_per_round"],
                               fused["reward_per_round"], atol=1e-3)
    assert legacy["mean_round_bytes"] == fused["mean_round_bytes"]


def test_pfit_shepherd_engine_matches_loop():
    from repro.core.pfit import PFITConfig, run_pfit
    kw = dict(method="shepherd", n_clients=2, rounds=2, shepherd_steps=2,
              rollout_batch=4, pretrain_steps=15, rm_steps=15, d_model=48,
              n_layers=2, gen_len=8, prompt_len=6, seed=0)
    legacy = run_pfit(PFITConfig(engine=False, **kw))
    fused = run_pfit(PFITConfig(engine=True, **kw))
    np.testing.assert_allclose(legacy["reward_per_round"],
                               fused["reward_per_round"], atol=1e-3)
    assert legacy["mean_round_bytes"] == fused["mean_round_bytes"]
