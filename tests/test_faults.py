"""Straggler-tolerant runtime: fault injection, bounded-staleness
aggregation, retransmission, and checkpoint/resume.

Parity discipline mirrors tests/test_cohort_engine.py: the legacy
per-client loop is the oracle, and the fused robust engine must reproduce
its per-round metrics and ledger totals exactly under identical
``FaultPlan`` seeds.  The zero-fault plan must additionally be *bitwise*
the synchronous engine (same accs, same bytes) — the robust machinery is
free when nothing fails.
"""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.robust import RoundPlan, StalenessConfig, StalenessTracker
from repro.sharding import auto_mesh
from repro.wireless.faults import FaultPlan, FaultTrace, RoundFaults


# ---------------------------------------------------------------------------
# FaultPlan / FaultTrace
# ---------------------------------------------------------------------------

FULL_PLAN = dict(dropout_p=0.2, straggle_p=0.25, max_straggle=2,
                 crash_p=0.1, max_crash=3, snr_dip_p=0.2, seed=7)


def test_fault_plan_seeded_and_deterministic():
    a = FaultPlan(**FULL_PLAN).realize(6, 12)
    b = FaultPlan(**FULL_PLAN).realize(6, 12)
    for f in ("train", "tx", "recv", "rejoin", "gain_scale"):
        np.testing.assert_array_equal(getattr(a, f), getattr(b, f))
    c = FaultPlan(**{**FULL_PLAN, "seed": 8}).realize(6, 12)
    assert not np.array_equal(a.train, c.train)


def test_fault_plan_prefix_stable_across_horizons():
    """A shorter-horizon realization must be a prefix of a longer one —
    what lets --resume replay the same trace for fewer remaining rounds."""
    long = FaultPlan(**FULL_PLAN).realize(5, 10)
    short = FaultPlan(**FULL_PLAN).realize(5, 4)
    for f in ("train", "tx", "recv", "rejoin", "gain_scale"):
        np.testing.assert_array_equal(getattr(long, f)[:4], getattr(short, f))


def test_fault_plan_zero_is_all_ones():
    plan = FaultPlan()
    assert plan.is_zero()
    tr = plan.realize(4, 6)
    assert tr.train.all() and tr.tx.all() and tr.recv.all()
    assert not tr.rejoin.any()
    np.testing.assert_array_equal(tr.gain_scale, 1.0)


def test_fault_trace_clamps_past_horizon():
    tr = FaultPlan(dropout_p=1.0).realize(3, 2)
    rf = tr.round(5)                      # past horizon → fault-free
    assert rf.train.all() and rf.tx.all() and rf.recv.all()
    assert not tr.round(1).train.any()    # in-horizon: everyone dropped


def test_fault_trace_mask_invariants():
    tr = FaultPlan(**FULL_PLAN).realize(8, 30)
    for f in ("train", "tx", "recv", "rejoin"):
        v = getattr(tr, f)
        assert set(np.unique(v)) <= {0.0, 1.0}, f
    # a rejoin round receives the broadcast (resync from global)
    assert (tr.recv[tr.rejoin > 0] == 1.0).all()
    # straggle delivery rounds exist: tx=1 with train=0 somewhere
    assert ((tr.tx > 0) & (tr.train == 0)).any()


def test_fault_plan_serialization_roundtrip(tmp_path):
    plan = FaultPlan(**FULL_PLAN)
    assert FaultPlan.from_dict(plan.to_dict()) == plan
    p = tmp_path / "plan.json"
    p.write_text(json.dumps(plan.to_dict()))
    assert FaultPlan.from_spec(str(p)) == plan
    inline = FaultPlan.from_spec("dropout_p=0.3,max_straggle=4,seed=2")
    assert inline == FaultPlan(dropout_p=0.3, max_straggle=4, seed=2)
    assert FaultPlan.from_spec(None) is None
    assert FaultPlan.from_spec("none") is None
    with pytest.raises(ValueError):
        FaultPlan.from_dict({"dropout": 0.5})     # typo'd field


# ---------------------------------------------------------------------------
# StalenessTracker (host-side bookkeeping both execution paths share)
# ---------------------------------------------------------------------------


def _faults(train, tx, recv=None, rejoin=None):
    n = len(train)
    f32 = lambda v: np.asarray(v, np.float32)
    return RoundFaults(
        train=f32(train), tx=f32(tx),
        recv=f32(recv if recv is not None else [1.0] * n),
        rejoin=f32(rejoin if rejoin is not None else [0.0] * n),
        gain_scale=np.ones(n, np.float32))


def test_tracker_zero_faults_equals_outage_weights():
    tk = StalenessTracker(3, StalenessConfig(a=0.5, max_staleness=2))
    for outage in ([1.0, 1.0, 1.0], [1.0, 0.0, 1.0]):
        plan = tk.begin_round(_faults([1, 1, 1], [1, 1, 1]),
                              np.asarray(outage))
        np.testing.assert_array_equal(plan.agg_w, np.asarray(outage, np.float32))
        np.testing.assert_array_equal(plan.staleness, 0)
        tk.end_round(plan, np.full(3, 100.0))


def test_tracker_retransmits_with_staleness_discount():
    cfg = StalenessConfig(a=1.0, max_staleness=2)
    tk = StalenessTracker(2, cfg)
    # round 0: both train; client 0's uplink is lost to an outage
    p0 = tk.begin_round(_faults([1, 1], [1, 1]), np.asarray([0.0, 1.0]))
    charged = tk.end_round(p0, np.asarray([64.0, 64.0]))
    np.testing.assert_array_equal(charged, [64.0, 64.0])   # both attempted
    # round 1: client 0 straggles (no fresh train) but retransmits the
    # buffered round-0 payload at staleness 1 and the stored bit size
    p1 = tk.begin_round(_faults([0, 1], [1, 1]), np.asarray([1.0, 1.0]))
    assert p1.attempt[0] == 1.0 and p1.staleness[0] == 1
    np.testing.assert_allclose(p1.agg_w, [cfg.discount(np.asarray([1]))[0], 1.0])
    charged = tk.end_round(p1, np.asarray([0.0, 64.0]))
    assert charged[0] == 64.0                              # stored bits
    # delivered → the pending slot is free; nothing more on the air
    p2 = tk.begin_round(_faults([0, 1], [1, 1]), np.asarray([1.0, 1.0]))
    assert p2.attempt[0] == 0.0 and p2.agg_w[0] == 0.0


def test_tracker_max_staleness_zero_drops_like_sync():
    tk = StalenessTracker(1, StalenessConfig(max_staleness=0))
    p0 = tk.begin_round(_faults([1], [1]), np.asarray([0.0]))   # outage
    tk.end_round(p0, np.asarray([32.0]))
    p1 = tk.begin_round(_faults([0], [1]), np.asarray([1.0]))
    assert p1.attempt[0] == 0.0        # aged past the bound → abandoned


def test_tracker_rejoin_clears_pending():
    tk = StalenessTracker(1, StalenessConfig(max_staleness=5))
    p0 = tk.begin_round(_faults([1], [1]), np.asarray([0.0]))
    tk.end_round(p0, np.asarray([32.0]))
    p1 = tk.begin_round(_faults([0], [0], rejoin=[1.0]), np.asarray([1.0]))
    tk.end_round(p1, np.asarray([0.0]))
    p2 = tk.begin_round(_faults([0], [1]), np.asarray([1.0]))
    assert p2.attempt[0] == 0.0        # crash dropped the buffered payload


def test_tracker_state_dict_roundtrip():
    tk = StalenessTracker(2, StalenessConfig(a=0.5, max_staleness=3))
    p = tk.begin_round(_faults([1, 1], [1, 1]), np.asarray([0.0, 1.0]))
    tk.end_round(p, np.asarray([10.0, 20.0]))
    tk2 = StalenessTracker(2, tk.cfg)
    tk2.load_state_dict(json.loads(json.dumps(tk.state_dict())))
    np.testing.assert_array_equal(tk.valid, tk2.valid)
    np.testing.assert_array_equal(tk.age, tk2.age)
    np.testing.assert_array_equal(tk.bits, tk2.bits)


# ---------------------------------------------------------------------------
# robust engine round step: direct unit semantics (ghost padding)
# ---------------------------------------------------------------------------


def _toy_robust_setup(n_clients):
    from repro import trees
    from repro.optim import sgd

    def loss_fn(tr, batch):
        return jnp.mean((tr["shared"]["w"].sum() + tr["local"]["v"].sum()
                         - batch["tgt"]) ** 2)

    opt = sgd(1e-2)

    def local_step(tr, op, batch):
        loss, grads = jax.value_and_grad(loss_fn)(tr, batch)
        updates, op = opt.update(grads, op, tr)
        return jax.tree_util.tree_map(lambda p, u: p + u, tr, updates), op, loss

    rng = np.random.RandomState(0)
    mk = lambda i: {"shared": {"w": jnp.asarray(rng.randn(3), jnp.float32)},
                    "local": {"v": jnp.asarray(rng.randn(2), jnp.float32)}}
    ts = [mk(i) for i in range(n_clients)]
    st_tr = trees.stack(ts)
    st_op = trees.stack([opt.init(t) for t in ts])
    batches = {"tgt": jnp.asarray(rng.randn(n_clients, 4, 1), jnp.float32)}
    return local_step, st_tr, st_op, batches


def test_robust_round_ghost_padding_invariant():
    """Ghost clients (copies of client 0, fault masks padded train/recv=1,
    rejoin=0, agg weight 0 — ``CohortSharding.pad_vec`` semantics) must
    leave the real clients' robust round output bitwise unchanged."""
    from repro import trees
    from repro.core.cohort import build_supervised_round

    local_step, st_tr2, st_op2, batches2 = _toy_robust_setup(2)
    step = build_supervised_round(local_step,
                                  lambda p: p.startswith("shared"),
                                  donate=False, robust=True)
    pending2 = jax.tree_util.tree_map(
        jnp.zeros_like, trees.select(st_tr2, lambda p: p.startswith("shared")))

    pad = lambda t: jax.tree_util.tree_map(
        lambda l: jnp.concatenate([l, l[:1], l[:1]]), t)
    st_tr4, st_op4, batches4, pending4 = (pad(st_tr2), pad(st_op2),
                                          pad(batches2), pad(pending2))
    # client 1 straggles: no train, retransmits pending at half weight
    train2 = jnp.asarray([1.0, 0.0])
    aggw2 = jnp.asarray([1.0, 0.5])
    recv2 = jnp.asarray([1.0, 1.0])
    rej2 = jnp.asarray([0.0, 0.0])
    one, zero = jnp.ones(2), jnp.zeros(2)
    ref = step(st_tr2, st_op2, pending2, batches2, train2, aggw2, recv2, rej2,
               one)
    got = step(st_tr4, st_op4, pending4, batches4,
               jnp.concatenate([train2, one]),      # ghosts train like sync
               jnp.concatenate([aggw2, zero]),      # ...at zero agg weight
               jnp.concatenate([recv2, one]),
               jnp.concatenate([rej2, zero]),
               jnp.ones(4))                         # all on time
    for r, g in zip(ref[:3], got[:3]):
        for k, leaf in trees.flatten(r).items():
            np.testing.assert_array_equal(
                np.asarray(leaf), np.asarray(trees.flatten(g)[k])[:2],
                err_msg=k)
    np.testing.assert_array_equal(np.asarray(ref[3]), np.asarray(got[3])[:2])


# ---------------------------------------------------------------------------
# engine vs legacy loop under injected faults (end-to-end parity)
# ---------------------------------------------------------------------------

FAULTY = FaultPlan(dropout_p=0.3, straggle_p=0.3, max_straggle=2,
                   crash_p=0.15, max_crash=2, snr_dip_p=0.25, seed=3)
PFTT_KW = dict(n_clients=2, rounds=3, local_steps=3, pretrain_steps=20,
               samples_per_client=200, seed=0)
ROBUST_KW = dict(fault_plan=FAULTY, staleness_a=0.5, max_staleness=2)


def _assert_ledgers_equal(a, b):
    assert a["total_bytes"] == b["total_bytes"]
    np.testing.assert_allclose(a["mean_round_delay_s"],
                               b["mean_round_delay_s"], equal_nan=True)
    assert a["total_energy_j"] == b["total_energy_j"]


def test_pftt_fault_engine_matches_loop():
    from repro.core.pftt import PFTTConfig, run_pftt
    legacy = run_pftt(PFTTConfig(engine=False, **PFTT_KW, **ROBUST_KW))
    fused = run_pftt(PFTTConfig(engine=True, **PFTT_KW, **ROBUST_KW))
    np.testing.assert_allclose(legacy["acc_per_round"],
                               fused["acc_per_round"], atol=1e-5)
    _assert_ledgers_equal(legacy, fused)


def test_pftt_zero_fault_plan_is_bitwise_sync():
    """FaultPlan() + staleness discounting off must be byte-for-byte the
    synchronous engine — accs, bytes, delay, energy."""
    from repro.core.pftt import PFTTConfig, run_pftt
    sync = run_pftt(PFTTConfig(engine=True, **PFTT_KW))
    robust = run_pftt(PFTTConfig(engine=True, **PFTT_KW,
                                 fault_plan=FaultPlan(), max_staleness=2))
    assert sync["acc_per_round"] == robust["acc_per_round"]   # exact
    _assert_ledgers_equal(sync, robust)


def test_pftt_all_outage_degrades_gracefully():
    """Forced all-outage rounds (deep SNR) must no-op the global update
    without poisoning state, identically in both execution paths."""
    from repro.core.pftt import PFTTConfig, run_pftt
    kw = {**PFTT_KW, "snr_db": -30.0}
    legacy = run_pftt(PFTTConfig(engine=False, **kw, **ROBUST_KW))
    fused = run_pftt(PFTTConfig(engine=True, **kw, **ROBUST_KW))
    assert np.isfinite(fused["acc_per_round"]).all()
    np.testing.assert_allclose(legacy["acc_per_round"],
                               fused["acc_per_round"], atol=1e-5)
    _assert_ledgers_equal(legacy, fused)


def test_pftt_fault_sharded_one_device_matches_unsharded():
    """The robust round under shard_map (1-device mesh) must reproduce the
    unsharded engine — fault masks and agg weights ride the client axis."""
    from repro.core.pftt import PFTTConfig, run_pftt
    plain = run_pftt(PFTTConfig(engine=True, **PFTT_KW, **ROBUST_KW))
    mesh = auto_mesh((1, 1), ("pod", "data"))
    sharded = run_pftt(PFTTConfig(engine=True, **PFTT_KW, **ROBUST_KW),
                       mesh=mesh, client_axes=("pod", "data"))
    np.testing.assert_allclose(plain["acc_per_round"],
                               sharded["acc_per_round"], atol=1e-5)
    _assert_ledgers_equal(plain, sharded)


PFIT_KW = dict(n_clients=2, rounds=2, rollout_batch=4, pretrain_steps=15,
               rm_steps=15, d_model=48, n_layers=2, gen_len=8, prompt_len=6,
               seed=0)


def test_pfit_ppo_fault_engine_matches_loop():
    from repro.core.pfit import PFITConfig, run_pfit
    legacy = run_pfit(PFITConfig(engine=False, **PFIT_KW, **ROBUST_KW))
    fused = run_pfit(PFITConfig(engine=True, **PFIT_KW, **ROBUST_KW))
    np.testing.assert_allclose(legacy["reward_per_round"],
                               fused["reward_per_round"], atol=1e-3)
    _assert_ledgers_equal(legacy, fused)


def test_pfit_shepherd_fault_engine_matches_loop():
    from repro.core.pfit import PFITConfig, run_pfit
    kw = dict(method="shepherd", shepherd_steps=2, **PFIT_KW)
    legacy = run_pfit(PFITConfig(engine=False, **kw, **ROBUST_KW))
    fused = run_pfit(PFITConfig(engine=True, **kw, **ROBUST_KW))
    np.testing.assert_allclose(legacy["reward_per_round"],
                               fused["reward_per_round"], atol=1e-3)
    _assert_ledgers_equal(legacy, fused)


# ---------------------------------------------------------------------------
# checkpoint / resume
# ---------------------------------------------------------------------------


def test_pftt_kill_and_resume_reproduces_uninterrupted_run(tmp_path):
    """Kill after 2 of 4 rounds, resume from the round checkpoints: the
    continued run must reproduce the uninterrupted run's per-round metrics
    and ledger exactly."""
    from repro.core.pftt import PFTTConfig, run_pftt
    kw = {**PFTT_KW, "rounds": 4}
    full = run_pftt(PFTTConfig(engine=True, **kw, **ROBUST_KW))
    ck = str(tmp_path / "ck")
    os.makedirs(ck)
    run_pftt(PFTTConfig(engine=True, **{**kw, "rounds": 2}, **ROBUST_KW,
                        ckpt_dir=ck))                       # "killed" here
    resumed = run_pftt(PFTTConfig(engine=True, **kw, **ROBUST_KW,
                                  ckpt_dir=ck, resume=True))
    assert resumed["acc_per_round"] == full["acc_per_round"]
    _assert_ledgers_equal(full, resumed)
