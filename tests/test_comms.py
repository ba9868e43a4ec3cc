"""Uplink codec subsystem tests (``repro.comms``).

Covers: stochastic-rounding quantizer error bounds and unbiasedness under a
fixed PRNG key schedule, entropy-based bit accounting bounds, top-k exact
recovery on sparse trees, count-sketch heavy-hitter recovery on
top-k-dominated signals, SVD re-projection parity against the dense-merge
oracle on fedlora-shaped factors (≤1e-5, no densification on the server
path), codec-under-``shard_map`` parity with ghost-padded non-divisible
cohorts, ``ChannelBudget`` delay/energy accounting + the all-outage NaN
delay fix, ``tree_bytes`` itemsize overrides and treedef pairing, and
engine-vs-legacy-loop ledger agreement with a codec active."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import trees
from repro.comms import (ChannelBudget, dense_rank_r_oracle, get_codec,
                         payload_bits_upper_bound, roundtrip, svd_reproject)
from repro.comms import quantize, sketch
from repro.comms.factored_agg import factored_fedavg_tree
from repro.core.aggregation import fedavg_stacked
from repro.core.cohort import build_supervised_round
from repro.optim import sgd
from repro.sharding import auto_mesh
from repro.wireless import CommLedger, RayleighChannel, tree_bytes
from repro.wireless.channel import ChannelReport


def _key(i=0):
    return jax.random.PRNGKey(i)


# ---------------------------------------------------------------------------
# stochastic-rounding quantization (comms.quantize)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("bits", [8, 4])
def test_sr_quantize_roundtrip_error_bound(bits):
    """|decode - x| ≤ per-channel scale, elementwise (one SR step can move
    at most one quantization level)."""
    r = np.random.RandomState(0)
    x = jnp.asarray(r.randn(6, 33) * 0.3, jnp.float32)
    enc = quantize.sr_quantize(_key(1), x, bits)
    dec = quantize.sr_dequantize(enc)
    bound = np.broadcast_to(np.asarray(enc["scale"]), x.shape) * 1.0001
    assert (np.abs(np.asarray(dec - x)) <= bound + 1e-8).all()


@pytest.mark.parametrize("bits", [8, 4])
def test_sr_quantize_unbiased(bits):
    """E[decode] = x under stochastic rounding: averaging decodes over many
    fixed PRNG keys converges to the input."""
    r = np.random.RandomState(1)
    x = jnp.asarray(r.randn(4, 16) * 0.1, jnp.float32)

    def dec(i):
        return quantize.sr_dequantize(
            quantize.sr_quantize(jax.random.fold_in(_key(2), i), x, bits))

    n = 1500
    mean = np.mean([np.asarray(dec(i)) for i in range(n)], axis=0)
    scale = np.broadcast_to(np.asarray(
        quantize.channel_scale(x, bits)), x.shape)
    # CLT: SR noise per draw is Bernoulli-f within a level → var f(1-f) ≤ ¼,
    # so |mean - x| ≲ 4σ = 4·scale·½/√n for ≳99.99% of elements
    tol = 2.0 * scale / np.sqrt(n) + 1e-7
    assert (np.abs(mean - np.asarray(x)) <= tol).mean() > 0.99


def test_sr_quantize_zero_channels_exact():
    x = jnp.zeros((8, 8), jnp.float32)
    dec = quantize.sr_dequantize(quantize.sr_quantize(_key(), x, 8))
    np.testing.assert_array_equal(np.asarray(dec), 0.0)


@pytest.mark.parametrize("bits", [8, 4])
def test_entropy_bits_bounded_by_flat_bits(bits):
    r = np.random.RandomState(2)
    x = jnp.asarray(r.randn(32, 32), jnp.float32)
    enc = quantize.sr_quantize(_key(3), x, bits)
    ent = float(quantize.symbol_entropy_bits(enc["q"], bits))
    assert 0.0 < ent <= x.size * bits + 1e-6


def test_entropy_bits_mask_restricts_charge():
    r = np.random.RandomState(3)
    x = jnp.asarray(r.randn(16, 16), jnp.float32)
    enc = quantize.sr_quantize(_key(4), x, 8)
    full = float(quantize.symbol_entropy_bits(enc["q"], 8))
    m = jnp.zeros((16, 16)).at[:4].set(1.0)
    part = float(quantize.symbol_entropy_bits(enc["q"], 8, m))
    assert part < 0.5 * full


# ---------------------------------------------------------------------------
# sketches (comms.sketch)
# ---------------------------------------------------------------------------


def test_topk_exact_on_sparse_leaf():
    """A leaf with ≤k nonzeros decodes exactly (up to f16 value rounding)."""
    x = np.zeros((400,), np.float32)
    idx = np.asarray([3, 77, 200, 399])
    x[idx] = [1.5, -2.0, 0.25, 4.0]
    enc = sketch.topk_encode(jnp.asarray(x), frac=0.01)  # k = 4
    dec = np.asarray(sketch.topk_decode(enc, (400,)))
    np.testing.assert_allclose(dec, x, rtol=1e-3)


def test_count_sketch_recovers_heavy_hitters():
    """On a top-k-dominated signal the median-of-rows count-sketch estimate
    recovers the heavy coordinates within the collision-noise floor."""
    r = np.random.RandomState(4)
    x = r.randn(512).astype(np.float32) * 0.01          # background
    heavy_idx = r.choice(512, size=8, replace=False)
    x[heavy_idx] = np.sign(r.randn(8)) * 5.0            # heavy hitters
    enc = sketch.count_sketch_encode(jnp.asarray(x), leaf_seed=0, rows=5,
                                     ratio=0.5)
    dec = np.asarray(sketch.count_sketch_decode(enc, (512,), leaf_seed=0))
    # at worst one heavy hitter may lose its median to bucket collisions
    hits = np.abs(dec[heavy_idx] - x[heavy_idx]) < 0.5
    assert hits.sum() >= len(heavy_idx) - 1, dec[heavy_idx]
    # background coordinates stay near zero (collision-noise floor)
    bg = np.setdiff1d(np.arange(512), heavy_idx)
    assert np.median(np.abs(dec[bg])) < 0.25


def test_count_sketch_decode_is_linear_in_encode():
    """Same hashes on both sides: decode(encode(x)) is deterministic and
    jit-stable (server needs no negotiation traffic)."""
    x = jnp.asarray(np.random.RandomState(5).randn(128), jnp.float32)
    f = jax.jit(lambda v: sketch.count_sketch_decode(
        sketch.count_sketch_encode(v, leaf_seed=7, rows=3, ratio=0.25),
        (128,), leaf_seed=7))
    np.testing.assert_array_equal(np.asarray(f(x)), np.asarray(f(x)))


# ---------------------------------------------------------------------------
# tree-level roundtrip + bit accounting (comms.codec)
# ---------------------------------------------------------------------------


def _fedlora_like_tree(seed=0, scale_a=0.09, scale_b=0.02):
    r = np.random.RandomState(seed)
    mk = lambda *s: jnp.asarray(r.randn(*s), jnp.float32)
    return {"base": {"cls_head": mk(64, 4)},
            "lora": {"wq": {"a": mk(2, 64, 8) * scale_a,
                            "b": mk(2, 8, 64) * scale_b,
                            "mask": jnp.ones((2, 1, 1), jnp.float32)},
                     "wv": {"a": mk(2, 64, 8) * scale_a,
                            "b": mk(2, 8, 64) * scale_b,
                            "mask": jnp.ones((2, 1, 1), jnp.float32)}}}


@pytest.mark.parametrize("name,min_ratio", [("int8", 3.5), ("int4", 6.0),
                                            ("sketch", 5.0)])
def test_roundtrip_compresses_fedlora_tree(name, min_ratio):
    tree = _fedlora_like_tree()
    ref = jax.tree_util.tree_map(
        lambda x: x + 0.01 * jnp.asarray(
            np.random.RandomState(9).randn(*x.shape), jnp.float32), tree)
    codec = get_codec(name)
    dec, bits = jax.jit(
        lambda k, t, rf: roundtrip(codec, k, t, ref=rf))(_key(5), tree, ref)
    raw = sum(x.size * 32 for x in jax.tree_util.tree_leaves(tree))
    assert raw / float(bits) >= min_ratio, (name, raw / float(bits))
    # mask leaves are below MIN_CODED_SIZE: pass through exactly
    np.testing.assert_array_equal(
        np.asarray(dec["lora"]["wq"]["mask"]),
        np.asarray(tree["lora"]["wq"]["mask"]))
    # decode stays close to the true upload (deltas are small)
    err = max(float(jnp.abs(a - b).max()) for a, b in zip(
        trees.flatten(dec).values(), trees.flatten(tree).values()))
    assert err < 0.05, (name, err)


@pytest.mark.parametrize("name", ["int8", "int4", "sketch", "countsketch"])
def test_roundtrip_fully_masked_leaf_charges_zero_bits(name):
    """Weight-0 elements are not transmitted — a fully-masked leaf must
    charge 0 bits INCLUDING the per-channel scale / static sketch payload
    (the no-codec baseline ``tree_bytes(nonzero_mask=...)`` charges 0 for
    such leaves too, so ratios stay comparable)."""
    codec = get_codec(name)
    tree = {"w": jnp.asarray(np.random.RandomState(0).randn(64, 64),
                             jnp.float32)}
    masks = {"w": jnp.zeros((64, 64), jnp.float32)}
    ref = jax.tree_util.tree_map(jnp.zeros_like, tree)
    dec, bits = roundtrip(codec, _key(8), tree, ref=ref, bit_weights=masks)
    assert float(bits) == 0.0, (name, float(bits))
    # decode keeps the server-known reference on untransmitted lanes
    np.testing.assert_array_equal(np.asarray(dec["w"]), 0.0)
    # partial masks never charge more than the unmasked leaf (strictly less
    # for quantizers; sketches are already sublinear in n)
    half = {"w": jnp.zeros((64, 64)).at[:32].set(1.0)}
    _, b_half = roundtrip(codec, _key(8), tree, ref=ref, bit_weights=half)
    _, b_full = roundtrip(codec, _key(8), tree, ref=ref)
    assert 0.0 < float(b_half) <= float(b_full)
    if name in ("int8", "int4"):
        assert float(b_half) < float(b_full)


def test_roundtrip_entropy_bits_below_upper_bound():
    tree = _fedlora_like_tree()
    codec = get_codec("int8")
    _, bits = roundtrip(codec, _key(6), tree)
    assert float(bits) <= payload_bits_upper_bound(codec, tree) + 1e-3


def test_roundtrip_vmaps_over_clients():
    """The stacked-cohort form the engine uses: one vmapped dispatch, one
    bits scalar per client, per-client keys decorrelate the rounding."""
    tree = _fedlora_like_tree()
    st = trees.stack([tree, tree, tree])
    keys = jnp.stack([jax.random.fold_in(_key(7), i) for i in range(3)])
    codec = get_codec("int4")
    dec, bits = jax.vmap(lambda k, t: roundtrip(codec, k, t))(keys, st)
    assert bits.shape == (3,)
    a = np.asarray(trees.flatten(dec)["lora/wq/a"])
    assert not np.array_equal(a[0], a[1])   # different SR draws per client


# ---------------------------------------------------------------------------
# factored aggregation: SVD re-projection vs dense-merge oracle
# ---------------------------------------------------------------------------


def _factors(n=5, rep=2, d=96, r=8, seed=0):
    rng = np.random.RandomState(seed)
    st_a = jnp.asarray(rng.randn(n, rep, d, r) * d ** -0.5, jnp.float32)
    st_b = jnp.asarray(rng.randn(n, rep, r, d) * 0.02, jnp.float32)
    return st_a, st_b


@pytest.mark.parametrize("weights", [None, [1., 0., 1., .5, 0.]])
def test_svd_reprojection_matches_dense_oracle(weights):
    """A'·B' must equal the rank-r truncated SVD of the dense weighted-mean
    update Σ ŵ_i A_i·B_i to ≤1e-5 — computed via (d × n·r) QR factors only,
    the dense (d × d) matrix exists only inside the test oracle."""
    st_a, st_b = _factors()
    w = None if weights is None else jnp.asarray(weights)
    a2, b2 = svd_reproject(st_a, st_b, w)
    assert a2.shape == st_a.shape[1:] and b2.shape == st_b.shape[1:]
    oracle = dense_rank_r_oracle(st_a, st_b, w)
    err = float(jnp.abs(a2 @ b2 - oracle).max())
    assert err <= 1e-5, err


def test_svd_reprojection_beats_naive_factor_mean():
    """avg(A)·avg(B) ≠ avg(A·B): the re-projection approximates the true
    mean update strictly better than averaging factors elementwise."""
    st_a, st_b = _factors(seed=3)
    w = jnp.asarray([1., 1., 1., 1., 1.])
    ŵ = np.asarray(w) / np.asarray(w).sum()
    dense = np.einsum("n...dr,n...rf->...df",
                      np.asarray(st_a) * ŵ[:, None, None, None],
                      np.asarray(st_b))
    a2, b2 = svd_reproject(st_a, st_b, w)
    naive = np.asarray(fedavg_stacked({"a": st_a}, w)["a"]) @ \
        np.asarray(fedavg_stacked({"b": st_b}, w)["b"])
    err_svd = np.abs(np.asarray(a2 @ b2) - dense).max()
    err_naive = np.abs(naive - dense).max()
    assert err_svd < err_naive


def test_factored_fedavg_tree_mixes_pairs_and_plain_leaves():
    st = trees.stack([_fedlora_like_tree(i) for i in range(4)])
    w = jnp.asarray([1., 1., 0., 1.])
    out = factored_fedavg_tree(st, w)
    plain = fedavg_stacked(st, w)
    # non-factor leaves: plain weighted mean, bitwise
    np.testing.assert_array_equal(
        np.asarray(trees.flatten(out)["base/cls_head"]),
        np.asarray(trees.flatten(plain)["base/cls_head"]))
    # factor pairs: the re-projected product matches the dense oracle
    fo, fs = trees.flatten(out), trees.flatten(st)
    oracle = dense_rank_r_oracle(fs["lora/wq/a"], fs["lora/wq/b"], w)
    err = float(jnp.abs(fo["lora/wq/a"] @ fo["lora/wq/b"] - oracle).max())
    assert err <= 1e-5, err


# ---------------------------------------------------------------------------
# codec + factored aggregation inside the fused round, sharded, ghost-padded
# ---------------------------------------------------------------------------


def _toy_codec_round(codec, mesh=None, n_clients=3, factored_agg=True):
    opt = sgd(0.2)

    def local_step(tr, op, batch):
        loss, g = jax.value_and_grad(
            lambda t: jnp.sum((t["shared"]["lin"] - batch["tgt"]) ** 2)
            + jnp.sum((t["shared"]["fac"]["a"] @ t["shared"]["fac"]["b"]
                       - 0.1) ** 2)
            + jnp.sum((t["local"]["v"] - batch["tgt"]) ** 2))(tr)
        upd, op = opt.update(g, op, tr)
        return trees.tree_add(tr, upd), op, loss

    r = np.random.RandomState(0)
    trs = [{"shared": {"lin": jnp.asarray(r.randn(32), jnp.float32),
                       "fac": {"a": jnp.asarray(r.randn(24, 4) * 0.1,
                                                jnp.float32),
                               "b": jnp.asarray(r.randn(4, 24) * 0.1,
                                                jnp.float32)}},
            "local": {"v": jnp.zeros(32)}} for _ in range(n_clients)]
    st_tr = trees.stack(trs)
    st_op = trees.stack([opt.init(t) for t in trs])
    batches = {"tgt": jnp.asarray(np.stack(
        [np.full((3, 32), 1.0 + ci, np.float32)
         for ci in range(n_clients)]))}
    keys = jnp.stack([jax.random.fold_in(_key(11), i)
                      for i in range(n_clients)])
    step = build_supervised_round(local_step,
                                  lambda p: p.startswith("shared"),
                                  donate=False, codec=codec, mesh=mesh,
                                  factored_agg=factored_agg)
    return step, st_tr, st_op, batches, keys


def test_codec_round_sharded_one_device_mesh_matches_unsharded():
    """codec + factored_agg under shard_map (1-device ("pod","data") mesh)
    == the unsharded fused round — the collective math (psum + factor
    all-gather) collapses to the single-device math."""
    codec = get_codec("int8")
    mesh = auto_mesh((1, 1), ("pod", "data"))
    plain, st_tr, st_op, batches, keys = _toy_codec_round(codec)
    sharded, *_ = _toy_codec_round(codec, mesh=mesh)
    w = jnp.asarray([1.0, 0.0, 1.0])
    ref = plain(st_tr, st_op, batches, w, keys)
    got = sharded(st_tr, st_op, batches, w, keys)
    for (k, a), b in zip(trees.flatten(ref[0]).items(),
                         trees.flatten(got[0]).values()):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6,
                                   err_msg=k)
    np.testing.assert_allclose(np.asarray(ref[3]), np.asarray(got[3]),
                               rtol=1e-6)


def test_codec_round_ghost_padding_invariance():
    """Zero-weight ghost clients (the sharded engine's non-divisible-cohort
    padding) must not change the real clients — including the codec's
    stochastic rounding and the factored aggregation."""
    codec = get_codec("int8")
    step, st_tr, st_op, batches, keys = _toy_codec_round(codec)
    ref = step(st_tr, st_op, batches, jnp.asarray([1.0, 0.0, 1.0]), keys)
    pad = lambda t: jax.tree_util.tree_map(
        lambda l: jnp.concatenate([l, l[:1]]), t)
    out4 = step(pad(st_tr), pad(st_op), pad(batches),
                jnp.asarray([1.0, 0.0, 1.0, 0.0]),
                jnp.concatenate([keys, keys[:1]]))
    for (k, a), b in zip(trees.flatten(ref[0]).items(),
                         trees.flatten(out4[0]).values()):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b)[:3],
                                   atol=1e-6, err_msg=k)
    # ghost bits are produced but the round loop only reads the real rows
    assert np.asarray(out4[3]).shape == (4,)


def test_codec_round_all_outage_keeps_local():
    codec = get_codec("int8")
    step, st_tr, st_op, batches, keys = _toy_codec_round(codec)
    out, _, _, _ = step(st_tr, st_op, batches, jnp.zeros(3), keys)
    lin = np.asarray(trees.flatten(out)["shared/lin"])
    assert not np.allclose(lin[0], lin[1])     # gate: no agg, no broadcast


# ---------------------------------------------------------------------------
# ChannelBudget + CommLedger (bits → delay/energy; all-outage NaN delay)
# ---------------------------------------------------------------------------


def test_channel_budget_matches_channel_uplink():
    ch = RayleighChannel(mean_snr_db=5.0, seed=0)
    budget = ChannelBudget(ch, tx_power_w=0.25)
    rep = budget.report(8.0e6, gain=1.0)
    direct = ch.uplink(1.0e6, gain=1.0)
    assert rep.delay_s == direct.delay_s
    assert rep.bytes_sent == direct.bytes_sent
    np.testing.assert_allclose(rep.energy_j, 0.25 * rep.delay_s)


def test_channel_budget_outage_zero_energy_and_bytes():
    ch = RayleighChannel(mean_snr_db=5.0, seed=0)
    rep = ChannelBudget(ch).report(8.0e6, gain=1e-6)   # deep fade → outage
    assert rep.outage and rep.bytes_sent == 0 and rep.energy_j == 0.0


def test_ledger_all_outage_round_delay_is_nan_and_skipped():
    mk = lambda outage, delay: ChannelReport(
        snr_db=0.0, rate_bps=1.0, delay_s=delay, outage=outage,
        bytes_sent=0 if outage else 10)
    led = CommLedger()
    led.log_round([mk(True, np.inf), mk(True, np.inf)])   # all-outage
    led.log_round([mk(False, 2.0), mk(True, np.inf)])
    assert np.isnan(led.rounds[0]["delay_s"])
    assert led.mean_round_delay == 2.0                    # NaN skipped
    led2 = CommLedger()
    led2.log_round([mk(True, np.inf)])
    assert led2.mean_round_delay == 0.0                   # all rounds NaN


# ---------------------------------------------------------------------------
# tree_bytes: itemsize override + treedef pairing
# ---------------------------------------------------------------------------


def test_tree_bytes_itemsize_override():
    tree = {"w": jnp.zeros((10, 10), jnp.float32), "b": jnp.zeros(10)}
    assert tree_bytes(tree) == 440
    assert tree_bytes(tree, itemsize=1) == 110            # int8-quantized
    per_leaf = {"w": 0.5, "b": None}                      # int4 + raw f32
    assert tree_bytes(tree, itemsize=per_leaf) == 90


def test_tree_bytes_mask_pairs_by_treedef():
    tree = {"a": jnp.zeros((4, 4)), "b": jnp.zeros((8,))}
    mask = {"a": jnp.ones((4, 4)).at[0].set(0.0), "b": jnp.ones((8,))}
    assert tree_bytes(tree, nonzero_mask=mask) == (12 + 8) * 4
    with pytest.raises(ValueError):
        tree_bytes(tree, nonzero_mask={"a": mask["a"]})   # missing leaf
    with pytest.raises(ValueError):                       # extra leaf
        tree_bytes(tree, nonzero_mask=dict(mask, c=jnp.ones(2)))


# ---------------------------------------------------------------------------
# end-to-end: engine-vs-legacy-loop ledger agreement with a codec active
# ---------------------------------------------------------------------------


def test_pftt_codec_engine_matches_loop_including_ledger():
    """The fused round's vmapped codec must reproduce the legacy per-client
    roundtrip: accuracies AND ledger totals (encoded bytes, delay, energy)
    agree engine-vs-loop."""
    from repro.core.pftt import PFTTConfig, run_pftt
    kw = dict(n_clients=2, rounds=3, local_steps=3, pretrain_steps=20,
              samples_per_client=200, seed=0, method="fedlora",
              uplink_codec="int8", factored_agg=True)
    legacy = run_pftt(PFTTConfig(engine=False, **kw))
    fused = run_pftt(PFTTConfig(engine=True, **kw))
    np.testing.assert_allclose(legacy["acc_per_round"],
                               fused["acc_per_round"], atol=1e-5)
    np.testing.assert_allclose(legacy["total_bytes"], fused["total_bytes"],
                               rtol=1e-5)
    np.testing.assert_allclose(legacy["mean_round_delay_s"],
                               fused["mean_round_delay_s"], rtol=1e-5)
    np.testing.assert_allclose(legacy["total_energy_j"],
                               fused["total_energy_j"], rtol=1e-5)
    # the codec actually compresses: encoded < raw f32 accounting
    raw = run_pftt(PFTTConfig(engine=True, **dict(kw, uplink_codec="none",
                                                  factored_agg=False)))
    assert fused["total_bytes"] < 0.3 * raw["total_bytes"]


def test_pfit_ppo_codec_engine_matches_loop_including_ledger():
    """build_ppo_round's codec threading (trailing codec_keys arg, masked
    bit charge, decoded-upload masked aggregation) against the legacy
    per-client loop: rewards AND ledger totals agree."""
    from repro.core.pfit import PFITConfig, run_pfit
    kw = dict(n_clients=2, rounds=2, rollout_batch=4, pretrain_steps=15,
              rm_steps=15, d_model=48, n_layers=2, gen_len=8, prompt_len=6,
              seed=0, uplink_codec="int8")
    legacy = run_pfit(PFITConfig(engine=False, **kw))
    fused = run_pfit(PFITConfig(engine=True, **kw))
    np.testing.assert_allclose(legacy["reward_per_round"],
                               fused["reward_per_round"], atol=1e-3)
    np.testing.assert_allclose(legacy["total_bytes"], fused["total_bytes"],
                               rtol=1e-5)
    np.testing.assert_allclose(legacy["total_energy_j"],
                               fused["total_energy_j"], rtol=1e-5)
