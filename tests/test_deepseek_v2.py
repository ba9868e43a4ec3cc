"""DeepSeek-V2-Lite on the serving path against the plain reference
(``tests/ref_deepseek_v2.py``), at a small size on the CPU: d 64, 4 heads,
kv_lora 32, rope 16, nope 16, v 16, 16 experts of width 32, top 4, one
shared expert, one dense layer then two MoE layers, seeded random weights.

Tolerances: on the CPU the program's float32 matmuls are full float32, as
the reference's are at ``highest``; what is left is the order of
summation (the factored LoRA, the absorbed decode's merged latent
weights, the grouped rows), a few float32 ulps of the logits.  A routing
tie that such rounding flips would show as a gap of the size of one
expert's contribution; none occurs at these seeds."""
import dataclasses
import math
import zlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import ref_deepseek_v2 as ref
from repro import trees
from repro.configs import (LK, MLAConfig, RopeScaling, Stage, get_config,
                           list_configs)
from repro.launch.steps import make_prefill_step, make_serve_step
from repro.models import Model
from repro.models import moe as moe_mod
from repro.models import rope
from repro.models.mla import softmax_scale
from repro.sharding import MeshCtx

ATOL = 2e-4          # logits are O(1): float32 summation-order noise
SCALE = 2.0          # LoRA alpha / rank


def small_cfg(n_held=16, first_held=0, norm_topk_prob=False):
    base = get_config("deepseek-v2-lite")
    return dataclasses.replace(
        base, d_model=64, n_heads=4, n_kv_heads=4, head_dim=16, d_ff=96,
        vocab_size=512,
        stages=(Stage((LK("mla", "mlp"),), 1), Stage((LK("mla", "moe"),), 2)),
        moe=dataclasses.replace(base.moe, n_experts=16, top_k=4, d_ff=32,
                                n_shared_experts=1, n_held=n_held,
                                first_held=first_held,
                                norm_topk_prob=norm_topk_prob),
        mla=MLAConfig(kv_lora_rank=32, q_lora_rank=None, rope_head_dim=16,
                      nope_head_dim=16, v_head_dim=16),
        rope_scaling=RopeScaling(factor=40.0, original_max_position=4096,
                                 beta_fast=32.0, beta_slow=1.0, mscale=0.707,
                                 mscale_all_dim=0.707))


def ref_cfg(mc):
    """The reference's configuration dict (published key names)."""
    rs = mc.rope_scaling
    return {
        "hidden_size": mc.d_model, "num_attention_heads": mc.n_heads,
        "kv_lora_rank": mc.mla.kv_lora_rank,
        "qk_nope_head_dim": mc.mla.nope_head_dim,
        "qk_rope_head_dim": mc.mla.rope_head_dim,
        "v_head_dim": mc.mla.v_head_dim, "rms_norm_eps": mc.norm_eps,
        "rope_theta": mc.rope_theta,
        "rope_scaling": {"factor": rs.factor, "beta_fast": rs.beta_fast,
                         "beta_slow": rs.beta_slow, "mscale": rs.mscale,
                         "mscale_all_dim": rs.mscale_all_dim,
                         "original_max_position_embeddings":
                             rs.original_max_position, "type": "yarn"},
        "first_k_dense_replace": mc.stages[0].repeats,
        "num_hidden_layers": mc.n_layers,
        "num_experts_per_tok": mc.moe.top_k,
        "norm_topk_prob": mc.moe.norm_topk_prob,
        "routed_scaling_factor": mc.moe.routed_scaling,
        "n_routed_experts": mc.moe.n_held,
        "router_experts": mc.moe.n_experts,
        "first_held_expert": mc.moe.first_held,
    }


def params_of(model, seed=0):
    """The model's init with every norm scale moved off zero."""
    p = model.init(jax.random.PRNGKey(seed))
    k = jax.random.PRNGKey(seed + 100)
    return trees.map_with_path(
        lambda path, v: v + 0.1 * jax.random.normal(
            jax.random.fold_in(k, zlib.crc32(path.encode()) & 0xFFFF), v.shape)
        if path.endswith("scale") else v, p)


def lora_of(params, seed=1, rank=4):
    """Client factors on ``mixer/wq`` and ``mixer/wkv_b`` of every stage,
    B drawn non-zero: the program's tree and the reference's flat dict."""
    key = jax.random.PRNGKey(seed)
    flat = {}

    def mk(path, w):
        if not (path.endswith("mixer/wq") or path.endswith("mixer/wkv_b")):
            return None
        ka, kb = jax.random.split(jax.random.fold_in(key, len(path)
                                                     + w.shape[-1]))
        lf = {"a": jax.random.normal(ka, w.shape[:-1] + (rank,))
              * w.shape[-2] ** -0.5,
              "b": jax.random.normal(kb, (w.shape[0], rank, w.shape[-1]))
              * 0.05,
              "mask": jnp.ones((w.shape[0], 1, 1))}
        for n, v in lf.items():
            flat[f"{path}/{n}"] = v
        return lf

    return trees.map_with_path(mk, params), flat


def _ref_logits(params, lora_flat, tokens, mc):
    with jax.default_matmul_precision("highest"):
        return np.asarray(ref.forward(trees.flatten(params), lora_flat,
                                      jnp.asarray(tokens), ref_cfg(mc),
                                      scale=SCALE))


@pytest.mark.parametrize("backend", ["jnp", "pallas"])
def test_prefill_then_decode_match_reference(backend):
    """Prefill 12 tokens, then decode 4 more through the cache (the same
    steps the serving path jits), with the client LoRA on wq and wkv_b:
    every step's logits against the reference's full causal forward."""
    mc = small_cfg()
    model = Model(mc, meshctx=MeshCtx.single_device(),
                  opts={"lora_backend": backend})
    params = params_of(model)
    lora, lora_flat = lora_of(params)
    toks = np.asarray(jax.random.randint(jax.random.PRNGKey(3), (2, 16), 0,
                                         mc.vocab_size), np.int32)
    P, G = 12, 4
    prefill = jax.jit(make_prefill_step(model, P + G, lora_scale=SCALE))
    decode = jax.jit(make_serve_step(model, lora_scale=SCALE))
    logits, cache = prefill(params, {"tokens": jnp.asarray(toks[:, :P])},
                            lora)
    got = [np.asarray(logits)]
    for j in range(G - 1):
        logits, cache = decode(params, cache, jnp.asarray(toks[:, P + j:
                                                               P + j + 1]),
                               lora)
        got.append(np.asarray(logits))
    want = _ref_logits(params, lora_flat, toks[:, :P + G - 1], mc)
    for j, g in enumerate(got):
        np.testing.assert_allclose(g, want[:, P - 1 + j], atol=ATOL, rtol=0)
    rows = np.asarray(cache["stages"][1][0]["moe_rows"])      # (layers, 16)
    assert (rows.sum(-1) == 2 * (P + G - 1) * mc.moe.top_k).all()
    assert int(np.asarray(cache["stages"][1][0]["moe_dropped"]).sum()) == 0


def _moe_params(key, mc):
    p = moe_mod.init_moe(key, mc.d_model, mc.moe, mc.act, jnp.float32)
    return p


def _moe_ref(h, p, mc, **over):
    cfg = dict(ref_cfg(mc), **over)
    F = {"router": p["router"], "wg": p["wg"], "wu": p["wu"], "wd": p["wd"],
         **{f"shared/{k}": v for k, v in p["shared"].items()}}
    with jax.default_matmul_precision("highest"):
        return np.asarray(ref.moe_layer(h, F, cfg))


def test_shares_sum_to_the_whole_layer():
    """Four chips of four experts each: their routed parts, with the
    shared experts counted once, add up to the uncut reference layer."""
    whole = small_cfg(n_held=16)
    p = _moe_params(jax.random.PRNGKey(5), whole)
    h = jax.random.normal(jax.random.PRNGKey(6), (2, 24, whole.d_model))
    total = 0.0
    for rank in range(4):
        share = dataclasses.replace(whole.moe, n_held=4, first_held=4 * rank)
        sp = dict(p, wg=p["wg"][4 * rank:4 * rank + 4],
                  wu=p["wu"][4 * rank:4 * rank + 4],
                  wd=p["wd"][4 * rank:4 * rank + 4], shared=None)
        sp = {k: v for k, v in sp.items() if v is not None}
        y, _, counts = moe_mod.moe_held(h, sp, dataclasses.replace(
            share, n_shared_experts=0), "swiglu")
        assert int(counts["moe_dropped"]) == 0
        total = total + np.asarray(y)
    from repro.models.mlp import mlp
    total = total + np.asarray(mlp(h, p["shared"], "swiglu"))
    np.testing.assert_allclose(total, _moe_ref(h, p, whole), atol=1e-5)


def test_routing_is_dropless_under_skew():
    """Every token sends one of its picks to expert 3 and the rest to
    experts 0-2 (the router sees only positive inputs along column 3):
    four experts take all 512 rows, where a capacity of 1.25× the mean
    would hold 40 each; the held path drops none."""
    mc = small_cfg(n_held=16)
    p = _moe_params(jax.random.PRNGKey(7), mc)
    p["router"] = jnp.zeros_like(p["router"]).at[:, 3].set(10.0)
    h = jnp.abs(jax.random.normal(jax.random.PRNGKey(8), (4, 32, mc.d_model)))
    y, _, counts = moe_mod.moe_held(h, p, mc.moe, "swiglu")
    t = 4 * 32
    rows = np.asarray(counts["moe_rows"])
    assert int(counts["moe_dropped"]) == 0
    assert rows[3] == t and rows[:4].sum() == t * mc.moe.top_k
    assert int(counts["moe_hits"]) == 4
    np.testing.assert_allclose(np.asarray(y), _moe_ref(h, p, mc), atol=1e-5)


def test_prefill_chunks_agree_with_one_call(monkeypatch):
    """Token sets longer than ``HELD_CHUNK_TOKENS`` run in chunks; the
    result and the counters are those of one call."""
    mc = small_cfg(n_held=8, first_held=4)
    p = _moe_params(jax.random.PRNGKey(9), mc)
    h = jax.random.normal(jax.random.PRNGKey(10), (2, 32, mc.d_model))
    y1, _, c1 = moe_mod.moe_held(h, p, mc.moe, "swiglu")
    monkeypatch.setattr(moe_mod, "HELD_CHUNK_TOKENS", 16)
    y4, _, c4 = moe_mod.moe_held(h, p, mc.moe, "swiglu")
    np.testing.assert_allclose(np.asarray(y4), np.asarray(y1), atol=1e-6)
    np.testing.assert_array_equal(c4["moe_rows"], c1["moe_rows"])
    assert int(c4["moe_hits"]) >= int(c1["moe_hits"])
    np.testing.assert_allclose(np.asarray(y1), _moe_ref(h, p, mc), atol=1e-5)


@pytest.mark.parametrize("norm", [False, True])
def test_norm_topk_prob(norm):
    """Each setting matches the reference with the same setting, and the
    two differ: without renormalization the routed weights sum to the
    top-4's share of the softmax, under 1."""
    mc = small_cfg(n_held=16, norm_topk_prob=norm)
    p = _moe_params(jax.random.PRNGKey(11), mc)
    h = jax.random.normal(jax.random.PRNGKey(12), (2, 16, mc.d_model))
    y, _, _ = moe_mod.moe_held(h, p, mc.moe, "swiglu")
    np.testing.assert_allclose(np.asarray(y), _moe_ref(h, p, mc), atol=1e-5)
    other = _moe_ref(h, p, mc, norm_topk_prob=not norm)
    assert np.abs(np.asarray(y) - other).max() > 1e-2


def test_yarn_closed_forms():
    """DeepSeek-V2-Lite's rope part: dim 64, θ 1e4, factor 40, L0 4096,
    beta 32/1: the ramp runs from dim 10 to 23; cos/sin unscaled; the
    softmax scale is 192^-½·(0.1·0.707·ln 40 + 1)²."""
    mc = get_config("deepseek-v2-lite")
    rs = mc.rope_scaling
    got = np.asarray(rope.inv_freq(64, 1e4, rs), np.float64)
    i = np.arange(32)
    extra = 1e4 ** (-2 * i / 64)
    ramp = np.clip((i - 10) / (23 - 10), 0, 1)
    np.testing.assert_allclose(got, extra / 40 * ramp + extra * (1 - ramp),
                               rtol=1e-6)
    assert got[10] == pytest.approx(extra[10], rel=1e-6)
    assert got[23] == pytest.approx(extra[23] / 40, rel=1e-6)
    assert rope.cos_sin_scale(rs) == 1.0
    m = 0.1 * 0.707 * math.log(40) + 1
    assert softmax_scale(mc.mla, rs) == pytest.approx(192 ** -0.5 * m * m,
                                                      rel=1e-12)
    assert m * m == pytest.approx(1.5896, abs=1e-4)
    np.testing.assert_allclose(got, np.asarray(ref.yarn_inv_freq(
        {"qk_rope_head_dim": 64, "rope_theta": 1e4,
         "rope_scaling": {"factor": 40, "original_max_position_embeddings":
                          4096, "beta_fast": 32, "beta_slow": 1}})),
        rtol=1e-6)


def test_published_lite_sizes():
    """The registered config at its published sizes: 15.7B parameters, a
    direct query projection, 64 experts all held on one chip."""
    mc = get_config("deepseek-v2-lite")
    assert mc.n_layers == 27 and mc.mla.q_lora_rank is None
    assert mc.moe.n_held == 64 and not mc.moe.norm_topk_prob
    assert abs(mc.param_count() - 15.706e9) < 0.01e9
    shapes = jax.eval_shape(Model(mc.reduced()).init, jax.random.PRNGKey(0))
    mixer = shapes["stages"][1]["layers"][0]["mixer"]
    assert "wq" in mixer and "wq_a" not in mixer


@pytest.mark.parametrize("name", list_configs())
def test_every_registered_config_builds(name):
    """Every registered config still builds, reduced; deepseek-v2-236b
    keeps its q compression and the capacity MoE path."""
    mc = get_config(name)
    shapes = jax.eval_shape(Model(mc.reduced()).init, jax.random.PRNGKey(0))
    assert shapes["embed"].shape[1] == mc.reduced().d_model
    if name == "deepseek-v2-236b":
        assert mc.moe.n_held == 0 and mc.mla.q_lora_rank == 1536
        mixer = shapes["stages"][1]["layers"][0]["mixer"]
        assert "wq_a" in mixer and "q_norm" in mixer


def test_moe_gmm_kernel_matches_the_plain_path():
    """The megablox grouped matmul in the Pallas interpreter against the
    per-group path, with empty groups and rows past the last group (left
    unwritten by the kernel: only the groups' rows are compared)."""
    from repro.kernels.moe_gmm.ops import moe_gmm
    from repro.kernels.moe_gmm.ref import gmm_ref
    k1, k2 = jax.random.split(jax.random.PRNGKey(13))
    lhs = jax.random.normal(k1, (256, 128))
    rhs = jax.random.normal(k2, (4, 128, 256))
    sizes = jnp.asarray([37, 0, 120, 50], jnp.int32)
    got = np.asarray(moe_gmm(lhs, rhs, sizes, interpret=True))[:207]
    want = np.asarray(gmm_ref(lhs, rhs, sizes))
    np.testing.assert_allclose(got, want[:207], rtol=1e-4, atol=1e-3)
    assert (want[207:] == 0).all()
