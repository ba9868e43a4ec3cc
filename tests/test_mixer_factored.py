"""Factored LoRA through the non-dense mixer families: MLA and Mamba.

The universal fused path requires every mixer family to accept the
{'a','b','mask'} factor side channel unmerged: MLA's four low-rank
projections (``wq_a``/``wq_b``/``wkv_a``/``wkv_b``, including the
absorbed-decode latent-space merge), Mamba's ``in_proj``/``out_proj``, and
the Jamba attention+SSM hybrid.  Parity target is the ``apply_lora``
dense-merge oracle — forward hidden states, LM loss, factor gradients,
prefill/decode logits — under per-client vmap (frozen base unbatched) and
through ``run_arch_round`` on a 1-device mesh.  The trace-time
``peft.dense_merge_count`` counter proves the factored path never
materializes a dense delta."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import trees
from repro.configs import get_config
from repro.models import Model
from repro.models import peft as peft_mod
from repro.sharding import MeshCtx, auto_mesh

KEY = jax.random.PRNGKey(0)

MLA_TARGETS = ("mixer/wq_a", "mixer/wq_b", "mixer/wkv_a", "mixer/wkv_b")
SSM_TARGETS = ("mixer/in_proj", "mixer/out_proj")


def _randomize_factors(lora, seed=1):
    """init_lora zeros B (delta starts at 0); give every factor leaf real
    values so parity actually exercises the low-rank path."""
    def rnd(x):
        if hasattr(x, "ndim") and x.ndim >= 2 and x.shape[-2:] != (1, 1):
            return jax.random.normal(jax.random.fold_in(KEY, seed),
                                     x.shape) * 0.05
        return x
    return jax.tree_util.tree_map(rnd, lora)


def _mk(arch, targets, d_model=32, repeats=2, rank=4, seed=1):
    mcfg = get_config(arch).reduced(d_model=d_model, repeats=repeats)
    model = Model(mcfg, meshctx=MeshCtx.single_device())
    params = model.init(KEY, max_seq=64)
    pc = peft_mod.PEFTConfig(lora_rank=rank, lora_alpha=2.0 * rank,
                             lora_targets=targets)
    lora = _randomize_factors(peft_mod.init_lora(KEY, params, pc), seed=seed)
    return mcfg, model, params, pc, lora


def _toks(mcfg, shape=(2, 12), seed=2):
    return jax.random.randint(jax.random.fold_in(KEY, seed), shape, 6,
                              mcfg.vocab_size)


def _lm_batch(mcfg, b=2, s=12, seed=2):
    toks = np.asarray(_toks(mcfg, (b, s + 1), seed))
    return {"tokens": jnp.asarray(toks[:, :-1]),
            "labels": jnp.asarray(toks[:, 1:]),
            "mask": jnp.ones((b, s), jnp.float32)}


# ---------------------------------------------------------------------------
# forward / loss / gradient parity vs the dense-merge oracle
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("arch,targets", [
    ("deepseek-v2-236b", MLA_TARGETS),
    ("mamba2-1.3b", SSM_TARGETS),
    ("jamba-v0.1-52b", ("mixer/wq", "mixer/wv") + SSM_TARGETS),
])
def test_forward_parity(arch, targets):
    mcfg, model, params, pc, lora = _mk(arch, targets)
    toks = _toks(mcfg)
    merged = peft_mod.apply_lora(params, lora, pc)
    h_m, _ = model.forward(merged, toks)
    h_f, _ = model.forward(params, toks, lora=lora,
                           lora_scale=peft_mod.lora_scale(pc))
    np.testing.assert_allclose(np.asarray(h_f), np.asarray(h_m), atol=1e-5)


@pytest.mark.parametrize("arch,targets", [
    ("deepseek-v2-236b", MLA_TARGETS),
    ("mamba2-1.3b", SSM_TARGETS),
])
def test_loss_and_grad_parity(arch, targets):
    mcfg, model, params, pc, lora = _mk(arch, targets)
    batch = _lm_batch(mcfg)
    scale = peft_mod.lora_scale(pc)
    lm, gm = jax.value_and_grad(lambda lo: model.lm_loss(
        peft_mod.apply_lora(params, lo, pc), batch))(lora)
    lf, gf = jax.value_and_grad(lambda lo: model.lm_loss(
        params, batch, lora=lo, lora_scale=scale))(lora)
    np.testing.assert_allclose(float(lf), float(lm), atol=1e-5)
    flat_f = trees.flatten(gf)
    for path, gmv in trees.flatten(gm).items():
        np.testing.assert_allclose(np.asarray(flat_f[path]), np.asarray(gmv),
                                   atol=1e-5, err_msg=path)


def test_factored_forward_traces_zero_dense_merges():
    """The observable no-fallback invariant: tracing the factored forward
    must not bump the dense-merge counter (the oracle path must)."""
    for arch, targets in (("deepseek-v2-236b", MLA_TARGETS),
                          ("mamba2-1.3b", SSM_TARGETS)):
        mcfg, model, params, pc, lora = _mk(arch, targets)
        toks = _toks(mcfg)
        m0 = peft_mod.dense_merge_count()
        model.forward(params, toks, lora=lora,
                      lora_scale=peft_mod.lora_scale(pc))
        assert peft_mod.dense_merge_count() == m0, arch
        peft_mod.apply_lora(params, lora, pc)
        assert peft_mod.dense_merge_count() > m0   # counter itself works


# ---------------------------------------------------------------------------
# serving parity: prefill + cached decode
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("arch,targets", [
    ("deepseek-v2-236b", MLA_TARGETS),   # absorbed decode: latent-space merge
    ("mamba2-1.3b", SSM_TARGETS),        # conv/ssm state caches
    ("jamba-v0.1-52b", ("mixer/wq", "mixer/wv") + SSM_TARGETS),
])
def test_prefill_decode_parity(arch, targets):
    mcfg, model, params, pc, lora = _mk(arch, targets)
    scale = peft_mod.lora_scale(pc)
    prompts = _toks(mcfg, (2, 8), seed=3)
    merged = peft_mod.apply_lora(params, lora, pc)
    lg_m, c_m = model.prefill(merged, prompts, cache_len=12)
    lg_f, c_f = model.prefill(params, prompts, cache_len=12, lora=lora,
                              lora_scale=scale)
    np.testing.assert_allclose(np.asarray(lg_f), np.asarray(lg_m), atol=1e-4)
    tok = jnp.argmax(lg_m, -1)[:, None].astype(jnp.int32)
    d_m, _ = model.decode_step(merged, c_m, tok)
    d_f, _ = model.decode_step(params, c_f, tok, lora=lora, lora_scale=scale)
    np.testing.assert_allclose(np.asarray(d_f), np.asarray(d_m), atol=1e-4)


def test_launch_serve_steps_thread_lora():
    """launch.steps prefill/serve builders expose the factored side channel."""
    from repro.launch.steps import make_prefill_step, make_serve_step
    mcfg, model, params, pc, lora = _mk("deepseek-v2-236b", MLA_TARGETS)
    scale = peft_mod.lora_scale(pc)
    prompts = _toks(mcfg, (2, 8), seed=3)
    prefill = make_prefill_step(model, cache_len=12, lora_scale=scale)
    serve = make_serve_step(model, lora_scale=scale)
    lg_f, cache = prefill(params, {"tokens": prompts}, lora=lora)
    merged = peft_mod.apply_lora(params, lora, pc)
    lg_m, _ = model.prefill(merged, prompts, cache_len=12)
    np.testing.assert_allclose(np.asarray(lg_f), np.asarray(lg_m), atol=1e-4)
    tok = jnp.argmax(lg_m, -1)[:, None].astype(jnp.int32)
    d_f, _ = serve(params, cache, tok, lora=lora)
    assert d_f.shape == (2, mcfg.vocab_size)


# ---------------------------------------------------------------------------
# client vmap: frozen base stays unbatched, only factors carry the axis
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("arch,targets", [
    ("deepseek-v2-236b", MLA_TARGETS),
    ("mamba2-1.3b", SSM_TARGETS),
])
def test_client_vmap_parity(arch, targets):
    mcfg, model, params, pc, _ = _mk(arch, targets)
    scale = peft_mod.lora_scale(pc)
    loras = [_randomize_factors(peft_mod.init_lora(KEY, params, pc), seed=s)
             for s in (1, 2, 3)]
    batches = [_lm_batch(mcfg, seed=10 + s) for s in range(3)]
    stacked_lora = trees.stack(loras)
    stacked_batch = jax.tree_util.tree_map(
        lambda *ls: jnp.stack(ls), *batches)

    def client_loss(lf, b):       # params closed over: unbatched base
        return model.lm_loss(params, b, lora=lf, lora_scale=scale)

    fused = jax.vmap(client_loss)(stacked_lora, stacked_batch)
    for ci in range(3):
        ref = model.lm_loss(peft_mod.apply_lora(params, loras[ci], pc),
                            batches[ci])
        np.testing.assert_allclose(float(fused[ci]), float(ref), atol=1e-5)


# ---------------------------------------------------------------------------
# end-to-end: fused federated round on a 1-device mesh vs oracle loop
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("arch", ["deepseek-v2-236b", "mamba2-1.3b"])
def test_arch_round_one_device_mesh_matches_oracle(arch):
    from repro.core.arch_round import ArchRoundConfig, run_arch_round
    mesh = auto_mesh((1,), ("data",))
    res = run_arch_round(
        ArchRoundConfig(arch=arch, n_clients=2, rounds=1, local_steps=2,
                        batch=3, seq_len=12, d_model=32, oracle=True),
        mesh=mesh, client_axes=("data",))
    assert res["dense_merges_in_engine"] == 0
    assert res["dispatches_per_round"] == 1.0
    assert res["ragged"]                       # unequal client batch sizes
    assert res["oracle_loss_max_err"] <= 1e-5


@pytest.mark.slow
@pytest.mark.parametrize("arch", ["jamba-v0.1-52b", "dbrx-132b",
                                  "whisper-base", "gpt2-small"])
def test_arch_round_matrix_remaining_cells(arch):
    from repro.core.arch_round import ArchRoundConfig, run_arch_round
    res = run_arch_round(
        ArchRoundConfig(arch=arch, n_clients=2, rounds=1, local_steps=2,
                        batch=3, seq_len=12, d_model=32, oracle=True))
    assert res["dense_merges_in_engine"] == 0
    assert res["dispatches_per_round"] == 1.0
    assert res["oracle_loss_max_err"] <= 1e-5
