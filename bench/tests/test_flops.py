"""FLOP and byte counts, and the peak table."""
import json

import jax
import jax.numpy as jnp
import pytest

from lib import flops, peaks, ref_models, weights


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peaks("TPU v99")
    assert peaks.peaks("TPU v5 lite")["flops_bf16"] == 197e12


def test_lora_fused_call_counts():
    f, b = flops.lora_fused_call(8, 768, 768, 8, 4)
    assert f == 2 * 8 * 768 * 768 + 2 * 8 * 768 * 8 + 2 * 8 * 8 * 768
    assert b == 4 * (8 * 768 + 768 * 768 + 768 * 8 + 8 * 768 + 8 * 768)
    t, bound = flops.roofline_time(f, b, 197e12, 819e9)
    assert bound == "bytes" and t == pytest.approx(b / 819e9)


def test_roberta_base_train_tokens_near_350_mflop():
    cfg = json.loads(open(peaks.__file__.replace(
        "lib/peaks.py", "configs/roberta-base.json")).read())
    per_tok = flops.encoder_train_tokens(cfg, 128)
    assert 3.4e8 < per_tok < 3.6e8


def test_train_count_against_jaxpr_walk(tiny_roberta):
    """The required count of one training step against the program's
    jaxpr FLOP walker (``launch/jaxpr_cost.py``) on the plain reference's
    step, differentiated, as in training, with respect to the LoRA
    factors, adapters and head only. The walker counts what executes: the
    same matmuls plus one FLOP per elementwise output (norms, softmax,
    GELU, the optimizer-free gradient plumbing), which at this reduced
    width (d 64, 2 layers) adds 10-20%; at d 768 the elementwise share is
    a few percent. So walker / required lies in [1.0, 1.2]."""
    from repro.launch.jaxpr_cost import step_flops
    cfg = tiny_roberta
    B, S = 4, 16
    p = weights.make_params(cfg, weights.jax_key(1))
    train = {k: v for k, v in p.items()
             if "/adapter/" in k or k == "cls_head"}
    frozen = {k: v for k, v in p.items() if k not in train}
    lora = {k: v[0] for k, v in weights.make_lora(
        cfg, weights.jax_key(2), [0]).items()}
    toks = jnp.zeros((B, S), jnp.int32)
    labels = jnp.zeros((B,), jnp.int32)

    def loss(train, lora):
        return ref_models.cls_loss({**frozen, **train}, lora, toks, labels,
                                   cfg, scale=2.0)

    walked = step_flops(jax.grad(loss, argnums=(0, 1)), train, lora)
    required = B * (S * flops.encoder_train_tokens(cfg, S)
                    + 3 * 2 * cfg["hidden_size"] * cfg["num_labels"])
    assert 1.0 < walked / required < 1.2, walked / required
