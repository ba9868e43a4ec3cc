"""Plain references: the transformer stacks the cells run, written out
layer by layer in ``jax.numpy``, with no kernels, no cache, no scan and no
batching tricks. They read flat path -> array dicts (``lib/weights.py``
paths) and import nothing of the program.

The architecture is the program's statement of each model (pre-norm
layers, bias-free projections, tanh-approximated GELU, learned positions
from 0, no embedding norm); ``bench/configs/<name>.json`` lists where that
departs from the published model. Precision is the caller's: the
comparison runs these in float32 at the matmul precision the configuration
states (``matmul_precision``, JAX's ``default``: one bfloat16 pass on the
TPU, the products accumulated in float32), and the control runs the same
code with every weight, activation and optimizer state in bfloat16
(``dtype``), the precision below float32.

Per layer (``x`` the residual stream, ``s`` the LoRA scale alpha/r):

    h = LN1(x);  q, k, v = P(h, wq), P(h, wk), P(h, wv)
                 with P(h, w) = h·w + s·(h·A_w)·(mask·B_w) where w has LoRA
    x = x + softmax(q·kᵀ/√hd [+ causal mask])·v · wo
    x = x + gelu(LN2(x)·wu)·wd
    x = x + gelu(x·ad_wd)·ad_wu          (where the model has adapters)
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

LP = "stages/0/layers/0/"


def layer_norm(x, scale, bias, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * scale + bias


def gelu(x):
    c = math.sqrt(2.0 / math.pi)
    return 0.5 * x * (1.0 + jnp.tanh(c * (x + 0.044715 * x * x * x)))


def _proj(h, P, lora, name, l, scale):
    y = h @ P[LP + name][l]
    a = lora.get(LP + name + "/a")
    if a is not None:
        # the enable mask switches a layer's factors on or off; it is not
        # trained
        b = lora[LP + name + "/b"][l] * jax.lax.stop_gradient(
            lora[LP + name + "/mask"][l])
        y = y + scale * ((h @ a[l]) @ b)
    return y


def stack_forward(P, lora, tokens, cfg, *, causal: bool, scale: float,
                  dtype=jnp.float32):
    """Hidden states after the final norm, (B, S, d). ``P`` holds the base
    (and adapter) weights, ``lora`` the client's factors (may be empty);
    everything is computed in ``dtype``."""
    P = {k: v.astype(dtype) for k, v in P.items()}
    lora = {k: v.astype(dtype) for k, v in lora.items()}
    eps = cfg["layer_norm_eps"]
    nh = cfg["num_attention_heads"]
    b, s = tokens.shape
    d = cfg["hidden_size"]
    hd = d // nh
    x = P["embed"][tokens] + P["pos_embed"][:s][None]
    allowed = (jnp.tril(jnp.ones((s, s), bool)) if causal
               else jnp.ones((s, s), bool))
    for l in range(cfg["num_hidden_layers"]):
        h = layer_norm(x, P[LP + "norm1/scale"][l], P[LP + "norm1/bias"][l],
                       eps)
        q = _proj(h, P, lora, "mixer/wq", l, scale)
        k = _proj(h, P, lora, "mixer/wk", l, scale)
        v = _proj(h, P, lora, "mixer/wv", l, scale)
        q, k, v = (t.reshape(b, s, nh, hd) for t in (q, k, v))
        att = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.asarray(
            math.sqrt(hd), dtype)
        att = jnp.where(allowed, att, jnp.asarray(-1e30, att.dtype))
        att = jax.nn.softmax(att, axis=-1)
        o = jnp.einsum("bhqk,bkhd->bqhd", att, v).reshape(b, s, d)
        x = x + _proj(o, P, lora, "mixer/wo", l, scale)
        h = layer_norm(x, P[LP + "norm2/scale"][l], P[LP + "norm2/bias"][l],
                       eps)
        x = x + gelu(h @ P[LP + "ff/wu"][l]) @ P[LP + "ff/wd"][l]
        if LP + "adapter/wd" in P:
            x = x + gelu(x @ P[LP + "adapter/wd"][l]) @ P[LP + "adapter/wu"][l]
    return layer_norm(x, P["final_norm/scale"], P["final_norm/bias"], eps)


def cls_loss(P, lora, tokens, labels, cfg, *, scale, dtype=jnp.float32):
    """Mean cross-entropy of the classifier on the first position."""
    h = stack_forward(P, lora, tokens, cfg, causal=False, scale=scale,
                      dtype=dtype)
    logits = (h[:, 0] @ P["cls_head"].astype(dtype)).astype(jnp.float32)
    ll = jnp.take_along_axis(logits, labels[:, None], -1)[:, 0]
    return jnp.mean(jax.nn.logsumexp(logits, -1) - ll)


def cls_predict(P, lora, tokens, cfg, *, scale, dtype=jnp.float32):
    h = stack_forward(P, lora, tokens, cfg, causal=False, scale=scale,
                      dtype=dtype)
    logits = (h[:, 0] @ P["cls_head"].astype(dtype)).astype(jnp.float32)
    return jnp.argmax(logits, -1)


def lm_logits(P, lora, tokens, positions, cfg, *, scale,
              dtype=jnp.float32):
    """Logits (tied embeddings) at ``positions`` of each row: (B, n, V)."""
    h = stack_forward(P, lora, tokens, cfg, causal=True, scale=scale,
                      dtype=dtype)
    h = h[:, positions]
    return (h @ P["embed"].astype(dtype).T).astype(jnp.float32)
