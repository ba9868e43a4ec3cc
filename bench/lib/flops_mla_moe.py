"""Required operations and bytes of a latent-attention MoE configuration
(``bench/configs/deepseek-v2-lite.json``), counted from its shapes as
``lib/flops.py`` counts the dense ones: a matmul of (m, k) by (k, n)
counts 2·m·k·n; no recompute, no masked-out attention, no elementwise
work.  Routed experts count only the rows the program routed to this
chip's held experts (its ``moe_rows`` counters), never a capacity or a
padded bound.

Prefill runs latent attention in sequence form (per-head keys and values
unfolded from the latent); decode in absorbed form (the query folded into
the latent, attention against the compressed cache, the client's
``wkv_b`` factors merged into the latent weight once a step)."""
from __future__ import annotations


def _dims(cfg):
    return (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["kv_lora_rank"], cfg["qk_nope_head_dim"],
            cfg["qk_rope_head_dim"], cfg["v_head_dim"])


def _lora(cfg, target, din, dout) -> float:
    p = cfg["peft"]
    return (2 * p["lora_rank"] * (din + dout)
            if target in p["lora_targets"] else 0.0)


def ff_token(cfg, layer: int) -> float:
    """Feed-forward FLOPs of one token in ``layer``, routed experts left
    out: the dense SwiGLU, or the router and the shared experts."""
    d = cfg["hidden_size"]
    if layer < cfg["first_k_dense_replace"]:
        return 3 * 2 * d * cfg["intermediate_size"]
    sf = cfg["n_shared_experts"] * cfg["moe_intermediate_size"]
    return 2 * d * cfg["router_experts"] + 3 * 2 * d * sf


def expert_row(cfg) -> float:
    """FLOPs of one (token, held expert) row: the expert's SwiGLU."""
    return 3 * 2 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def mla_seq_token(cfg, ctx: float) -> float:
    """Sequence-form latent attention of one token over ``ctx`` keys."""
    d, H, r, dn, dr, dv = _dims(cfg)
    q = 2 * d * H * (dn + dr) + _lora(cfg, "mixer/wq", d, H * (dn + dr))
    kv_a = 2 * d * (r + dr)
    kv_b = 2 * r * H * (dn + dv) + _lora(cfg, "mixer/wkv_b", r, H * (dn + dv))
    att = 2 * H * (dn + dr) * ctx + 2 * H * dv * ctx
    return q + kv_a + kv_b + att + 2 * H * dv * d


def mla_decode_token(cfg, ctx: float) -> float:
    """Absorbed latent attention of one new token over ``ctx`` cache
    entries (the merge of ``wkv_b``'s factors is per step: ``mla_merge``)."""
    d, H, r, dn, dr, dv = _dims(cfg)
    q = 2 * d * H * (dn + dr) + _lora(cfg, "mixer/wq", d, H * (dn + dr))
    kv_a = 2 * d * (r + dr)
    absorb = 2 * H * dn * r + 2 * H * r * dv
    att = 2 * H * (r + dr) * ctx + 2 * H * r * ctx
    return q + kv_a + absorb + att + 2 * H * dv * d


def mla_merge(cfg) -> float:
    """One step's merge of the client's ``wkv_b`` factors into the latent
    weight, per layer (0 without a LoRA there)."""
    d, H, r, dn, dr, dv = _dims(cfg)
    p = cfg["peft"]
    return (2 * r * p["lora_rank"] * H * (dn + dv)
            if "mixer/wkv_b" in p["lora_targets"] else 0.0)


def prefill(cfg, *, batch: int, prompt: int) -> float:
    """Prefill FLOPs without the routed experts: causal attention (token t
    over t + 1 keys, so the mean context is (prompt + 1)/2: the count is
    affine in ctx), and the LM head of each prompt's last position."""
    L = cfg["num_hidden_layers"]
    per_tok = sum(mla_seq_token(cfg, (prompt + 1) / 2) + ff_token(cfg, l)
                  for l in range(L))
    return batch * (prompt * per_tok
                    + 2 * cfg["hidden_size"] * cfg["vocab_size"])


def decode_step(cfg, *, batch: int, ctx: int) -> float:
    """One decode step without the routed experts: each row's new token
    over ``ctx`` cache entries (itself included) and the LM head."""
    L = cfg["num_hidden_layers"]
    per_tok = sum(mla_decode_token(cfg, ctx) + ff_token(cfg, l)
                  for l in range(L))
    return (batch * (per_tok + 2 * cfg["hidden_size"] * cfg["vocab_size"])
            + L * mla_merge(cfg))


def batch_flops(cfg, *, batch: int, prompt: int, gen: int,
                routed_rows: float) -> float:
    """One served batch: the prefill, ``gen - 1`` decode steps and the
    ``routed_rows`` (token, held expert) rows the program counted."""
    return (prefill(cfg, batch=batch, prompt=prompt)
            + sum(decode_step(cfg, batch=batch, ctx=prompt + j)
                  for j in range(1, gen))
            + routed_rows * expert_row(cfg))


def gmm_call(rows: float, hit: float, k: int, n: int, itemsize: int = 4):
    """(FLOPs, bytes) of grouped-matmul work: ``rows`` routed rows of
    width ``k`` times the weights (k, n) of the ``hit`` experts that have
    rows; every weight slab hit, every input row and every output row
    moved once."""
    return 2 * rows * k * n, itemsize * (hit * k * n + rows * (k + n))


def expert_layer_gmm(cfg, rows: float, hits: float):
    """(FLOPs, bytes) of one expert layer's three grouped matmuls (gate,
    up: d -> f; down: f -> d) over ``rows`` rows and ``hits`` expert hits."""
    d, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    fl, nb = 0.0, 0.0
    for k, n in ((d, f), (d, f), (f, d)):
        a, b = gmm_call(rows, hits, k, n)
        fl, nb = fl + a, nb + b
    return fl, nb
