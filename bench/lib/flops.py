"""Required operations and bytes, counted from a configuration's shapes.

These are the FLOPs the algorithm needs, not what the compiled program
executes: no recompute, no masked-out attention, no elementwise work
(layer norms, softmax and activations are a few percent and are left out,
as model-FLOP utilization conventionally does). A matmul of (m, k) by
(k, n) counts 2·m·k·n.

Configuration keys (``bench/configs/<name>.json``): ``hidden_size``,
``num_hidden_layers``, ``intermediate_size``, ``vocab_size``, and
``peft`` with ``lora_rank``, ``lora_targets`` and ``adapter_dim``.
"""
from __future__ import annotations


def _dims(cfg):
    d = cfg["hidden_size"]
    return d, cfg["num_hidden_layers"], cfg["intermediate_size"]


def _peft(cfg):
    p = cfg.get("peft", {})
    return (p.get("lora_rank", 0), len(p.get("lora_targets", ())),
            p.get("adapter_dim", 0) if p.get("adapters", False) else 0)


def layer_forward(cfg, ctx: float) -> float:
    """Forward FLOPs of one layer for one token that attends over ``ctx``
    keys: the q/k/v/o and feed-forward projections, the two attention
    products (scores and weighted values), the LoRA side paths (x·A and
    (x·A)·B per target) and the bottleneck adapter (down and up)."""
    d, _, dff = _dims(cfg)
    r, nt, m = _peft(cfg)
    dense = 2 * (4 * d * d + 2 * d * dff)
    attn = 2 * 2 * d * ctx
    lora = nt * 2 * (2 * d * r)
    adapter = 2 * (2 * d * m)
    return dense + attn + lora + adapter


def encoder_train_tokens(cfg, seq: int) -> float:
    """Forward plus backward FLOPs per training token of the encoder
    classifier with a frozen base: the backward pass takes gradients with
    respect to activations through every layer (one matmul per forward
    projection, two per attention product) and with respect to the LoRA
    and adapter weights only. The first layer's q/k/v projections need no
    input gradient, since nothing below them trains. The classifier head
    (first token only) is counted per sequence in ``encoder_train_round``."""
    d, L, dff = _dims(cfg)
    r, nt, m = _peft(cfg)
    fwd = L * layer_forward(cfg, seq)
    dense_bwd = 2 * (4 * d * d + 2 * d * dff)
    attn_bwd = 2 * (2 * 2 * d * seq)
    side_bwd = 2 * (nt * 2 * (2 * d * r) + 2 * (2 * d * m))
    bwd = L * (dense_bwd + attn_bwd + side_bwd) - 3 * 2 * d * d
    return fwd + bwd


def encoder_train_round(cfg, *, cohort: int, local_steps: int, batch: int,
                        seq: int) -> float:
    """Training FLOPs of one round: every client's local steps."""
    d = cfg["hidden_size"]
    c = cfg.get("num_labels", 0)
    seqs = cohort * local_steps * batch
    head = 3 * 2 * d * c       # forward, input and weight gradient
    return seqs * (seq * encoder_train_tokens(cfg, seq) + head)


def encoder_eval_round(cfg, *, cohort: int, rows: int, seq: int) -> float:
    """Forward FLOPs of one round's eval: every client's held-out rows."""
    d, L, _ = _dims(cfg)
    c = cfg.get("num_labels", 0)
    seqs = cohort * rows
    return seqs * (seq * L * layer_forward(cfg, seq) + 2 * d * c)


def decoder_prefill(cfg, *, batch: int, prompt: int) -> float:
    """Prefill FLOPs: causal attention (token t attends over t+1 keys) and
    the LM head for the last position of each prompt only."""
    d, L, _ = _dims(cfg)
    # layer_forward is affine in ctx: the mean over tokens t = 0..prompt-1,
    # attending over t + 1 keys, is at ctx (prompt + 1) / 2
    per_seq = prompt * L * layer_forward(cfg, (prompt + 1) / 2)
    return batch * (per_seq + 2 * d * cfg["vocab_size"])


def decoder_decode_step(cfg, *, batch: int, ctx: int) -> float:
    """FLOPs of one decode step: each row's new token attends over ``ctx``
    keys (itself included) and goes through the LM head."""
    d, L, _ = _dims(cfg)
    return batch * (L * layer_forward(cfg, ctx) + 2 * d * cfg["vocab_size"])


def lora_fused_call(m: int, k: int, n: int, r: int, itemsize: int):
    """(FLOPs, bytes) of one fused LoRA projection ``x·W + s·(x·A)·B``:
    x (m, k), W (k, n), A (k, r), B (r, n), out (m, n), each read or
    written once."""
    flops = 2 * m * k * n + 2 * m * k * r + 2 * m * r * n
    nbytes = itemsize * (m * k + k * n + k * r + r * n + m * n)
    return flops, nbytes


def roofline_time(flops: float, nbytes: float, peak_flops: float,
                  peak_bytes_per_s: float):
    """(least seconds, bound): the larger of compute and memory time, and
    which of the two it is."""
    tc, tm = flops / peak_flops, nbytes / peak_bytes_per_s
    return (tc, "flops") if tc >= tm else (tm, "bytes")
