"""Parameter-efficient fine-tuning: LoRA + bottleneck adapters.

PFTT (paper §IV-D) composes both: *universal adapters* (aggregated globally)
and *local LoRA* (kept on-client).  PFIT (paper §IV-C) uses last-K-layer
unfreezing with a head-structured sparsity mask over attention parameters.

Representation choices:
* LoRA factors mirror targeted 2-D (or stacked 3-D) weight leaves:
  ``W (…, din, dout) → A (…, din, r), B (…, r, dout)``, with a per-repeat
  enable mask so clients can LoRA only their last-n layers ("10-12 local
  LoRAs based on local resources").
* **Factored execution contract** (the default hot path): the lora tree is
  threaded through the model forward as a *side channel* next to ``params``
  and every targeted projection computes

      y = x @ W + (α/r) · ((x @ A) @ (mask · B))        # ``lora_proj``

  so the dense ``(din, dout)`` delta is never formed, the frozen base ``W``
  stays UNBATCHED under the cohort engine's client-vmap (only the rank-r
  factors carry the client axis), and autodiff produces factor gradients
  directly.  ``Model.{lm_loss,cls_loss,forward,prefill,decode_step}`` all
  accept ``lora=``/``lora_scale=``; ``lora_proj(backend="pallas")`` lowers
  the projection to the fused ``repro.kernels.lora_fused`` kernel (the
  serving path).  Layer masks ride along the layer scan as ``(repeats,1,1)``
  leaves.
* ``apply_lora`` (materialize ``W + (α/r)·mask·A·B`` and run the plain
  forward) is kept as the merged parity ORACLE — exercised by tests and by
  the ``factored=False`` flags in ``core/pftt.py``/``core/pfit.py``.
* Adapters are genuine new modules (bottleneck ``up(gelu(down(x)))`` with a
  residual) injected per layer; ``blocks.apply_layer_*`` applies them when
  the key is present.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro import trees
from repro.configs.base import ModelConfig

LORA_DEFAULT_TARGETS = ("mixer/wq", "mixer/wv", "mixer/wq_a", "mixer/wq_b",
                        "mixer/wkv_a", "mixer/wkv_b", "mixer/in_proj",
                        "mixer/out_proj")


@dataclasses.dataclass(frozen=True)
class PEFTConfig:
    lora_rank: int = 8
    lora_alpha: float = 16.0
    lora_targets: Tuple[str, ...] = LORA_DEFAULT_TARGETS
    lora_layers: int = 0          # 0 → all repeats; n → only last n repeats
    adapter_dim: int = 64
    enable_lora: bool = True
    enable_adapters: bool = True


def _is_target(path: str, targets) -> bool:
    return any(path.endswith(t) for t in targets)


# ---------------------------------------------------------------------------
# LoRA
# ---------------------------------------------------------------------------


def init_lora(key, params, peft: PEFTConfig) -> Dict:
    """Mirror of ``params`` with {'a','b','mask'} at each targeted leaf and
    None elsewhere (mergeable structure)."""
    flat = trees.flatten(params)
    seed = [0]

    def make(path, w):
        if not _is_target(path, peft.lora_targets) or w.ndim < 2:
            return None
        k = jax.random.fold_in(key, seed[0]); seed[0] += 1
        *lead, din, dout = w.shape
        r = peft.lora_rank
        a = (jax.random.normal(k, (*lead, din, r)) * din ** -0.5).astype(w.dtype)
        b = jnp.zeros((*lead, r, dout), w.dtype)
        if lead:
            # always (repeats, 1, 1) so the factors AND their enable mask can
            # ride the layer scan together (scalar masks are not scannable)
            mask = (jnp.arange(lead[0]) >= lead[0] - peft.lora_layers
                    if peft.lora_layers else jnp.ones((lead[0],), bool))
            mask = mask.astype(w.dtype).reshape(lead[0], *([1] * 2))
        else:
            mask = jnp.ones((), w.dtype)
        return {"a": a, "b": b, "mask": mask}

    return trees.map_with_path(make, params)


# Trace-time dense-merge accounting: every merge of a present factor leaf
# bumps this counter, so tests and the arch-matrix launcher can assert the
# factored hot path never fell back to materializing ``W + s·A·B`` (compile
# caching means later identical rounds don't re-trace — a zero delta over a
# run proves the fused program contains no merged weights).
_DENSE_MERGE_COUNT = [0]


def dense_merge_count() -> int:
    """Number of factor-leaf dense merges traced so far (process-global)."""
    return _DENSE_MERGE_COUNT[0]


def merge_factors(params, lora, scale: float):
    """Dense-merge ``W + scale·mask·(A·B)`` over any (sub)tree pair.  The
    merged parity oracle — and the per-layer fallback for the one remaining
    module whose internals don't accept factors (the MoE expert FFN)."""
    if lora is None:
        return params

    def combine(w, l):
        if l is None:
            return w
        _DENSE_MERGE_COUNT[0] += 1
        delta = jnp.einsum("...dr,...rf->...df", l["a"], l["b"])
        return w + scale * jax.lax.stop_gradient(l["mask"]) * delta

    return jax.tree_util.tree_map(combine, params, lora,
                                  is_leaf=is_lora_leaf)


def apply_lora(params, lora, peft: PEFTConfig):
    """Materialize W + (α/r)·mask·(A·B) for targeted leaves (merged oracle;
    the hot path threads factors via ``lora_proj`` instead)."""
    if lora is None:
        return params
    return merge_factors(params, lora, peft.lora_alpha / peft.lora_rank)


def merge_lora(params, lora, peft: PEFTConfig):
    """Permanent merge (legacy serving path; factored serving threads the
    lora tree instead — see ``lora_proj``)."""
    return apply_lora(params, lora, peft)


# ---------------------------------------------------------------------------
# Factored (unmerged) execution — the hot-path contract
# ---------------------------------------------------------------------------


def lora_scale(peft: PEFTConfig) -> float:
    """The α/r multiplier of the low-rank path."""
    return peft.lora_alpha / peft.lora_rank


def is_lora_leaf(x) -> bool:
    """is_leaf predicate for {'a','b','mask'} factor dicts (or None)."""
    return x is None or (isinstance(x, dict) and "a" in x)


def has_factors(lf) -> bool:
    """True if a factor (sub)tree carries any actual {'a','b'} leaf —
    distinguishes a real side channel from the all-None mirror
    ``init_lora`` leaves on untargeted weights."""
    if lf is None:
        return False
    return any(isinstance(l, dict) and l.get("a") is not None
               for l in jax.tree_util.tree_leaves(lf, is_leaf=is_lora_leaf))


def effective_weight(w, lf, scale: float):
    """Merge ONE leaf's factors into its base weight: ``W + scale·(A·(mask·
    B))``.  Reserved for contractions that consume the weight itself rather
    than projecting activations through it (absorbed-MLA decode contracts
    q/ctx against ``wkv_b`` directly) — there the merged matrix lives in the
    LATENT space (kv_lora_rank × heads·dims, the same order as the factor's
    own B), never a d_model² delta, so it does not count as a dense-merge
    fallback."""
    if lf is None or lf.get("a") is None:
        return w
    b = lf["b"] * jax.lax.stop_gradient(lf["mask"]).astype(lf["b"].dtype)
    return w + scale * (lf["a"] @ b)


def lora_proj(x, w, lf, *, scale: float, backend: str = "jnp"):
    """Factored LoRA projection ``y = x@W + scale·((x@A)@(mask·B))``.

    ``lf`` is the {'a','b','mask'} factor dict mirroring ``w`` (or None →
    plain ``x@w``).  The dense (din, dout) delta is never materialized, so
    under a client-vmap only the rank-r factors carry the client axis while
    ``w`` stays broadcast.  ``backend="pallas"`` lowers the whole projection
    to the fused ``repro.kernels.lora_fused`` kernel (serving path; 2-D
    unstacked weights only).
    """
    if lf is None or lf.get("a") is None:
        return x @ w
    a, b = lf["a"], lf["b"]
    mask = jax.lax.stop_gradient(lf["mask"])
    # fold the per-layer enable mask into B: mask is () or (1, 1) once the
    # layer scan has sliced the (repeats, 1, 1) leaf, broadcasting over
    # (r, dout) — identical math to masking the dense delta
    b = b * mask.astype(b.dtype)
    if backend == "pallas":
        if w.ndim != 2 or a.ndim != 2 or x.ndim < 2:
            raise ValueError(
                f"lora_proj(backend='pallas') takes 2-D unstacked weights and "
                f"x of rank >= 2; got x {x.shape}, w {w.shape}, a {a.shape}")
        from repro.kernels.lora_fused.ops import lora_matmul
        return lora_matmul(x, w, a, b, scale=scale)
    return x @ w + scale * ((x @ a) @ b)


@dataclasses.dataclass(frozen=True)
class LoraProj:
    """A projection bundling a frozen base weight with optional rank-r
    factors; calling it runs ``lora_proj``.  ``blocks._proj`` builds one
    per targeted weight so the factored path reads like the dense path."""
    w: object
    lf: Optional[dict] = None
    scale: float = 1.0
    backend: str = "jnp"

    def __call__(self, x):
        return lora_proj(x, self.w, self.lf, scale=self.scale,
                         backend=self.backend)


# ---------------------------------------------------------------------------
# Adapters
# ---------------------------------------------------------------------------


def adapter_fwd(x, ap):
    """Bottleneck adapter with residual: x + up(gelu(down(x))).
    Called inside the layer scan, so weights are already unstacked 2-D."""
    return x + jax.nn.gelu(x @ ap["wd"]) @ ap["wu"]


def init_adapters(key, params, cfg: ModelConfig, peft: PEFTConfig):
    """Insert an ``adapter`` dict into every stacked layer of every stage.
    Returns a *new params tree* (base params unchanged, adapters added)."""
    new_stages = []
    for si, stage_params in enumerate(params["stages"]):
        stage = cfg.stages[si]
        new_layers = []
        for pi, lp in enumerate(stage_params["layers"]):
            k = jax.random.fold_in(key, si * 64 + pi)
            r = stage.repeats
            a = peft.adapter_dim
            wd = (jax.random.normal(k, (r, cfg.d_model, a))
                  * cfg.d_model ** -0.5).astype(params["embed"].dtype)
            wu = jnp.zeros((r, a, cfg.d_model), params["embed"].dtype)
            new_layers.append(dict(lp, adapter={"wd": wd, "wu": wu}))
        new_stages.append(dict(stage_params, layers=new_layers))
    return dict(params, stages=new_stages)


def strip_adapters(params):
    new_stages = []
    for sp in params["stages"]:
        new_layers = [{k: v for k, v in lp.items() if k != "adapter"}
                      for lp in sp["layers"]]
        new_stages.append(dict(sp, layers=new_layers))
    return dict(params, stages=new_stages)


# ---------------------------------------------------------------------------
# Trainable/frozen splits & path predicates (used by FL aggregation too)
# ---------------------------------------------------------------------------


def is_adapter_path(path: str) -> bool:
    return "/adapter/" in path


def is_lora_path(path: str) -> bool:  # within a lora tree everything is lora
    return True


def last_k_layers_mask(params, cfg: ModelConfig, k: int):
    """Gradient mask: 1.0 on the last-k repeats of the LAST decoder stage
    (+ the final norm / heads), 0.0 elsewhere — PFIT's 'train only the last
    two layers'."""
    decoder_stages = [si for si, s in enumerate(cfg.stages)
                      if s.stream == "decoder"]
    # encoder-only models (roberta): unfreeze the last encoder layers instead
    last_si = max(decoder_stages) if decoder_stages else len(cfg.stages) - 1
    r = cfg.stages[last_si].repeats
    lo = max(0, r - k)

    def mk(path, v):
        if path.startswith(f"stages/{last_si}/layers/"):
            lm = (jnp.arange(r) >= lo).astype(jnp.float32)
            return lm.reshape((r,) + (1,) * (v.ndim - 1))
        if path.startswith(("final_norm", "cls_head", "value_head",
                            "reward_head")):
            return jnp.ones((), jnp.float32)
        return jnp.zeros((), jnp.float32)

    return trees.map_with_path(mk, params)


def head_sparsity_mask(params, cfg: ModelConfig, sparsity: float, seed: int):
    """The paper's sparse-attention *communication* mask: zero out a
    ``sparsity`` fraction of attention heads' q/o parameters (head-structured)
    so they are neither trained nor uploaded.  Deterministic per seed
    (client)."""
    h, hd = cfg.n_heads, cfg.hd
    if h == 0:
        return trees.map_with_path(lambda p, v: jnp.ones((), jnp.float32), params)
    n_keep = max(1, int(round(h * (1.0 - sparsity))))
    key = jax.random.PRNGKey(seed)
    keep = jnp.zeros((h,)).at[
        jax.random.permutation(key, h)[:n_keep]].set(1.0)
    per_dim = jnp.repeat(keep, hd)  # (h*hd,)

    def mk(path, v):
        if re.search(r"mixer/w[qkv]$", path) and v.shape[-1] == h * hd:
            # wq always; wk/wv only when MHA (kv heads == q heads) so the
            # head-structured mask stays well defined under GQA
            return per_dim.reshape((1,) * (v.ndim - 1) + (h * hd,))
        if re.search(r"mixer/wo$", path) and v.shape[-2] == h * hd:
            return per_dim.reshape((1,) * (v.ndim - 2) + (h * hd, 1))
        return jnp.ones((), jnp.float32)

    return trees.map_with_path(mk, params)


def apply_grad_mask(grads, *masks):
    out = grads
    for m in masks:
        out = jax.tree_util.tree_map(lambda g, mm: g * mm.astype(g.dtype),
                                     out, m)
    return out
