"""Model: scan-based stack runner over stage patterns.

Supports decoder-only (dense/MoE/SSM/hybrid), encoder-only (roberta),
encoder-decoder (whisper), and VLM (prefix patch embeddings) families with
three entry points used by the launchers:

* ``loss``        — training objective (chunked cross-entropy / classifier)
* ``prefill``     — full-prompt forward that builds a decode cache
* ``decode_step`` — one token against the cache (``serve_step``)

Layers are grouped into stages of repeating patterns; parameters of each
pattern position are stacked along a leading repeat axis and the stack is
``lax.scan``ned (small HLO even for 95-layer models), with optional
``jax.checkpoint`` (remat) around the scan body for training.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, Stage
from repro.models.blocks import (LayerCtx, apply_layer_decode, apply_layer_seq,
                                 init_layer, layer_cache_shape)
from repro.models.norms import apply_norm
from repro.sharding import MeshCtx

AUX_WEIGHT = 0.01


def _init_norm(cfg, dim, dtype):
    p = {"scale": jnp.zeros((dim,), dtype)}
    if cfg.norm == "ln":
        p["scale"] = jnp.ones((dim,), dtype)
        p["bias"] = jnp.zeros((dim,), dtype)
    return p


class Model:
    def __init__(self, cfg: ModelConfig, meshctx: Optional[MeshCtx] = None,
                 dtype=jnp.float32, impl: str = "auto", remat: bool = False,
                 seq_shard_boundary: bool = True, opts: Optional[dict] = None):
        self.cfg = cfg
        self.meshctx = meshctx
        self.dtype = dtype
        self.impl = impl
        self.remat = remat
        self.seq_shard_boundary = seq_shard_boundary
        self.opts = opts or {}

    # ------------------------------------------------------------------ init
    def init(self, key, max_seq: int = 0) -> Dict[str, Any]:
        cfg = self.cfg
        dtype = self.dtype
        keys = jax.random.split(key, 8 + len(cfg.stages))
        params: Dict[str, Any] = {
            "embed": (jax.random.normal(keys[0], (cfg.vocab_size, cfg.d_model))
                      * 0.02).astype(dtype),
            "final_norm": _init_norm(cfg, cfg.d_model, dtype),
        }
        if not cfg.tie_embeddings:
            params["lm_head"] = (jax.random.normal(
                keys[1], (cfg.d_model, cfg.vocab_size)) * 0.02).astype(dtype)
        if cfg.pos == "learned":
            n_pos = max(cfg.max_position, max_seq, 1024)
            params["pos_embed"] = (jax.random.normal(
                keys[2], (n_pos, cfg.d_model)) * 0.02).astype(dtype)
        if cfg.n_prefix_tokens:
            params["projector"] = (jax.random.normal(
                keys[3], (cfg.prefix_dim, cfg.d_model))
                * cfg.prefix_dim ** -0.5).astype(dtype)
        if cfg.encoder_seq:
            params["enc_pos"] = (jax.random.normal(
                keys[4], (cfg.encoder_seq, cfg.d_model)) * 0.02).astype(dtype)
            params["enc_norm"] = _init_norm(cfg, cfg.d_model, dtype)
        if cfg.n_classes:
            params["cls_head"] = (jax.random.normal(
                keys[5], (cfg.d_model, cfg.n_classes)) * 0.02).astype(dtype)
        stages = []
        for si, stage in enumerate(cfg.stages):
            skey = keys[8 + si]
            layers = []
            for pi, kind in enumerate(stage.pattern):
                pkeys = jax.random.split(
                    jax.random.fold_in(skey, pi), stage.repeats)
                layers.append(jax.vmap(
                    lambda k, kd=kind: init_layer(k, cfg, kd, dtype))(pkeys))
            stages.append({"layers": layers})
        params["stages"] = stages
        return params

    # -------------------------------------------------------------- plumbing
    def _constrain(self, x, seq_shard: bool):
        mc = self.meshctx
        if mc is None or mc.mesh.size <= 1:
            return x
        seq_axis = mc.model_axis if (seq_shard and self.seq_shard_boundary) else None
        spec = mc.spec(x.shape, [mc.batch_axes, seq_axis, None])
        return jax.lax.with_sharding_constraint(x, mc.sharding(spec))

    def _run_stage_seq(self, x, sp, stage: Stage, ctx: LayerCtx,
                       collect_cache: bool, lsp=None):
        """``lsp`` is the stage's LoRA factor subtree (mirrors ``sp``): its
        rank-r leaves are stacked on the same leading repeat axis as the
        params and ride the layer scan as a second xs tree."""
        def body(carry, xs):
            layer_params, layer_lora = xs
            h = carry
            caches = []
            aux = jnp.zeros((), jnp.float32)
            for pi, kind in enumerate(stage.pattern):
                h, c, a = apply_layer_seq(h, layer_params[pi], kind, ctx,
                                          lora=layer_lora[pi])
                caches.append(c)
                aux = aux + a
            h = self._constrain(h, seq_shard=True)
            return h, (caches if collect_cache else 0, aux)

        if self.remat and ctx.mode == "train":
            body = jax.checkpoint(body)
        lora_layers = (tuple(lsp["layers"]) if lsp is not None
                       else tuple(None for _ in sp["layers"]))
        x, (caches, auxs) = jax.lax.scan(body, x,
                                         (tuple(sp["layers"]), lora_layers))
        return x, caches, auxs.sum()

    def _embed_tokens(self, params, tokens, positions):
        cfg = self.cfg
        x = params["embed"][tokens].astype(self.dtype)
        if cfg.embed_scale:
            x = x * jnp.asarray(cfg.d_model ** 0.5, self.dtype)
        if cfg.pos == "learned":
            pos_table = params["pos_embed"]
            x = x + pos_table[positions].astype(self.dtype)
        return x

    @staticmethod
    def _lora_stage(lora, si):
        """The per-stage slice of a LoRA side-channel tree (None-safe)."""
        return None if lora is None else lora["stages"][si]

    @staticmethod
    def _check_lora(lora):
        """The factored side channel only reaches layer-stack projections;
        factors mirroring any other leaf (cls_head, lm_head, embed, …)
        would be SILENTLY ignored — fail loudly at trace time instead
        (the merged oracle ``peft.apply_lora`` does support them)."""
        if lora is None:
            return
        from repro import trees
        stray = [p for p in trees.flatten(lora) if not p.startswith("stages/")]
        if stray:
            raise ValueError(
                "factored LoRA execution only supports factors on stage "
                f"layer weights; found factors at {sorted(set(stray))} — "
                "merge these with peft.apply_lora instead")

    def _encode(self, params, frames, ctx_kwargs, lora=None):
        """Whisper encoder: frames are post-conv embeddings (B, S_enc, d)."""
        cfg = self.cfg
        x = frames.astype(self.dtype) + params["enc_pos"][None].astype(self.dtype)
        ctx = LayerCtx(cfg=cfg, meshctx=self.meshctx,
                       positions=jnp.arange(frames.shape[1]),
                       causal=False, opts=self.opts, **ctx_kwargs)
        for si, stage in enumerate(cfg.stages):
            if stage.stream != "encoder":
                continue
            x, _, _ = self._run_stage_seq(x, params["stages"][si], stage, ctx,
                                          collect_cache=False,
                                          lsp=self._lora_stage(lora, si))
        return apply_norm(x, params["enc_norm"], cfg.norm, cfg.norm_eps)

    # -------------------------------------------------------------- forward
    def forward(self, params, tokens, *, frames=None, patches=None,
                impl: Optional[str] = None, mode: str = "train",
                collect_cache: bool = False, lora=None,
                lora_scale: float = 1.0):
        """Returns (hidden, aux[, caches]).  tokens: (B, S_text).

        ``lora`` is an optional factored-LoRA side channel (``peft.init_lora``
        structure, mirroring ``params``): targeted projections run
        ``y = x@W + lora_scale·(x@A)@B`` without merging, so the base stays
        unbatched under an outer client-vmap."""
        cfg = self.cfg
        impl = impl or self.impl
        self._check_lora(lora)
        memory = None
        if cfg.is_encoder_decoder:
            memory = self._encode(params, frames,
                                  dict(impl=impl, mode=mode,
                                       lora_scale=lora_scale), lora=lora)
        if cfg.is_encoder_only:
            positions = jnp.arange(tokens.shape[1])
            x = self._embed_tokens(params, tokens, positions)
            ctx = LayerCtx(cfg=cfg, meshctx=self.meshctx, positions=positions,
                           impl=impl, mode=mode, causal=False, opts=self.opts,
                           lora_scale=lora_scale)
            aux_total = jnp.zeros((), jnp.float32)
            for si, stage in enumerate(cfg.stages):
                x, _, aux = self._run_stage_seq(x, params["stages"][si], stage,
                                                ctx, collect_cache=False,
                                                lsp=self._lora_stage(lora, si))
                aux_total += aux
            x = apply_norm(x, params["final_norm"], cfg.norm, cfg.norm_eps)
            return (x, aux_total, None) if collect_cache else (x, aux_total)

        if cfg.n_prefix_tokens:
            prefix = (patches.astype(self.dtype) @ params["projector"])
            positions = jnp.arange(cfg.n_prefix_tokens + tokens.shape[1])
            xt = self._embed_tokens(params, tokens,
                                    positions[cfg.n_prefix_tokens:])
            x = jnp.concatenate([prefix, xt], axis=1)
        else:
            positions = jnp.arange(tokens.shape[1])
            x = self._embed_tokens(params, tokens, positions)

        ctx = LayerCtx(cfg=cfg, meshctx=self.meshctx, positions=positions,
                       impl=impl, memory=memory, mode=mode, opts=self.opts,
                       lora_scale=lora_scale)
        x = self._constrain(x, seq_shard=True)
        aux_total = jnp.zeros((), jnp.float32)
        caches = []
        for si, stage in enumerate(cfg.stages):
            if stage.stream != "decoder":
                caches.append(None)
                continue
            x, c, aux = self._run_stage_seq(x, params["stages"][si], stage,
                                            ctx, collect_cache=collect_cache,
                                            lsp=self._lora_stage(lora, si))
            caches.append(c)
            aux_total += aux
        x = apply_norm(x, params["final_norm"], cfg.norm, cfg.norm_eps)
        if collect_cache:
            return x, aux_total, caches
        return x, aux_total

    # ----------------------------------------------------------------- loss
    def _lm_head(self, params):
        if self.cfg.tie_embeddings:
            return params["embed"].T
        return params["lm_head"]

    def lm_loss(self, params, batch, *, impl: Optional[str] = None,
                chunk: int = 512, lora=None, lora_scale: float = 1.0):
        """Chunked cross-entropy: never materializes (B, S, vocab)."""
        cfg = self.cfg
        hidden, aux = self.forward(
            params, batch["tokens"], frames=batch.get("frames"),
            patches=batch.get("patches"), impl=impl, mode="train",
            lora=lora, lora_scale=lora_scale)
        labels, mask = batch["labels"], batch["mask"]
        if cfg.n_prefix_tokens:  # loss only on text positions
            hidden = hidden[:, cfg.n_prefix_tokens:]
        b, s, d = hidden.shape
        head = self._lm_head(params)
        chunk = min(chunk, s)
        if s % chunk:
            chunk = s
        nc = s // chunk
        hc = hidden.reshape(b, nc, chunk, d).swapaxes(0, 1)
        lc = labels.reshape(b, nc, chunk).swapaxes(0, 1)
        mc = mask.reshape(b, nc, chunk).swapaxes(0, 1)

        def step(carry, xs):
            h, l, m = xs
            logits = (h @ head).astype(jnp.float32)
            logz = jax.nn.logsumexp(logits, axis=-1)
            ll = jnp.take_along_axis(logits, l[..., None], axis=-1)[..., 0]
            return (carry[0] + ((logz - ll) * m).sum(),
                    carry[1] + m.sum()), None

        (tot, cnt), _ = jax.lax.scan(
            step, (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32)),
            (hc, lc, mc))
        return tot / jnp.maximum(cnt, 1.0) + AUX_WEIGHT * aux

    def cls_loss(self, params, batch, *, impl: Optional[str] = None,
                 lora=None, lora_scale: float = 1.0):
        """Encoder classifier loss (PFTT / roberta).  batch: tokens, label,
        and optionally ``valid`` — a (B,) sample weight the padded ragged-
        cohort path rides in (``cohort.HostBatchStacker``): the weighted
        mean over real rows equals the plain mean of the unpadded batch, so
        padded rows contribute exactly zero to loss and gradients."""
        hidden, aux = self.forward(params, batch["tokens"], impl=impl,
                                   lora=lora, lora_scale=lora_scale)
        logits = (hidden[:, 0] @ params["cls_head"]).astype(jnp.float32)
        logz = jax.nn.logsumexp(logits, axis=-1)
        ll = jnp.take_along_axis(logits, batch["label"][:, None], axis=-1)[:, 0]
        correct = (logits.argmax(-1) == batch["label"]).astype(jnp.float32)
        w = batch.get("valid")
        if w is None:
            return (logz - ll).mean() + AUX_WEIGHT * aux, correct.mean()
        wsum = jnp.maximum(w.sum(), 1.0)
        return (((logz - ll) * w).sum() / wsum + AUX_WEIGHT * aux,
                (correct * w).sum() / wsum)

    def logits(self, params, hidden):
        return (hidden @ self._lm_head(params)).astype(jnp.float32)

    # ---------------------------------------------------------------- cache
    def init_cache(self, batch: int, cache_len: int, dtype=None):
        cfg = self.cfg
        dtype = dtype or self.dtype
        stages = []
        for stage in cfg.stages:
            if stage.stream != "decoder":
                stages.append(None)
                continue
            entries = []
            for kind in stage.pattern:
                shapes = layer_cache_shape(
                    cfg, kind, batch, cache_len, dtype,
                    sparse_kv=bool(self.opts.get("sparse_kv_seq")))
                entries.append({k: jnp.zeros((stage.repeats,) + shp, dt)
                                for k, (shp, dt) in shapes.items()})
            stages.append(entries)
        return {"pos": jnp.zeros((), jnp.int32), "stages": stages}

    def cache_spec(self, batch: int, cache_len: int, dtype=None):
        """ShapeDtypeStruct pytree of the cache (for dry-run lowering)."""
        dtype = dtype or self.dtype
        return jax.eval_shape(
            functools.partial(self.init_cache, batch, cache_len, dtype))

    # -------------------------------------------------------------- prefill
    def prefill(self, params, tokens, cache_len: int, *, frames=None,
                patches=None, impl: Optional[str] = None, lora=None,
                lora_scale: float = 1.0):
        """Run the prompt, return (last_token_logits, cache)."""
        cfg = self.cfg
        hidden, _, caches = self.forward(
            params, tokens, frames=frames, patches=patches, impl=impl,
            mode="prefill", collect_cache=True, lora=lora,
            lora_scale=lora_scale)
        s_prompt = hidden.shape[1]
        stages = []
        for si, stage in enumerate(cfg.stages):
            if stage.stream != "decoder":
                stages.append(None)
                continue
            entries = []
            for pi, kind in enumerate(stage.pattern):
                entry = {}
                raw = caches[si][pi]
                shapes = layer_cache_shape(cfg, kind, tokens.shape[0],
                                           cache_len, self.dtype)
                for name, (shp, dt) in shapes.items():
                    full = jnp.zeros((stage.repeats,) + shp, dt)
                    got = raw[name].astype(dt)
                    if name in ("h", "conv", "xk", "xv") or name.startswith(
                            "moe_"):
                        entry[name] = got      # not positions of the prompt
                        continue
                    sc = shp[1]  # cache seq length for this layer kind
                    if got.shape[2] <= sc:
                        entry[name] = jax.lax.dynamic_update_slice_in_dim(
                            full, got, 0, axis=2)
                    else:  # ring (window) cache: keep last sc positions
                        tail = got[:, :, -sc:]
                        slots = jnp.mod(jnp.arange(s_prompt - sc, s_prompt), sc)
                        entry[name] = full.at[:, :, slots].set(tail)
                entries.append(entry)
            stages.append(entries)
        cache = {"pos": jnp.asarray(s_prompt, jnp.int32), "stages": stages}
        last = hidden[:, -1]
        return self.logits(params, last), cache

    # ---------------------------------------------------------------- decode
    def decode_step(self, params, cache, tokens, *, impl: Optional[str] = None,
                    lora=None, lora_scale: float = 1.0):
        """tokens: (B, 1) → (logits (B, vocab), updated cache)."""
        cfg = self.cfg
        impl = impl or self.impl
        self._check_lora(lora)
        pos = cache["pos"]
        x = self._embed_tokens(params, tokens,
                               jnp.full(tokens.shape, pos, jnp.int32))
        ctx = LayerCtx(cfg=cfg, meshctx=self.meshctx, positions=None,
                       impl=impl, mode="decode", pos=pos, opts=self.opts,
                       lora_scale=lora_scale)
        new_stages = []
        for si, stage in enumerate(cfg.stages):
            if stage.stream != "decoder":
                new_stages.append(cache["stages"][si])
                continue

            def body(carry, xs, stage=stage):
                h = carry
                layer_params, cache_slices, layer_lora = xs
                new_slices = []
                for pi, kind in enumerate(stage.pattern):
                    h, nc = apply_layer_decode(h, layer_params[pi], kind,
                                               cache_slices[pi], ctx,
                                               lora=layer_lora[pi])
                    new_slices.append(nc)
                return h, new_slices

            lsp = self._lora_stage(lora, si)
            lora_layers = (tuple(lsp["layers"]) if lsp is not None
                           else tuple(None for _ in stage.pattern))
            x, new_cache = jax.lax.scan(
                body, x, (tuple(params["stages"][si]["layers"]),
                          tuple(cache["stages"][si]), lora_layers))
            new_stages.append(list(new_cache))
        x = apply_norm(x, params["final_norm"], cfg.norm, cfg.norm_eps)
        logits = self.logits(params, x[:, 0])
        return logits, {"pos": pos + 1, "stages": new_stages}
