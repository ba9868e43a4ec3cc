"""Serving launcher: prefill + batched KV-cached decode, optionally with a
per-client LoRA (PFTT personalized serving).

    PYTHONPATH=src python -m repro.launch.serve --arch llama3.2-1b --reduced \
        --batch 4 --prompt-len 32 --gen 32

``serve()`` is the same path as a function: it compiles the prefill and
decode programs ahead of time (their compile seconds are set-up, reported
apart from the step seconds) and returns what it served.
"""
import argparse
import time
import zlib

import jax
import jax.numpy as jnp
import numpy as np

from repro import trees
from repro.compile_cache import enable_compile_cache
from repro.configs import get_config, list_configs
from repro.models import Model
from repro.models import peft as peft_mod
from repro.sharding import MeshCtx


def _client_lora(key, params, pc):
    """A random personalized LoRA: ``init_lora``'s zero B would serve the
    base model unchanged, so B is drawn too, as a trained client's is."""
    lora = peft_mod.init_lora(key, params, pc)
    kb = jax.random.fold_in(key, 1)
    return trees.map_with_path(
        lambda p, v: (jax.random.normal(
            jax.random.fold_in(kb, zlib.crc32(p.encode())), v.shape)
            * 0.02).astype(v.dtype) if p.endswith("/b") else v, lora)


def serve(cfg, *, batch: int, prompt_len: int, gen: int, lora_rank: int = 0,
          lora_merge: bool = False, seed: int = 0):
    """Prefill ``batch`` random prompts and greedily decode ``gen`` tokens.

    With ``lora_rank`` a random client LoRA is served UNMERGED through the
    fused Pallas projection (``lora_merge`` bakes it into the base instead).
    Returns the model, params, LoRA, prompts, generated tokens, the prefill
    and per-step decode logits, compile and step seconds, and the compiled
    decode program's HLO text."""
    serve_factored = bool(lora_rank) and not lora_merge
    model = Model(cfg, meshctx=MeshCtx.single_device(),
                  opts={"lora_backend": "pallas"} if serve_factored else None)
    key = jax.random.PRNGKey(seed)
    cache_len = prompt_len + gen
    params = model.init(key, max_seq=cache_len)
    lora, lscale, pc = None, 1.0, None
    if lora_rank:
        pc = peft_mod.PEFTConfig(lora_rank=lora_rank)
        lora = _client_lora(key, params, pc)
        lscale = peft_mod.lora_scale(pc)
        if lora_merge:
            params = peft_mod.merge_lora(params, lora, pc)
            lora = None

    rng = np.random.RandomState(seed)
    kw = {}
    if cfg.is_encoder_decoder:
        kw["frames"] = jnp.asarray(rng.randn(batch, cfg.encoder_seq,
                                             cfg.d_model), jnp.float32)
    if cfg.n_prefix_tokens:
        kw["patches"] = jnp.asarray(rng.randn(batch, cfg.n_prefix_tokens,
                                              cfg.prefix_dim), jnp.float32)
    prompts = jnp.asarray(rng.randint(6, cfg.vocab_size,
                                      size=(batch, prompt_len)))

    def prefill_fn(p, toks, lf, extra):
        return model.prefill(p, toks, cache_len=cache_len, lora=lf,
                             lora_scale=lscale, **extra)

    def decode_fn(p, cache, tok, lf):
        return model.decode_step(p, cache, tok, lora=lf, lora_scale=lscale)

    t0 = time.perf_counter()
    prefill = jax.jit(prefill_fn).lower(params, prompts, lora, kw).compile()
    prefill_compile_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    logits, cache = prefill(params, prompts, lora, kw)
    logits.block_until_ready()
    prefill_s = time.perf_counter() - t0
    prefill_logits = np.asarray(logits)

    tok0 = jnp.zeros((batch, 1), jnp.int32)
    t0 = time.perf_counter()
    decode = jax.jit(decode_fn).lower(params, cache, tok0, lora).compile()
    decode_compile_s = time.perf_counter() - t0

    tokens, decode_logits = [], []
    t0 = time.perf_counter()
    for _ in range(gen):
        nxt = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
        tokens.append(nxt[:, 0])
        logits, cache = decode(params, cache, nxt, lora)
        decode_logits.append(logits)
    logits.block_until_ready()
    decode_s = time.perf_counter() - t0
    return {
        "model": model, "params": params, "lora": lora, "peft": pc,
        "lora_scale": lscale, "prompts": prompts,
        "tokens": np.stack([np.asarray(t) for t in tokens], 1),
        "prefill_logits": prefill_logits,
        "decode_logits": [np.asarray(lg) for lg in decode_logits],
        "prefill_compile_s": prefill_compile_s,
        "decode_compile_s": decode_compile_s,
        "prefill_s": prefill_s, "decode_s": decode_s,
        "decode_hlo": decode.as_text(),
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=list_configs())
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--lora-rank", type=int, default=0,
                    help="serve with a random personalized LoRA (PFTT mode)")
    ap.add_argument("--lora-merge", action="store_true",
                    help="legacy: bake the LoRA into the base weights "
                         "(default serves factored/unmerged via the fused "
                         "Pallas projection)")
    args = ap.parse_args()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    if cfg.is_encoder_only:
        raise SystemExit("encoder-only architectures have no decode path")
    enable_compile_cache()
    if args.lora_rank:
        print(f"serving {'merged' if args.lora_merge else 'UNMERGED'} client "
              f"LoRA (rank {args.lora_rank}"
              + (")" if args.lora_merge
                 else ", fused Pallas lowering): base stays shared"))
    res = serve(cfg, batch=args.batch, prompt_len=args.prompt_len,
                gen=args.gen, lora_rank=args.lora_rank,
                lora_merge=args.lora_merge)
    print(f"compile (set-up): prefill {res['prefill_compile_s']:.2f}s, "
          f"decode {res['decode_compile_s']:.2f}s")
    print(f"prefill: {res['prefill_s']:.2f}s "
          f"({args.batch}×{args.prompt_len} tokens)")
    dt = res["decode_s"]
    print(f"decode: {args.gen} steps in {dt:.2f}s "
          f"→ {args.batch * args.gen / dt:.1f} tok/s")
    print("sample:", res["tokens"][0][:16])


if __name__ == "__main__":
    main()
