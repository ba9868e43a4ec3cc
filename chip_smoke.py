#!/usr/bin/env python3
"""Smoke run of the system's main path on a TPU, at published widths.

    python chip_smoke.py              # one chip: phases (a) and (b)
    python chip_smoke.py --chips 4    # four chips: phase (c) only

(a) Trainer: ``run_pftt`` on roberta-base's published config (12 layers,
    d768, vocab 50265) — a cohort of 8 clients, seq 128, 5 local steps,
    3 rounds, through the fused factored engine.  Checks finite losses and
    accuracies, one round-step dispatch per round, no dense LoRA merge, and
    parity with the per-client loop (``engine=False``) over 2 rounds.
(b) Server: ``launch.serve.serve`` on gpt2-small's published config, a
    rank-8 client LoRA served unmerged through the compiled ``lora_fused``
    kernel; batch 4, prompt 128, 16 decoded tokens.  Checks that the
    compiled decode step holds the kernel (``tpu_custom_call``) and that the
    prefill and decode logits match the merged-LoRA jnp path.
(c) ``--chips 4``: the round of (a) with the cohort sharded over a
    ("data",) mesh of four chips, against the same cohort on one device.

Weights are random from fixed seeds.  Every timing printed is a set-up or
smoke timing, not a benchmark number.  The last line of stdout is one JSON
object naming the device; any failed check exits non-zero before it.
"""
import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

import numpy as np

SRC = Path(__file__).resolve().parent / "src"

# Tolerances, fixed before the first chip run.
# Engine vs per-client loop, and sharded vs one device: the same f32 math
# in differently fused programs.  On the TPU an f32 matmul at default
# precision is one bf16 pass (8-bit mantissa), so a last-bit difference in
# an input can round to a different bf16 value (2^-9 relative); over 10
# AdamW steps that stays well under 1% of the ~1.4 mean loss.
LOSS_RTOL = 1e-2
# A rounding-level logit difference can flip a near-tie prediction: allow
# about one flipped sample per client in the mean test accuracy.
ACC_ATOL = 5e-2
# Served logits (default precision: bf16-pass matmuls through 12 layers)
# against the merged-LoRA reference at "highest" precision, as a fraction
# of the largest reference logit.  The LoRA itself moves the logits by
# ~25% of that (gpt2-small, rank 8, measured on the CPU), so a dropped or
# mis-tiled LoRA path cannot pass.
SERVE_RTOL = 2e-2

SERVE_SMOKE = dict(batch=4, prompt_len=128, gen=16, lora_rank=8)
PFTT_SMOKE = dict(n_clients=8, seq_len=128, local_steps=5, rounds=3,
                  batch=8,            # per client; the compile rehearsal
                                      # puts the round step at ~10 GB
                  pretrain_steps=4, samples_per_client=100,
                  published_widths=True, engine=True, factored=True, seed=0)


class CompileMeter:
    """Backend compile seconds and persistent-cache hits, from JAX's own
    monitoring events (a cache hit's compile event times the read)."""

    def __init__(self, jax):
        self.secs = 0.0
        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.secs += secs

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def snapshot(self):
        return self.secs, self.hits, self.misses


class Checks:
    def __init__(self):
        self.failed = []

    def __call__(self, name, ok, detail=""):
        print(f"  check {name}: {'ok' if ok else 'FAILED'} {detail}".rstrip())
        if not ok:
            self.failed.append(name)


def _timed(meter, fn, *args, **kw):
    c0, h0, m0 = meter.snapshot()
    t0 = time.perf_counter()
    out = fn(*args, **kw)
    wall = time.perf_counter() - t0
    c1, h1, m1 = meter.snapshot()
    return out, {"wall_s": wall, "compile_s": c1 - c0,
                 "cache_hits": h1 - h0, "cache_misses": m1 - m0}


def _timing_line(label, t):
    print(f"  {label} (set-up + smoke timing, not a benchmark): wall "
          f"{t['wall_s']:.2f}s, of which backend compile {t['compile_s']:.2f}s "
          f"(persistent cache hits {t['cache_hits']}, misses "
          f"{t['cache_misses']})")


def _peak(jax):
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def phase_trainer(jax, meter, check):
    from repro.configs import get_config
    from repro.core.pftt import PFTTConfig, run_pftt
    from repro.models import peft

    cfg = PFTTConfig(**PFTT_SMOKE)
    print(f"[a] trainer: run_pftt roberta-base, published widths "
          f"{cfg.published_widths}; {cfg.n_clients} clients x batch "
          f"{cfg.batch} x seq {cfg.seq_len}, {cfg.local_steps} local steps, "
          f"{cfg.rounds} rounds, fused factored engine")
    merges0 = peft.dense_merge_count()
    eng, t = _timed(meter, run_pftt, cfg)
    _timing_line("run_pftt engine, 3 rounds", t)
    published = get_config("roberta-base")
    print(f"  backbone run: {eng['model']}")
    if cfg.published_widths:
        check("backbone at published widths",
              eng["model"] == {"name": published.name,
                               "n_layers": published.n_layers,
                               "d_model": published.d_model,
                               "vocab_size": published.vocab_size})
    print(f"  losses/round {eng['loss_per_round']}")
    print(f"  accs/round   {eng['acc_per_round']}")
    check("finite losses", bool(np.isfinite(eng["loss_per_round"]).all()))
    check("finite accs", bool(np.isfinite(eng["acc_per_round"]).all()))
    check("one round-step dispatch per round",
          eng["dispatches_per_round"] == 1.0,
          f"({eng['dispatches_per_round']})")
    check("no dense LoRA merge traced",
          peft.dense_merge_count() == merges0,
          f"({peft.dense_merge_count() - merges0} merges)")
    print(f"  peak_bytes_in_use {_peak(jax)}")

    loop, t = _timed(meter, run_pftt,
                     dataclasses.replace(cfg, rounds=2, engine=False))
    _timing_line("run_pftt per-client loop, 2 rounds", t)
    eng_l, loop_l = np.asarray(eng["loss_per_round"][:2]), np.asarray(
        loop["loss_per_round"])
    err = float(np.max(np.abs(eng_l - loop_l) / np.abs(loop_l)))
    acc_err = float(np.max(np.abs(np.asarray(eng["acc_per_round"][:2])
                                  - np.asarray(loop["acc_per_round"]))))
    print(f"  loop losses/round {loop['loss_per_round']}, accs/round "
          f"{loop['acc_per_round']}")
    check("engine matches per-client loop (loss)", err <= LOSS_RTOL,
          f"(max rel err {err:.3e}, tol {LOSS_RTOL})")
    check("engine matches per-client loop (acc)", acc_err <= ACC_ATOL,
          f"(max abs err {acc_err:.3e}, tol {ACC_ATOL})")
    print(f"  peak_bytes_in_use {_peak(jax)}")


def phase_server(jax, meter, check):
    from repro.configs import get_config
    from repro.launch.serve import serve
    from repro.models import Model, peft
    from repro.sharding import MeshCtx

    cfg = get_config("gpt2-small")
    batch, prompt_len, gen, rank = (SERVE_SMOKE[k] for k in (
        "batch", "prompt_len", "gen", "lora_rank"))
    print(f"[b] server: launch.serve gpt2-small published widths (layers "
          f"{cfg.n_layers}, d_model {cfg.d_model}, vocab {cfg.vocab_size}); "
          f"LoRA rank {rank} unmerged via lora_fused; batch {batch}, prompt "
          f"{prompt_len}, {gen} decoded tokens")
    res, t = _timed(meter, serve, cfg, batch=batch, prompt_len=prompt_len,
                    gen=gen, lora_rank=rank)
    _timing_line("serve", t)
    print(f"  compile (set-up): prefill {res['prefill_compile_s']:.2f}s, "
          f"decode {res['decode_compile_s']:.2f}s; smoke timing: prefill "
          f"{res['prefill_s']:.4f}s, {gen} decode steps {res['decode_s']:.4f}s")
    check("decode step holds the Pallas kernel (tpu_custom_call)",
          "tpu_custom_call" in res["decode_hlo"])

    ref_model = Model(cfg, meshctx=MeshCtx.single_device())
    merged = peft.merge_lora(res["params"], res["lora"], res["peft"])
    cache_len = prompt_len + gen
    with jax.default_matmul_precision("highest"):
        prefill = jax.jit(lambda p, toks: ref_model.prefill(
            p, toks, cache_len=cache_len))
        decode = jax.jit(ref_model.decode_step)
        ref, cache = prefill(merged, res["prompts"])
        ref = np.asarray(ref)
        scale = float(np.abs(ref).max())
        pre_err = float(np.abs(res["prefill_logits"] - ref).max()) / scale
        dec_err = 0.0
        for i in range(gen):
            ref, cache = decode(merged, cache,
                                jax.numpy.asarray(res["tokens"][:, i:i + 1]))
            ref = np.asarray(ref)
            dec_err = max(dec_err, float(np.abs(res["decode_logits"][i]
                                                - ref).max())
                          / float(np.abs(ref).max()))
    check("prefill logits match merged-LoRA jnp reference",
          pre_err <= SERVE_RTOL, f"(rel err {pre_err:.3e}, tol {SERVE_RTOL})")
    check("decode logits match merged-LoRA jnp reference",
          dec_err <= SERVE_RTOL, f"(max rel err {dec_err:.3e}, tol "
                                 f"{SERVE_RTOL})")
    print(f"  peak_bytes_in_use {_peak(jax)}")


def phase_four_chips(jax, meter, check):
    from repro.core.pftt import PFTTConfig, run_pftt
    from repro.sharding import auto_mesh

    cfg = PFTTConfig(**PFTT_SMOKE)
    mesh = auto_mesh((4,), ("data",))
    print(f"[c] four chips: the round of [a] with the cohort sharded over "
          f"mesh {dict(mesh.shape)}, against the same cohort on one device")
    one, t = _timed(meter, run_pftt, cfg)
    _timing_line("run_pftt one device", t)
    four, t = _timed(meter, run_pftt, cfg, mesh=mesh, client_axes=("data",))
    _timing_line("run_pftt sharded over 4", t)
    print(f"  one device losses/round {one['loss_per_round']} accs/round "
          f"{one['acc_per_round']}")
    print(f"  4 chips    losses/round {four['loss_per_round']} accs/round "
          f"{four['acc_per_round']}")
    l1, l4 = np.asarray(one["loss_per_round"]), np.asarray(
        four["loss_per_round"])
    err = float(np.max(np.abs(l4 - l1) / np.abs(l1)))
    acc_err = float(np.max(np.abs(np.asarray(four["acc_per_round"])
                                  - np.asarray(one["acc_per_round"]))))
    check("finite losses", bool(np.isfinite(l4).all()))
    check("sharded matches one device (loss)", err <= LOSS_RTOL,
          f"(max rel err {err:.3e}, tol {LOSS_RTOL})")
    check("sharded matches one device (acc)", acc_err <= ACC_ATOL,
          f"(max abs err {acc_err:.3e}, tol {ACC_ATOL})")
    check("stacked state spans 4 devices",
          four["cohort_state_devices"] == 4,
          f"({four['cohort_state_devices']} devices)")
    check("one round-step dispatch per round",
          four["dispatches_per_round"] == 1.0)
    for d in jax.devices():
        print(f"  {d} peak_bytes_in_use "
              f"{(d.memory_stats() or {}).get('peak_bytes_in_use')}")


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the sharded four-chip phase")
    args = ap.parse_args()
    if not (SRC / "repro").is_dir():
        sys.exit(f"chip_smoke: {SRC / 'repro'} not found; run this script "
                 f"from a checkout of the repository")
    sys.path.insert(0, str(SRC))

    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: no TPU found — JAX's first device is "
                 f"{dev.platform} ({dev.device_kind}); this smoke run needs "
                 f"a TPU")
    if len(devices) < args.chips:
        sys.exit(f"chip_smoke: --chips {args.chips} needs {args.chips} TPU "
                 f"devices, found {len(devices)}")

    from repro.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    meter = CompileMeter(jax)
    print(f"device: {dev.platform} {dev.device_kind!r} x {len(devices)}; "
          f"jax {jax.__version__}; compile cache {cache_dir}")
    check = Checks()
    t0 = time.perf_counter()
    if args.chips == 4:
        phase_four_chips(jax, meter, check)
    else:
        phase_trainer(jax, meter, check)
        phase_server(jax, meter, check)
    secs, hits, misses = meter.snapshot()
    print(f"total (smoke timing): {time.perf_counter() - t0:.1f}s, backend "
          f"compile {secs:.1f}s, persistent cache hits {hits} misses "
          f"{misses}")
    if check.failed:
        sys.exit(f"chip_smoke: failed checks: {check.failed}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))


if __name__ == "__main__":
    main()
