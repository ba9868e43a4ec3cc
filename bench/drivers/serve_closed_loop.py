"""Driver: closed-loop serving with one client LoRA, one batch in flight.

The timed path is the program's serving step pair: jitted
``launch/steps.py::make_prefill_step`` and ``make_serve_step`` on a
``Model`` whose LoRA backend is the traffic's (``pallas``: the factored
projection lowers to ``kernels/lora_fused``), with the cache donated to
each decode step. Each batch of requests is due when the previous one has
finished: its prompts are drawn (``lib/data.py``), prefilled, and decoded
greedily, one token per step, the host reading every step's tokens as a
streaming server does.

TTFT is timed from a batch's due time to the host holding its first
tokens; the gap between tokens from one host read to the next. The check
once the window has closed: the plain reference's full forward pass over
a seeded sample of the window's requests, prompt and served tokens, and
the widest gap by which a served token's reference logit lies below the
reference's best (greedy tokens only); and the compiled decode step must
hold the kernel (``tpu_custom_call``).
"""
from __future__ import annotations

import gc
import sys
import time
from types import SimpleNamespace

import numpy as np

from lib import (checks, data, flops, harness, peaks, program, ref_models,
                 trace_reduce, weights)

HOST_SPANS = ("batch", "prompt", "prefill", "decode")
TRAFFIC_KEYS = ("driver", "batch", "prompt_len", "gen_tokens", "warm_index",
                "trace_batches", "kernel_match", "check_requests",
                "check_block", "limits")


def build(ctx, seed: int):
    import jax
    import jax.numpy as jnp
    from repro.launch.steps import make_prefill_step, make_serve_step
    from repro.models import Model
    from repro.sharding import MeshCtx

    cfg, tr = ctx.config, ctx.traffic
    harness.check_traffic(tr, TRAFFIC_KEYS)
    peft = cfg["peft"]
    b = SimpleNamespace()
    b.streams = dict(zip(("weights", "clients", "data", "sample"),
                         weights.seed_streams(seed, 4)))
    mcfg = program.program_config(cfg)
    model = Model(mcfg, meshctx=MeshCtx.single_device(),
                  opts={"lora_backend": peft["backend"]})
    flat = weights.make_params(cfg, weights.jax_key(b.streams["weights"]))
    program.check_layout(flat, jax.eval_shape(model.init,
                                              jax.random.PRNGKey(0)))
    base = weights.nest(flat)
    lf = weights.make_lora(cfg, weights.jax_key(b.streams["clients"]), [0])
    targets = set(weights.lora_leaves(cfg))
    lora = weights.mirror(base, lambda p, v: {
        "a": lf[p + "/a"][0], "b": lf[p + "/b"][0],
        "mask": lf[p + "/mask"][0]} if p in targets else None)
    scale = peft["lora_alpha"] / peft["lora_rank"]
    B, P, G = tr["batch"], tr["prompt_len"], tr["gen_tokens"]
    cache_len = P + G
    prefill = jax.jit(make_prefill_step(model, cache_len, lora_scale=scale))
    decode = jax.jit(make_serve_step(model, lora_scale=scale),
                     donate_argnums=(1,))
    argmax = jax.jit(lambda lg: jnp.argmax(lg, -1).astype(jnp.int32)[:, None])
    ann = jax.profiler.TraceAnnotation
    V = cfg["vocab_size"]

    def serve_batch(index):
        """Serve one batch; returns the host times of each token read
        (G of them) and the served tokens (B, G)."""
        with ann("batch"):
            with ann("prompt"):
                toks = jnp.asarray(data.prompts(
                    b.streams["data"], index, batch=B, length=P, vocab=V))
            with ann("prefill"):
                logits, cache = prefill(base, {"tokens": toks}, lora)
                tok = argmax(logits)
                got = [np.asarray(tok)]
            times = [time.perf_counter()]
            for _ in range(G - 1):
                with ann("decode"):
                    logits, cache = decode(base, cache, tok, lora)
                    tok = argmax(logits)
                    got.append(np.asarray(tok))
                times.append(time.perf_counter())
        return times, np.concatenate(got, 1)

    b.serve_batch = serve_batch
    b.decode_hlo = lambda: decode.lower(
        base, model.cache_spec(B, cache_len),
        jax.ShapeDtypeStruct((B, 1), jnp.int32), lora).compile().as_text()
    return b


def reference_gaps(cfg, tr, streams, picks, served, control=False):
    """Widest served-token gap over the sampled requests ``picks``
    ((batch index, row) pairs) with their ``served`` tokens (n, G), by the
    float32 reference at the configuration's matmul precision; with
    ``control`` also the same gap for the tokens that the reference
    computed in bfloat16 puts first."""
    import jax
    import jax.numpy as jnp
    P, G = tr["prompt_len"], tr["gen_tokens"]
    base = weights.make_params(cfg, weights.jax_key(streams["weights"]))
    lf = weights.make_lora(cfg, weights.jax_key(streams["clients"]), [0])
    lora = {k: v[0] for k, v in lf.items()}
    scale = cfg["peft"]["lora_alpha"] / cfg["peft"]["lora_rank"]
    pos = np.arange(P - 1, P + G - 1)
    seqs = np.stack([np.concatenate([data.prompts(
        streams["data"], i, batch=tr["batch"], length=P,
        vocab=cfg["vocab_size"])[r], served[j, :G - 1]])
        for j, (i, r) in enumerate(picks)]).astype(np.int32)

    fns = {dt: jax.jit(lambda P_, L_, t, dt=dt: ref_models.lm_logits(
        P_, L_, t, pos, cfg, scale=scale, dtype=dt))
        for dt in (jnp.float32, jnp.bfloat16)}

    gaps, ctl = [], []
    block = tr["check_block"]
    for s in range(0, len(seqs), block):
        rows = jnp.asarray(seqs[s:s + block])
        with jax.default_matmul_precision(cfg["matmul_precision"]):
            ref = np.asarray(fns[jnp.float32](base, lora, rows))
            low = (np.asarray(fns[jnp.bfloat16](base, lora, rows))
                   if control else None)
        gaps.append(checks.served_gap(ref, served[s:s + block]))
        if control:
            ctl.append(checks.served_gap(ref, low.argmax(-1)))
    return max(gaps), (max(ctl) if control else None)


def batch_flops(cfg, tr) -> float:
    B, P, G = tr["batch"], tr["prompt_len"], tr["gen_tokens"]
    return (flops.decoder_prefill(cfg, batch=B, prompt=P)
            + sum(flops.decoder_decode_step(cfg, batch=B, ctx=P + j)
                  for j in range(1, G)))


def kernel_least_s(cfg, tr, batches, pk):
    """Least time and bound of the traced batches' lora_fused calls."""
    d, r = cfg["hidden_size"], cfg["peft"]["lora_rank"]
    per = len(cfg["peft"]["lora_targets"]) * cfg["num_hidden_layers"]
    B, P, G = tr["batch"], tr["prompt_len"], tr["gen_tokens"]
    least, bounds = 0.0, set()
    for m, n_calls in ((B * P, per), (B, per * (G - 1))):
        f, nb = flops.lora_fused_call(m, d, d, r, 4)
        t, bound = flops.roofline_time(f, nb, pk["flops_bf16"],
                                       pk["hbm_bytes_per_s"])
        least += t * n_calls * batches
        bounds.add(bound)
    return least, "+".join(sorted(bounds)), batches * per * G


def run(ctx) -> dict:
    import jax
    cfg, tr = ctx.config, ctx.traffic
    B, G = tr["batch"], tr["gen_tokens"]
    b = build(ctx, ctx.seed)
    b.serve_batch(tr["warm_index"])           # compiles every shape

    m0 = ctx.meter.snapshot()
    t0 = time.perf_counter()
    setup_s = t0 - ctx.t_start
    due, ttft, gaps, served, index = t0, [], [], [], 0
    while True:
        times, toks = b.serve_batch(index)
        ttft.append(times[0] - due)
        gaps.extend(np.diff(times))
        served.append(toks)
        due = times[-1]
        index += 1
        if due - t0 >= ctx.seconds:
            break
    window_s = due - t0
    m1 = ctx.meter.snapshot()
    kind = ctx.devices[0].device_kind
    pk = (peaks.peaks(kind) if ctx.devices[0].platform == "tpu"
          else None)
    run_info = {"cell": ctx.cell["name"], "config": cfg, "traffic": tr,
                "chips": len(ctx.devices), "device_kind": kind,
                "window_s": window_s, "batches": index,
                "flops": index * batch_flops(cfg, tr), "trace": None,
                "peaks": pk}
    breakdown = None
    if ctx.trace:
        tdir = harness.trace_dir(ctx.cell["name"])
        jax.profiler.start_trace(str(tdir))
        with jax.profiler.TraceAnnotation("bench-window"):
            for i in range(tr["trace_batches"]):
                b.serve_batch(index + i)
        jax.profiler.stop_trace()
        red = trace_reduce.reduce_trace(
            trace_reduce.find_xplane(str(tdir)), host_spans=HOST_SPANS,
            kernels=(tr["kernel_match"],))
        run_info["trace"] = red
        if pk:
            least, bound, calls = kernel_least_s(cfg, tr,
                                                 tr["trace_batches"], pk)
            run_info["kernel"] = {"match": tr["kernel_match"],
                                  "least_s": least, "bound": bound,
                                  "calls": calls}
            print(f"lora_fused: {red['kernel_calls']} events, {calls} calls "
                  f"counted, least {least:.6f} s ({bound}-bound), device "
                  f"{red['kernel_s']}", file=sys.stderr)
        breakdown = {"device_ops": trace_reduce.top(red["ops"]),
                     "idle_gaps": trace_reduce.top(red["idle_by_span"])}
    has_kernel = "tpu_custom_call" in b.decode_hlo()
    mem_peak = harness.memory_peak(ctx.devices)
    streams = b.streams
    del b
    gc.collect()

    # ---- the check: a seeded sample of the window's requests
    served = np.stack(served)                       # (batches, B, G)
    g = np.random.default_rng([streams["sample"], index])
    n = min(tr["check_requests"], index * B)
    flat_ids = g.choice(index * B, size=n, replace=False)
    picks = [(int(i // B), int(i % B)) for i in flat_ids]
    sv = np.stack([served[i, r] for i, r in picks])
    t_ref = time.perf_counter()
    gap, _ = reference_gaps(cfg, tr, streams, picks, sv)
    print(f"setup_s {setup_s:.3f}, window {window_s:.3f} s, {index} batches, "
          f"reference {time.perf_counter() - t_ref:.3f} s, compiles in "
          f"window {m1['compiles'] - m0['compiles']}", file=sys.stderr)
    return {"metrics": {"ttft_p95_ms": 1e3 * harness.p95(ttft),
                        "token_gap_p95_ms": 1e3 * harness.p95(gaps),
                        "setup_s": setup_s},
            "numbers": {"logit_gap": gap,
                        "missing_kernel": 0.0 if has_kernel else 1.0},
            "limits": tr["limits"],
            "window_compiles": m1["compiles"] - m0["compiles"],
            "attempted": index * B, "failed": 0,
            "memory_peak_bytes": mem_peak, "run": run_info,
            "breakdown": breakdown}
