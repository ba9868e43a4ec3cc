"""Driver: closed-loop serving of a latent-attention MoE model (one chip's
expert share) with one client LoRA, one batch in flight.

The timed path is the program's serving step pair, as for the dense
model (``serve_closed_loop``): jitted ``launch/steps.py::make_prefill_step``
and ``make_serve_step`` on a ``Model`` whose LoRA backend is the
traffic's (``pallas``: the factored projections lower to
``kernels/lora_fused``), the cache donated to each decode step, a batch
due when the previous one has finished, every step's tokens read by the
host. The configuration's MoE layers hold ``n_routed_experts`` of the
router's ``router_experts`` (``lib/program_mla_moe.py``): they route over
all of them and compute the held experts' part through the grouped matmul
(``kernels/moe_gmm``).

The expert layers' counters ride in the cache (``models/moe.py::
cache_counters``); the driver keeps each batch's final counters (device
arrays, read after the window) and folds them into a ``SpanTracer`` as
``moe.rows``, ``moe.experts_hit`` and ``moe.dropped``. The check once the
window has closed: the plain reference's (``lib/ref_mla_moe.py``) full
causal forward over every request of a seeded batch of the window, prompt
and served tokens, one request at a time (``reference_check``);
``logit_gap``, the widest gap by which a served token's reference logit
lies below the reference's best, and ``logit_error``, how far the
batch's logits (the batch served again after the window, untimed, to
keep them) lie from the reference's; ``moe_rows_gap``, how
far the rows the batch's grouped matmuls computed lie from the (token,
held expert) pairs the reference routed, so that a drop the program does
not report is seen; ``missing_kernel``, unless the compiled decode step
holds ``tpu_custom_call``; ``moe_dropped``, the pairs the program itself
counts as routed to a held expert and not computed over the window.
"""
from __future__ import annotations

import gc
import sys
import time
from types import SimpleNamespace

import numpy as np

from lib import (checks, data, flops, flops_mla_moe, harness, peaks,
                 program, program_mla_moe, ref_mla_moe, scopes,
                 trace_reduce, weights, weights_mla_moe)

HOST_SPANS = ("batch", "prompt", "prefill", "decode")
SCOPES = ("moe", "mla", "moe/gmm")
TRAFFIC_KEYS = ("driver", "batch", "prompt_len", "gen_tokens", "warm_index",
                "trace_batches", "kernel_match", "check_block", "limits")


def build(ctx, seed: int):
    import jax
    import jax.numpy as jnp
    from repro.launch.steps import make_prefill_step, make_serve_step
    from repro.models import Model
    from repro.models.moe import cache_counters
    from repro.sharding import MeshCtx

    cfg, tr = ctx.config, ctx.traffic
    harness.check_traffic(tr, TRAFFIC_KEYS)
    peft = cfg["peft"]
    b = SimpleNamespace()
    b.streams = dict(zip(("weights", "clients", "data", "sample"),
                         weights.seed_streams(seed, 4)))
    mcfg = program_mla_moe.program_config(cfg)
    model = Model(mcfg, meshctx=MeshCtx.single_device(),
                  opts={"lora_backend": peft["backend"]})
    flat = weights_mla_moe.make_params(cfg,
                                       weights.jax_key(b.streams["weights"]))
    program.check_layout(flat, jax.eval_shape(model.init,
                                              jax.random.PRNGKey(0)))
    base = weights.nest(flat)
    lf = weights_mla_moe.make_lora(cfg, weights.jax_key(b.streams["clients"]))
    targets = set(weights_mla_moe.lora_leaves(cfg))
    lora = weights.mirror(base, lambda p, v: {
        "a": lf[p + "/a"], "b": lf[p + "/b"], "mask": lf[p + "/mask"]}
        if p in targets else None)
    scale = peft["lora_alpha"] / peft["lora_rank"]
    B, P, G = tr["batch"], tr["prompt_len"], tr["gen_tokens"]
    cache_len = P + G
    prefill = jax.jit(make_prefill_step(model, cache_len, lora_scale=scale))
    decode = jax.jit(make_serve_step(model, lora_scale=scale),
                     donate_argnums=(1,))
    argmax = jax.jit(lambda lg: jnp.argmax(lg, -1).astype(jnp.int32)[:, None])
    ann = jax.profiler.TraceAnnotation
    V = cfg["vocab_size"]

    def serve_batch(index, read_prefill=False, logits_out=None):
        """Serve one batch; returns the host times of each token read
        (G of them), the served tokens (B, G) and the expert layers'
        counters after the last step (and after the prefill, read at once,
        with ``read_prefill``).  Each step's logits (B, V) are appended to
        the list ``logits_out`` on the host where one is given."""
        with ann("batch"):
            with ann("prompt"):
                toks = jnp.asarray(data.prompts(
                    b.streams["data"], index, batch=B, length=P, vocab=V))
            with ann("prefill"):
                logits, cache = prefill(base, {"tokens": toks}, lora)
                tok = argmax(logits)
                got = [np.asarray(tok)]
                if logits_out is not None:
                    logits_out.append(np.asarray(logits))
            times = [time.perf_counter()]
            after_prefill = (jax.device_get(cache_counters(cache))
                             if read_prefill else None)
            for _ in range(G - 1):
                with ann("decode"):
                    logits, cache = decode(base, cache, tok, lora)
                    tok = argmax(logits)
                    got.append(np.asarray(tok))
                    if logits_out is not None:
                        logits_out.append(np.asarray(logits))
                times.append(time.perf_counter())
        counters = cache_counters(cache)
        if read_prefill:
            counters = (after_prefill, jax.device_get(counters))
        return times, np.concatenate(got, 1), counters

    b.serve_batch = serve_batch
    b.decode_hlo = lambda: decode.lower(
        base, model.cache_spec(B, cache_len),
        jax.ShapeDtypeStruct((B, 1), jnp.int32), lora).compile().as_text()
    return b


def reference_check(cfg, tr, streams, index, served, logits,
                    control=False):
    """The plain reference over every request of the checked batch
    ``index``, prompt and served tokens ``served`` (B, G), one block of
    ``check_block`` requests at a time, in float32 at the configuration's
    matmul precision: each served token's gap below the reference's best
    logit (B, G); the squared error of the program's ``logits`` (B, G, V)
    and the squared spread of the reference's own, summed
    (``logit_error``); and the (token, held expert) pairs each MoE layer
    routed (n_moe_layers,).  With ``control`` also the same for the
    reference computed in bfloat16 put in the program's place: the gaps
    of the tokens it puts first, its logits' error and its own routed
    pairs."""
    import jax
    import jax.numpy as jnp
    B, P, G = tr["batch"], tr["prompt_len"], tr["gen_tokens"]
    base = weights_mla_moe.make_params(cfg,
                                       weights.jax_key(streams["weights"]))
    lora = weights_mla_moe.make_lora(cfg, weights.jax_key(streams["clients"]))
    scale = cfg["peft"]["lora_alpha"] / cfg["peft"]["lora_rank"]
    pos = np.arange(P - 1, P + G - 1)
    prompts = data.prompts(streams["data"], index, batch=B, length=P,
                           vocab=cfg["vocab_size"])
    seqs = np.concatenate([prompts, served[:, :G - 1]], 1).astype(np.int32)
    fns = {dt: jax.jit(lambda P_, L_, t, dt=dt: ref_mla_moe.forward(
        P_, L_, t, cfg, scale=scale, dtype=dt, positions=pos))
        for dt in (jnp.float32, jnp.bfloat16)}
    out = {"gaps": [], "held": 0, "err": np.zeros(2),
           "control_gaps": [], "control_held": 0, "control_err": np.zeros(2)}
    block = tr["check_block"]
    for s in range(0, B, block):
        rows = jnp.asarray(seqs[s:s + block])
        with jax.default_matmul_precision(cfg["matmul_precision"]):
            ref, held = jax.device_get(fns[jnp.float32](base, lora, rows))
            if control:
                low, low_held = jax.device_get(
                    fns[jnp.bfloat16](base, lora, rows))
        out["gaps"].append(token_gaps(ref, served[s:s + block]))
        out["held"] = out["held"] + held
        out["err"] += error_sums(logits[s:s + block], ref)
        if control:
            out["control_gaps"].append(token_gaps(ref, low.argmax(-1)))
            out["control_held"] = out["control_held"] + low_held
            out["control_err"] += error_sums(low, ref)
    out["gaps"] = np.concatenate(out["gaps"])
    if control:
        out["control_gaps"] = np.concatenate(out["control_gaps"])
    return out


def token_gaps(ref_logits, served):
    """By how much each served token's reference logit lies below the
    reference's best at its position (0 where it is the best)."""
    ref = np.asarray(ref_logits, np.float64)
    got = np.take_along_axis(ref, np.asarray(served)[..., None], -1)[..., 0]
    return ref.max(-1) - got


def error_sums(logits, ref):
    """(Σ (logits - ref)², Σ (ref - its mean over the vocabulary)²) over
    every position and vocabulary entry."""
    ref = np.asarray(ref, np.float64)
    diff = np.asarray(logits, np.float64) - ref
    return np.array([np.sum(diff * diff),
                     np.sum((ref - ref.mean(-1, keepdims=True)) ** 2)])


def logit_numbers(gaps, err) -> dict:
    """``logit_gap``: the widest gap of a served token; ``logit_error``:
    the root-mean-square error of the logits at the served positions
    relative to the reference logits' own spread over the vocabulary."""
    return {"logit_gap": checks._finite(np.max(gaps)),
            "logit_error": checks._finite(np.sqrt(err[0] / err[1]))}


def rows_gap(rows, held) -> float:
    """The widest relative gap, over the MoE layers, between the rows the
    program's grouped matmuls computed (``rows``) and the (token, held
    expert) pairs the reference routed (``held``), both over the checked
    batch's tokens."""
    rows, held = np.asarray(rows, np.float64), np.asarray(held, np.float64)
    return checks._finite(np.max(np.abs(rows - held)
                                 / np.maximum(held, 1.0)))


def batch_rows(counters) -> np.ndarray:
    """Rows the grouped matmuls computed in each MoE layer over one
    batch's prefill and decode steps, from its final counters
    (``models/moe.py::cache_counters``), in layer order."""
    import jax
    return np.concatenate([np.asarray(c["moe_rows"]).sum(-1)
                           for c in jax.device_get(counters)])


def lora_least_s(cfg, tr, batches, pk):
    """Least time and bound of the traced batches' ``lora_fused`` calls:
    each target of every layer in the prefill; ``wq`` of every layer in
    each decode step (absorbed decode merges ``wkv_b``'s factors into the
    latent weight instead)."""
    d, H, r = (cfg["hidden_size"], cfg["num_attention_heads"],
               cfg["kv_lora_rank"])
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    dims = {"mixer/wq": (d, H * (dn + dr)), "mixer/wkv_b": (r, H * (dn + dv))}
    L, rank = cfg["num_hidden_layers"], cfg["peft"]["lora_rank"]
    B, P, G = tr["batch"], tr["prompt_len"], tr["gen_tokens"]
    targets = cfg["peft"]["lora_targets"]
    least, bounds, calls = 0.0, set(), 0
    for t in targets:
        steps = [(B * P, 1)] + ([(B, G - 1)] if t == "mixer/wq" else [])
        for m, n_steps in steps:
            f, nb = flops.lora_fused_call(m, *dims[t], rank, 4)
            secs, bound = flops.roofline_time(f, nb, pk["flops_bf16"],
                                              pk["hbm_bytes_per_s"])
            least += secs * L * n_steps * batches
            calls += L * n_steps * batches
            bounds.add(bound)
    return least, "+".join(sorted(bounds)), calls


def gmm_least_s(cfg, traced, pk):
    """Least time of the traced batches' grouped expert matmuls, from the
    program's counters: per expert layer, the prefill's rows and expert
    hits, and the decode steps' (the totals less the prefill's). Each part
    is the larger of its FLOPs over peak and its bytes over peak bandwidth
    (``flops_mla_moe.gmm_call``), for the gate, up and down matmuls. Where
    a part spans several kernel calls (the prefill's token chunks, the
    decode steps) this is at most the sum of the calls' own bounds, so the
    share it gives is never above the true one; decode's calls are all
    bytes-bound (at most batch × top-k rows against whole expert slabs),
    where the two agree."""
    d, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    least = 0.0
    for after_prefill, final in traced:
        for pre, end in zip(after_prefill, final):
            pre_rows = np.asarray(pre["moe_rows"]).sum(-1)
            end_rows = np.asarray(end["moe_rows"]).sum(-1)
            parts = [(pre_rows, np.asarray(pre["moe_hits"])),
                     (end_rows - pre_rows,
                      np.asarray(end["moe_hits"]) - np.asarray(
                          pre["moe_hits"]))]
            for rows, hits in parts:
                for r_, h_ in zip(rows, hits):
                    for k, n in ((d, f), (d, f), (f, d)):
                        fl, nb = flops_mla_moe.gmm_call(float(r_), float(h_),
                                                        k, n)
                        least += max(fl / pk["flops_bf16"],
                                     nb / pk["hbm_bytes_per_s"])
    return least


def run(ctx) -> dict:
    import jax
    from repro.models.moe import fold_counters
    from repro.obs.trace import SpanTracer
    cfg, tr = ctx.config, ctx.traffic
    B, P, G = tr["batch"], tr["prompt_len"], tr["gen_tokens"]
    b = build(ctx, ctx.seed)
    b.serve_batch(tr["warm_index"])           # compiles every shape

    m0 = ctx.meter.snapshot()
    t0 = time.perf_counter()
    setup_s = t0 - ctx.t_start
    due, ttft, gaps, served, counters, index = t0, [], [], [], [], 0
    while True:
        times, toks, cnt = b.serve_batch(index)
        ttft.append(times[0] - due)
        gaps.extend(np.diff(times))
        served.append(toks)
        counters.append(cnt)
        due = times[-1]
        index += 1
        if due - t0 >= ctx.seconds:
            break
    window_s = due - t0
    m1 = ctx.meter.snapshot()
    tracer = SpanTracer()
    for cnt in counters:
        fold_counters(tracer, cnt)
    counts = tracer.counts()
    rows = counts.get("moe.rows", 0)
    kind = ctx.devices[0].device_kind
    pk = (peaks.peaks(kind) if ctx.devices[0].platform == "tpu"
          else None)
    run_info = {"cell": ctx.cell["name"], "config": cfg, "traffic": tr,
                "chips": len(ctx.devices), "device_kind": kind,
                "window_s": window_s, "batches": index, "counters": counts,
                "flops": (index * flops_mla_moe.batch_flops(
                    cfg, batch=B, prompt=P, gen=G, routed_rows=0)
                    + rows * flops_mla_moe.expert_row(cfg)),
                "trace": None, "peaks": pk}
    breakdown = None
    if ctx.trace:
        tdir = harness.trace_dir(ctx.cell["name"])
        traced = []
        jax.profiler.start_trace(str(tdir))
        with jax.profiler.TraceAnnotation("bench-window"):
            for i in range(tr["trace_batches"]):
                traced.append(b.serve_batch(index + i, read_prefill=True)[2])
        jax.profiler.stop_trace()
        xplane = trace_reduce.find_xplane(str(tdir))
        red = trace_reduce.reduce_trace(xplane, host_spans=HOST_SPANS,
                                        kernels=(tr["kernel_match"],))
        run_info["trace"] = red
        run_info["scopes"] = scopes.scope_times(xplane, SCOPES)
        if pk:
            least, bound, calls = lora_least_s(cfg, tr, tr["trace_batches"],
                                               pk)
            run_info["kernel"] = {"match": tr["kernel_match"],
                                  "least_s": least, "bound": bound,
                                  "calls": calls}
            run_info["gmm"] = {"least_s": gmm_least_s(cfg, traced, pk),
                               "scope": "moe/gmm"}
            sc = run_info["scopes"]
            print(f"lora_fused: {red['kernel_calls']} events, {calls} calls "
                  f"counted, least {least:.6f} s ({bound}-bound), device "
                  f"{red['kernel_s']}; moe_gmm: least "
                  f"{run_info['gmm']['least_s']:.6f} s, device "
                  f"{sc.get('kernel_s')} in {sc.get('kernel_calls')} events; "
                  f"scopes {sc.get('scope_s')}", file=sys.stderr)
        breakdown = {"device_ops": trace_reduce.top(red["ops"]),
                     "idle_gaps": trace_reduce.top(red["idle_by_span"])}
    # the checked batch, a seeded pick of the window's, served again
    # untimed to keep every step's logits
    g = np.random.default_rng([b.streams["sample"], index])
    pick = int(g.integers(index))
    logits = []
    replayed = b.serve_batch(pick, logits_out=logits)[1]
    has_kernel = "tpu_custom_call" in b.decode_hlo()
    mem_peak = harness.memory_peak(ctx.devices)
    streams = b.streams
    del b
    gc.collect()

    # ---- the check: every request of a seeded batch of the window's
    t_ref = time.perf_counter()
    ref = reference_check(cfg, tr, streams, pick, served[pick],
                          np.stack(logits, 1))
    del logits
    numbers = logit_numbers(ref["gaps"], ref["err"])
    numbers["moe_rows_gap"] = rows_gap(batch_rows(counters[pick]),
                                       ref["held"])
    print(f"setup_s {setup_s:.3f}, window {window_s:.3f} s, {index} batches, "
          f"reference {time.perf_counter() - t_ref:.3f} s (batch {pick}, "
          f"replayed tokens differing {np.mean(replayed != served[pick])}), "
          f"compiles in window {m1['compiles'] - m0['compiles']}, counters "
          f"{counts}", file=sys.stderr)
    return {"metrics": {"ttft_p95_ms": 1e3 * harness.p95(ttft),
                        "token_gap_p95_ms": 1e3 * harness.p95(gaps),
                        "setup_s": setup_s},
            "numbers": {**numbers,
                        "missing_kernel": 0.0 if has_kernel else 1.0,
                        "moe_dropped": float(counts.get("moe.dropped", 0))},
            "limits": tr["limits"],
            "window_compiles": m1["compiles"] - m0["compiles"],
            "attempted": index * B, "failed": 0,
            "memory_peak_bytes": mem_peak, "run": run_info,
            "breakdown": breakdown}
