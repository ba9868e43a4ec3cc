"""Driver: PFTT federated rounds over a host-resident client population.

The timed path is the program's own population round, assembled as
``core/pftt.py::_run_pftt_population`` assembles it, minus pre-training,
checkpointing and telemetry:

- ``fl/population.py::PopulationRunner.run_round`` samples the cohort,
  plans the round over the Rayleigh uplink, gathers the sampled rows of
  the ``PopulationStore``, runs the fused round step and scatters the
  results back;
- the round step is ``core/cohort.py::build_supervised_round(robust=True,
  base=...)`` over the client step of ``core/pftt.py::_client_fns``
  (factored LoRA on the frozen base, AdamW);
- every round ends with one ``core/cohort.py::build_cohort_eval``
  dispatch over the cohort's held-out rows, and its block.

The client store is built as that function builds it: one vmapped
per-client init on the device (``fl/population.py::stacked_client_init``)
pulled to host numpy, zero pending uploads, all in a ``PopulationStore``.
Weights, client factors and rows are the benchmark's, drawn from the seed
(``lib/weights.py``, ``lib/data.py``). Set-up drives the first
``check_rounds`` rounds through the same calls, recording what the plain
reference (``lib/ref_fl.py``) recomputes once the window has closed.
"""
from __future__ import annotations

import contextlib
import gc
import math
import sys
import time
from types import SimpleNamespace

import numpy as np

from lib import (checks, data, flops, harness, peaks, program, ref_fl,
                 trace_reduce, weights)

HOST_SPANS = ("round", "sample", "plan", "gather", "device-step", "scatter",
              "ledger", "eval")
TRAFFIC_KEYS = ("driver", "population", "sampler", "cohort", "batch", "seq",
                "local_steps", "eval_rows", "snr_db", "outage_snr_db",
                "tx_power_w", "check_rounds", "trace_rounds",
                "max_rounds_per_s", "limits")


def build(ctx, seed: int):
    """Set up everything the timed path needs, from ``seed``."""
    import jax
    import jax.numpy as jnp
    from repro import trees
    from repro.comms import ChannelBudget
    from repro.core.cohort import (HostBatchStacker, build_cohort_eval,
                                   build_supervised_round)
    from repro.core.pftt import PFTTConfig, _client_fns, _upload_pred
    from repro.core.robust import StalenessConfig, StalenessTracker
    from repro.fl.population import (ClientSampler, PopulationConfig,
                                     PopulationRunner, PopulationStore,
                                     stacked_client_init)
    from repro.models import Model
    from repro.models import peft as peft_mod
    from repro.obs.trace import SpanTracer
    from repro.optim import adamw
    from repro.sharding import MeshCtx
    from repro.wireless import CommLedger, FaultPlan, RayleighChannel
    from repro.wireless.scenarios import Scenario

    class AnnotatingTracer(SpanTracer):
        """The program's span tracer, with each span also written into the
        profiler's trace, so idle gaps can be named by it."""

        @contextlib.contextmanager
        def span(self, name, **args):
            with jax.profiler.TraceAnnotation(name):
                with super().span(name, **args) as sp:
                    yield sp

    cfg, tr = ctx.config, ctx.traffic
    harness.check_traffic(tr, TRAFFIC_KEYS)
    if tr["sampler"] != "uniform":
        raise ValueError(f"traffic sampler {tr['sampler']!r}: the reference "
                         f"draws cohorts as the uniform sampler does")
    peft = cfg["peft"]
    N, K = tr["population"], tr["cohort"]
    S, B, T = tr["local_steps"], tr["batch"], tr["seq"]
    V, C = cfg["vocab_size"], cfg["num_labels"]
    b = SimpleNamespace()
    b.streams = dict(zip(("weights", "clients", "sampler", "channel",
                          "data"), weights.seed_streams(seed, 5)))
    mcfg = program.program_config(cfg)
    model = Model(mcfg, meshctx=MeshCtx.single_device())
    peft_cfg = peft_mod.PEFTConfig(
        lora_rank=peft["lora_rank"], lora_alpha=peft["lora_alpha"],
        adapter_dim=peft["adapter_dim"],
        lora_targets=tuple(peft["lora_targets"]))
    pcfg = PFTTConfig(method=peft["method"], lora_rank=peft["lora_rank"],
                      adapter_dim=peft["adapter_dim"],
                      lr=cfg["optimizer"]["lr"], local_steps=S, batch=B,
                      seq_len=T, snr_db=tr["snr_db"])
    o = cfg["optimizer"]
    opt = adamw(o["lr"], b1=o["b1"], b2=o["b2"], eps=o["eps"],
                update_mask=lambda p: not p.endswith("/mask"))

    b.phases = {"import": time.perf_counter() - ctx.t_start}
    t = time.perf_counter()

    # ---- weights from the seed; the N-client store as the program builds
    # it: one vmapped init on the device, pulled to host numpy
    flat = weights.make_params(cfg, weights.jax_key(b.streams["weights"]))
    base = weights.nest(flat)
    program.check_layout(flat, jax.eval_shape(
        lambda k: peft_mod.init_adapters(k, model.init(k), mcfg, peft_cfg),
        jax.random.PRNGKey(0)))
    ckey = weights.jax_key(b.streams["clients"])
    targets = set(weights.lora_leaves(cfg))
    upload_pred = _upload_pred(peft["method"])

    def client_init(cid):
        lf = weights.client_lora(ckey, cid, cfg)
        t = {"shared": weights.mirror(base, lambda p, v: v
                                      if ref_fl.is_shared(cfg, p) else None),
             "local": {"lora": weights.mirror(base, lambda p, v: {
                 "a": lf[p + "/a"], "b": lf[p + "/b"],
                 "mask": lf[p + "/mask"]} if p in targets else None)}}
        return {"t": t, "o": opt.init(t)}

    stacked = stacked_client_init(client_init, jnp.arange(N, dtype=jnp.int32))
    pending = jax.tree_util.tree_map(
        np.zeros_like, trees.select(stacked["t"], upload_pred))
    store = PopulationStore({"trainable": stacked["t"], "opt": stacked["o"],
                             "pending": pending})
    del stacked, pending
    b.phases["weights_and_store"] = time.perf_counter() - t
    t = time.perf_counter()
    global_shared = jax.tree_util.tree_map(
        np.array, trees.select(store.row("trainable", 0), upload_pred))

    # ---- the round driver, over the population
    local_step, eval_client = _client_fns(pcfg, model, opt, peft_cfg)
    round_step = build_supervised_round(local_step, upload_pred, robust=True,
                                        base=base)
    b.round_specs = None

    def recording_step(*args):
        if b.round_specs is None:
            b.round_specs = jax.tree_util.tree_map(
                lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), args)
        return round_step(*args)

    b.round_step = round_step
    eval_cohort = build_cohort_eval(eval_client, base=base)
    rounds_cap = (tr["check_rounds"] + tr["trace_rounds"]
                  + math.ceil(ctx.seconds * tr["max_rounds_per_s"]))
    channel = RayleighChannel(mean_snr_db=tr["snr_db"],
                              outage_snr_db=tr["outage_snr_db"],
                              seed=b.streams["channel"])
    tracer = AnnotatingTracer()
    runner = PopulationRunner(
        pop=PopulationConfig(population=N, cohort_size=K,
                             sampler=tr["sampler"]),
        store=store, global_shared=global_shared, upload_pred=upload_pred,
        channel=channel,
        budget=ChannelBudget(channel, tx_power_w=tr["tx_power_w"]),
        ledger=CommLedger(),
        tracker=StalenessTracker(N, StalenessConfig()),
        trace=FaultPlan().realize(N, rounds_cap),
        strace=Scenario().realize(N, rounds_cap),
        sampler=ClientSampler(tr["sampler"], N, K,
                              seed=b.streams["sampler"]),
        tracer=tracer)
    shared_bytes = sum(v.nbytes for v in
                       weights.flatten(global_shared).values())
    e_toks = np.zeros((K, tr["eval_rows"], T), np.int32)
    e_labels = np.zeros((K, tr["eval_rows"]), np.int32)
    e_valid = np.ones((K, tr["eval_rows"]), np.float32)

    def draw(cid, rnd):
        toks, labels = data.train_batches(
            b.streams["data"], cid, rnd, steps=S, batch=B, seq=T, vocab=V,
            n_labels=C)
        return [{"tokens": toks[s], "label": labels[s]} for s in range(S)]

    stacker = HostBatchStacker()

    def one_round(rnd):
        if rnd >= rounds_cap:
            raise RuntimeError(f"round {rnd} is past the {rounds_cap} rounds "
                               f"realized in set-up: raise max_rounds_per_s")
        out = runner.run_round(rnd, round_step=recording_step,
                               stacker=stacker, draw_batches=draw,
                               local_steps=S, payload_bits=shared_bytes * 8)
        with tracer.span("eval"):
            for j, cid in enumerate(out["ids"]):
                e_toks[j], e_labels[j] = data.test_rows(
                    b.streams["data"], int(cid), rows=tr["eval_rows"],
                    seq=T, vocab=V, n_labels=C)
            corr, _ = eval_cohort(out["cohort_tr"], jnp.asarray(e_toks),
                                  jnp.asarray(e_labels),
                                  jnp.asarray(e_valid))
            corr = np.asarray(corr)
        return out, corr

    b.phases["round_driver"] = time.perf_counter() - t
    b.one_round = one_round
    b.runner, b.store, b.tracer = runner, store, tracer
    b.eval_cohort, b.base = eval_cohort, base
    return b


def first_rounds(b, n: int) -> dict:
    """Drive rounds 0..n-1 and record what the reference recomputes."""
    rec = {"ids": [], "losses": [], "eval_correct": [], "mu1": {}}
    for rnd in range(n):
        out, corr = b.one_round(rnd)
        ids = out["ids"]
        rec["ids"].append(np.array(ids))
        rec["losses"].append(np.asarray(out["losses"], np.float32))
        rec["eval_correct"].append(np.array(corr))
        if rnd == 0:
            rec["mu1"] = {int(c): {k: np.array(v) for k, v in weights.flatten(
                b.store.row("opt", int(c))["mu"]).items()} for c in ids}
    touched = sorted({int(c) for ids in rec["ids"] for c in ids})
    rec["rows"] = {c: {k: np.array(v) for k, v in weights.flatten(
        b.store.row("trainable", c)).items()} for c in touched}
    rec["pending"] = {c: {k: np.array(v) for k, v in weights.flatten(
        b.store.row("pending", c)).items()} for c in touched}
    rec["global"] = {k: np.array(v) for k, v in
                     weights.flatten(b.runner.global_shared).items()}
    return rec


def reference(cfg, traffic, streams, n, control=False):
    """The plain reference's first ``n`` rounds, in float32 at the
    configuration's matmul precision; the ``control`` computes them in
    bfloat16."""
    import jax
    import jax.numpy as jnp
    with jax.default_matmul_precision(cfg["matmul_precision"]):
        return ref_fl.reference_rounds(
            cfg, traffic, streams, n,
            jnp.bfloat16 if control else jnp.float32)


def round_flops(cfg, tr) -> float:
    return (flops.encoder_train_round(
        cfg, cohort=tr["cohort"], local_steps=tr["local_steps"],
        batch=tr["batch"], seq=tr["seq"])
        + flops.encoder_eval_round(cfg, cohort=tr["cohort"],
                                   rows=tr["eval_rows"], seq=tr["seq"]))


def run(ctx) -> dict:
    import jax
    cfg, tr = ctx.config, ctx.traffic
    b = build(ctx, ctx.seed)
    t = time.perf_counter()
    rec = first_rounds(b, tr["check_rounds"])
    b.phases["check_rounds"] = time.perf_counter() - t
    print(f"set-up phases (s): { {k: round(v, 3) for k, v in b.phases.items()} }",
          file=sys.stderr)

    # ---- the measured window
    m0 = ctx.meter.snapshot()
    before = b.tracer.totals()
    t0 = time.perf_counter()
    setup_s = t0 - ctx.t_start
    rounds, rnd = 0, tr["check_rounds"]
    while True:
        b.one_round(rnd)
        rnd += 1
        rounds += 1
        if time.perf_counter() - t0 >= ctx.seconds:
            break
    window_s = time.perf_counter() - t0
    m1 = ctx.meter.snapshot()
    after = b.tracer.totals()
    spans = {k: after[k] - before.get(k, 0.0) for k in after}
    tokens = rounds * tr["cohort"] * tr["local_steps"] * tr["batch"] * tr["seq"]
    kind = ctx.devices[0].device_kind
    run_info = {"cell": ctx.cell["name"], "config": cfg, "traffic": tr,
                "chips": len(ctx.devices), "device_kind": kind,
                "window_s": window_s, "rounds": rounds, "spans": spans,
                "flops": rounds * round_flops(cfg, tr), "trace": None}
    breakdown = None
    if ctx.trace:
        tdir = harness.trace_dir(ctx.cell["name"])
        jax.profiler.start_trace(str(tdir))
        with jax.profiler.TraceAnnotation("bench-window"):
            for _ in range(tr["trace_rounds"]):
                b.one_round(rnd)
                rnd += 1
        jax.profiler.stop_trace()
        red = trace_reduce.reduce_trace(trace_reduce.find_xplane(str(tdir)),
                                        host_spans=HOST_SPANS)
        run_info["trace"] = red
        breakdown = {"device_ops": trace_reduce.top(red["ops"]),
                     "idle_gaps": trace_reduce.top(red["idle_by_span"])}
        ma = b.round_step.func.lower(b.base, *b.round_specs).compile() \
            .memory_analysis()
        run_info["memory_analysis"] = {
            k: int(getattr(ma, k)) for k in (
                "argument_size_in_bytes", "output_size_in_bytes",
                "temp_size_in_bytes", "alias_size_in_bytes",
                "generated_code_size_in_bytes") if hasattr(ma, k)}
        print(f"round step memory_analysis {run_info['memory_analysis']}",
              file=sys.stderr)
    mem_peak = harness.memory_peak(ctx.devices)
    run_info["memory_peak_bytes"] = mem_peak
    run_info["peaks"] = (peaks.peaks(kind) if ctx.devices[0].platform == "tpu"
                          else None)
    streams = b.streams
    del b
    gc.collect()

    t_ref = time.perf_counter()
    ref = reference(cfg, tr, streams, tr["check_rounds"])
    numbers = checks.train_numbers(rec, ref)
    print(f"setup_s {setup_s:.3f}, window {window_s:.3f} s, {rounds} rounds, "
          f"reference {time.perf_counter() - t_ref:.3f} s, compiles in "
          f"window {m1['compiles'] - m0['compiles']}", file=sys.stderr)
    return {"metrics": {"train_tokens_per_s": tokens / window_s,
                        "setup_s": setup_s},
            "numbers": numbers, "limits": tr["limits"],
            "window_compiles": m1["compiles"] - m0["compiles"],
            "attempted": rounds, "failed": 0,
            "memory_peak_bytes": mem_peak, "run": run_info,
            "breakdown": breakdown}
