"""Training launcher: runs real steps on the available devices (CPU here,
TPU pod in production — the same pjit program the dry-run compiles).

    PYTHONPATH=src python -m repro.launch.train --arch tinyllama-1.1b \
        --reduced --steps 50 --batch 8 --seq 128

``--fl-clients N`` instead runs the federated cohort engine with the
stacked client axis sharded over every available device (``shard_map``
round, psum aggregation — core/cohort.py).  ``--arch roberta-base`` runs
PFTT's roberta classification cohort, reduced unless
``--published-widths`` (``--steps``/``--seq`` don't apply;
``--batch``/``--lr``/``--fl-rounds`` do):

    XLA_FLAGS=--xla_force_host_platform_device_count=8 PYTHONPATH=src \
        python -m repro.launch.train --arch roberta-base --fl-clients 8 \
        --fl-rounds 3

``--population N --cohort K`` (roberta-base) switches PFTT to
population mode: the host holds N clients' adapter/opt trees
(``fl.population.PopulationStore``) and every round a seeded sampler
draws a K-client cohort into the SAME fused round the ``--fl-clients K``
run compiles.  ``--scenario`` adds non-IID data / availability /
mobility (``wireless.scenarios``); fault plans, deadlines, codecs, and
checkpointing compose unchanged:

    PYTHONPATH=src python -m repro.launch.train --arch roberta-base \
        --population 256 --cohort 8 --fl-rounds 2 \
        --scenario alpha=0.1,avail=diurnal --sampler availability

Any other ``--arch`` runs the universal fused round on that architecture
(``core/arch_round.py``): a ragged LoRA cohort trained through ONE fused
dispatch per round with the frozen base replicated and only the rank-r
factors batched.  ``--assert-fused`` turns the run into the CI arch-matrix
check — it fails unless zero dense merges were traced, each round was one
dispatch, and the losses match the legacy dense-merge oracle to ≤1e-5:

    PYTHONPATH=src python -m repro.launch.train --arch deepseek-v2-236b \
        --fl-clients 4 --fl-rounds 2 --assert-fused
"""
import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.configs import get_config, list_configs
from repro.launch.steps import make_train_step
from repro.models import Model
from repro.sharding import MeshCtx, auto_mesh, param_specs


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=list_configs())
    ap.add_argument("--reduced", action="store_true",
                    help="reduced config (CPU-runnable)")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--data-axis", type=int, default=0,
                    help="data-parallel axis size (0 → n_devices)")
    ap.add_argument("--fl-clients", type=int, default=0,
                    help="run a federated PFTT cohort of this size with the "
                         "client axis sharded over all devices (0 → off)")
    ap.add_argument("--fl-rounds", type=int, default=3)
    ap.add_argument("--uplink-codec", default="none",
                    choices=["none", "int8", "int4", "sketch"],
                    help="compress FL uploads inside the fused round step "
                         "(repro.comms): stochastic-rounding int8/int4 "
                         "quantization or top-k sketching of the delta "
                         "against the last broadcast global")
    ap.add_argument("--factored-agg", action="store_true",
                    help="aggregate LoRA factor pairs via SVD re-projection "
                         "of the weighted-mean update (never densified)")
    ap.add_argument("--fault-plan", default=None,
                    help="inject wireless faults into the FL run: 'k=v,...' "
                         "(dropout_p/straggle_p/crash_p/snr_dip_p/corrupt_p/"
                         "seed/...) or a JSON file path "
                         "(wireless.faults.FaultPlan)")
    ap.add_argument("--deadline-s", type=float, default=None,
                    help="continuous-time FL round: server closes the round "
                         "this many simulated seconds after dispatch; late "
                         "arrivals buffer as stale retransmissions "
                         "(wireless.arrivals.DeadlineConfig)")
    ap.add_argument("--backoff-base-s", type=float, default=0.0,
                    help="retransmission backoff base: the n-th failure of "
                         "a payload waits base*2^(n-1) simulated seconds")
    ap.add_argument("--max-retries", type=int, default=8,
                    help="abandon a pending payload after this many failed "
                         "retransmissions")
    ap.add_argument("--min-quorum", type=int, default=0,
                    help="void the round (no merge, deliveries NACKed back "
                         "to pending) when fewer payloads arrive in time")
    ap.add_argument("--compute-time-s", type=float, default=0.0,
                    help="mean per-round local compute time before a fresh "
                         "upload starts transmitting (stragglers scale it)")
    ap.add_argument("--staleness-a", type=float, default=0.0,
                    help="staleness discount exponent: late uploads merge "
                         "with weight α·(1+s)^(-a)")
    ap.add_argument("--max-staleness", type=int, default=0,
                    help="retransmit failed uploads for up to this many "
                         "rounds (0 = synchronous drop-on-failure)")
    ap.add_argument("--population", type=int, default=0,
                    help="population mode (roberta-base): the host holds "
                         "this many clients' adapter/opt trees and every "
                         "round samples a --cohort cohort into the fused "
                         "round (fl.population; 0 → off)")
    ap.add_argument("--cohort", type=int, default=8,
                    help="population mode: sampled cohort size per round")
    ap.add_argument("--sampler", default="uniform",
                    choices=["uniform", "availability"],
                    help="population mode: per-round client sampler "
                         "(availability weights by the scenario's "
                         "avail_p trace)")
    ap.add_argument("--scenario", default=None,
                    help="population scenario spec: 'k=v,...' "
                         "(alpha/avail/avail_period/mobility/seed/... — "
                         "wireless.scenarios.Scenario.from_spec) or a JSON "
                         "file path")
    ap.add_argument("--ckpt-dir", default=None,
                    help="FL engine: save the stacked round state each round "
                         "here so a killed run can --resume")
    ap.add_argument("--resume", action="store_true",
                    help="FL engine: restart from --ckpt-dir's last round")
    ap.add_argument("--assert-fused", action="store_true",
                    help="FL engine: fail unless the run took the fused "
                         "factored path — zero dense merges, one dispatch "
                         "per round, and (non-roberta archs) ≤1e-5 parity "
                         "vs the legacy dense-merge oracle")
    ap.add_argument("--telemetry-dir", default=None,
                    help="FL runs: write the structured run telemetry "
                         "(events.jsonl — schema-versioned round metrics "
                         "joining eval, comm ledger, staleness and health "
                         "signals; repro.obs) into this directory")
    ap.add_argument("--trace", action="store_true",
                    help="with --telemetry-dir: also write trace.json, a "
                         "Chrome trace-event file of the host round phases "
                         "(open in Perfetto / chrome://tracing)")
    ap.add_argument("--jax-profile", action="store_true",
                    help="with --telemetry-dir: bracket the run in a "
                         "jax.profiler trace under <dir>/jax_profile")
    ap.add_argument("--fl-seq", type=int, default=16,
                    help="arch FL round: per-sample sequence length")
    ap.add_argument("--fl-dmodel", type=int, default=64,
                    help="arch FL round: reduced-config width")
    ap.add_argument("--published-widths", action="store_true",
                    help="FL runs: build the model from the architecture's "
                         "published config (roberta-base for PFTT: 12 "
                         "layers, d768, vocab 50265) instead of the reduced "
                         "one; --fl-dmodel is then ignored")
    ap.add_argument("--ckpt", default=None)
    args = ap.parse_args()

    enable_compile_cache()
    n_dev = len(jax.devices())
    if args.population and args.arch != "roberta-base":
        raise SystemExit("--population runs the PFTT workload: "
                         "use --arch roberta-base")
    if args.fl_clients and args.arch != "roberta-base":
        from repro.core.arch_round import ArchRoundConfig, run_arch_round
        print(f"universal fused round: --arch {args.arch}, "
              f"{args.fl_clients} clients on {n_dev} device(s)")
        mesh = auto_mesh((n_dev,), ("data",))
        cfg = ArchRoundConfig(arch=args.arch, n_clients=args.fl_clients,
                              rounds=args.fl_rounds,
                              batch=min(args.batch, 4), seq_len=args.fl_seq,
                              d_model=args.fl_dmodel, lr=args.lr,
                              published_widths=args.published_widths,
                              oracle=args.assert_fused)
        res = run_arch_round(cfg, mesh=mesh, client_axes=("data",))
        print(f"arch={res['arch']} targets={res['lora_targets']} "
              f"ragged={res['ragged']} ghosts={res['n_ghosts']} "
              f"dispatches/round={res['dispatches_per_round']} "
              f"dense_merges={res['dense_merges_in_engine']} "
              f"loss/round={['%.4f' % l for l in res['loss_per_round']]}")
        if args.assert_fused:
            err = res["oracle_loss_max_err"]
            print(f"oracle parity max err {err:.2e}")
            assert res["dense_merges_in_engine"] == 0, \
                "dense-merge fallback taken inside the fused round"
            assert res["dispatches_per_round"] == 1.0, \
                "cohort fell back to per-client dispatch"
            assert err <= 1e-5, f"factored/oracle divergence {err:.2e}"
            print("fused path asserted: factored, one dispatch, "
                  "oracle parity OK")
        return
    telemetry = None
    if args.telemetry_dir:
        from repro.obs import TelemetryConfig
        telemetry = TelemetryConfig(out_dir=args.telemetry_dir,
                                    trace=args.trace,
                                    jax_profile=args.jax_profile)
    if args.fl_clients or args.population:
        import math

        from repro.core.pftt import PFTTConfig, run_pftt
        from repro.wireless import DeadlineConfig, FaultPlan
        deadline = None
        if (args.deadline_s is not None or args.backoff_base_s > 0
                or args.min_quorum > 0 or args.compute_time_s > 0):
            deadline = DeadlineConfig(
                deadline_s=(args.deadline_s if args.deadline_s is not None
                            else math.inf),
                backoff_base_s=args.backoff_base_s,
                max_retries=args.max_retries, min_quorum=args.min_quorum,
                compute_mean_s=args.compute_time_s)
        population = None
        if args.population:
            from repro.fl.population import PopulationConfig
            from repro.wireless.scenarios import Scenario
            population = PopulationConfig(
                population=args.population, cohort_size=args.cohort,
                sampler=args.sampler,
                scenario=Scenario.from_spec(args.scenario))
            print(f"population PFTT: {args.population} clients, "
                  f"cohort {args.cohort}/round ({args.sampler} sampling) "
                  f"on {n_dev} device(s)")
        else:
            print(f"federated cohort demo (PFTT roberta workload, "
                  f"{'published' if args.published_widths else 'reduced'} "
                  f"widths; --steps/--seq ignored) on {n_dev} device(s)")
        mesh = auto_mesh((n_dev,), ("data",))
        cfg = PFTTConfig(n_clients=args.fl_clients or args.cohort,
                         rounds=args.fl_rounds,
                         batch=args.batch, lr=args.lr, local_steps=5,
                         published_widths=args.published_widths,
                         pretrain_steps=50, samples_per_client=200,
                         uplink_codec=args.uplink_codec,
                         factored_agg=args.factored_agg,
                         fault_plan=FaultPlan.from_spec(args.fault_plan),
                         staleness_a=args.staleness_a,
                         max_staleness=args.max_staleness,
                         deadline=deadline, population=population,
                         ckpt_dir=args.ckpt_dir, resume=args.resume,
                         telemetry=telemetry, verbose=True)
        res = run_pftt(cfg, mesh=mesh, client_axes=("data",))
        print(f"sharded cohort over {n_dev} device(s): final acc "
              f"{res['final_acc']:.3f} mean round bytes "
              f"{res['mean_round_bytes']:,.0f} "
              f"(codec={args.uplink_codec}) mean round delay "
              f"{res['mean_round_delay_s']:.3f}s energy "
              f"{res['total_energy_j']:.2f}J")
        if population is not None:
            print(f"population: sampled {res['participation_frac']:.1%} of "
                  f"{res['population']} clients, host overhead "
                  f"{res['host_overhead_frac']:.1%} of round wall-clock, "
                  f"store {res['store_bytes'] / 1e6:.1f}MB")
        if deadline is not None:
            print(f"continuous-time round: sim time "
                  f"{res['total_sim_time_s']:.1f}s quorum no-ops "
                  f"{res['quorum_noops']}")
        if args.assert_fused:
            assert res["fused_engine"], "PFTT ran the legacy per-client loop"
            print("fused path asserted: engine round")
        return
    d = args.data_axis or n_dev
    mesh = auto_mesh((d, n_dev // d), ("data", "model"))
    meshctx = MeshCtx(mesh=mesh)
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    model = Model(cfg, meshctx=meshctx, remat=True)
    key = jax.random.PRNGKey(0)
    params = model.init(key, max_seq=args.seq)
    step_fn, opt = make_train_step(model, lr=args.lr)
    opt_state = opt.init(params)

    pspecs = param_specs(meshctx, jax.eval_shape(lambda: params), cfg)
    params = jax.device_put(params, jax.tree.map(
        meshctx.sharding, pspecs,
        is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec)))

    rng = np.random.RandomState(0)
    jstep = jax.jit(step_fn)
    t0 = time.time()
    with jax.set_mesh(mesh):
        for i in range(args.steps):
            toks = jnp.asarray(rng.randint(6, cfg.vocab_size,
                                           size=(args.batch, args.seq + 1)))
            batch = {"tokens": toks[:, :-1], "labels": toks[:, 1:],
                     "mask": jnp.ones((args.batch, args.seq))}
            if cfg.is_encoder_decoder:
                batch["frames"] = jnp.asarray(rng.randn(
                    args.batch, cfg.encoder_seq, cfg.d_model), jnp.float32)
            if cfg.n_prefix_tokens:
                batch["patches"] = jnp.asarray(rng.randn(
                    args.batch, cfg.n_prefix_tokens, cfg.prefix_dim),
                    jnp.float32)
            params, opt_state, loss = jstep(params, opt_state, batch)
            if i % 10 == 0:
                print(f"step {i:4d} loss {float(loss):.4f} "
                      f"({(time.time()-t0)/(i+1):.2f}s/step)")
    if args.ckpt:
        from repro.checkpoint import save_checkpoint
        save_checkpoint(args.ckpt, params)
        print("saved", args.ckpt)


if __name__ == "__main__":
    main()
