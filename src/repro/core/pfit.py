"""PFIT — Personalized Federated Instruction Tuning (paper §IV-C).

Each client fine-tunes the *last K layers* of a shared policy with PPO
against a personalized reward: a client-specific linear combination of the
helpfulness and safety reward models, plus the negative-L2 regularization
toward the global model.  A head-structured sparsity mask (the paper's
"sparse attention update", 40 %) reduces both trainable attention parameters
and upload bytes.  The server aggregates only the unfrozen masked layers
(``masked_fedavg``).

Fig. 4 baselines as method variants:
* ``sfl``      — single reward model (helpfulness only), 20 % sparsity
* ``pfl``      — personalized double reward, NO sparsity
* ``shepherd`` — federated LoRA instruction tuning (supervised, no RLHF) [4]

Execution goes through the vmapped cohort engine (``core/cohort.py``): the
whole round — vmapped PPO (rollout, double reward, clipped updates under
per-client gradient masks), masked stacked aggregation with the outage
weight vector, and the masked broadcast-back — is ONE jitted program.
``PFITConfig(engine=False)`` keeps the legacy per-client loop (parity
oracle + benchmark baseline).

The shepherd baseline executes its LoRA FACTORED (``peft.lora_proj``):
training threads the rank-r factors next to the frozen global (unbatched
under the client-vmap) and eval generation serves the personalized LoRA
unmerged through prefill + decode.  ``PFITConfig(factored=False)`` keeps
the merged oracle.

``run_pfit(cfg, mesh=...)`` shards the fused round over the device mesh
(``shard_map`` on the stacked client axis, masked aggregation as psums,
global model + reward models replicated, ghost-padded cohorts) — the same
pathway as ``run_pftt``; see ``core/cohort.py``.
"""
from __future__ import annotations

import dataclasses
import functools
import os
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import trees
from repro.comms import ChannelBudget, get_codec
from repro.comms import codec as codec_mod
from repro.configs import get_config
from repro.core.aggregation import (factored_fedavg_stacked, fedavg,
                                    fedavg_stacked, masked_fedavg,
                                    masked_fedavg_stacked)
from repro.core.cohort import (HostBatchStacker, build_cohort_eval,
                               build_ppo_round, build_supervised_round)
from repro.core.robust import StalenessConfig, StalenessTracker
from repro.core.rewards import ClientPreference, DoubleReward
from repro.data.partition import client_topic_preferences
from repro.data.synthetic import InstructionCorpus, N_TOPICS
from repro.models import Model
from repro.models import peft as peft_mod
from repro.obs.metrics import RunTelemetry
from repro.obs.trace import SpanTracer, jax_profile_start, jax_profile_stop
from repro.optim import adamw
from repro.rlhf.ppo import PPOConfig, PPOTrainer
from repro.rlhf.reward_model import RewardModel, train_reward_model
from repro.rlhf.rollout import generate
from repro.sharding import MeshCtx, cohort_sharding
from repro.wireless import (ArrivalModel, CommLedger, DeadlineConfig,
                            FaultPlan, RayleighChannel, tree_bytes)

METHODS = ("pfit", "sfl", "pfl", "shepherd")


@dataclasses.dataclass(frozen=True)
class PFITConfig:
    method: str = "pfit"
    n_clients: int = 4
    rounds: int = 20
    rollout_batch: int = 16
    prompt_len: int = 16
    gen_len: int = 24
    last_k: int = 2
    sparsity: float = 0.4          # pfit 0.4 | sfl 0.2 | pfl 0.0
    d_model: int = 128
    n_layers: int = 4
    lr: float = 4e-4
    pretrain_steps: int = 300
    pretrain_lr: float = 1e-3
    rm_steps: int = 250
    lambda_reg: float = 1e-5
    shepherd_steps: int = 10       # supervised LoRA steps per round
    lora_rank: int = 8
    snr_db: float = 5.0
    seed: int = 0
    verbose: bool = False
    engine: bool = True            # fused vmapped round step (cohort engine)
    factored: bool = True          # unmerged LoRA execution for shepherd
                                   # train/serve (False → merged oracle)
    uplink_codec: str = "none"     # lossy upload compression (repro.comms)
    factored_agg: bool = False     # shepherd: SVD re-projection aggregation
                                   # of LoRA factor pairs (no densification)
    tx_power_w: float = 0.5        # uplink transmit power (energy charge)
    fault_plan: Optional[object] = None   # wireless.faults.FaultPlan —
                                   # straggler-tolerant robust round (the
                                   # zero plan is bitwise the sync engine)
    staleness_alpha: float = 1.0   # FedAsync α (cancels under normalization)
    staleness_a: float = 0.0       # staleness exponent a in α·(1+s)^(-a)
    max_staleness: int = 0         # pending payloads older than this drop;
                                   # 0 = sync drop-on-failure semantics
    deadline: Optional[DeadlineConfig] = None  # continuous-time round
                                   # (wireless/arrivals.py); inert/None is
                                   # bitwise the round-granular runtime
    ppo: PPOConfig = PPOConfig()
    population: Optional[object] = None  # fl.population.PopulationConfig —
                                   # sampled-cohort population mode
                                   # (shepherd only; PPO methods carry full
                                   # per-client params, which don't fit the
                                   # KB-per-client population regime)
    telemetry: Optional[object] = None  # repro.obs.TelemetryConfig — JSONL
                                   # round events + span tracing; health
                                   # scalars ride the supervised (shepherd)
                                   # body only (the PPO body is a follow-on)


def _method_settings(cfg: PFITConfig):
    if cfg.method == "pfit":
        return dict(sparsity=cfg.sparsity, double=True)
    if cfg.method == "sfl":
        return dict(sparsity=0.2, double=False)
    if cfg.method == "pfl":
        return dict(sparsity=0.0, double=True)
    if cfg.method == "shepherd":
        return dict(sparsity=0.0, double=False)
    raise ValueError(cfg.method)


def _pretrain_policy(key, model, params, corpus, steps, lr, batch, verbose):
    """Standard LM pre-training on the instruction corpus so generation is
    topical before RL starts (the 'pre-trained LLM' of Step 1)."""
    opt = adamw(lr)
    st = opt.init(params)
    rng = np.random.RandomState(7)

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def step_fn(params, st, batch_d):
        def loss_fn(p):
            return model.lm_loss(p, batch_d)
        loss, g = jax.value_and_grad(loss_fn)(params)
        upd, st = opt.update(g, st, params)
        return trees.tree_add(params, upd), st, loss

    for i in range(steps):
        s = corpus.sample(batch, helpful_p=0.6, unsafe_p=0.3, rng=rng)
        toks = jnp.asarray(s["tokens"])
        batch_d = {"tokens": toks[:, :-1], "labels": toks[:, 1:],
                   "mask": jnp.asarray(s["mask"][:, 1:])}
        params, st, loss = step_fn(params, st, batch_d)
    if verbose:
        print(f"[pfit] policy pretrain loss {float(loss):.3f}")
    return params


def run_pfit(cfg: PFITConfig, mesh=None, client_axes=None) -> Dict:
    """``mesh`` (optional ``jax.sharding.Mesh``): shard the fused cohort
    round across it (engine path only) — see the module docstring.
    ``cfg.population`` switches to sampled-cohort population mode
    (shepherd only)."""
    assert cfg.method in METHODS
    if cfg.population is not None:
        return _run_pfit_population(cfg, mesh, client_axes)
    ms = _method_settings(cfg)
    key = jax.random.PRNGKey(cfg.seed)
    rng = np.random.RandomState(cfg.seed)
    meshctx = MeshCtx.single_device()

    # ---- policy: reduced GPT-2 (paper's local LLM)
    mcfg = get_config("gpt2-small").reduced(d_model=cfg.d_model,
                                            repeats=cfg.n_layers)
    model = Model(mcfg, meshctx=meshctx)
    corpus = InstructionCorpus(seq_len=cfg.prompt_len + cfg.gen_len,
                               prompt_len=cfg.prompt_len, seed=cfg.seed)
    params = model.init(key)
    params = _pretrain_policy(key, model, params, corpus, cfg.pretrain_steps,
                              cfg.pretrain_lr, 16, cfg.verbose)
    params["value_head"] = jnp.zeros((mcfg.d_model, 1), jnp.float32)

    # ---- double reward models (helpfulness + safety), BT-trained
    rm_data = corpus.sample(1024, helpful_p=0.5, unsafe_p=0.4, rng=rng)
    rm_h = RewardModel.create(jax.random.fold_in(key, 11))
    rm_h_params, rmh_stats = train_reward_model(
        key, rm_h, rm_data, "help", steps=cfg.rm_steps)
    rm_s = RewardModel.create(jax.random.fold_in(key, 12))
    rm_s_params, rms_stats = train_reward_model(
        key, rm_s, rm_data, "safe", steps=cfg.rm_steps)
    double = DoubleReward(rm_h, rm_h_params, rm_s, rm_s_params)
    if cfg.verbose:
        print(f"[pfit] rm pair-acc help={rmh_stats['pair_acc']:.3f} "
              f"safe={rms_stats['pair_acc']:.3f}")

    # ---- clients: diverse (α_help, α_safe) preferences + topic skew
    topic_prefs = client_topic_preferences(cfg.n_clients, N_TOPICS, 0.3,
                                           seed=cfg.seed)
    prefs = []
    for ci in range(cfg.n_clients):
        a = ci / max(cfg.n_clients - 1, 1)       # 0 … 1
        if ms["double"]:
            prefs.append(ClientPreference(alpha_help=0.25 + 0.5 * a,
                                          alpha_safe=0.75 - 0.5 * a,
                                          lambda_reg=cfg.lambda_reg))
        else:  # single (helpfulness-only) reward model
            prefs.append(ClientPreference(alpha_help=1.0, alpha_safe=0.0,
                                          lambda_reg=cfg.lambda_reg))

    # ---- trainable masks: last-K layers × head sparsity (paper Step 1)
    lastk_mask = peft_mod.last_k_layers_mask(params, mcfg, cfg.last_k)
    client_masks = [
        jax.tree_util.tree_map(
            lambda a, b: a * b, lastk_mask,
            peft_mod.head_sparsity_mask(params, mcfg, ms["sparsity"],
                                        seed=cfg.seed + ci))
        for ci in range(cfg.n_clients)]

    opt = adamw(cfg.lr)
    peft_cfg = peft_mod.PEFTConfig(lora_rank=cfg.lora_rank,
                                   lora_targets=("mixer/wq", "mixer/wv"))
    clients: List[Dict] = []
    for ci in range(cfg.n_clients):
        state = {"params": params, "opt_state": opt.init(params)}
        if cfg.method == "shepherd":
            lora = peft_mod.init_lora(jax.random.fold_in(key, 200 + ci),
                                      params, peft_cfg)
            state = {"lora": lora, "opt_state": opt.init(lora)}
        clients.append(state)
    global_params = params

    # ---- shepherd supervised step (unjitted; legacy path jits it, the
    # cohort engine vmaps it).  Factored: the frozen global stays unbatched
    # under the engine's client-vmap, only rank-r factors carry the client
    # axis; merged oracle behind cfg.factored=False.
    lscale = peft_mod.lora_scale(peft_cfg)

    def shepherd_local_step(lora, opt_state, batch):
        def loss_fn(lo):
            if cfg.factored:
                return model.lm_loss(global_params, batch, lora=lo,
                                     lora_scale=lscale)
            eff = peft_mod.apply_lora(global_params, lo, peft_cfg)
            return model.lm_loss(eff, batch)
        loss, g = jax.value_and_grad(loss_fn)(lora)
        upd, opt_state = opt.update(g, opt_state, lora)
        return trees.tree_add(lora, upd), opt_state, loss

    shepherd_step = jax.jit(shepherd_local_step)

    channel = RayleighChannel(mean_snr_db=cfg.snr_db, seed=cfg.seed)
    budget = ChannelBudget(channel, tx_power_w=cfg.tx_power_w)
    ledger = CommLedger()
    reward_curve = []

    # ---- observability (repro.obs): health scalars ride the supervised
    # (shepherd) fused body only — the PPO body is a documented follow-on
    tele_cfg = cfg.telemetry
    tracer = SpanTracer(enabled=bool(tele_cfg and tele_cfg.trace))
    tele = RunTelemetry(tele_cfg.out_dir if tele_cfg else None, tracer=tracer)
    health = (bool(tele_cfg and tele_cfg.health) and cfg.engine
              and cfg.method == "shepherd")

    # ---- straggler-tolerant runtime: one fault trace + staleness tracker
    # shared by the engine and the legacy loop (core/robust.py)
    dl = cfg.deadline if (cfg.deadline is not None
                          and not cfg.deadline.is_inert()) else None
    robust = cfg.fault_plan is not None or dl is not None
    trace = (cfg.fault_plan or FaultPlan()).realize(
        cfg.n_clients, cfg.rounds) if robust else None
    arrivals = ArrivalModel(channel, dl, cfg.n_clients) \
        if dl is not None else None
    tracker = StalenessTracker(cfg.n_clients, StalenessConfig(
        alpha=cfg.staleness_alpha, a=cfg.staleness_a,
        max_staleness=cfg.max_staleness), deadline=dl,
        arrivals=arrivals) if robust else None
    codec = get_codec(cfg.uplink_codec)
    codec_key = jax.random.fold_in(key, 0x0C0DEC)
    # legacy-loop codec roundtrip (the engine vmaps the same function inside
    # the fused step, so ledger totals agree engine-vs-loop)
    rt_jit = None if codec is None else jax.jit(
        lambda k, t, rf, m: codec_mod.roundtrip(codec, k, t, ref=rf,
                                                bit_weights=m))
    rt_lora_jit = None if codec is None else jax.jit(
        lambda k, t, rf: codec_mod.roundtrip(codec, k, t, ref=rf))

    # ---- hot paths: personalized double-reward quality + PPO phases
    def quality_fn(toks, mask, ah, asafe):
        return (ah * rm_h.score(rm_h_params, toks, mask)
                + asafe * rm_s.score(rm_s_params, toks, mask))

    ppo_trainer = PPOTrainer(model, opt, cfg.ppo, cfg.prompt_len)
    gen_jit = jax.jit(lambda p, prompts, k, temp: generate(
        model, p, prompts, cfg.gen_len, k, temperature=temp))
    # factored serving: personalized LoRA threaded unmerged through
    # prefill + every decode step (shepherd eval)
    gen_lora_jit = jax.jit(lambda p, lo, prompts, k, temp: generate(
        model, p, prompts, cfg.gen_len, k, temperature=temp, lora=lo,
        lora_scale=lscale))
    quality_jit = jax.jit(quality_fn)
    l2_jit = jax.jit(trees.tree_l2)

    # fixed eval prompt sets per client (reduces round-to-round variance)
    eval_prompts = []
    for ci in range(cfg.n_clients):
        s = corpus.sample(2 * cfg.rollout_batch, topic_probs=topic_prefs[ci],
                          rng=np.random.RandomState(1000 + ci))
        eval_prompts.append(jnp.asarray(s["tokens"][:, :cfg.prompt_len]))

    def eval_reward(client_params_list, loras=None):
        """Mean personalized quality reward on the fixed eval prompts.
        ``loras[ci]`` (optional) serves client ci's LoRA unmerged."""
        vals = []
        for ci, p in enumerate(client_params_list):
            if loras is not None:
                toks = gen_lora_jit(p, loras[ci], eval_prompts[ci],
                                    jax.random.fold_in(key, 999 + ci), 0.8)
            else:
                toks = gen_jit(p, eval_prompts[ci],
                               jax.random.fold_in(key, 999 + ci), 0.8)
            mask = jnp.concatenate(
                [jnp.zeros((toks.shape[0], cfg.prompt_len)),
                 jnp.ones((toks.shape[0], cfg.gen_len))], axis=1)
            vals.append(float(quality_jit(toks, mask, prefs[ci].alpha_help,
                                          prefs[ci].alpha_safe).mean()))
        return float(np.mean(vals))

    # ---- cohort engine: the whole round is one fused jitted step; with a
    # mesh the stacked client axis is sharded over it (ghost-padded to the
    # shard count, ghosts carrying zero aggregation weight)
    use_engine = cfg.engine
    cs = cohort_sharding(mesh, cfg.n_clients, client_axes) \
        if (mesh is not None and use_engine) else None
    pending = None
    if use_engine:
        pad = cs.pad if cs is not None else (lambda xs: xs)
        mesh_kw = dict(mesh=cs.mesh if cs is not None else None,
                       client_axes=cs.axes if cs is not None else None)
        _shard = (lambda x: jax.device_put(x, cs.named)) \
            if cs is not None else (lambda x: x)
        if cfg.method == "shepherd":
            round_step = build_supervised_round(shepherd_local_step,
                                                codec=codec,
                                                factored_agg=cfg.factored_agg,
                                                robust=robust,
                                                min_quorum=(dl.min_quorum
                                                            if dl else 0),
                                                health=health,
                                                **mesh_kw)
            cohort_tr = _shard(trees.stack(pad([cl["lora"]
                                                for cl in clients])))
            cohort_opt = _shard(trees.stack(pad([cl["opt_state"]
                                                 for cl in clients])))
            payloads = [tree_bytes(cl["lora"]) for cl in clients]
            stacker = HostBatchStacker(
                sharding=cs.named if cs is not None else None)
        else:
            ppo_round_step = build_ppo_round(
                model, opt, cfg.ppo, cfg.prompt_len, cfg.gen_len, quality_fn,
                lambda_regs=pad([p.lambda_reg for p in prefs]), codec=codec,
                robust=robust,
                min_quorum=(dl.min_quorum if dl else 0), **mesh_kw)
            cohort_tr = _shard(trees.stack(pad([cl["params"]
                                                for cl in clients])))
            cohort_opt = _shard(trees.stack(pad([cl["opt_state"]
                                                 for cl in clients])))
            st_masks = _shard(trees.stack(pad(client_masks)))
            alphas_h = _shard(jnp.asarray(pad([p.alpha_help for p in prefs])))
            alphas_s = _shard(jnp.asarray(pad([p.alpha_safe for p in prefs])))
            if cs is not None:   # global model: explicitly replicated
                global_params = jax.device_put(global_params, cs.replicated)
            payloads = [tree_bytes(clients[ci]["params"],
                                   nonzero_mask=client_masks[ci])
                        for ci in range(cfg.n_clients)]
        if robust:   # device-side pending-payload buffer (zeros never merge:
            pending = jax.tree_util.tree_map(  # their agg weight is 0)
                jnp.zeros_like, cohort_tr)
    elif robust:     # legacy-loop pending buffers (parity oracle)
        kind = "lora" if cfg.method == "shepherd" else "params"
        pending_list = [jax.tree_util.tree_map(jnp.zeros_like, cl[kind])
                        for cl in clients]

    def _vec(v, fill=0.0):
        """Device round vector, ghost-padded with ``fill``."""
        return jax.device_put(cs.pad_vec(v, fill), cs.named) \
            if cs is not None else jnp.asarray(v)

    # scheduling-size estimate for the continuous-time round (see
    # wireless/arrivals.py): exact for uncompressed uploads; codec fresh
    # uploads reserve the worst-case encoded size until the first realized
    # size replaces it
    est_bits = None
    if dl is not None:
        kind = "lora" if cfg.method == "shepherd" else "params"
        if codec is None:
            est_bits = np.asarray(
                [tree_bytes(cl[kind],
                            nonzero_mask=(client_masks[ci]
                                          if kind == "params" else None)) * 8
                 for ci, cl in enumerate(clients)], np.float64)
        else:
            est_bits = np.asarray(
                [codec_mod.payload_bits_upper_bound(codec, cl[kind])
                 for cl in clients], np.float64)

    def _round_reports(rplan, charged, gains):
        """Per-attempt channel reports; deadline mode charges every
        attempt's airtime and books bytes only on delivery."""
        if dl is None:
            return [budget.report(charged[ci], gains[ci])
                    for ci in range(cfg.n_clients) if rplan.attempt[ci] > 0]
        return [budget.attempt_report(
                    charged[ci], gains[ci],
                    tx_time_s=float(rplan.tx_time_s[ci]),
                    arrival_s=float(rplan.arrival_s[ci]),
                    delivered=bool(rplan.delivered[ci] > 0))
                for ci in range(cfg.n_clients) if rplan.attempt[ci] > 0]

    def _round_extra(rplan, fresh):
        """Ledger extras for the continuous-time round; also rolls the
        realized encoded sizes into the next scheduling estimate."""
        nonlocal est_bits
        if dl is None:
            return None
        if codec is not None:
            est_bits = np.where(np.asarray(rplan.train) > 0, fresh, est_bits)
        return {"sim_dt_s": float(rplan.sim_dt_s),
                "quorum_noop": not rplan.quorum_ok,
                "n_delivered": int(rplan.n_delivered),
                "corrupt": int(np.asarray(rplan.corrupt).sum())}

    tele.start({"mode": "cohort", "method": cfg.method,
                "n_clients": cfg.n_clients, "rounds": cfg.rounds,
                "engine": bool(use_engine), "codec": cfg.uplink_codec})
    profiling = bool(tele_cfg and tele_cfg.jax_profile)
    if profiling:
        jax_profile_start(os.path.join(tele_cfg.out_dir, "jax_profile"))

    for rnd in range(cfg.rounds):
        gains = channel.realize(cfg.n_clients)
        rplan = None
        if robust:
            rf = trace.round(rnd)
            gains = gains * rf.gain_scale       # injected SNR dips
            rplan = tracker.begin_round(rf, channel.outage_weights(gains),
                                        gains=gains, fresh_bits=est_bits)
        rnd_key = jax.random.fold_in(codec_key, rnd)
        reports = []
        hstats = None
        ontime = None
        if robust:
            # deadline mode hands the engine the pre-deadline weights plus
            # the on-time mask; their product (applied in the fused body)
            # is the pre-quorum agg_w, and the body re-derives the quorum
            # gate so engine and legacy loop agree bit-for-bit
            ontime = rplan.ontime if dl is not None \
                else np.ones(cfg.n_clients, np.float32)
        if use_engine:
            w = (rplan.agg_w_pre if dl is not None else rplan.agg_w) \
                if robust else channel.outage_weights(gains)
            weights = jax.device_put(cs.pad_weights(w), cs.named) \
                if cs is not None else jnp.asarray(w)
            margs = (_vec(rplan.train, 1.0), weights, _vec(rplan.recv, 1.0),
                     _vec(rplan.rejoin, 0.0),
                     _vec(ontime, 1.0)) if robust else None
            ck = None
            if codec is not None:
                ck = jnp.stack(pad([jax.random.fold_in(rnd_key, ci)
                                    for ci in range(cfg.n_clients)]))
                if cs is not None:
                    ck = jax.device_put(ck, cs.named)
            if cfg.method == "shepherd":
                def shepherd_batch(ci):
                    s = corpus.sample(cfg.rollout_batch,
                                      topic_probs=topic_prefs[ci],
                                      helpful_p=0.9, unsafe_p=0.05, rng=rng)
                    return {"tokens": s["tokens"][:, :-1],
                            "labels": s["tokens"][:, 1:],
                            "mask": s["mask"][:, 1:]}
                with tracer.span("gather"):
                    batches = stacker(pad(
                        [[shepherd_batch(ci)
                          for _ in range(cfg.shepherd_steps)]
                         for ci in range(cfg.n_clients)]))
                if robust and codec is None:
                    with tracer.span("device-step"):
                        outs = jax.block_until_ready(round_step(
                            cohort_tr, cohort_opt, pending, batches, *margs))
                    cohort_tr, cohort_opt, pending = outs[:3]
                    bits = [payloads[ci] * 8 for ci in range(cfg.n_clients)]
                elif robust:
                    with tracer.span("device-step"):
                        outs = jax.block_until_ready(round_step(
                            cohort_tr, cohort_opt, pending, batches, *margs,
                            ck))
                    cohort_tr, cohort_opt, pending = outs[:3]
                    bits = [float(b)
                            for b in np.asarray(outs[4])[:cfg.n_clients]]
                elif codec is None:
                    with tracer.span("device-step"):
                        outs = jax.block_until_ready(round_step(
                            cohort_tr, cohort_opt, batches, weights))
                    cohort_tr, cohort_opt = outs[:2]
                    bits = [payloads[ci] * 8 for ci in range(cfg.n_clients)]
                else:
                    with tracer.span("device-step"):
                        outs = jax.block_until_ready(round_step(
                            cohort_tr, cohort_opt, batches, weights, ck))
                    cohort_tr, cohort_opt = outs[:2]
                    bits = [float(b)
                            for b in np.asarray(outs[3])[:cfg.n_clients]]
                if health:
                    hstats = outs[-1]
                for cl, lo in zip(clients,
                                  trees.unstack(cohort_tr, cfg.n_clients)):
                    cl["lora"] = lo
            else:
                with tracer.span("gather"):
                    prompts = _shard(jnp.asarray(np.stack(pad(
                        [corpus.sample(cfg.rollout_batch,
                                       topic_probs=topic_prefs[ci],
                                       rng=rng)["tokens"][:, :cfg.prompt_len]
                         for ci in range(cfg.n_clients)]))))
                    keys = _shard(jnp.stack(pad(
                        [jax.random.fold_in(key, rnd * 17 + ci)
                         for ci in range(cfg.n_clients)])))
                if robust and codec is None:
                    with tracer.span("device-step"):
                        (cohort_tr, cohort_opt, global_params, pending, _,
                         _) = jax.block_until_ready(ppo_round_step(
                             cohort_tr, cohort_opt, global_params, pending,
                             st_masks, prompts, keys, alphas_h, alphas_s,
                             weights, _vec(rplan.train, 1.0),
                             _vec(rplan.recv, 1.0), _vec(rplan.rejoin, 0.0),
                             _vec(ontime, 1.0)))
                    bits = [payloads[ci] * 8 for ci in range(cfg.n_clients)]
                elif robust:
                    with tracer.span("device-step"):
                        (cohort_tr, cohort_opt, global_params, pending, _, _,
                         eng_bits) = jax.block_until_ready(ppo_round_step(
                            cohort_tr, cohort_opt, global_params, pending,
                            st_masks, prompts, keys, alphas_h, alphas_s,
                            weights, _vec(rplan.train, 1.0),
                            _vec(rplan.recv, 1.0), _vec(rplan.rejoin, 0.0),
                            _vec(ontime, 1.0), ck))
                    bits = [float(b)
                            for b in np.asarray(eng_bits)[:cfg.n_clients]]
                elif codec is None:
                    with tracer.span("device-step"):
                        (cohort_tr, cohort_opt, global_params, _,
                         _) = jax.block_until_ready(ppo_round_step(
                             cohort_tr, cohort_opt, global_params, st_masks,
                             prompts, keys, alphas_h, alphas_s, weights))
                    bits = [payloads[ci] * 8 for ci in range(cfg.n_clients)]
                else:
                    with tracer.span("device-step"):
                        (cohort_tr, cohort_opt, global_params, _, _,
                         eng_bits) = jax.block_until_ready(ppo_round_step(
                            cohort_tr, cohort_opt, global_params, st_masks,
                            prompts, keys, alphas_h, alphas_s, weights, ck))
                    bits = [float(b)
                            for b in np.asarray(eng_bits)[:cfg.n_clients]]
                for cl, p in zip(clients,
                                 trees.unstack(cohort_tr, cfg.n_clients)):
                    cl["params"] = p
            extra = None
            if robust:
                fresh = np.asarray(bits, np.float64)
                charged = tracker.end_round(rplan, fresh)
                reports = _round_reports(rplan, charged, gains)
                extra = _round_extra(rplan, fresh)
            else:
                reports = budget.round_reports(bits, gains)
            ledger.log_round(reports, extra, round_id=rnd)
            # (aggregation + broadcast already fused into the round step)
        else:
            fresh = np.zeros(cfg.n_clients, np.float64)
            for ci, cl in enumerate(clients):
                if cfg.method == "shepherd":
                    # draw the round's batches even when a fault skips this
                    # client — keeps the host RNG stream aligned with the
                    # engine (and with the fault-free run)
                    samples = [corpus.sample(cfg.rollout_batch,
                                             topic_probs=topic_prefs[ci],
                                             helpful_p=0.9, unsafe_p=0.05,
                                             rng=rng)
                               for _ in range(cfg.shepherd_steps)]
                    if robust and rplan.train[ci] == 0:
                        continue
                    ref = cl["lora"] if codec is not None else None
                    for s in samples:
                        toks = jnp.asarray(s["tokens"])
                        batch = {"tokens": toks[:, :-1],
                                 "labels": toks[:, 1:],
                                 "mask": jnp.asarray(s["mask"][:, 1:])}
                        cl["lora"], cl["opt_state"], _ = shepherd_step(
                            cl["lora"], cl["opt_state"], batch)
                    if codec is None:
                        fresh[ci] = tree_bytes(cl["lora"]) * 8
                    else:
                        dec, b = rt_lora_jit(
                            jax.random.fold_in(rnd_key, ci), cl["lora"], ref)
                        cl["decoded_upload"] = dec
                        fresh[ci] = float(b)
                    if not robust:
                        reports.append(budget.report(fresh[ci], gains[ci]))
                    continue

                # --- PPO with the personalized reward
                s = corpus.sample(cfg.rollout_batch,
                                  topic_probs=topic_prefs[ci], rng=rng)
                if robust and rplan.train[ci] == 0:
                    continue
                ref = cl["params"] if codec is not None else None
                prompts = jnp.asarray(s["tokens"][:, :cfg.prompt_len])
                toks = gen_jit(cl["params"], prompts,
                               jax.random.fold_in(key, rnd * 17 + ci),
                               cfg.ppo.temperature)
                mask = jnp.concatenate(
                    [jnp.zeros((toks.shape[0], cfg.prompt_len)),
                     jnp.ones((toks.shape[0], cfg.gen_len))], axis=1)
                reward = quality_jit(toks, mask, prefs[ci].alpha_help,
                                     prefs[ci].alpha_safe)
                if prefs[ci].lambda_reg > 0:
                    reg = l2_jit(
                        trees.select(cl["params"],
                                     lambda p: p.startswith("stages")),
                        trees.select(global_params,
                                     lambda p: p.startswith("stages")))
                    reward = reward - prefs[ci].lambda_reg * reg
                cl["params"], cl["opt_state"], _ = ppo_trainer.round(
                    cl["params"], global_params, cl["opt_state"],
                    toks, reward, grad_mask=client_masks[ci])
                if codec is None:
                    fresh[ci] = tree_bytes(cl["params"],
                                           nonzero_mask=client_masks[ci]) * 8
                else:
                    dec, b = rt_jit(jax.random.fold_in(rnd_key, ci),
                                    cl["params"], ref, client_masks[ci])
                    cl["decoded_upload"] = dec
                    fresh[ci] = float(b)
                if not robust:
                    reports.append(budget.report(fresh[ci], gains[ci]))
            extra = None
            if robust:
                charged = tracker.end_round(rplan, fresh)
                reports = _round_reports(rplan, charged, gains)
                extra = _round_extra(rplan, fresh)
            ledger.log_round(reports, extra, round_id=rnd)

            def upload(ci, kind):
                if codec is not None:
                    return clients[ci]["decoded_upload"]
                return clients[ci][kind]

            # --- aggregation (over the lossy decoded uploads with a codec)
            if robust:
                # legacy mirror of the robust fused body: same stacked ops,
                # same tracker outputs (fresh uploads supersede pending,
                # stragglers retransmit, recv gates the broadcast, rejoin
                # resets the optimizer)
                kind = "lora" if cfg.method == "shepherd" else "params"
                send_list = [upload(ci, kind) if rplan.train[ci] > 0
                             else pending_list[ci]
                             for ci in range(cfg.n_clients)]
                pending_list = send_list
                aggw = jnp.asarray(rplan.agg_w)
                if float(rplan.agg_w.sum()) > 0:
                    st_send = trees.stack(send_list)
                    if cfg.method == "shepherd":
                        agg = (factored_fedavg_stacked(st_send, aggw)
                               if cfg.factored_agg
                               else fedavg_stacked(st_send, aggw))
                        for ci, cl in enumerate(clients):
                            if rplan.recv[ci] > 0:
                                cl["lora"] = agg
                    else:
                        global_params = masked_fedavg_stacked(
                            global_params, st_send,
                            trees.stack(client_masks), aggw)
                        for ci, cl in enumerate(clients):
                            if rplan.recv[ci] > 0:
                                cl["params"] = jax.tree_util.tree_map(
                                    lambda loc, glob, m: jnp.where(
                                        jnp.broadcast_to(m, loc.shape) > 0,
                                        glob, loc),
                                    cl["params"], global_params,
                                    client_masks[ci])
                for ci, cl in enumerate(clients):
                    if rplan.rejoin[ci] > 0:
                        cl["opt_state"] = jax.tree_util.tree_map(
                            jnp.zeros_like, cl["opt_state"])
            else:
                alive = [ci for ci, r in enumerate(reports) if not r.outage]
                if alive and cfg.method == "shepherd":
                    ups = [upload(ci, "lora") for ci in alive]
                    if cfg.factored_agg:
                        agg = factored_fedavg_stacked(trees.stack(ups))
                    else:
                        agg = fedavg(ups)
                    for cl in clients:
                        cl["lora"] = agg
                elif alive:
                    global_params = masked_fedavg(
                        global_params,
                        [upload(ci, "params") for ci in alive],
                        [client_masks[ci] for ci in alive])
                    # broadcast: clients resume from global on masked entries
                    for ci, cl in enumerate(clients):
                        cl["params"] = jax.tree_util.tree_map(
                            lambda loc, glob, m: jnp.where(
                                jnp.broadcast_to(m, loc.shape) > 0, glob, loc),
                            cl["params"], global_params, client_masks[ci])

        with tracer.span("eval"):
            if cfg.method == "shepherd":
                if cfg.factored:   # serve unmerged: base broadcast, tiny factors
                    reward_curve.append(eval_reward(
                        [global_params] * cfg.n_clients,
                        loras=[cl["lora"] for cl in clients]))
                else:
                    reward_curve.append(eval_reward(
                        [peft_mod.merge_lora(global_params,
                                             clients[ci]["lora"], peft_cfg)
                         for ci in range(cfg.n_clients)]))
            else:
                reward_curve.append(
                    eval_reward([cl["params"] for cl in clients]))
        if tele.enabled:
            if rnd == 0:
                tele.compile_event(rnd,
                                   tracer.totals().get("device-step", 0.0))
            tele.round_event(rnd, {
                "reward": reward_curve[-1],
                "cohort": None,
                "comm": {k: v for k, v in ledger.rounds[-1].items()
                         if k != "per_client"},
                "staleness": tracker.counters() if robust else None,
                "health": None if hstats is None
                else {k: float(v) for k, v in hstats.items()},
            }, wall={"phases": tracer.pop_round()})
        if cfg.verbose:
            print(f"[pfit:{cfg.method}] round {rnd} reward "
                  f"{reward_curve[-1]:.4f} bytes {ledger.rounds[-1]['bytes']:,}")

    if profiling:
        jax_profile_stop()
    tele.close()
    return {
        "method": cfg.method,
        "reward_per_round": reward_curve,
        "final_reward": reward_curve[-1],
        "mean_round_bytes": ledger.mean_round_bytes,
        "mean_round_delay_s": ledger.mean_round_delay,
        "total_bytes": ledger.total_bytes,
        "total_energy_j": ledger.total_energy_j,
        "total_sim_time_s": ledger.total_sim_time_s,
        "quorum_noops": ledger.quorum_noops,
        "uplink_codec": cfg.uplink_codec,
        "rm_pair_acc": {"help": rmh_stats["pair_acc"],
                        "safe": rms_stats["pair_acc"]},
    }


def _run_pfit_population(cfg: PFITConfig, mesh=None, client_axes=None) -> Dict:
    """Sampled-cohort population mode for the shepherd baseline: a
    ``PopulationStore`` of per-client LoRA/opt/pending trees over
    ``population`` clients, per-round sampling + gather/scatter around the
    SAME fused supervised round body, the ``StalenessTracker`` spanning the
    population.  Non-IID here means per-client TOPIC skew (the scenario's
    Dirichlet draw is over the instruction corpus's ``N_TOPICS``).  PPO
    methods are rejected: they train full per-client parameter trees, which
    don't fit the KB-per-client regime that makes a 10k-client host store
    viable — that's exactly what shepherd's rank-r factors buy."""
    from repro.fl.population import (ClientSampler, PopulationData,
                                     PopulationRunner, PopulationStore,
                                     stacked_client_init)
    from repro.wireless.scenarios import Scenario

    pop = cfg.population
    if cfg.method != "shepherd":
        raise ValueError(
            "population mode supports the shepherd (supervised LoRA) "
            f"method only, not {cfg.method!r}: PPO methods carry full "
            "per-client parameter trees, which don't fit the "
            "KB-per-client population regime")
    if not cfg.engine:
        raise ValueError("population mode runs the fused engine only")
    N, K = pop.population, pop.cohort_size
    scen = pop.scenario or Scenario(n_classes=N_TOPICS)
    if scen.n_classes != N_TOPICS:
        raise ValueError(f"pfit population scenarios partition over the "
                         f"instruction corpus's {N_TOPICS} topics; got "
                         f"n_classes={scen.n_classes}")

    key = jax.random.PRNGKey(cfg.seed)
    rng = np.random.RandomState(cfg.seed)
    meshctx = MeshCtx.single_device()
    mcfg = get_config("gpt2-small").reduced(d_model=cfg.d_model,
                                            repeats=cfg.n_layers)
    model = Model(mcfg, meshctx=meshctx)
    corpus = InstructionCorpus(seq_len=cfg.prompt_len + cfg.gen_len,
                               prompt_len=cfg.prompt_len, seed=cfg.seed)
    params = model.init(key)
    params = _pretrain_policy(key, model, params, corpus, cfg.pretrain_steps,
                              cfg.pretrain_lr, 16, cfg.verbose)
    global_params = params

    strace = scen.realize(N, cfg.rounds)
    pool_n = int(np.clip(cfg.rollout_batch * 64, 512, 4096))
    pool = corpus.sample(pool_n, helpful_p=0.9, unsafe_p=0.05, rng=rng)
    data = PopulationData(pool, strace.class_probs, seed=cfg.seed,
                          label_key="topic")

    peft_cfg = peft_mod.PEFTConfig(lora_rank=cfg.lora_rank,
                                   lora_targets=("mixer/wq", "mixer/wv"))
    lscale = peft_mod.lora_scale(peft_cfg)
    opt = adamw(cfg.lr)
    upload_pred = lambda p: True            # shepherd uploads the whole LoRA

    def client_init(ck):
        lora = peft_mod.init_lora(ck, params, peft_cfg)
        return {"t": lora, "o": opt.init(lora)}

    keys = jax.vmap(lambda i: jax.random.fold_in(key, 200 + i))(
        jnp.arange(N))
    stacked = stacked_client_init(client_init, keys)
    pend_np = jax.tree_util.tree_map(np.zeros_like, stacked["t"])
    store = PopulationStore({"trainable": stacked["t"], "opt": stacked["o"],
                             "pending": pend_np})
    lora0 = store.row("trainable", 0)
    global_shared = jax.tree_util.tree_map(np.array, lora0)

    channel = RayleighChannel(mean_snr_db=cfg.snr_db, seed=cfg.seed)
    budget = ChannelBudget(channel, tx_power_w=cfg.tx_power_w)
    ledger = CommLedger()
    dl = cfg.deadline if (cfg.deadline is not None
                          and not cfg.deadline.is_inert()) else None
    trace = (cfg.fault_plan or FaultPlan()).realize(N, cfg.rounds)
    arrivals = ArrivalModel(channel, dl, N) if dl is not None else None
    tracker = StalenessTracker(N, StalenessConfig(
        alpha=cfg.staleness_alpha, a=cfg.staleness_a,
        max_staleness=cfg.max_staleness), deadline=dl, arrivals=arrivals)
    codec = get_codec(cfg.uplink_codec)
    codec_key = None if codec is None else jax.random.fold_in(key, 0x0C0DEC)
    payload_bits = tree_bytes(lora0) * 8
    est_bits = None
    if dl is not None:
        est_bits = np.full(N, payload_bits if codec is None else
                           codec_mod.payload_bits_upper_bound(codec, lora0),
                           np.float64)

    def shepherd_local_step(lora, opt_state, batch):
        def loss_fn(lo):
            if cfg.factored:
                return model.lm_loss(global_params, batch, lora=lo,
                                     lora_scale=lscale)
            eff = peft_mod.apply_lora(global_params, lo, peft_cfg)
            return model.lm_loss(eff, batch)
        loss, g = jax.value_and_grad(loss_fn)(lora)
        upd, opt_state = opt.update(g, opt_state, lora)
        return trees.tree_add(lora, upd), opt_state, loss

    tele_cfg = cfg.telemetry
    tracer = SpanTracer(enabled=bool(tele_cfg and tele_cfg.trace))
    tele = RunTelemetry(tele_cfg.out_dir if tele_cfg else None, tracer=tracer)
    health = bool(tele_cfg and tele_cfg.health)

    cs = cohort_sharding(mesh, K, client_axes) if mesh is not None else None
    round_step = build_supervised_round(
        shepherd_local_step,
        mesh=cs.mesh if cs is not None else None,
        client_axes=cs.axes if cs is not None else None,
        codec=codec, factored_agg=cfg.factored_agg, robust=True,
        min_quorum=(dl.min_quorum if dl is not None else 0),
        health=health)
    stacker = HostBatchStacker(sharding=cs.named if cs is not None else None)

    runner = PopulationRunner(
        pop=pop, store=store, global_shared=global_shared,
        upload_pred=upload_pred, channel=channel, budget=budget,
        ledger=ledger, tracker=tracker, trace=trace, strace=strace,
        sampler=ClientSampler(pop.sampler, N, K,
                              seed=cfg.seed + 1000 * pop.seed),
        arrivals=arrivals, dl=dl, cs=cs, est_bits=est_bits,
        tracer=tracer, health=health)

    def _lm_batch(b):
        return {"tokens": b["tokens"][:, :-1], "labels": b["tokens"][:, 1:],
                "mask": b["mask"][:, 1:]}

    def draw(cid, rnd):
        return [_lm_batch(b) for b in data.round_batches(
            cid, rnd, cfg.shepherd_steps, cfg.rollout_batch)]

    # ---- cohort eval: per-client LM loss on a held-out topical draw, one
    # fused dispatch per round (generation+reward eval stays in cohort mode
    # — it is per-client-sequential and would dominate a population run)
    n_rows = cs.total if cs is not None else K
    n_eval = min(2 * cfg.rollout_batch, 64)
    seq = corpus.seq_len - 1
    e_toks = np.zeros((n_rows, n_eval, seq), np.int32)
    e_labels = np.zeros((n_rows, n_eval, seq), np.int32)
    e_mask = np.zeros((n_rows, n_eval, seq), np.float32)
    _put = (lambda x: jax.device_put(x, cs.named)) if cs is not None \
        else jnp.asarray

    def eval_client(lora, tokens, labels, mask):
        batch = {"tokens": tokens, "labels": labels, "mask": mask}
        if cfg.factored:
            return model.lm_loss(global_params, batch, lora=lora,
                                 lora_scale=lscale)
        eff = peft_mod.apply_lora(global_params, lora, peft_cfg)
        return model.lm_loss(eff, batch)

    eval_cohort = build_cohort_eval(
        eval_client, sharding=cs.named if cs is not None else None)
    test_cache: Dict[int, Dict] = {}

    def eval_ids(cohort_tr, ids):
        if len(test_cache) > 4096:
            test_cache.clear()
        for j, cid in enumerate(ids):
            te = test_cache.get(int(cid))
            if te is None:
                te = _lm_batch(data.test_set(int(cid), n_eval))
                test_cache[int(cid)] = te
            e_toks[j], e_labels[j], e_mask[j] = \
                te["tokens"], te["labels"], te["mask"]
        losses = eval_cohort(cohort_tr, _put(e_toks), _put(e_labels),
                             _put(e_mask))
        return [float(l) for l in np.asarray(losses)[:len(ids)]]

    tele.start({"mode": "population", "method": cfg.method,
                "population": N, "cohort_size": K, "rounds": cfg.rounds,
                "sampler": pop.sampler, "codec": cfg.uplink_codec})
    profiling = bool(tele_cfg and tele_cfg.jax_profile)
    if profiling:
        jax_profile_start(os.path.join(tele_cfg.out_dir, "jax_profile"))

    loss_per_round: List[float] = []
    for rnd in range(cfg.rounds):
        out = runner.run_round(rnd, round_step=round_step, stacker=stacker,
                               draw_batches=draw,
                               local_steps=cfg.shepherd_steps,
                               payload_bits=payload_bits,
                               codec_key=codec_key)
        with tracer.span("eval"):
            loss_per_round.append(
                float(np.mean(eval_ids(out["cohort_tr"], out["ids"]))))
        if tele.enabled:
            if rnd == 0:
                tele.compile_event(rnd,
                                   tracer.totals().get("device-step", 0.0))
            tele.round_event(rnd, {
                "eval_loss": loss_per_round[-1],
                "cohort": [int(i) for i in out["ids"]],
                "comm": {k: v for k, v in ledger.rounds[-1].items()
                         if k != "per_client"},
                "staleness": tracker.counters(),
                "health": out["health"],
            }, wall={"phases": tracer.pop_round()})
        if cfg.verbose:
            print(f"[pfit-pop:shepherd] round {rnd} "
                  f"cohort lm-loss {loss_per_round[-1]:.4f}")

    runner.settle()       # the last round's results into the store
    if profiling:
        jax_profile_stop()
    tele.close()
    return {
        "method": cfg.method,
        "eval_loss_per_round": loss_per_round,
        "final_eval_loss": loss_per_round[-1] if loss_per_round else 0.0,
        "mean_round_bytes": ledger.mean_round_bytes,
        "mean_round_delay_s": ledger.mean_round_delay,
        "total_bytes": ledger.total_bytes,
        "total_energy_j": ledger.total_energy_j,
        "total_sim_time_s": ledger.total_sim_time_s,
        "quorum_noops": ledger.quorum_noops,
        "uplink_codec": cfg.uplink_codec,
        "population": N,
        "cohort_size": K,
        "sampler": pop.sampler,
        "scenario": scen.to_dict(),
        "participation_frac": float(runner.seen.mean()),
        "host_overhead_frac": runner.host_overhead_frac,
        "store_bytes": store.nbytes(),
    }
