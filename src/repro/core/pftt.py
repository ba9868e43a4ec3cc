"""PFTT — Personalized Federated Task Tuning (paper §IV-D).

Universal adapters (and the classifier head) are aggregated globally each
round; local LoRA is trained but never uploaded, giving per-client
personalization.  Baselines from the paper's Fig. 5 are method variants:

* ``vanilla_fl`` — adapters + LoRA + head all uploaded and aggregated [1]
* ``fedbert``    — split learning: client trains embeddings + head, the body
                   stays on the server (frozen here); round traffic is the
                   *activation* exchange of split learning [3]
* ``fedlora``    — LoRA-only federated fine-tuning, LoRA aggregated [8]

Every round runs over a simulated Rayleigh uplink (outage → the client's
update is dropped that round) and is logged to a CommLedger (bytes + delay).

Execution goes through the vmapped cohort engine (``core/cohort.py``): one
fused jitted round step (vmap over clients of a scan over local steps +
stacked aggregation + broadcast) instead of O(n_clients × local_steps)
dispatches.  ``PFTTConfig(engine=False)`` keeps the legacy per-client loop
(parity oracle + benchmark baseline).  Ragged cohorts (clients with unequal
batch shapes) are padded and validity-masked by the ``HostBatchStacker``
(the ``"valid"`` sample weights ride the stacked batch into ``cls_loss``),
so they compile to the same single fused step — no legacy fallback.

LoRA executes FACTORED by default (``peft.lora_proj``): the loss threads
the rank-r factor tree next to the params, so under the client-vmap the
frozen base stays unbatched — memory/FLOPs scale as n_clients × rank-r
factors, not n_clients × full weights.  ``PFTTConfig(factored=False)`` is
the merged oracle.  Per-round eval pads every client's test set to one
validity-masked shape and scores the stacked cohort in ONE jitted vmapped
dispatch (``core/cohort.py::build_cohort_eval``).

``PFTTConfig(uplink_codec=...)`` compresses every upload INSIDE the fused
round step (``repro.comms``: stochastic-rounding int8/int4 quantization or
top-k/count-sketch sketching of the delta against the last broadcast
global); the server aggregates the lossy decode and the ledger charges the
encoded payload bits through ``ChannelBudget`` (bits → Rayleigh delay +
transmit energy) instead of the raw ``tree_bytes``.
``PFTTConfig(factored_agg=True)`` aggregates LoRA ``{'a','b'}`` pairs as
the SVD re-projection of the weighted-mean update (never densified) —
see ``repro.comms.factored_agg``.

``run_pftt(cfg, mesh=...)`` shards the fused round across the device mesh:
the stacked client axis is split over the mesh's non-"model" axes via
``shard_map`` (aggregation → psum of weighted partial sums), cohort state
and the round's host batches are placed with a client-axis
``NamedSharding`` (per-shard transfers), and cohorts that don't divide the
shard count are padded with zero-weight ghost clients the aggregation
weight vector masks out.  The frozen base stays replicated; only trainable
state and optimizer moments carry the sharded client axis.
"""
from __future__ import annotations

import dataclasses
import functools
import json
import os
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import trees
from repro.checkpoint.ckpt import load_checkpoint, save_checkpoint
from repro.comms import ChannelBudget, get_codec
from repro.comms import codec as codec_mod
from repro.core.aggregation import (factored_fedavg_stacked, fedavg,
                                    fedavg_stacked)
from repro.core.cohort import (HostBatchStacker, build_cohort_eval,
                               build_supervised_round)
from repro.core.robust import StalenessConfig, StalenessTracker
from repro.configs import get_config
from repro.data.partition import dirichlet_partition
from repro.data.pipeline import batch_iterator
from repro.data.synthetic import ClassificationCorpus
from repro.models import Model
from repro.models import peft as peft_mod
from repro.obs.metrics import RunTelemetry
from repro.obs.trace import SpanTracer, jax_profile_start, jax_profile_stop
from repro.optim import adamw
from repro.sharding import MeshCtx, cohort_sharding
from repro.wireless import (ArrivalModel, CommLedger, DeadlineConfig,
                            FaultPlan, RayleighChannel, tree_bytes)

METHODS = ("pftt", "vanilla_fl", "fedbert", "fedlora")


@dataclasses.dataclass(frozen=True)
class PFTTConfig:
    method: str = "pftt"
    n_clients: int = 4
    rounds: int = 40
    local_steps: int = 10
    batch: int = 16
    seq_len: int = 32
    d_model: int = 128             # width of the reduced backbone
    published_widths: bool = False # backbone at roberta-base's published
                                   # config (12 layers, d768, 12 heads,
                                   # vocab 50265) instead of the reduced
                                   # 2-layer d_model one
    lora_rank: int = 8
    adapter_dim: int = 8
    dirichlet_alpha: float = 0.3
    lr: float = 1e-3
    pretrain_steps: int = 200
    pretrain_lr: float = 1e-3
    samples_per_client: int = 400
    test_samples: int = 200
    snr_db: float = 5.0
    seed: int = 0
    verbose: bool = False
    engine: bool = True            # fused vmapped round step (cohort engine)
    factored: bool = True          # unmerged LoRA execution (False → merged
                                   # parity oracle: materialize W + sAB)
    uplink_codec: str = "none"     # none|int8|int4|sketch|countsketch —
                                   # lossy upload compression (repro.comms)
    factored_agg: bool = False     # aggregate LoRA {'a','b'} pairs via SVD
                                   # re-projection (never densified)
    tx_power_w: float = 0.5        # uplink transmit power for the energy
                                   # charge (ChannelBudget)
    fault_plan: Optional[object] = None   # wireless.faults.FaultPlan —
                                   # enables the straggler-tolerant robust
                                   # round (the zero plan is bitwise the
                                   # synchronous engine)
    staleness_alpha: float = 1.0   # FedAsync α (cancels under weight
                                   # normalization — kept for async_agg parity)
    staleness_a: float = 0.0       # staleness exponent a in α·(1+s)^(-a)
    max_staleness: int = 0         # drop pending payloads older than this;
                                   # 0 = sync drop-on-failure semantics
    deadline: Optional[DeadlineConfig] = None  # continuous-time round
                                   # (wireless/arrivals.py): channel-driven
                                   # arrival times, server deadline, retry
                                   # backoff, min_quorum gate; an inert
                                   # config (or None) is bitwise the
                                   # round-granular robust runtime
    ckpt_dir: Optional[str] = None # save the stacked round state per round
                                   # (engine path) for kill + --resume
    resume: bool = False           # restart from ckpt_dir's last round
    population: Optional[object] = None  # fl.population.PopulationConfig —
                                   # population mode: n_clients becomes the
                                   # host-resident population and every
                                   # round samples a cohort_size cohort
                                   # (fused body unchanged; see
                                   # _run_pftt_population)
    telemetry: Optional[object] = None  # repro.obs.TelemetryConfig — JSONL
                                   # round-event stream + host span tracing
                                   # + on-device health scalars (None = off;
                                   # see docs/observability.md)


def _upload_pred(method: str):
    """Which paths are uploaded/aggregated (within the trainable tree)."""
    if method == "pftt":
        return lambda p: p.startswith("shared/")
    if method in ("vanilla_fl", "fedlora", "fedbert"):
        return lambda p: True
    raise ValueError(method)


def _build_trainable(method: str, params, lora):
    """trainable := {'shared': subtree uploaded, 'local': kept on-client}."""
    if method == "pftt":
        shared = trees.select(params, lambda p: peft_mod.is_adapter_path(p)
                              or p.startswith("cls_head"))
        return {"shared": shared, "local": {"lora": lora}}
    if method == "vanilla_fl":
        shared = trees.select(params, lambda p: peft_mod.is_adapter_path(p)
                              or p.startswith("cls_head"))
        return {"shared": {"base": shared, "lora": lora}, "local": {}}
    if method == "fedlora":
        shared = trees.select(params, lambda p: p.startswith("cls_head"))
        return {"shared": {"base": shared, "lora": lora}, "local": {}}
    if method == "fedbert":
        shared = trees.select(params, lambda p: p.startswith(("embed",
                                                              "pos_embed",
                                                              "cls_head")))
        return {"shared": shared, "local": {}}
    raise ValueError(method)


def _split_trainable(method: str, base_params, trainable):
    """(effective params WITHOUT lora merged, unmerged lora tree) — the
    factored-path contract: the base (and non-lora trainables merged into
    it) stays a broadcastable tree under the engine's client-vmap; only the
    returned rank-r factor tree carries the client axis."""
    if method == "pftt":
        return (trees.merge(base_params, trainable["shared"]),
                trainable["local"].get("lora"))
    if method in ("vanilla_fl", "fedlora"):
        return (trees.merge(base_params, trainable["shared"]["base"]),
                trainable["shared"]["lora"])
    if method == "fedbert":
        return trees.merge(base_params, trainable["shared"]), None
    raise ValueError(method)


def _merge_trainable(method: str, base_params, trainable, peft_cfg):
    """Materialize effective params from (frozen base, trainable) — the
    MERGED parity oracle (``PFTTConfig(factored=False)``)."""
    full, lora = _split_trainable(method, base_params, trainable)
    if lora is not None:
        full = peft_mod.apply_lora(full, lora, peft_cfg)
    return full


def _setup_backbone(cfg: PFTTConfig):
    """Shared model setup: roberta (reduced, or at its published widths
    with ``cfg.published_widths``), MLM pretrain over all topics, PEFT
    insertion.  Both the cohort path (``run_pftt``) and the population
    path consume it, so their backbones (and the host RNG stream handed
    back) are identical."""
    rng = np.random.RandomState(cfg.seed)
    key = jax.random.PRNGKey(cfg.seed)
    meshctx = MeshCtx.single_device()

    # ---- model: roberta (paper's backbone), pre-trained on IID data
    mcfg = get_config("roberta-base")
    if not cfg.published_widths:
        mcfg = mcfg.reduced(d_model=cfg.d_model, repeats=2)
    model = Model(mcfg, meshctx=meshctx)
    base = model.init(key)

    # self-supervised MLM pre-training over ALL topics (like the real
    # RoBERTa); the downstream 4-class task is then learned federated
    pre_corpus = ClassificationCorpus(n_classes=8, seq_len=cfg.seq_len,
                                      seed=cfg.seed, skew=0.8)
    corpus = ClassificationCorpus(seq_len=cfg.seq_len, seed=cfg.seed)
    pre = pre_corpus.sample(2048, rng=rng)
    opt_pre = adamw(cfg.pretrain_lr)
    from repro.data.synthetic import SPECIAL

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def pre_step(params, opt_state, batch):
        def loss_fn(p):
            return model.lm_loss(p, batch)
        loss, g = jax.value_and_grad(loss_fn)(params)
        upd, opt_state = opt_pre.update(g, opt_state, params)
        return trees.tree_add(params, upd), opt_state, loss

    st = opt_pre.init(base)
    it = batch_iterator(pre, cfg.batch, seed=cfg.seed)
    for i in range(cfg.pretrain_steps):
        b = next(it)
        toks = b["tokens"]
        mpos = rng.rand(*toks.shape) < 0.15
        inp = np.where(mpos, SPECIAL["mask"], toks)
        batch = {"tokens": jnp.asarray(inp), "labels": jnp.asarray(toks),
                 "mask": jnp.asarray(mpos.astype(np.float32))}
        base, st, l = pre_step(base, st, batch)
    if cfg.verbose:
        print(f"[pftt:{cfg.method}] MLM pretrain loss {float(l):.3f}")

    # ---- PEFT insertion
    peft_cfg = peft_mod.PEFTConfig(
        lora_rank=cfg.lora_rank, adapter_dim=cfg.adapter_dim,
        lora_targets=("mixer/wq", "mixer/wv"))
    use_adapters = cfg.method in ("pftt", "vanilla_fl")
    use_lora = cfg.method in ("pftt", "vanilla_fl", "fedlora")
    params = peft_mod.init_adapters(key, base, mcfg, peft_cfg) \
        if use_adapters else base
    return model, mcfg, params, peft_cfg, corpus, key, rng, use_lora


def _client_fns(cfg: PFTTConfig, model, opt, peft_cfg):
    """``(local_step, eval_client)`` of one client, each taking the frozen
    base as its first argument so the compiled round and eval receive it
    as an argument (``build_supervised_round(base=)``) instead of
    embedding ~0.5 GB of constants at published widths.  LoRA runs
    factored, or merged per ``cfg.factored`` (the oracle)."""
    scale = peft_mod.lora_scale(peft_cfg)

    def effective(frozen, t):
        """(params, lora, lora_scale) per the factored/merged flag."""
        if cfg.factored:
            full, lora = _split_trainable(cfg.method, frozen, t)
            return full, lora, scale
        return _merge_trainable(cfg.method, frozen, t, peft_cfg), None, 1.0

    def local_step(frozen, trainable, opt_state, batch):
        def loss_fn(t):
            full, lora, ls = effective(frozen, t)
            return model.cls_loss(full, batch, lora=lora, lora_scale=ls)[0]
        loss, g = jax.value_and_grad(loss_fn)(trainable)
        upd, opt_state = opt.update(g, opt_state, trainable)
        return trees.tree_add(trainable, upd), opt_state, loss

    def eval_client(frozen, trainable, tokens, label, valid):
        full, lora, ls = effective(frozen, trainable)
        hidden, _ = model.forward(full, tokens, lora=lora, lora_scale=ls)
        pred = (hidden[:, 0] @ full["cls_head"]).astype(jnp.float32).argmax(-1)
        correct = (pred == label).astype(jnp.float32) * valid
        return correct.sum(), valid.sum()

    return local_step, eval_client


def run_pftt(cfg: PFTTConfig, mesh=None, client_axes=None) -> Dict:
    """``mesh`` (optional ``jax.sharding.Mesh``): shard the fused cohort
    round across it — see the module docstring.  ``client_axes`` overrides
    which mesh axes carry the client dim (default: every non-"model" axis).
    Ragged cohorts run the same fused (and sharded) round via
    pad-and-mask.  ``cfg.population`` switches to sampled-cohort population
    mode (``_run_pftt_population``)."""
    assert cfg.method in METHODS, cfg.method
    if cfg.population is not None:
        return _run_pftt_population(cfg, mesh, client_axes)
    model, mcfg, params, peft_cfg, corpus, key, rng, use_lora = \
        _setup_backbone(cfg)

    # ---- non-IID client data (Dirichlet over labels, paper §V-B.2)
    all_data = corpus.sample(cfg.samples_per_client * cfg.n_clients, rng=rng)
    parts = dirichlet_partition(all_data["label"], cfg.n_clients,
                                cfg.dirichlet_alpha, seed=cfg.seed)
    client_train, client_test, client_iters, client_batch_sizes = [], [], [], []
    for ci, idx in enumerate(parts):
        cut = max(1, int(len(idx) * 0.8))
        tr = {k: v[idx[:cut]] for k, v in all_data.items()}
        te = {k: v[idx[cut:]] for k, v in all_data.items()}
        client_train.append(tr)
        client_test.append(te)
        client_batch_sizes.append(min(cfg.batch, max(2, len(idx[:cut]))))
        client_iters.append(batch_iterator(tr, client_batch_sizes[-1],
                                           seed=cfg.seed + ci))

    # ---- per-client trainable state
    opt = adamw(cfg.lr, update_mask=lambda p: not p.endswith("/mask"))
    clients: List[Dict] = []
    for ci in range(cfg.n_clients):
        ck = jax.random.fold_in(key, 100 + ci)
        # "each client incorporates 10-12 local LoRAs based on resources":
        # clients get different numbers of LoRA'd layers / ranks
        lora = peft_mod.init_lora(ck, params, peft_cfg) if use_lora else None
        t = _build_trainable(cfg.method, params, lora)
        clients.append({"trainable": t, "opt_state": opt.init(t)})

    frozen = params
    local_step, eval_client = _client_fns(cfg, model, opt, peft_cfg)
    # legacy per-client path
    local_step_jit = functools.partial(jax.jit(local_step), frozen)

    # ragged cohorts (unequal client batch sizes) pad-and-mask inside the
    # HostBatchStacker ("valid" sample weights → cls_loss weighted mean), so
    # EVERY cohort compiles to one fused round step.  The sharded engine
    # (mesh=) ghost-pads the cohort to a multiple of the shard count with
    # zero aggregation weight.
    use_engine = cfg.engine
    cs = cohort_sharding(mesh, cfg.n_clients, client_axes) \
        if (mesh is not None and use_engine) else None
    n_rows = cs.total if cs is not None else cfg.n_clients

    # ---- engine-side eval: every client's test set padded to one common
    # shape (validity-masked) and the WHOLE stacked cohort scored in ONE
    # jitted vmapped dispatch per round — O(1) dispatches regardless of
    # cohort size (and no per-test-set-shape retraces).  Ghost rows are
    # all-invalid, so they drop out of the per-client accuracy list.
    max_test = max([len(te["label"]) for te in client_test] + [1])
    seq = client_test[0]["tokens"].shape[1]
    t_toks = np.zeros((n_rows, max_test, seq), np.int32)
    t_labels = np.zeros((n_rows, max_test), np.int32)
    t_valid = np.zeros((n_rows, max_test), np.float32)
    for ci, te in enumerate(client_test):
        n = len(te["label"])
        t_toks[ci, :n] = te["tokens"]
        t_labels[ci, :n] = te["label"]
        t_valid[ci, :n] = 1.0
    _put = (lambda x: jax.device_put(x, cs.named)) if cs is not None \
        else jnp.asarray
    t_toks, t_labels, t_valid = _put(t_toks), _put(t_labels), _put(t_valid)

    eval_cohort = build_cohort_eval(
        eval_client, sharding=cs.named if cs is not None else None,
        base=frozen)
    eval_dispatches = [0]

    def eval_round_accs(stacked_trainable):
        """Per-client accuracies — one fused dispatch for the whole cohort
        (clients with an empty test set are dropped, as in the legacy
        per-client loop)."""
        eval_dispatches[0] += 1
        corr, cnt = eval_cohort(stacked_trainable, t_toks, t_labels, t_valid)
        corr, cnt = np.asarray(corr), np.asarray(cnt)
        return [float(c / n) for c, n in zip(corr, cnt) if n > 0]

    channel = RayleighChannel(mean_snr_db=cfg.snr_db, seed=cfg.seed)
    budget = ChannelBudget(channel, tx_power_w=cfg.tx_power_w)
    ledger = CommLedger()
    upload_pred = _upload_pred(cfg.method)
    accs_per_round = []
    losses_per_round = []   # mean local-step loss over clients and steps
                            # (the rounds this process ran)

    # ---- observability (repro.obs): JSONL round events + host span tracer
    # (a disabled tracer still times, it just records nothing) + on-device
    # health scalars riding the fused round outputs (engine path only —
    # they live inside the compiled body, so dispatches/round stays 1)
    tele_cfg = cfg.telemetry
    tracer = SpanTracer(enabled=bool(tele_cfg and tele_cfg.trace))
    tele = RunTelemetry(tele_cfg.out_dir if tele_cfg else None, tracer=tracer)
    health = bool(tele_cfg and tele_cfg.health) and cfg.engine

    # ---- straggler-tolerant runtime (core/robust.py + wireless/faults.py):
    # the fault trace and the staleness tracker are shared verbatim by the
    # engine and the legacy loop, so both paths see identical weights/charges.
    # A non-inert DeadlineConfig switches the tracker to the continuous-time
    # round (wireless/arrivals.py) — with or without an injected fault plan
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
    # legacy-loop codec roundtrip (per client; the engine vmaps the same
    # function inside the fused step, so ledgers agree engine-vs-loop)
    rt_jit = None if codec is None else jax.jit(
        lambda k, t, rf: codec_mod.roundtrip(codec, k, t, ref=rf))

    def act_bits() -> float:
        """fedbert split learning: per-step activation exchange dominates —
        uncompressed either way (the codec covers parameter uploads)."""
        if cfg.method != "fedbert":
            return 0.0
        return cfg.local_steps * cfg.batch * cfg.seq_len * mcfg.d_model \
            * 4 * 2 * 8

    def payload_bytes(trainable) -> int:
        shared = trees.select(trainable, upload_pred)
        return tree_bytes(shared) + act_bits() / 8

    pending = None
    step_dispatches = [0]
    if use_engine:
        fused_step = build_supervised_round(
            local_step, upload_pred,
            mesh=cs.mesh if cs is not None else None,
            client_axes=cs.axes if cs is not None else None,
            codec=codec, factored_agg=cfg.factored_agg, robust=robust,
            min_quorum=(dl.min_quorum if dl is not None else 0),
            health=health, base=frozen)

        def round_step(*args):
            step_dispatches[0] += 1
            return fused_step(*args)

        pad = cs.pad if cs is not None else (lambda xs: xs)
        cohort_tr = trees.stack(pad([cl["trainable"] for cl in clients]))
        cohort_opt = trees.stack(pad([cl["opt_state"] for cl in clients]))
        if cs is not None:     # client axis over the mesh, base replicated
            cohort_tr = jax.device_put(cohort_tr, cs.named)
            cohort_opt = jax.device_put(cohort_opt, cs.named)
        if robust:             # pending-payload buffer (uploaded subtree)
            pending = jax.tree_util.tree_map(
                jnp.zeros_like, trees.select(cohort_tr, upload_pred))
        payloads = [payload_bytes(cl["trainable"]) for cl in clients]
        stacker = HostBatchStacker(   # host buffer reused round-over-round
            sharding=cs.named if cs is not None else None)
    elif robust:               # legacy-loop pending buffer (parity oracle)
        pending_list = [jax.tree_util.tree_map(
            jnp.zeros_like, trees.select(cl["trainable"], upload_pred))
            for cl in clients]

    # scheduling-size estimate for the continuous-time round (see
    # wireless/arrivals.py): exact for uncompressed uploads; codec fresh
    # uploads reserve the worst-case encoded size until the first realized
    # size replaces it.  The ledger always charges realized bits.
    est_bits = None
    if dl is not None:
        if codec is None:
            est_bits = np.asarray(
                [payload_bytes(cl["trainable"]) * 8 for cl in clients],
                np.float64)
        else:
            est_bits = np.asarray(
                [codec_mod.payload_bits_upper_bound(
                    codec, trees.select(cl["trainable"], upload_pred))
                 + act_bits() for cl in clients], np.float64)

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

    def _vec(v, fill=0.0):
        """Device round vector, ghost-padded with ``fill``."""
        return jax.device_put(cs.pad_vec(v, fill), cs.named) \
            if cs is not None else jnp.asarray(v)

    # ---- round-level checkpoint/resume (engine path): the stacked device
    # state restores exactly; the host RNG streams (channel fading draws,
    # per-client batch iterators) are replayed to the resume point so the
    # continued run is the uninterrupted run
    ckpt_file = meta_file = None
    start_round = 0
    if cfg.ckpt_dir and use_engine:
        ckpt_file = os.path.join(cfg.ckpt_dir, f"pftt_{cfg.method}.npz")
        meta_file = os.path.join(cfg.ckpt_dir, f"pftt_{cfg.method}.json")
        if cfg.resume and os.path.exists(meta_file):
            with open(meta_file) as f:
                meta = json.load(f)
            start_round = int(meta["next_round"])
            accs_per_round[:] = meta["accs_per_round"]
            ledger.rounds[:] = meta["ledger_rounds"]
            tpl = {"trainable": cohort_tr, "opt": cohort_opt}
            if robust:
                tpl["pending"] = pending
                tracker.load_state_dict(meta["tracker"])
                if dl is not None and "est_bits" in meta:
                    est_bits = np.asarray(meta["est_bits"], np.float64)
            state = load_checkpoint(ckpt_file, tpl)
            cohort_tr, cohort_opt = state["trainable"], state["opt"]
            if robust:
                pending = state["pending"]
            if cs is not None:
                cohort_tr = jax.device_put(cohort_tr, cs.named)
                cohort_opt = jax.device_put(cohort_opt, cs.named)
                if robust:
                    pending = jax.device_put(pending, cs.named)
            for _ in range(start_round):        # burn the skipped rounds'
                channel.realize(cfg.n_clients)  # host RNG draws
                if arrivals is not None:
                    arrivals.burn_round()       # compute-time draws
                for ci in range(cfg.n_clients):
                    for _s in range(cfg.local_steps):
                        next(client_iters[ci])

    run_meta = {"mode": "cohort", "method": cfg.method,
                "n_clients": cfg.n_clients, "rounds": cfg.rounds,
                "engine": bool(use_engine), "codec": cfg.uplink_codec}
    if start_round > 0:
        tele.resume(start_round, run_meta)
    else:
        tele.start(run_meta)
    profiling = bool(tele_cfg and tele_cfg.jax_profile)
    if profiling:
        jax_profile_start(os.path.join(tele_cfg.out_dir, "jax_profile"))

    for rnd in range(start_round, cfg.rounds):
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
        if use_engine:
            # host side: draw the round's batches in the legacy (client,
            # step) order into the preallocated stacked buffer, one
            # (per-shard when meshed) device_put, and run ONE compiled
            # round step; ghost clients reuse client 0's batches and get
            # zero aggregation weight
            with tracer.span("gather"):
                batches = stacker(pad(
                    [[next(client_iters[ci])
                      for _ in range(cfg.local_steps)]
                     for ci in range(cfg.n_clients)]))
            # deadline mode hands the engine the pre-deadline weights plus
            # the on-time mask; their product (applied in the fused body)
            # is the pre-quorum agg_w, and the body re-derives the quorum
            # gate so engine and legacy loop agree bit-for-bit
            w = (rplan.agg_w_pre if dl is not None else rplan.agg_w) \
                if robust else channel.outage_weights(gains)
            weights = jax.device_put(cs.pad_weights(w), cs.named) \
                if cs is not None else jnp.asarray(w)
            ck = None
            if codec is not None:
                with tracer.span("encode"):
                    ck = jnp.stack(pad(
                        [jax.random.fold_in(rnd_key, ci)
                         for ci in range(cfg.n_clients)]))
                    if cs is not None:
                        ck = jax.device_put(ck, cs.named)
            if robust:
                # ghosts train + receive like real clients (as in the sync
                # engine) but never rejoin and carry zero agg weight
                ontime = rplan.ontime if dl is not None \
                    else np.ones(cfg.n_clients, np.float32)
                margs = (_vec(rplan.train, 1.0), weights,
                         _vec(rplan.recv, 1.0), _vec(rplan.rejoin, 0.0),
                         _vec(ontime, 1.0))
                if codec is None:
                    with tracer.span("device-step"):
                        outs = round_step(
                            cohort_tr, cohort_opt, pending, batches, *margs)
                    cohort_tr, cohort_opt, pending = outs[:3]
                    fresh = np.asarray([payloads[ci] * 8
                                        for ci in range(cfg.n_clients)])
                else:
                    with tracer.span("device-step"):
                        outs = round_step(cohort_tr, cohort_opt, pending,
                                          batches, *margs, ck)
                    cohort_tr, cohort_opt, pending = outs[:3]
                    eng_bits = outs[4]
                    fresh = (np.asarray(eng_bits, np.float64)[:cfg.n_clients]
                             + act_bits())
                if health:
                    hstats = outs[-1]
                charged = tracker.end_round(rplan, fresh)
                reports = _round_reports(rplan, charged, gains)
            elif codec is None:
                with tracer.span("device-step"):
                    outs = round_step(cohort_tr, cohort_opt, batches,
                                      weights)
                cohort_tr, cohort_opt = outs[:2]
                if health:
                    hstats = outs[-1]
                bits = [payloads[ci] * 8 for ci in range(cfg.n_clients)]
                reports = budget.round_reports(bits, gains)
            else:
                with tracer.span("device-step"):
                    outs = round_step(cohort_tr, cohort_opt, batches,
                                      weights, ck)
                cohort_tr, cohort_opt, eng_bits = outs[0], outs[1], outs[3]
                if health:
                    hstats = outs[-1]
                bits = [float(b) + act_bits()
                        for b in np.asarray(eng_bits)[:cfg.n_clients]]
                reports = budget.round_reports(bits, gains)
            step_losses = np.asarray(outs[3] if robust else outs[2])
            losses_per_round.append(
                float(step_losses[:cfg.n_clients].mean()))
        else:
            fresh = np.zeros(cfg.n_clients, np.float64)
            step_losses = np.zeros((cfg.n_clients, cfg.local_steps))
            for ci, cl in enumerate(clients):
                # every client draws its round batches even when a fault
                # skips its training — keeps the host data stream aligned
                # with the engine (and with the fault-free run)
                round_batches = [next(client_iters[ci])
                                 for _ in range(cfg.local_steps)]
                if robust and rplan.train[ci] == 0:
                    continue
                ref = (trees.select(cl["trainable"], upload_pred)
                       if codec is not None else None)
                for si, b_np in enumerate(round_batches):
                    batch = {k: jnp.asarray(v) for k, v in b_np.items()}
                    cl["trainable"], cl["opt_state"], loss = local_step_jit(
                        cl["trainable"], cl["opt_state"], batch)
                    step_losses[ci, si] = float(loss)
                if codec is None:
                    fresh[ci] = payload_bytes(cl["trainable"]) * 8
                else:
                    dec, b = rt_jit(jax.random.fold_in(rnd_key, ci),
                                    trees.select(cl["trainable"],
                                                 upload_pred), ref)
                    cl["decoded_upload"] = dec
                    fresh[ci] = float(b) + act_bits()
                if not robust:
                    reports.append(budget.report(fresh[ci], gains[ci]))
            if robust:
                charged = tracker.end_round(rplan, fresh)
                reports = _round_reports(rplan, charged, gains)
            losses_per_round.append(float(step_losses.mean()))
        extra = None
        if dl is not None:
            extra = {"sim_dt_s": float(rplan.sim_dt_s),
                     "quorum_noop": not rplan.quorum_ok,
                     "n_delivered": int(rplan.n_delivered),
                     "corrupt": int(np.asarray(rplan.corrupt).sum())}
            if codec is not None:   # realized encoded size becomes the next
                est_bits = np.where(  # scheduling estimate
                    np.asarray(rplan.train) > 0, fresh, est_bits)
        ledger.log_round(reports, extra, round_id=rnd)

        # --- aggregation over surviving clients (partial for pftt); in the
        # engine path this already happened inside the fused round step.
        # With a codec the server aggregates the lossy decoded uploads.
        if robust and not use_engine:
            # legacy mirror of the robust fused body: same stacked ops, same
            # tracker outputs — fresh uploads supersede pending payloads,
            # stragglers retransmit, recv gates the broadcast, rejoin resets
            # the optimizer
            send_list = [
                (clients[ci]["decoded_upload"] if codec is not None
                 else trees.select(clients[ci]["trainable"], upload_pred))
                if rplan.train[ci] > 0 else pending_list[ci]
                for ci in range(cfg.n_clients)]
            pending_list = send_list
            if float(rplan.agg_w.sum()) > 0:
                st_send = trees.stack(send_list)
                aggw = jnp.asarray(rplan.agg_w)
                agg = (factored_fedavg_stacked(st_send, aggw)
                       if cfg.factored_agg else fedavg_stacked(st_send, aggw))
                for ci, cl in enumerate(clients):
                    if rplan.recv[ci] > 0:
                        cl["trainable"] = trees.merge(cl["trainable"], agg)
            for ci, cl in enumerate(clients):
                if rplan.rejoin[ci] > 0:
                    cl["opt_state"] = jax.tree_util.tree_map(
                        jnp.zeros_like, cl["opt_state"])
        elif not use_engine:
            alive = [ci for ci, r in enumerate(reports) if not r.outage]
            if alive:
                shared_trees = [
                    clients[ci]["decoded_upload"] if codec is not None
                    else trees.select(clients[ci]["trainable"], upload_pred)
                    for ci in alive]
                if cfg.factored_agg:
                    agg = factored_fedavg_stacked(trees.stack(shared_trees))
                else:
                    agg = fedavg(shared_trees)
                for cl in clients:
                    cl["trainable"] = trees.merge(cl["trainable"], agg)

        with tracer.span("eval"):
            accs = eval_round_accs(
                cohort_tr if use_engine
                else trees.stack([cl["trainable"] for cl in clients]))
        accs_per_round.append(float(np.mean(accs)))
        # round event BEFORE the checkpoint (the exactly-once contract:
        # a kill between them re-records the round on resume; a kill after
        # the checkpoint keeps it — resume() drops rounds >= next_round)
        if tele.enabled:
            if rnd == start_round:  # first dispatch of this process paid
                tele.compile_event(  # XLA compilation inside device-step
                    rnd, tracer.totals().get("device-step", 0.0))
            tele.round_event(rnd, {
                "acc": accs_per_round[-1],
                "cohort": None,   # cohort mode: every client, every round
                "comm": {k: v for k, v in ledger.rounds[-1].items()
                         if k != "per_client"},
                "staleness": tracker.counters() if robust else None,
                "health": None if hstats is None else
                {k: float(v) for k, v in hstats.items()},
            }, wall={"phases": tracer.pop_round()})
        if ckpt_file is not None:   # round-level checkpoint (kill-safe)
            with tracer.span("checkpoint"):
                state = {"trainable": cohort_tr, "opt": cohort_opt}
                if robust:
                    state["pending"] = pending
                save_checkpoint(ckpt_file, state)
                meta = {"next_round": rnd + 1,
                        "accs_per_round": accs_per_round,
                        "ledger_rounds": ledger.rounds}
                if robust:
                    meta["tracker"] = tracker.state_dict()
                    if dl is not None:
                        meta["est_bits"] = [float(b) for b in est_bits]
                with open(meta_file, "w") as f:
                    json.dump(meta, f)
            tele.checkpoint(rnd)
        if cfg.verbose and rnd % 5 == 0:
            print(f"[pftt:{cfg.method}] round {rnd} acc {accs_per_round[-1]:.3f} "
                  f"bytes {ledger.rounds[-1]['bytes']:,} "
                  f"outages {ledger.rounds[-1]['outages']}")

    if use_engine:   # sync the per-client dicts once, after the last round
        for cl, tr in zip(clients, trees.unstack(cohort_tr, cfg.n_clients)):
            cl["trainable"] = tr

    if profiling:
        jax_profile_stop()
    tele.close()

    return {
        "method": cfg.method,
        "acc_per_round": accs_per_round,
        "final_acc": accs_per_round[-1],
        "loss_per_round": losses_per_round,
        "model": {"name": mcfg.name, "n_layers": mcfg.n_layers,
                  "d_model": mcfg.d_model, "vocab_size": mcfg.vocab_size},
        # fewest devices any leaf of the engine's stacked client state spans
        "cohort_state_devices": min(
            len(l.sharding.device_set)
            for l in jax.tree_util.tree_leaves(cohort_tr)) if use_engine
        else None,
        "dispatches_per_round": (step_dispatches[0]
                                 / max(cfg.rounds - start_round, 1)),
        "mean_round_bytes": ledger.mean_round_bytes,
        "mean_round_delay_s": ledger.mean_round_delay,
        "total_bytes": ledger.total_bytes,
        "total_energy_j": ledger.total_energy_j,
        "total_sim_time_s": ledger.total_sim_time_s,
        "quorum_noops": ledger.quorum_noops,
        "round_records": ledger.rounds,
        "uplink_codec": cfg.uplink_codec,
        "eval_dispatches_per_round": eval_dispatches[0] / max(cfg.rounds, 1),
        "fused_engine": bool(use_engine),
        "ragged_cohort": len(set(client_batch_sizes)) > 1,
    }


def _run_pftt_population(cfg: PFTTConfig, mesh=None, client_axes=None) -> Dict:
    """Sampled-cohort population mode (``cfg.population``): the host holds
    a ``PopulationStore`` of per-client adapter/opt/pending trees sized to
    ``population`` clients; every round a ``ClientSampler`` draws a
    ``cohort_size`` cohort, the ``PopulationRunner`` gathers the sampled
    rows (overlaying the server's global into the uploaded subtree — the
    downlink), the SAME fused robust round body that a
    ``n_clients=cohort_size`` run compiles executes once, and results
    scatter back.  The ``StalenessTracker`` spans the population, so a
    straggler's pending payload survives rounds it isn't sampled in.
    Non-IID data / availability / mobility come from the
    ``wireless.scenarios.Scenario`` trace; an injected ``FaultPlan`` and a
    ``DeadlineConfig`` compose on top exactly as in cohort mode."""
    from repro.fl.population import (ClientSampler, PopulationData,
                                     PopulationRunner, PopulationStore,
                                     stacked_client_init)
    from repro.wireless.scenarios import Scenario

    pop = cfg.population
    if not cfg.engine:
        raise ValueError("population mode runs the fused engine only "
                         "(PFTTConfig(engine=True))")
    N, K = pop.population, pop.cohort_size
    scen = pop.scenario or Scenario()
    if scen.n_classes != 4:
        raise ValueError("the PFTT classification task is 4-class; "
                         f"scenario has n_classes={scen.n_classes}")
    model, mcfg, params, peft_cfg, corpus, key, rng, use_lora = \
        _setup_backbone(cfg)
    strace = scen.realize(N, cfg.rounds)

    # ---- shared class-bucketed pool; clients draw lazily from their
    # Dirichlet label distribution (no per-client iterator state → nothing
    # to replay on resume)
    pool_n = int(np.clip(cfg.samples_per_client * 16, 1024, 16384))
    pool = corpus.sample(pool_n, rng=rng)
    data = PopulationData(pool, strace.class_probs, seed=cfg.seed)

    # ---- the N-client store: ONE vmapped init over folded keys (constant
    # leaves broadcast), pulled to host numpy
    opt = adamw(cfg.lr, update_mask=lambda p: not p.endswith("/mask"))
    upload_pred = _upload_pred(cfg.method)

    def client_init(ck):
        lora = peft_mod.init_lora(ck, params, peft_cfg) if use_lora else None
        t = _build_trainable(cfg.method, params, lora)
        return {"t": t, "o": opt.init(t)}

    keys = jax.vmap(lambda i: jax.random.fold_in(key, 100 + i))(
        jnp.arange(N))
    stacked = stacked_client_init(client_init, keys)
    pend_np = jax.tree_util.tree_map(
        np.zeros_like, trees.select(stacked["t"], upload_pred))
    store = PopulationStore({"trainable": stacked["t"], "opt": stacked["o"],
                             "pending": pend_np})
    shared0 = trees.select(store.row("trainable", 0), upload_pred)
    global_shared = jax.tree_util.tree_map(np.array, shared0)

    # ---- wireless runtime over the POPULATION (channel draws, fault
    # trace, staleness tracker, optional continuous-time deadline)
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
    ab = 0.0 if cfg.method != "fedbert" else \
        cfg.local_steps * cfg.batch * cfg.seq_len * mcfg.d_model * 4 * 2 * 8
    payload_bits = tree_bytes(shared0) * 8 + ab
    est_bits = None
    if dl is not None:
        est_bits = np.full(N, payload_bits if codec is None else
                           codec_mod.payload_bits_upper_bound(codec, shared0)
                           + ab, np.float64)

    # ---- the fused round body: identical to a cohort_size-client robust
    # run (population mode changes NOTHING below the host orchestration)
    local_step, eval_client = _client_fns(cfg, model, opt, peft_cfg)

    # ---- observability: the runner owns the spans (its "round" span is
    # the round_s/host_s accounting); health scalars ride the fused body
    tele_cfg = cfg.telemetry
    tracer = SpanTracer(enabled=bool(tele_cfg and tele_cfg.trace))
    tele = RunTelemetry(tele_cfg.out_dir if tele_cfg else None, tracer=tracer)
    health = bool(tele_cfg and tele_cfg.health)

    cs = cohort_sharding(mesh, K, client_axes) if mesh is not None else None
    round_step = build_supervised_round(
        local_step, upload_pred,
        mesh=cs.mesh if cs is not None else None,
        client_axes=cs.axes if cs is not None else None,
        codec=codec, factored_agg=cfg.factored_agg, robust=True,
        min_quorum=(dl.min_quorum if dl is not None else 0),
        health=health, base=params)
    stacker = HostBatchStacker(sharding=cs.named if cs is not None else None)

    runner = PopulationRunner(
        pop=pop, store=store, global_shared=global_shared,
        upload_pred=upload_pred, channel=channel, budget=budget,
        ledger=ledger, tracker=tracker, trace=trace, strace=strace,
        sampler=ClientSampler(pop.sampler, N, K,
                              seed=cfg.seed + 1000 * pop.seed),
        arrivals=arrivals, dl=dl, cs=cs, est_bits=est_bits, act_bits=ab,
        tracer=tracer, health=health)

    # ---- cohort eval: the sampled clients' held-out draws refill one
    # preallocated buffer and score in ONE fused dispatch per round
    n_rows = cs.total if cs is not None else K
    n_eval = int(min(max(cfg.test_samples, 4), 64))
    e_toks = np.zeros((n_rows, n_eval, cfg.seq_len), np.int32)
    e_labels = np.zeros((n_rows, n_eval), np.int32)
    e_valid = np.zeros((n_rows, n_eval), np.float32)
    _put = (lambda x: jax.device_put(x, cs.named)) if cs is not None \
        else jnp.asarray

    eval_cohort = build_cohort_eval(
        eval_client, sharding=cs.named if cs is not None else None,
        base=params)
    test_cache: Dict[int, Dict] = {}

    def eval_ids(cohort_tr, ids):
        if len(test_cache) > 4096:
            test_cache.clear()
        for j, cid in enumerate(ids):
            te = test_cache.get(int(cid))
            if te is None:
                te = data.test_set(int(cid), n_eval)
                test_cache[int(cid)] = te
            e_toks[j], e_labels[j], e_valid[j] = \
                te["tokens"], te["label"], 1.0
        e_valid[len(ids):] = 0.0
        corr, cnt = eval_cohort(cohort_tr, _put(e_toks), _put(e_labels),
                                _put(e_valid))
        corr, cnt = np.asarray(corr), np.asarray(cnt)
        return [float(c / n)
                for c, n in zip(corr[:len(ids)], cnt[:len(ids)]) if n > 0]

    def draw(cid, rnd):
        return data.round_batches(cid, rnd, cfg.local_steps, cfg.batch)

    # ---- checkpoint/resume: store + global in the npz, sampler RNG /
    # tracker / flags in the JSON sidecar; channel + arrival draws burn
    accs_per_round: List[float] = []
    ckpt_file = meta_file = None
    start_round = 0
    if cfg.ckpt_dir:
        ckpt_file = os.path.join(cfg.ckpt_dir, f"pftt_pop_{cfg.method}.npz")
        meta_file = os.path.join(cfg.ckpt_dir, f"pftt_pop_{cfg.method}.json")
        if cfg.resume and os.path.exists(meta_file):
            with open(meta_file) as f:
                meta = json.load(f)
            start_round = int(meta["next_round"])
            accs_per_round[:] = meta["accs_per_round"]
            ledger.rounds[:] = meta["ledger_rounds"]
            runner.load_state_dict(meta["runner"])
            runner.load_checkpoint_tree(
                load_checkpoint(ckpt_file, runner.checkpoint_tree()))
            runner.burn_rounds(start_round)

    run_meta = {"mode": "population", "method": cfg.method,
                "population": N, "cohort": K, "rounds": cfg.rounds,
                "sampler": pop.sampler, "codec": cfg.uplink_codec}
    if start_round > 0:
        tele.resume(start_round, run_meta)
    else:
        tele.start(run_meta)
    profiling = bool(tele_cfg and tele_cfg.jax_profile)
    if profiling:
        jax_profile_start(os.path.join(tele_cfg.out_dir, "jax_profile"))

    for rnd in range(start_round, cfg.rounds):
        out = runner.run_round(rnd, round_step=round_step, stacker=stacker,
                               draw_batches=draw,
                               local_steps=cfg.local_steps,
                               payload_bits=payload_bits,
                               codec_key=codec_key)
        with tracer.span("eval"):
            accs = eval_ids(out["cohort_tr"], out["ids"])
        accs_per_round.append(float(np.mean(accs)) if accs else 0.0)
        # round event BEFORE the checkpoint — see run_pftt (the same
        # exactly-once resume ordering)
        if tele.enabled:
            if rnd == start_round:
                tele.compile_event(
                    rnd, tracer.totals().get("device-step", 0.0))
            tele.round_event(rnd, {
                "acc": accs_per_round[-1],
                "cohort": [int(i) for i in out["ids"]],
                "comm": {k: v for k, v in ledger.rounds[-1].items()
                         if k != "per_client"},
                "staleness": tracker.counters(),
                "health": out["health"],
            }, wall={"phases": tracer.pop_round()})
        if ckpt_file is not None:
            with tracer.span("checkpoint"):
                save_checkpoint(ckpt_file, runner.checkpoint_tree())
                meta = {"next_round": rnd + 1,
                        "accs_per_round": accs_per_round,
                        "ledger_rounds": ledger.rounds,
                        "runner": runner.state_dict()}
                with open(meta_file, "w") as f:
                    json.dump(meta, f)
            tele.checkpoint(rnd)
        if cfg.verbose and rnd % 5 == 0:
            print(f"[pftt-pop:{cfg.method}] round {rnd} "
                  f"cohort acc {accs_per_round[-1]:.3f} "
                  f"sampled {sorted(int(i) for i in out['ids'])[:8]}… "
                  f"host {runner.host_overhead_frac:.1%}")

    if profiling:
        jax_profile_stop()
    tele.close()

    return {
        "method": cfg.method,
        "acc_per_round": accs_per_round,
        "final_acc": accs_per_round[-1] if accs_per_round else 0.0,
        "mean_round_bytes": ledger.mean_round_bytes,
        "mean_round_delay_s": ledger.mean_round_delay,
        "total_bytes": ledger.total_bytes,
        "total_energy_j": ledger.total_energy_j,
        "total_sim_time_s": ledger.total_sim_time_s,
        "quorum_noops": ledger.quorum_noops,
        "round_records": ledger.rounds,
        "uplink_codec": cfg.uplink_codec,
        "fused_engine": True,
        "population": N,
        "cohort_size": K,
        "sampler": pop.sampler,
        "scenario": scen.to_dict(),
        "participation_frac": float(runner.seen.mean()),
        "host_overhead_frac": runner.host_overhead_frac,
        "host_s": runner.host_s,
        "round_s": runner.round_s,
        "round_wall": list(runner.round_wall),
        "store_bytes": store.nbytes(),
    }
