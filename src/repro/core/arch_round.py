"""Generic per-architecture fused federated round — the config-zoo scenario.

``run_arch_round`` runs a FedLoRA-style cohort round on ANY
``configs/`` architecture (dense gpt2, MLA deepseek, SSM mamba/jamba, MoE
dbrx, enc-dec whisper): per-client rank-r LoRA factor trees train through
``core/cohort.build_supervised_round`` — one fused vmapped (and optionally
``shard_map``-sharded) step per round — against the replicated frozen base,
with FedAvg over the factors and broadcast-back inside the compiled step.

This is the CI ``arch-matrix`` workload (`launch/train.py --fl-clients N
--arch <zoo>`): every cell proves the UNIVERSAL fused path —

* the LoRA side channel stays factored through every mixer family
  (``peft.dense_merge_count()`` must not move while the engine runs);
* ragged cohorts (unequal per-client batch sizes, the default here) compile
  to ONE dispatch per round via the ``HostBatchStacker`` pad-and-mask
  machinery (the ``"valid"`` sample weights fold into the LM token mask);
* ``oracle=True`` replays the identical padded batches through the legacy
  per-client dense-merge loop (``peft.apply_lora`` each step) and reports
  the max per-(round, client, step) loss deviation — the factored fused
  round must match the dense-merge oracle to ≤1e-5.
"""
from __future__ import annotations

import dataclasses
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from repro import trees
from repro.configs import get_config
from repro.core.aggregation import fedavg_stacked
from repro.core.cohort import HostBatchStacker, build_supervised_round
from repro.models import Model
from repro.models import peft as peft_mod
from repro.optim import adamw
from repro.sharding import MeshCtx, cohort_sharding

# which mixer projections carry LoRA per layer family — the universal
# factored contract (models/mla.py, models/ssm.py, blocks._qkv)
MIXER_TARGETS = {
    "attn": ("mixer/wq", "mixer/wv"),
    "local": ("mixer/wq", "mixer/wv"),
    "enc": ("mixer/wq", "mixer/wv"),
    "dec": ("mixer/wq", "mixer/wv"),
    "mla": ("mixer/wq_a", "mixer/wq_b", "mixer/wkv_a", "mixer/wkv_b"),
    "mamba": ("mixer/in_proj", "mixer/out_proj"),
}


def arch_lora_targets(mcfg) -> tuple:
    """LoRA target paths covering every mixer family in the config's
    stage patterns."""
    targets = []
    for stage in mcfg.stages:
        for kind in stage.pattern:
            for t in MIXER_TARGETS.get(kind.mixer, ()):
                if t not in targets:
                    targets.append(t)
    return tuple(targets)


@dataclasses.dataclass(frozen=True)
class ArchRoundConfig:
    arch: str
    n_clients: int = 4
    rounds: int = 2
    local_steps: int = 2
    batch: int = 4
    seq_len: int = 16
    d_model: int = 64      # width of the reduced config
    repeats: int = 1
    published_widths: bool = False  # run the arch's published config
                                    # instead of reduced(d_model, repeats)
    lora_rank: int = 4
    lr: float = 1e-3
    seed: int = 0
    ragged: bool = True    # vary per-client batch size (pad-and-mask path)
    oracle: bool = False   # replay the legacy dense-merge loop, report parity


def _draw_round_batches(mcfg, rng, sizes, local_steps, seq_len):
    """[client][step] host LM batches; the sample axis is ragged when
    ``sizes`` differ (the stacker pads and masks)."""
    out = []
    for b in sizes:
        steps = []
        for _ in range(local_steps):
            toks = rng.randint(6, mcfg.vocab_size, size=(b, seq_len + 1))
            batch = {"tokens": toks[:, :-1].astype(np.int32),
                     "labels": toks[:, 1:].astype(np.int32),
                     "mask": np.ones((b, seq_len), np.float32)}
            if mcfg.is_encoder_decoder:
                batch["frames"] = rng.randn(
                    b, mcfg.encoder_seq, mcfg.d_model).astype(np.float32)
            if mcfg.n_prefix_tokens:
                batch["patches"] = rng.randn(
                    b, mcfg.n_prefix_tokens, mcfg.prefix_dim).astype(np.float32)
            steps.append(batch)
        out.append(steps)
    return out


def _fold_valid(batch):
    """Padded-row sample weights → the LM token mask (exact: padded rows
    then weigh zero in lm_loss's tot/cnt)."""
    b = dict(batch)
    v = b.pop("valid", None)
    if v is not None:
        b["mask"] = b["mask"] * v[:, None]
    return b


def run_arch_round(cfg: ArchRoundConfig, mesh=None,
                   client_axes=None) -> Dict:
    """Run the fused factored cohort round for one architecture (reduced,
    or at its published widths); see the module docstring.  ``mesh`` shards the client axis (ghost-padding
    non-divisible cohorts)."""
    mcfg = get_config(cfg.arch)
    if not cfg.published_widths:
        mcfg = mcfg.reduced(d_model=cfg.d_model, repeats=cfg.repeats)
    model = Model(mcfg, meshctx=MeshCtx.single_device())
    key = jax.random.PRNGKey(cfg.seed)
    params = model.init(key, max_seq=cfg.seq_len)
    targets = arch_lora_targets(mcfg)
    pc = peft_mod.PEFTConfig(lora_rank=cfg.lora_rank,
                             lora_alpha=2.0 * cfg.lora_rank,
                             lora_targets=targets)
    scale = peft_mod.lora_scale(pc)
    loras = [peft_mod.init_lora(jax.random.fold_in(key, 100 + ci), params, pc)
             for ci in range(cfg.n_clients)]
    opt = adamw(cfg.lr, update_mask=lambda p: not p.endswith("/mask"))

    def local_step(base, lora, opt_state, batch):
        def loss_fn(lf):
            return model.lm_loss(base, _fold_valid(batch), lora=lf,
                                 lora_scale=scale)
        loss, g = jax.value_and_grad(loss_fn)(lora)
        upd, opt_state = opt.update(g, opt_state, lora)
        return trees.tree_add(lora, upd), opt_state, loss

    cs = cohort_sharding(mesh, cfg.n_clients, client_axes) \
        if mesh is not None else None
    pad = cs.pad if cs is not None else (lambda xs: list(xs))
    round_step = build_supervised_round(
        local_step, None, mesh=cs.mesh if cs is not None else None,
        client_axes=cs.axes if cs is not None else None, base=params)
    cohort = trees.stack(pad(loras))
    cohort_opt = trees.stack(pad([opt.init(l) for l in loras]))
    if cs is not None:
        cohort = jax.device_put(cohort, cs.named)
        cohort_opt = jax.device_put(cohort_opt, cs.named)
    stacker = HostBatchStacker(sharding=cs.named if cs is not None else None)

    rng = np.random.RandomState(cfg.seed)
    sizes = ([max(1, cfg.batch - (ci % 2)) for ci in range(cfg.n_clients)]
             if cfg.ragged and cfg.n_clients > 1
             else [cfg.batch] * cfg.n_clients)
    round_batches = [_draw_round_batches(mcfg, rng, sizes, cfg.local_steps,
                                         cfg.seq_len)
                     for _ in range(cfg.rounds)]
    w = np.ones(cfg.n_clients, np.float32)
    weights = jax.device_put(cs.pad_weights(w), cs.named) \
        if cs is not None else jnp.asarray(w)

    eng_losses, padded_rounds = [], []
    dispatches = 0
    merges_in_engine = 0
    for rnd in range(cfg.rounds):
        batches = stacker(pad(round_batches[rnd]))
        if cfg.oracle:
            # snapshot the padded rows the engine actually sees; np.array
            # COPIES — np.asarray of a CPU jax array is a zero-copy view
            # into a device buffer that is freed when ``batches`` is rebound
            padded_rounds.append({k: np.array(v) for k, v in
                                  batches.items()})
        m0 = peft_mod.dense_merge_count()
        cohort, cohort_opt, losses = round_step(cohort, cohort_opt, batches,
                                                weights)
        merges_in_engine += peft_mod.dense_merge_count() - m0
        dispatches += 1
        eng_losses.append(np.asarray(losses)[:cfg.n_clients])

    result = {
        "arch": cfg.arch,
        "lora_targets": list(targets),
        "ragged": len(set(sizes)) > 1,
        "n_ghosts": cs.n_pad if cs is not None else 0,
        "dispatches_per_round": dispatches / max(cfg.rounds, 1),
        "dense_merges_in_engine": int(merges_in_engine),
        "loss_per_round": [float(l.mean()) for l in eng_losses],
    }

    if cfg.oracle:
        # legacy dense-merge loop over the IDENTICAL padded batches: one
        # jitted per-client step that materializes W + sAB every call
        @jax.jit
        def oracle_step(base, lora, opt_state, batch):
            def loss_fn(lf):
                eff = peft_mod.apply_lora(base, lf, pc)
                return model.lm_loss(eff, _fold_valid(batch))
            loss, g = jax.value_and_grad(loss_fn)(lora)
            upd, opt_state = opt.update(g, opt_state, lora)
            return trees.tree_add(lora, upd), opt_state, loss

        o_loras = list(loras)
        o_opts = [opt.init(l) for l in o_loras]
        max_err = 0.0
        for rnd in range(cfg.rounds):
            stacked = padded_rounds[rnd]
            for ci in range(cfg.n_clients):
                for si in range(cfg.local_steps):
                    batch = {k: jnp.asarray(v[ci, si])
                             for k, v in stacked.items()}
                    o_loras[ci], o_opts[ci], loss = oracle_step(
                        params, o_loras[ci], o_opts[ci], batch)
                    max_err = max(max_err, abs(float(loss)
                                               - eng_losses[rnd][ci, si]))
            agg = fedavg_stacked(trees.stack(o_loras),
                                 jnp.ones(cfg.n_clients))
            o_loras = [agg] * cfg.n_clients
        result["oracle_loss_max_err"] = float(max_err)

    return result
