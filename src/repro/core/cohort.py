"""Vmapped federated cohort engine — the FL simulation hot path.

The legacy ``run_pfit``/``run_pftt`` loops dispatch O(n_clients ×
local_steps) separate jitted programs per round (one per client per local
step) plus per-client Python aggregation, so wall-clock scales linearly in
cohort size.  The engine instead stacks per-client trainable state along a
leading client axis (``trees.stack``) and compiles ONE fused round step:

    round_step = vmap_over_clients( lax.scan over local steps )   # training
               ∘ stacked aggregation with an outage weight vector  # server
               ∘ (masked) broadcast-back                           # downlink

``donate_argnums`` on the stacked state lets XLA reuse the cohort buffers
round-over-round instead of copying the whole parameter stack.  Per-round
dispatch count is O(1) regardless of cohort size — see
``benchmarks/fl_engine_bench.py`` for the measured looped-vs-fused curve.

Two round builders cover the repo's workloads:

* ``build_supervised_round`` — PFTT-style local SGD (any trainable pytree,
  any upload predicate); also drives PFIT's ``shepherd`` baseline.
* ``build_ppo_round`` — PFIT's personalized-RLHF round: vmapped rollout
  generation, double-reward scoring, PPO updates under per-client gradient
  masks, masked aggregation against the global model, masked broadcast.

Both builders take ``codec=`` (``repro.comms``): the per-client upload is
lossily encoded→decoded (vmapped ``comms.codec.roundtrip``, delta against
the round-input reference) INSIDE the fused step, the server aggregates the
decode, and the step returns the per-client encoded payload bits the round
loop feeds to ``comms.ChannelBudget`` — compression never leaves the
compiled program either.

Outages never leave the compiled program: the wireless layer contributes a
per-client weight *vector* (``RayleighChannel.outage_weights``), zero
entries drop a client from the weighted mean, and an all-zero vector gates
both the global update and the broadcast (clients keep local state), which
reproduces the legacy skip-on-all-outage semantics bit-for-bit.

Both builders take ``robust=True`` (``core/robust.py`` + ``wireless/
faults.py``): the fused step then carries a device-side **pending-update
buffer** (each client's latest produced-but-unmerged upload) and consumes
per-round fault masks — ``train`` (client computed this round), ``recv``
(client gets the broadcast), ``rejoin`` (crash recovery: optimizer state
zeroed) — plus a host-computed **staleness-discounted aggregation weight
vector** (``α·(1+s)^(-a)`` per ``core/robust.StalenessTracker``).  A client
whose uplink failed (channel outage or injected fault) keeps its payload in
the pending buffer and retransmits it next round instead of losing the
work; a straggler's round-``k`` update merges at round ``k+s``.  With
all-ones masks and undiscounted weights the robust body reduces exactly
(bitwise) to the synchronous round.

Both round builders take ``mesh=``/``client_axes=``: the round body is then
wrapped in ``shard_map`` with the stacked client axis sharded over the
given mesh axes (("pod","data") on the production mesh), so ONE fused round
spans every device.  Each shard runs the client-vmap × local-step scan on
its local client slice; the stacked aggregation becomes a ``psum`` of
per-shard weighted partial sums (``aggregation.*_stacked(axis_names=...)``)
and the broadcast-back consumes the replicated global.  Anything without a
client axis — the frozen base, the PPO global model, reward models — stays
replicated (closed-over or ``P()``-specced), so only rank-r LoRA factors /
trainables and optimizer moments pay per-device memory.  Cohorts that do
not divide the shard count are padded with zero-weight **ghost clients**
(``repro.sharding.cohort_sharding``) that the weight vector masks out of
the aggregation exactly.
"""
from __future__ import annotations

import functools
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import trees
from repro.comms import codec as codec_mod
from repro.core.aggregation import (broadcast_merge_stacked,
                                    factored_fedavg_stacked, fedavg_stacked,
                                    masked_fedavg_stacked)
from repro.core.aggregation import _pad_mask
from repro.obs.health import cohort_health
from repro.rlhf.ppo import PPOConfig, make_ppo_fns
from repro.rlhf.rollout import generate
from repro.sharding import client_shard_axes


def _where_clients(mask, new, old):
    """Per-client select over stacked trees: leaf ← new where the client's
    ``mask`` entry > 0, else old (leading-axis aligned broadcast)."""
    return jax.tree_util.tree_map(
        lambda n, o: jnp.where(_pad_mask(mask, n.ndim) > 0, n, o), new, old)


def _zero_clients(mask, tree):
    """Zero every leaf row whose client ``mask`` entry > 0 (crash-rejoin
    optimizer reset: adamw moments and step counts re-init to zeros)."""
    return jax.tree_util.tree_map(
        lambda l: jnp.where(_pad_mask(mask, l.ndim) > 0,
                            jnp.zeros_like(l), l), tree)


class HostBatchStacker:
    """Stacks the round's [client][step] host batches into the engine's
    (n_clients, local_steps, …) layout WITHOUT reallocating: the stacked
    numpy buffer is allocated once on the first round and refilled in place,
    then shipped with a single ``jax.device_put`` call per round (one
    transfer per leaf, no per-(client, step) ``np.stack`` garbage).

    Ragged cohorts (clients with unequal per-step batch shapes) are padded
    to the per-leaf maximum and get an extra ``"valid"`` leaf — a
    (n_clients, local_steps, max_batch) float mask with 1.0 on real sample
    rows (every leaf's axis 0 is the sample axis) — so unequal cohorts
    still compile to ONE fused round step.  The loss must weight samples by
    ``batch["valid"]`` (``Model.cls_loss`` does); padded rows then
    contribute exactly zero to loss, gradients, and aggregation, so parity
    with the legacy per-client loop holds.  Uniform cohorts are unchanged:
    no ``"valid"`` leaf, bitwise-identical buffers.

    ``sharding`` (a client-axis ``NamedSharding``, e.g.
    ``CohortSharding.named``): each device receives ONLY its own client
    shard of the host buffer — per-shard slices instead of one replicated
    whole-cohort transfer per device."""

    def __init__(self, sharding: Optional[NamedSharding] = None):
        self._bufs = None
        self._ragged = False
        self._sharding = sharding

    def _scan_shapes(self, per_client_batches):
        first = per_client_batches[0][0]
        shapes = {k: np.shape(v) for k, v in first.items()}
        ragged = False
        for cb in per_client_batches:
            for step in cb:
                for k, v in step.items():
                    if np.shape(v) != shapes[k]:
                        ragged = True
                        shapes[k] = tuple(max(a, b) for a, b in
                                          zip(shapes[k], np.shape(v)))
        return shapes, ragged

    def _alloc(self, per_client_batches, nc, ns):
        first = per_client_batches[0][0]
        shapes, ragged = self._scan_shapes(per_client_batches)
        self._ragged = ragged
        alloc = np.zeros if ragged else np.empty   # pad region stays defined
        self._bufs = {k: alloc((nc, ns) + shapes[k],
                               np.asarray(first[k]).dtype) for k in first}
        if ragged:
            max_b = shapes[next(iter(first))][0]
            self._bufs["valid"] = np.zeros((nc, ns, max_b), np.float32)

    def _compatible(self, per_client_batches, nc, ns):
        """Reusable iff the buffer's (nc, ns) layout matches and every leaf
        still fits: exactly (uniform) or within the padded max (ragged)."""
        ref = {k: v for k, v in self._bufs.items() if k != "valid"}
        if any(v.shape[:2] != (nc, ns) for v in ref.values()):
            return False
        shapes, ragged = self._scan_shapes(per_client_batches)
        if set(shapes) != set(ref):
            return False
        if not self._ragged:
            return not ragged and all(ref[k].shape[2:] == s
                                      for k, s in shapes.items())
        return all(all(d <= bd for d, bd in zip(s, ref[k].shape[2:]))
                   for k, s in shapes.items())

    def __call__(self, per_client_batches):
        nc = len(per_client_batches)
        ns = len(per_client_batches[0])
        if self._bufs is None or not self._compatible(per_client_batches,
                                                      nc, ns):
            # cohorts whose shapes drift (uniform → ragged, a new max batch)
            # pay one realloc; steady-state rounds reuse the buffer
            self._alloc(per_client_batches, nc, ns)
        if self._ragged:
            valid = self._bufs["valid"]
            valid[:] = 0.0
            for ci, cb in enumerate(per_client_batches):
                for si, step in enumerate(cb):
                    n = None
                    for k, v in step.items():
                        v = np.asarray(v)
                        n = v.shape[0] if n is None else n
                        sl = (ci, si) + tuple(slice(0, d) for d in v.shape)
                        self._bufs[k][sl] = v
                    valid[ci, si, :n] = 1.0
        else:
            for ci, cb in enumerate(per_client_batches):
                for si, step in enumerate(cb):
                    for k, v in step.items():
                        self._bufs[k][ci, si] = v
        if self._sharding is None:
            return jax.device_put(self._bufs)
        return jax.device_put(self._bufs, self._sharding)


def stack_host_batches(per_client_batches):
    """[client][step] list of {name: np.ndarray} → one device dict with
    leading (n_clients, local_steps) axes — the engine's data layout.
    One-shot helper; round loops should hold a ``HostBatchStacker`` to
    reuse the host buffer across rounds."""
    return HostBatchStacker()(per_client_batches)


def build_cohort_eval(eval_fn: Callable,
                      sharding: Optional[NamedSharding] = None, base=None):
    """Fuse per-client eval into ONE jitted vmapped dispatch per round.

    ``eval_fn(trainable, *per_client_data) -> pytree`` is the UNJITTED
    single-client eval; every argument is stacked on a leading client axis
    (ragged test sets are padded to a common shape with a validity mask —
    the mask rides in as one of the stacked args).  Returns the vmapped
    jitted cohort eval.

    ``sharding`` (client-axis ``NamedSharding``): every stacked input is
    constrained to the client sharding, so GSPMD keeps the vmapped eval
    device-parallel over the mesh instead of gathering the cohort.

    ``base`` (the frozen model, unbatched): handed to
    ``eval_fn(base, trainable, *per_client_data)`` as an argument of the
    compiled eval — see ``build_supervised_round``."""
    def constrain(x):
        spec = tuple(sharding.spec)
        full = P(*(spec + (None,) * (x.ndim - len(spec))))
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(sharding.mesh, full))

    def cohort_eval(b, *args):
        if sharding is not None:
            args = jax.tree_util.tree_map(constrain, args)
        fn = eval_fn if base is None else functools.partial(eval_fn, b)
        return jax.vmap(fn)(*args)

    return functools.partial(jax.jit(cohort_eval), base)


def build_supervised_round(local_step_fn: Callable,
                           upload_pred: Optional[Callable[[str], bool]] = None,
                           *, donate: bool = True, mesh=None,
                           client_axes=None, codec=None,
                           factored_agg: bool = False,
                           robust: bool = False, min_quorum: int = 0,
                           health: bool = False, base=None):
    """Fuse per-client local SGD + FedAvg + broadcast into one jitted step.

    ``local_step_fn(trainable, opt_state, batch) -> (trainable, opt_state,
    loss)`` is the UNJITTED per-client step (the engine owns compilation).
    ``upload_pred`` selects the uploaded/aggregated subtree by path (None →
    the full tree, plain FedAvg).

    Returns ``round_step(stacked_trainable, stacked_opt, batches, weights)``
    where ``batches`` leaves have leading (n_clients, local_steps) axes and
    ``weights`` is the (n_clients,) outage vector.  Produces the updated
    stacked state and the (n_clients, local_steps) loss matrix.

    ``codec`` (a ``repro.comms`` codec): the uploaded subtree is lossily
    encoded→decoded per client INSIDE the fused step (vmapped
    ``comms.codec.roundtrip`` against the round-input reference) before
    aggregation, and the step takes one extra ``keys`` arg ((n, 2) uint32,
    the per-client PRNG keys for stochastic rounding) and returns one extra
    ``payload_bits`` (n,) output — the encoded uplink charge per client.

    ``factored_agg``: aggregate ``{'a','b'}`` LoRA factor pairs as the SVD
    re-projection of the weighted-mean update instead of averaging the
    factors elementwise (``aggregation.factored_fedavg_stacked`` — the
    server never densifies).

    ``mesh`` (+ optional ``client_axes``, default every non-"model" axis):
    wrap the round in ``shard_map`` with the client axis sharded over the
    mesh — each shard trains its local client slice, aggregation is a psum
    of weighted partial sums, and the broadcast-back writes the replicated
    global into every local slot.  Stacked inputs must then be sharded with
    the matching client-axis ``NamedSharding`` and the cohort size must be
    a multiple of the shard count (ghost-pad via ``cohort_sharding``).

    ``robust``: straggler-tolerant signature — ``round_step(st_trainable,
    st_opt, pending, batches, train_m, agg_w, recv_m, rejoin_m, ontime_m
    [, keys])`` → ``(st_trainable, st_opt, pending, losses[, bits])``.
    ``pending`` is the stacked device-side buffer of each client's latest
    produced-but-unmerged upload (uploaded-subtree structure, zeros-init);
    ``train_m``/``recv_m``/``rejoin_m`` are the round's (n,) fault masks
    (``wireless.faults``) and ``agg_w`` is the host-computed
    staleness-discounted aggregation weight vector
    (``core/robust.StalenessTracker``): the server merges ``train`` clients'
    fresh uploads and stragglers' pending payloads in the same weighted
    mean, non-``recv`` clients keep their local shared values, and
    ``rejoin`` clients get zeroed optimizer state.  ``ontime_m`` is the
    continuous-time deadline mask (``wireless/arrivals.py``: 1 = the
    client's upload arrives before the server cutoff) — the body merges
    with ``agg_w · ontime_m``, so a deadline miss keeps the payload in
    ``pending`` at weight 0; all-ones when no deadline is configured.
    ``min_quorum`` (static) generalizes the all-outage gate: a round with
    fewer than ``min_quorum`` positive-weight deliveries is a no-op merge
    (0 keeps the plain ``Σw > 0`` gate).  All-ones masks + undiscounted
    weights reduce bitwise to the synchronous round.

    ``health``: append one extra output — a dict of replicated f32
    training-health scalars (``repro.obs.health.cohort_health``) computed
    inside the same compiled body, so the round still costs exactly one
    dispatch and the factored path is untouched.

    ``base`` (the frozen model, replicated): handed to
    ``local_step_fn(base, trainable, opt_state, batch)`` as an ARGUMENT of
    the compiled round, bound into the returned step so its signature is
    unchanged.  A closed-over array would be embedded in the executable as
    a constant instead: at roberta-base widths a ~1 GB program that
    compiles slowly and outgrows the persistent compile cache.
    """
    pred = upload_pred or (lambda p: True)
    axes = None if mesh is None else client_shard_axes(mesh, client_axes)
    agg_fn = factored_fedavg_stacked if factored_agg else fedavg_stacked

    def local(b):
        return local_step_fn if base is None else functools.partial(
            local_step_fn, b)

    def robust_body(b, st_trainable, st_opt, pending, batches, train_m, agg_w,
                    recv_m, rejoin_m, ontime_m, keys=None):
        # round-input uploaded subtree: the codec's delta reference AND the
        # health scalars' update baseline (send − up_in = this round's delta)
        up_in = (trees.select(st_trainable, pred)
                 if (codec is not None or health) else None)
        ref = up_in if codec is not None else None
        step_fn = local(b)

        def client(tr, op, client_batches):
            def step(carry, batch):
                tr, op = carry
                tr, op, loss = step_fn(tr, op, batch)
                return (tr, op), loss

            (tr, op), losses = jax.lax.scan(step, (tr, op), client_batches)
            return tr, op, losses

        trained_tr, trained_op, losses = jax.vmap(client)(
            st_trainable, st_opt, batches)
        # non-training clients (straggling / crashed / dropped) keep state
        st_trainable = _where_clients(train_m, trained_tr, st_trainable)
        st_opt = _where_clients(train_m, trained_op, st_opt)
        losses = losses * train_m[:, None]

        uploaded = trees.select(st_trainable, pred)
        raw = uploaded if (health and codec is not None) else None
        bits = jnp.zeros_like(agg_w)
        if codec is not None:
            uploaded, bits = jax.vmap(
                lambda k, t, rf: codec_mod.roundtrip(codec, k, t, ref=rf)
            )(keys, uploaded, ref)
        # what goes on the air: a fresh upload supersedes the client's
        # pending payload; stragglers retransmit the pending one
        send = _where_clients(train_m, uploaded, pending)
        # deadline mask: a late arrival merges at weight 0 (it stays in
        # pending and retransmits with its staleness discount next chance)
        agg_w = agg_w * ontime_m
        with jax.named_scope("aggregate"):   # the weighted psum, sharded
            agg = agg_fn(send, agg_w, axis_names=axes)
            wsum = agg_w.sum()
            n_del = (agg_w > 0).astype(jnp.float32).sum()
            if axes is not None:
                wsum = jax.lax.psum(wsum, axes)
                n_del = jax.lax.psum(n_del, axes)
        flat_agg = trees.flatten(agg)
        # nothing delivered (or an under-quorum cohort) → no-op update
        gate = jnp.logical_and(wsum > 0, n_del >= min_quorum)

        def put(path, loc):
            if path not in flat_agg:
                return loc
            bc = jnp.broadcast_to(flat_agg[path][None].astype(loc.dtype),
                                  loc.shape)
            rm = jnp.broadcast_to(_pad_mask(recv_m, loc.ndim) > 0, loc.shape)
            return jnp.where(jnp.logical_and(gate, rm), bc, loc)

        st_trainable = trees.map_with_path(put, st_trainable)
        st_opt = _zero_clients(rejoin_m, st_opt)   # crash-rejoin: fresh opt
        outs = (st_trainable, st_opt, send, losses)
        if codec is not None:
            outs = outs + (bits,)
        if health:
            outs = outs + (cohort_health(
                send, up_in, losses, agg_w, gate.astype(jnp.float32),
                train_m=train_m, raw=raw,
                decoded=uploaded if codec is not None else None,
                axis_names=axes),)
        return outs

    def round_body(b, st_trainable, st_opt, batches, weights, keys=None):
        # server-known reference for delta coding: the round-input value of
        # the uploaded subtree (the previous broadcast global on every
        # non-all-outage round); doubles as the health-delta baseline
        up_in = (trees.select(st_trainable, pred)
                 if (codec is not None or health) else None)
        ref = up_in if codec is not None else None
        step_fn = local(b)

        def client(tr, op, client_batches):
            def step(carry, batch):
                tr, op = carry
                tr, op, loss = step_fn(tr, op, batch)
                return (tr, op), loss

            (tr, op), losses = jax.lax.scan(step, (tr, op), client_batches)
            return tr, op, losses

        st_trainable, st_opt, losses = jax.vmap(client)(
            st_trainable, st_opt, batches)

        # server: weighted mean of the uploaded subtree over surviving
        # clients (a psum over the mesh when sharded), broadcast back into
        # every client's stacked slot.  With a codec, the server only ever
        # sees the lossy decode of each client's upload.
        uploaded = trees.select(st_trainable, pred)
        raw = uploaded if (health and codec is not None) else None
        bits = None
        if codec is not None:
            uploaded, bits = jax.vmap(
                lambda k, t, rf: codec_mod.roundtrip(codec, k, t, ref=rf)
            )(keys, uploaded, ref)
        with jax.named_scope("aggregate"):   # the weighted psum, sharded
            agg = agg_fn(uploaded, weights, axis_names=axes)
            wsum = weights.sum()
            if axes is not None:
                wsum = jax.lax.psum(wsum, axes)
        flat_agg = trees.flatten(agg)
        gate = wsum > 0                    # all-outage round → keep local

        def put(path, loc):
            if path not in flat_agg:
                return loc
            bc = jnp.broadcast_to(flat_agg[path][None].astype(loc.dtype),
                                  loc.shape)
            return jnp.where(gate, bc, loc)

        st_trainable = trees.map_with_path(put, st_trainable)
        outs = (st_trainable, st_opt, losses)
        if codec is not None:
            outs = outs + (bits,)
        if health:
            outs = outs + (cohort_health(
                uploaded, up_in, losses, weights, gate.astype(jnp.float32),
                raw=raw, decoded=uploaded if codec is not None else None,
                axis_names=axes),)
        return outs

    body = robust_body if robust else round_body
    if mesh is None:
        round_step = body
    else:
        # the codec variant carries one extra stacked input (PRNG keys) and
        # one extra stacked output (payload bits); the robust variant adds
        # the pending buffer + three fault masks (all client-sharded);
        # shard_map calls the body positionally so one body serves both
        # arities
        pc = P(axes)
        n_in, n_out = (5, 4) if codec is not None else (4, 3)
        if robust:
            n_in, n_out = n_in + 5, n_out + 1
        # health scalars are psum-ed inside the body → replicated out-spec
        out_specs = (pc,) * n_out + ((P(),) if health else ())
        round_step = jax.shard_map(body, mesh=mesh,
                                   in_specs=(P(),) + (pc,) * n_in,
                                   out_specs=out_specs, check_vma=False)
    donate_args = ((0, 1, 2) if robust else (0, 1)) if donate else ()
    if base is None:     # a plain jitted step (``.lower`` for AOT compiles)
        return jax.jit(functools.partial(round_step, None),
                       donate_argnums=donate_args)
    return functools.partial(  # argument 0 is the base, never donated
        jax.jit(round_step,
                donate_argnums=tuple(i + 1 for i in donate_args)), base)


def build_ppo_round(model, opt, ppo_cfg: PPOConfig, prompt_len: int,
                    gen_len: int, quality_fn: Callable, *,
                    lambda_regs=None,
                    reg_pred: Optional[Callable[[str], bool]] = None,
                    donate: bool = True, mesh=None, client_axes=None,
                    codec=None, robust: bool = False, min_quorum: int = 0):
    """Fuse PFIT's per-client PPO round + masked aggregation + masked
    broadcast into one jitted step.

    ``quality_fn(tokens, resp_mask, alpha_help, alpha_safe)`` scores a
    rollout batch with the personalized double reward (closed over the
    frozen reward-model params).  ``lambda_regs`` is the PER-CLIENT
    (n_clients,) vector of the paper's negative-L2 pull toward the global
    model (None/all-zero skips the reg term entirely); ``reg_pred`` selects
    the regularized subtree.

    Returns ``round_step(st_params, st_opt, global_params, st_masks,
    prompts, keys, alphas_help, alphas_safe, weights)`` →
    ``(st_params, st_opt, new_global, mean_rewards, mean_kls)`` with all
    per-client inputs stacked on a leading client axis.

    ``codec`` (a ``repro.comms`` codec): each client's post-PPO params are
    lossily encoded→decoded (delta against the round-input params, bit
    charge restricted to the client's sparsity-mask entries — unmasked
    parameters are never uploaded) before the masked aggregation, the step
    takes an extra trailing ``keys`` arg ((n, 2) uint32) and returns an
    extra ``payload_bits`` (n,) output.

    ``mesh`` (+ optional ``client_axes``): as in ``build_supervised_round``
    — the whole PPO round runs under ``shard_map`` with per-client state
    sharded over the mesh, the global model replicated (``P()`` in and
    out), and the masked aggregation's numerator/denominator ``psum``ed.
    ``lambda_regs`` must then already cover the ghost-padded cohort.

    ``robust``: straggler-tolerant signature — ``round_step(st_params,
    st_opt, global_params, pending, st_masks, prompts, keys, alphas_help,
    alphas_safe, agg_w, train_m, recv_m, rejoin_m, ontime_m
    [, codec_keys])`` → ``(st_params, st_opt, new_global, pending,
    mean_rewards, mean_kls[, bits])``: same pending-buffer / fault-mask /
    discounted-weight / deadline-mask / ``min_quorum``-gate contract as the
    supervised builder, with the masked aggregation consuming fresh uploads
    and retransmitted pending payloads in one weighted mean and the masked
    broadcast gated per client on ``recv_m``.
    """
    prep, step = make_ppo_fns(model, opt, ppo_cfg, prompt_len)
    reg_pred = reg_pred or (lambda p: p.startswith("stages"))
    lams = None if lambda_regs is None else np.asarray(lambda_regs,
                                                       np.float32)
    use_reg = lams is not None and bool((lams > 0).any())
    axes = None if mesh is None else client_shard_axes(mesh, client_axes)

    def _make_client(global_params):
        def client(params, opt_state, grad_mask, client_prompts, key,
                   a_help, a_safe, lam):
            toks = generate(model, params, client_prompts, gen_len, key,
                            temperature=ppo_cfg.temperature)
            resp = jnp.concatenate(
                [jnp.zeros((toks.shape[0], prompt_len)),
                 jnp.ones((toks.shape[0], gen_len))], axis=1)
            reward = quality_fn(toks, resp, a_help, a_safe)
            if use_reg:
                reg = trees.tree_l2(trees.select(params, reg_pred),
                                    trees.select(global_params, reg_pred))
                reward = reward - lam * reg
            old_logp, adv, ret, resp_mask, mean_kl = prep(
                params, global_params, toks, reward)
            for _ in range(ppo_cfg.ppo_epochs):
                params, opt_state, _, _ = step(
                    params, opt_state, toks, old_logp, adv, ret, resp_mask,
                    grad_mask)
            return params, opt_state, reward.mean(), mean_kl
        return client

    def robust_ppo_body(st_params, st_opt, global_params, pending, st_masks,
                        prompts, keys, alphas_help, alphas_safe, agg_w,
                        train_m, recv_m, rejoin_m, ontime_m, st_lams,
                        codec_keys=None):
        ref = st_params if codec is not None else None   # round-input params
        trained_p, trained_o, mean_rewards, mean_kls = jax.vmap(
            _make_client(global_params))(
            st_params, st_opt, st_masks, prompts, keys, alphas_help,
            alphas_safe, st_lams)
        st_params = _where_clients(train_m, trained_p, st_params)
        st_opt = _where_clients(train_m, trained_o, st_opt)
        mean_rewards = mean_rewards * train_m
        mean_kls = mean_kls * train_m

        uploaded, bits = st_params, jnp.zeros_like(agg_w)
        if codec is not None:
            uploaded, bits = jax.vmap(
                lambda k, t, rf, m: codec_mod.roundtrip(
                    codec, k, t, ref=rf, bit_weights=m)
            )(codec_keys, st_params, ref, st_masks)
        # fresh upload supersedes the pending payload; stragglers/outage
        # clients retransmit the buffered one with its staleness discount;
        # a deadline miss merges at weight 0 (stays pending — see
        # wireless/arrivals.py) and an under-quorum round is a no-op merge
        send = _where_clients(train_m, uploaded, pending)
        agg_w = agg_w * ontime_m
        new_global = masked_fedavg_stacked(global_params, send, st_masks,
                                           agg_w, axis_names=axes)
        wsum = agg_w.sum()
        n_del = (agg_w > 0).astype(jnp.float32).sum()
        if axes is not None:
            wsum = jax.lax.psum(wsum, axes)
            n_del = jax.lax.psum(n_del, axes)
        merged = broadcast_merge_stacked(
            st_params, new_global, st_masks,
            gate=jnp.logical_and(wsum > 0, n_del >= min_quorum))
        st_params = _where_clients(recv_m, merged, st_params)
        st_opt = _zero_clients(rejoin_m, st_opt)   # crash-rejoin: fresh opt
        if codec is not None:
            return (st_params, st_opt, new_global, send, mean_rewards,
                    mean_kls, bits)
        return st_params, st_opt, new_global, send, mean_rewards, mean_kls

    def round_body(st_params, st_opt, global_params, st_masks, prompts, keys,
                   alphas_help, alphas_safe, weights, st_lams,
                   codec_keys=None):
        ref = st_params if codec is not None else None   # round-input params

        st_params, st_opt, mean_rewards, mean_kls = jax.vmap(
            _make_client(global_params))(
            st_params, st_opt, st_masks, prompts, keys, alphas_help,
            alphas_safe, st_lams)

        # server: sparse-mask-weighted aggregation over surviving clients
        # (all-outage → den 0 everywhere → global kept), then each client
        # resumes from the new global on its own masked entries.  With a
        # codec the server aggregates the lossy decode of each client's
        # masked delta upload instead of the exact params.
        uploaded, bits = st_params, None
        if codec is not None:
            uploaded, bits = jax.vmap(
                lambda k, t, rf, m: codec_mod.roundtrip(
                    codec, k, t, ref=rf, bit_weights=m)
            )(codec_keys, st_params, ref, st_masks)
        new_global = masked_fedavg_stacked(global_params, uploaded, st_masks,
                                           weights, axis_names=axes)
        wsum = weights.sum()
        if axes is not None:
            wsum = jax.lax.psum(wsum, axes)
        st_params = broadcast_merge_stacked(st_params, new_global, st_masks,
                                            gate=wsum > 0)
        if codec is not None:
            return st_params, st_opt, new_global, mean_rewards, mean_kls, bits
        return st_params, st_opt, new_global, mean_rewards, mean_kls

    inner = robust_ppo_body if robust else round_body
    if mesh is None:
        body = inner
    else:
        pc, pr = P(axes), P()
        n_extra = 1 if codec is not None else 0
        if robust:
            # pending + three fault masks + agg_w + the deadline mask are
            # client-sharded; the extra `send` output (the next pending
            # buffer) likewise
            in_specs = ((pc, pc, pr, pc, pc, pc, pc, pc, pc, pc, pc, pc, pc,
                         pc, pc) + (pc,) * n_extra)
            out_specs = (pc, pc, pr, pc, pc, pc) + (pc,) * n_extra
        else:
            in_specs = (pc, pc, pr, pc, pc, pc, pc, pc, pc, pc) \
                + (pc,) * n_extra
            out_specs = (pc, pc, pr, pc, pc) + (pc,) * n_extra
        body = jax.shard_map(inner, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)

    def _st_lams(alphas_help):
        # per-client λ rides in as a stacked arg so the shard_map slices it
        # with the rest of the client axis (a closed-over vector would stay
        # whole-cohort-sized and break the local vmap)
        return (jnp.asarray(lams) if use_reg
                else jnp.zeros_like(alphas_help))

    if robust:
        def round_step(st_params, st_opt, global_params, pending, st_masks,
                       prompts, keys, alphas_help, alphas_safe, agg_w,
                       train_m, recv_m, rejoin_m, ontime_m, codec_keys=None):
            args = (st_params, st_opt, global_params, pending, st_masks,
                    prompts, keys, alphas_help, alphas_safe, agg_w,
                    train_m, recv_m, rejoin_m, ontime_m,
                    _st_lams(alphas_help))
            if codec is not None:
                args = args + (codec_keys,)
            return body(*args)

        donate_args = (0, 1, 3) if donate else ()
    else:
        def round_step(st_params, st_opt, global_params, st_masks, prompts,
                       keys, alphas_help, alphas_safe, weights,
                       codec_keys=None):
            args = (st_params, st_opt, global_params, st_masks, prompts,
                    keys, alphas_help, alphas_safe, weights,
                    _st_lams(alphas_help))
            if codec is not None:
                args = args + (codec_keys,)
            return body(*args)

        donate_args = (0, 1) if donate else ()
    return jax.jit(round_step, donate_argnums=donate_args)
