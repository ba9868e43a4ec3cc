"""Mixture-of-Experts feed-forward with expert parallelism.

Distribution scheme (DESIGN.md §5): *replicated-activation expert
parallelism* under ``shard_map`` — layer-boundary activations are already
replicated along the ``model`` axis (tensor-parallel layout), experts are
sharded along ``model``.  Each device routes its local (data-shard) tokens,
gathers the capacity-C token set for **its** experts, runs a batched GEMM over
(E_local, C, d), scatters back, and a single ``psum`` over the model axis
combines expert contributions.  No dispatch all-to-all is required at this
topology; the psum is the same collective a tensor-parallel dense FF needs.

Routing is token-choice top-k with capacity dropping (sort-based dispatch
table, gather/scatter with ``mode='drop'``).  For tiny token counts (decode)
capacity is set to T·k → dropless.

One chip's expert-parallel share (``MoEConfig.n_held`` > 0, ``moe_held``):
the chip holds experts ``first_held .. first_held + n_held - 1`` of a
layer whose experts are divided over several chips.  It routes every
token over all ``n_experts`` (softmax in float32, top-k, renormalized only
if ``norm_topk_prob``, times ``routed_scaling``) and computes the part of
the result its held experts give, ``Σ_{e ∈ topk ∩ held} w_e·FFN_e(x)``,
plus the shared experts.  Nothing stands in for the other chips' experts,
and on one chip there is no exchange.  Routing is dropless: the (token,
held expert) pairs are sorted by expert and the held experts run as one
grouped matmul (``kernels/moe_gmm``) over the rows actually routed, within
the static bound ``t·min(k, n_held)`` rows.  The layer also returns its
counters: rows routed to each held expert, the grouped calls' experts hit,
and pairs dropped (0 by construction).  Named scopes for the device
trace: ``moe`` with ``route``, ``gmm``, ``combine`` and ``shared``.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import MoEConfig
from repro.kernels.moe_gmm.ops import moe_gmm
from repro.models.mlp import act_fn
from repro.sharding import MeshCtx

# tokens per grouped call: longer token sets (prefill) run in chunks, which
# bounds the sorted rows' temporaries at t·min(k, n_held) per chunk
HELD_CHUNK_TOKENS = 16384


def _capacity(n_tokens: int, cfg: MoEConfig) -> int:
    tk = n_tokens * cfg.top_k
    if tk <= 4096:
        return tk  # dropless for small batches (decode / smoke)
    c = int(tk * cfg.capacity_factor / cfg.n_experts)
    return max(8, -(-c // 8) * 8)


def _local_moe(x, router, wg, wu, wd, *, cfg: MoEConfig, act: str,
               e_loc: int, model_axis: str, shard_experts: bool,
               batch_axes: Tuple[str, ...], psum_axes: Tuple[str, ...] = ()):
    """Per-device body.  x: (B_loc, S, d) local tokens (replicated along the
    model axis); wg/wu/wd: (E_loc, d, f)/(E_loc, f, d) local expert slabs."""
    b, s, d = x.shape
    t = b * s
    e = cfg.n_experts
    k = cfg.top_k
    c = _capacity(t, cfg)
    xt = x.reshape(t, d)

    gates = jax.nn.softmax((xt.astype(jnp.float32) @ router.astype(jnp.float32)))
    w, idx = jax.lax.top_k(gates, k)                      # (t, k)
    w = w / jnp.maximum(w.sum(-1, keepdims=True), 1e-9)

    flat_e = idx.reshape(-1)                              # (t*k,)
    order = jnp.argsort(flat_e, stable=True)
    sorted_e = flat_e[order]
    starts = jnp.searchsorted(sorted_e, jnp.arange(e))
    pos = jnp.arange(t * k) - starts[sorted_e]
    e0 = (jax.lax.axis_index(model_axis) * e_loc) if shard_experts else 0
    local_e = sorted_e - e0
    ok = (pos < c) & (local_e >= 0) & (local_e < e_loc)
    le = jnp.where(ok, local_e, e_loc)                    # OOB → dropped
    pc = jnp.where(ok, pos, c)
    tok = order // k

    table = jnp.full((e_loc, c), t, jnp.int32).at[le, pc].set(tok, mode="drop")
    wtab = jnp.zeros((e_loc, c), jnp.float32).at[le, pc].set(
        w.reshape(-1)[order], mode="drop")

    xp = jnp.concatenate([xt, jnp.zeros((1, d), xt.dtype)])
    xe = xp[table]                                        # (E_loc, C, d)
    if act in ("swiglu", "geglu"):
        h = act_fn(act)(jnp.einsum("ecd,edf->ecf", xe, wg)) * \
            jnp.einsum("ecd,edf->ecf", xe, wu)
    else:
        h = act_fn(act)(jnp.einsum("ecd,edf->ecf", xe, wu))
    ye = jnp.einsum("ecf,efd->ecd", h, wd)
    ye = (ye.astype(jnp.float32) * wtab[..., None]).astype(x.dtype)

    y = jnp.zeros((t + 1, d), x.dtype).at[table].add(ye)[:t]
    if psum_axes:
        y = jax.lax.psum(y, psum_axes)

    # Switch-style load-balance auxiliary loss (replicated along model axis).
    frac_routed = jnp.zeros((e,), jnp.float32).at[flat_e].add(1.0) / (t * k)
    frac_prob = gates.mean(0)
    aux = e * jnp.sum(frac_routed * frac_prob)
    if batch_axes:
        aux = jax.lax.pmean(aux, batch_axes)
    return y.reshape(b, s, d), aux


def moe_ffn(x, params, cfg: MoEConfig, meshctx: MeshCtx, act: str):
    """x: (B, S, d) global.  Returns (y, aux_loss)."""
    msize = meshctx.model_size
    shard_experts = msize > 1 and cfg.n_experts % msize == 0
    e_loc = cfg.n_experts // msize if shard_experts else cfg.n_experts

    e_ax = meshctx.model_axis if shard_experts else None
    # batch dim shards over the data axes only when divisible (long_500k has
    # global_batch=1 → tokens replicated, experts still sharded).  At decode
    # (S == 1) tokens are ALWAYS replicated: gathering B·d token bytes (~MBs)
    # is far cheaper than gathering FSDP expert slabs every layer — the
    # 2D-sharded expert path below then applies.
    batch_ax = (None if x.shape[1] == 1
                else meshctx.dim_axis(x.shape[0], meshctx.batch_axes))
    # When tokens are replicated over the data axes (decode, B < data size),
    # 2D-shard the experts: E over model AND f over data — avoids gathering
    # the expert slabs (FSDP layout) every layer for one token; the partial
    # f-contributions fold into the same psum.
    f_ax = (meshctx.dim_axis(cfg.d_ff, meshctx.batch_axes)
            if batch_ax is None else None)
    gu_spec = P(e_ax, None, f_ax)
    d_spec = P(e_ax, f_ax, None)
    psum_axes = (meshctx.model_axis,) if shard_experts else ()
    if f_ax is not None:
        psum_axes = psum_axes + tuple(meshctx.batch_axes)
    bspec = P(batch_ax, None, None)
    aux_axes = meshctx.batch_axes if batch_ax is not None else ()
    body = functools.partial(
        _local_moe, cfg=cfg, act=act, e_loc=e_loc,
        model_axis=meshctx.model_axis, shard_experts=shard_experts,
        batch_axes=aux_axes, psum_axes=psum_axes)
    args = (x, params["router"], params["wg"], params["wu"], params["wd"])

    if not psum_axes and not aux_axes:
        # every operand is replicated, so the body is plain local compute.
        # Run it directly: inside the cohort round's shard_map a nested
        # shard_map over the model's own mesh is refused.
        y, aux = body(*args)
    else:
        y, aux = jax.shard_map(
            body, mesh=meshctx.mesh,
            in_specs=(bspec, P(None, None), gu_spec, gu_spec, d_spec),
            out_specs=(bspec, P()),
            check_vma=False,
        )(*args)

    # shared (always-on) experts — a plain dense FF of width n_shared·f
    if cfg.n_shared_experts > 0:
        from repro.models.mlp import mlp
        y = y + mlp(x, params["shared"], act)
    return y, aux


def _held_rows(xt, w, idx, params, cfg: MoEConfig, act: str):
    """The held experts' part for one set of tokens.  xt: (t, d); w, idx:
    (t, k) gate weights and expert ids.  Returns (y (t, d), rows per held
    expert (n_held,), experts hit, pairs dropped)."""
    t, d = xt.shape
    k, eh = cfg.top_k, cfg.n_held
    local = idx - cfg.first_held
    key = jnp.where((local >= 0) & (local < eh), local, eh).reshape(-1)
    rows = jnp.zeros((eh,), jnp.int32).at[key].add(1, mode="drop")
    bound = t * min(k, eh)              # a token holds ≤ min(k, eh) pairs
    m = -(-bound // 128) * 128          # the kernel's row tiles
    order = jnp.argsort(key, stable=True)[:min(m, t * k)]
    if order.shape[0] < m:              # tiny token sets: pad the rows
        order = jnp.concatenate(
            [order, jnp.full((m - order.shape[0],), t * k, order.dtype)])
    n_pairs = rows.sum()
    valid = jnp.arange(m) < n_pairs
    tok = jnp.minimum(order // k, t - 1)
    xs = xt[tok]
    with jax.named_scope("gmm"):
        if act in ("swiglu", "geglu"):
            h = act_fn(act)(moe_gmm(xs, params["wg"], rows)) * \
                moe_gmm(xs, params["wu"], rows)
        else:
            h = act_fn(act)(moe_gmm(xs, params["wu"], rows))
        ys = moe_gmm(h, params["wd"], rows)
    with jax.named_scope("combine"):
        wr = jnp.concatenate([w.reshape(-1), jnp.zeros((1,), w.dtype)])[order]
        contrib = jnp.where(valid[:, None],
                            ys.astype(jnp.float32) * wr[:, None], 0.0)
        y = jnp.zeros((t, d), jnp.float32).at[tok].add(contrib)
    return (y.astype(xt.dtype), rows, (rows > 0).sum().astype(jnp.int32),
            jnp.maximum(n_pairs - bound, 0).astype(jnp.int32))


def moe_held(x, params, cfg: MoEConfig, act: str):
    """One chip's share of the expert layer (module docstring).  x: (B, S,
    d).  Returns (y, aux, counts) with counts ``moe_rows`` (n_held,),
    ``moe_hits`` and ``moe_dropped`` (int32 scalars), summed over the
    layer's grouped calls."""
    b, s, d = x.shape
    t = b * s
    xt = x.reshape(t, d)
    with jax.named_scope("moe"):
        with jax.named_scope("route"):
            gates = jax.nn.softmax(xt.astype(jnp.float32)
                                   @ params["router"].astype(jnp.float32))
            w, idx = jax.lax.top_k(gates, cfg.top_k)
            if cfg.norm_topk_prob:
                w = w / jnp.maximum(w.sum(-1, keepdims=True), 1e-9)
            if cfg.routed_scaling != 1.0:
                w = w * cfg.routed_scaling
        experts = {n: params[n] for n in ("wg", "wu", "wd") if n in params}
        c = HELD_CHUNK_TOKENS if t > HELD_CHUNK_TOKENS else t
        if t % c:
            c = t
        if c == t:
            y, rows, hits, dropped = _held_rows(xt, w, idx, experts, cfg, act)
        else:
            ys, rows, hits, dropped = jax.lax.map(
                lambda a: _held_rows(*a, experts, cfg, act),
                (xt.reshape(t // c, c, d), w.reshape(t // c, c, -1),
                 idx.reshape(t // c, c, -1)))
            y = ys.reshape(t, d)
            rows, hits, dropped = rows.sum(0), hits.sum(), dropped.sum()
        y = y.reshape(b, s, d)
        if cfg.n_shared_experts > 0:
            from repro.models.mlp import mlp
            with jax.named_scope("shared"):
                y = y + mlp(x, params["shared"], act)
        frac_routed = jnp.zeros((cfg.n_experts,), jnp.float32).at[
            idx.reshape(-1)].add(1.0) / (t * cfg.top_k)
        aux = cfg.n_experts * jnp.sum(frac_routed * gates.mean(0))
    return y, aux, {"moe_rows": rows, "moe_hits": hits,
                    "moe_dropped": dropped}


def moe_layer(x, params, cfg: MoEConfig, meshctx: MeshCtx, act: str,
              a2a: bool = False):
    """The expert layer for the share of experts ``cfg`` states: this
    chip's held experts (``moe_held``) where ``n_held`` is set, else every
    expert through the capacity path (``moe_ffn``, or ``moe_ffn_a2a``
    with ``a2a``).  Returns (y, aux, counts); counts are empty but for
    held experts."""
    if cfg.n_held:
        return moe_held(x, params, cfg, act)
    fn = moe_ffn_a2a if a2a else moe_ffn
    y, aux = fn(x, params, cfg, meshctx, act)
    return y, aux, {}


def init_moe(key, d_model: int, cfg: MoEConfig, act: str, dtype):
    ks = jax.random.split(key, 5)
    e, f = cfg.n_slab, cfg.d_ff
    std_in, std_out = d_model ** -0.5, f ** -0.5
    p = {
        "router": (jax.random.normal(ks[0], (d_model, cfg.n_experts))
                   * std_in).astype(jnp.float32),
        "wg": (jax.random.normal(ks[1], (e, d_model, f)) * std_in).astype(dtype),
        "wu": (jax.random.normal(ks[2], (e, d_model, f)) * std_in).astype(dtype),
        "wd": (jax.random.normal(ks[3], (e, f, d_model)) * std_out).astype(dtype),
    }
    if cfg.n_shared_experts > 0:
        from repro.models.mlp import init_mlp
        p["shared"] = init_mlp(ks[4], d_model, cfg.n_shared_experts * f, act, dtype)
    return p


# ---------------------------------------------------------------------------
# All-to-all dispatch expert parallelism (§Perf optimization B)
# ---------------------------------------------------------------------------
#
# The replicated-token EP above needs layer-boundary activations replicated
# along the model axis — the dry-run showed those all-gathers DOMINATE the
# collective term for MoE-heavy stacks (jamba train: ~143 GB/device/step).
# Production MoE systems route tokens with all-to-all instead: tokens stay
# sharded over (data × seq/model); each device sends only its routed tokens
# (t·k/M per peer) to the expert owners and receives them back — wire bytes
# drop from O(full activations × layers) to O(routed tokens × layers).


def _bucket_table(bucket_ids, n_buckets: int, capacity: int):
    """Sort-based dispatch: bucket_ids (N,) → table (n_buckets, capacity) of
    indices into N (sentinel N for empty/overflow slots)."""
    n = bucket_ids.shape[0]
    order = jnp.argsort(bucket_ids, stable=True)
    sorted_b = bucket_ids[order]
    starts = jnp.searchsorted(sorted_b, jnp.arange(n_buckets))
    pos = jnp.arange(n) - starts[sorted_b]
    ok = (pos < capacity) & (sorted_b >= 0) & (sorted_b < n_buckets)
    bi = jnp.where(ok, sorted_b, n_buckets)
    pi = jnp.where(ok, pos, capacity)
    return jnp.full((n_buckets, capacity), n, jnp.int32).at[bi, pi].set(
        order.astype(jnp.int32), mode="drop")


def _local_moe_a2a(x, router, wg, wu, wd, *, cfg: MoEConfig, act: str,
                   e_loc: int, model_axis: str, n_model: int, axes=()):
    """Per-device body; x: (B_loc, S_loc, d) — tokens sharded over data AND
    model (the seq-parallel boundary layout, no replication)."""
    b, s, d = x.shape
    t = b * s
    k = cfg.top_k
    xt = x.reshape(t, d)

    gates = jax.nn.softmax(xt.astype(jnp.float32) @ router.astype(jnp.float32))
    w, idx = jax.lax.top_k(gates, k)
    w = w / jnp.maximum(w.sum(-1, keepdims=True), 1e-9)
    flat_e = idx.reshape(-1)
    flat_w = w.reshape(-1)
    dest = flat_e // e_loc                                # target device
    c_out = max(8, -(-int(t * k / max(n_model, 1) * 1.5) // 8) * 8)

    table = _bucket_table(dest, n_model, c_out)           # (M, c_out) slots
    slot_ok = table < t * k
    tok = jnp.where(slot_ok, table // k, t)
    xpad = jnp.concatenate([xt, jnp.zeros((1, d), xt.dtype)])
    send_x = xpad[tok]                                    # (M, c_out, d)
    epad = jnp.concatenate([flat_e, jnp.full((1,), 0, flat_e.dtype)])
    wpad = jnp.concatenate([flat_w, jnp.zeros((1,), flat_w.dtype)])
    send_e = jnp.where(slot_ok, epad[jnp.minimum(table, t * k)] % e_loc, e_loc)
    send_w = jnp.where(slot_ok, wpad[jnp.minimum(table, t * k)], 0.0)

    recv_x = jax.lax.all_to_all(send_x, model_axis, 0, 0, tiled=True)
    recv_e = jax.lax.all_to_all(send_e, model_axis, 0, 0, tiled=True)
    recv_w = jax.lax.all_to_all(send_w, model_axis, 0, 0, tiled=True)

    n_recv = n_model * c_out
    rx = recv_x.reshape(n_recv, d)
    re = recv_e.reshape(n_recv)
    rw = recv_w.reshape(n_recv)

    # second-level (local, no comm) dispatch to this device's experts —
    # c_out is already over-provisioned 1.5×, so no extra factor here
    c2 = max(8, -(-int(n_recv / max(e_loc, 1)) // 8) * 8)
    c2 = min(c2, n_recv)
    table2 = _bucket_table(re, e_loc, c2)                 # (E_loc, c2)
    rxp = jnp.concatenate([rx, jnp.zeros((1, d), rx.dtype)])
    xe = rxp[jnp.minimum(table2, n_recv)]                 # (E_loc, c2, d)
    xe = jnp.where((table2 < n_recv)[..., None], xe, 0)
    if act in ("swiglu", "geglu"):
        h = act_fn(act)(jnp.einsum("ecd,edf->ecf", xe, wg)) * \
            jnp.einsum("ecd,edf->ecf", xe, wu)
    else:
        h = act_fn(act)(jnp.einsum("ecd,edf->ecf", xe, wu))
    ye = jnp.einsum("ecf,efd->ecd", h, wd)
    wtab = jnp.where(table2 < n_recv,
                     jnp.concatenate([rw, jnp.zeros(1)])[
                         jnp.minimum(table2, n_recv)], 0.0)
    ye = (ye.astype(jnp.float32) * wtab[..., None]).astype(x.dtype)

    # scatter back into recv slots, reverse a2a, combine at source
    back = jnp.zeros((n_recv + 1, d), x.dtype).at[
        jnp.minimum(table2, n_recv)].add(ye, mode="drop")[:n_recv]
    back = back.reshape(n_model, c_out, d)
    ret = jax.lax.all_to_all(back, model_axis, 0, 0, tiled=True)
    # tok: (M, c_out) source-token ids (sentinel t) ; ret: (M, c_out, d)
    y = jnp.zeros((t + 1, d), x.dtype).at[tok].add(ret)[:t]

    frac_routed = jnp.zeros((cfg.n_experts,), jnp.float32).at[flat_e].add(1.0) / (t * k)
    aux = cfg.n_experts * jnp.sum(frac_routed * gates.mean(0))
    aux = jax.lax.pmean(aux, axes)  # tokens sharded over data AND model
    return y.reshape(b, s, d), aux


def moe_ffn_a2a(x, params, cfg: MoEConfig, meshctx: MeshCtx, act: str):
    """All-to-all EP MoE.  x: (B, S, d) with S shardable over model."""
    msize = meshctx.model_size
    if msize <= 1 or cfg.n_experts % msize != 0 or x.shape[1] % msize != 0:
        return moe_ffn(x, params, cfg, meshctx, act)      # fallback
    e_loc = cfg.n_experts // msize
    batch_ax = meshctx.dim_axis(x.shape[0], meshctx.batch_axes)
    bspec = P(batch_ax, meshctx.model_axis, None)
    expert_spec = P(meshctx.model_axis, None, None)
    aux_axes = ((meshctx.batch_axes if batch_ax is not None else ())
                + (meshctx.model_axis,))
    body = functools.partial(
        _local_moe_a2a, cfg=cfg, act=act, e_loc=e_loc,
        model_axis=meshctx.model_axis, n_model=msize, axes=aux_axes)
    y, aux = jax.shard_map(
        body, mesh=meshctx.mesh,
        in_specs=(bspec, P(None, None), expert_spec, expert_spec, expert_spec),
        out_specs=(bspec, P()),
        check_vma=False,
    )(x, params["router"], params["wg"], params["wu"], params["wd"])
    if cfg.n_shared_experts > 0:
        from repro.models.mlp import mlp
        y = y + mlp(x, params["shared"], act)
    return y, aux


# ---------------------------------------------------------------------------
# Held-expert counters, as a serving cache carries them
# ---------------------------------------------------------------------------

COUNTERS = ("moe_rows", "moe_hits", "moe_dropped")


def cache_counters(cache):
    """The held-expert layers' counters in a prefill or decode cache: one
    ``{name: array}`` per layer pattern position (arrays stacked over its
    layers), running totals since the prefill.  Nothing is read from the
    device, so a caller may keep them past the cache's donation."""
    return [{k: entry[k] for k in COUNTERS}
            for stage in cache["stages"] for entry in (stage or ())
            if "moe_rows" in entry]


def fold_counters(tracer, counters) -> None:
    """Add ``cache_counters`` totals to ``tracer``'s counters ``moe.rows``
    (rows routed to held experts), ``moe.experts_hit`` (held experts with
    rows, summed over grouped calls) and ``moe.dropped`` (pairs routed to
    a held expert and not computed)."""
    for c in jax.device_get(counters):
        tracer.count("moe.rows", int(c["moe_rows"].sum()))
        tracer.count("moe.experts_hit", int(c["moe_hits"].sum()))
        tracer.count("moe.dropped", int(c["moe_dropped"].sum()))
