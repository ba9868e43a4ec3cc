"""Mamba-2 SSD (state-space duality) mixer.

Sequence mode uses the chunked matmul form (TPU-friendly: the intra-chunk
term is a masked batched GEMM for the MXU; the inter-chunk recurrence is a
short ``lax.scan`` over chunk states).  Decode mode is the O(1) recurrent
update.  ``repro.kernels.ssd_chunk`` implements the intra-chunk GEMM as a
Pallas kernel; this module is the jnp lowering/oracle path.

Factored-LoRA contract (the universal fused path): ``mamba_seq`` and
``mamba_decode`` take an optional ``lora`` side channel — a dict mirroring
the param leaves with ``{'a','b','mask'}`` factor dicts (``peft.init_lora``)
on ``in_proj`` and/or ``out_proj`` — plus ``scale`` (α/r) and ``backend``.
Targeted projections run ``peft.lora_proj``
(``y = x@W + scale·((x@A)@(mask·B))``) so the dense delta is never formed
and, under the cohort engine's client vmap, the frozen base stays UNBATCHED
while only the rank-r factors carry the client axis.  ``mamba_seq_sp`` (the
sequence-parallel shard_map path) deliberately does NOT take factors — its
in_specs replicate the raw weights — so ``blocks`` routes factored layers
through ``mamba_seq`` instead (``peft.has_factors`` gate).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import SSMConfig
from repro.models.norms import rmsnorm
from repro.models.peft import lora_proj


def _lf(lora, key):
    """One leaf's factor dict from the mixer side channel (None-safe)."""
    return None if lora is None else lora.get(key)


def segsum(a):
    """a: (..., L) → (..., L, L) with out[i,j] = sum_{k=j+1..i} a_k (i≥j),
    -inf above the diagonal."""
    L = a.shape[-1]
    cs = jnp.cumsum(a, axis=-1)
    diff = cs[..., :, None] - cs[..., None, :]
    mask = jnp.tril(jnp.ones((L, L), bool))
    return jnp.where(mask, diff, -jnp.inf)


def ssd_chunk_scan(x, dt, a_coef, b_mat, c_mat, chunk: int, h0=None):
    """Chunked SSD scan.

    x:     (B, S, H, P)   per-head inputs
    dt:    (B, S, H)      post-softplus step sizes
    a_coef:(H,)           negative decay coefficients (= -exp(A_log))
    b_mat: (B, S, H, N)   input projections (groups already broadcast)
    c_mat: (B, S, H, N)   output projections
    Returns y (B, S, H, P), h_final (B, H, P, N).
    """
    b, s, h, p = x.shape
    n = b_mat.shape[-1]
    chunk = min(chunk, s)
    s_orig = s
    if s % chunk:
        # pad with dt=0 positions: decay exp(0)=1, contribution dt·B·x = 0 —
        # state passes through unchanged, padded outputs are discarded.
        pad = chunk - s % chunk
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        b_mat = jnp.pad(b_mat, ((0, 0), (0, pad), (0, 0), (0, 0)))
        c_mat = jnp.pad(c_mat, ((0, 0), (0, pad), (0, 0), (0, 0)))
        s = s + pad
    nc = s // chunk

    def resh(t):
        return t.reshape(b, nc, chunk, *t.shape[2:]).swapaxes(0, 1)

    xc, dtc, bc, cc = resh(x), resh(dt), resh(b_mat), resh(c_mat)
    if h0 is None:
        h0 = jnp.zeros((b, h, p, n), jnp.float32)

    def step(hprev, inp):
        xk, dtk, bk, ck = inp                    # (b, L, h, ...)
        ad = (dtk.astype(jnp.float32)
              * a_coef.astype(jnp.float32)[None, None, :])   # (b, L, h)
        adt = ad.swapaxes(1, 2)                   # (b, h, L)
        cs = jnp.cumsum(adt, axis=-1)             # (b, h, L)
        # intra-chunk (masked attention-like term)
        ss = jnp.exp(segsum(adt))                 # (b, h, L, L)
        scores = jnp.einsum("blhn,bmhn->bhlm", ck.astype(jnp.float32),
                            bk.astype(jnp.float32))
        scores = scores * ss * dtk.astype(jnp.float32).transpose(0, 2, 1)[:, :, None, :]
        y_intra = jnp.einsum("bhlm,bmhp->blhp", scores, xk.astype(jnp.float32))
        # inter-chunk: contribution of carried state
        decay_in = jnp.exp(cs)                    # (b, h, L)
        y_inter = jnp.einsum("blhn,bhpn,bhl->blhp", ck.astype(jnp.float32),
                             hprev, decay_in)
        # state update
        total = cs[..., -1]                       # (b, h)
        decay_out = jnp.exp(total[..., None] - cs)            # (b, h, L)
        contrib = (bk.astype(jnp.float32)
                   * (dtk.astype(jnp.float32)
                      * decay_out.swapaxes(1, 2))[..., None])  # (b, L, h, n)
        hnew = (jnp.exp(total)[..., None, None] * hprev
                + jnp.einsum("blhn,blhp->bhpn", contrib, xk.astype(jnp.float32)))
        return hnew, (y_intra + y_inter)

    h_final, yc = jax.lax.scan(step, h0, (xc, dtc, bc, cc))
    y = yc.swapaxes(0, 1).reshape(b, s, h, p)[:, :s_orig]
    return y.astype(x.dtype), h_final


def ssd_decode_step(xt, dtt, a_coef, bt, ct, hprev):
    """Single-token recurrence.  xt: (B,H,P); dtt: (B,H); bt/ct: (B,H,N);
    hprev: (B,H,P,N) → (y (B,H,P), hnew)."""
    ad = jnp.exp(dtt.astype(jnp.float32) * a_coef[None, :])     # (B,H)
    hnew = (ad[..., None, None] * hprev
            + jnp.einsum("bhp,bhn,bh->bhpn", xt.astype(jnp.float32),
                         bt.astype(jnp.float32), dtt.astype(jnp.float32)))
    y = jnp.einsum("bhpn,bhn->bhp", hnew, ct.astype(jnp.float32))
    return y.astype(xt.dtype), hnew


# ---------------------------------------------------------------------------
# Full mamba2 mixer (projections + conv + SSD + gated norm)
# ---------------------------------------------------------------------------


def init_mamba(key, d_model: int, cfg: SSMConfig, dtype):
    d_in = cfg.expand * d_model
    h = d_in // cfg.headdim
    conv_dim = d_in + 2 * cfg.n_groups * cfg.state
    proj_out = 2 * d_in + 2 * cfg.n_groups * cfg.state + h
    ks = jax.random.split(key, 4)
    return {
        "in_proj": (jax.random.normal(ks[0], (d_model, proj_out))
                    * d_model ** -0.5).astype(dtype),
        "conv_w": (jax.random.normal(ks[1], (cfg.conv_width, conv_dim))
                   * cfg.conv_width ** -0.5).astype(dtype),
        "conv_b": jnp.zeros((conv_dim,), dtype),
        "a_log": jnp.zeros((h,), jnp.float32),
        "d_skip": jnp.ones((h,), jnp.float32),
        "dt_bias": jnp.zeros((h,), jnp.float32),
        "gate_norm": {"scale": jnp.zeros((d_in,), dtype)},
        "out_proj": (jax.random.normal(ks[2], (d_in, d_model))
                     * d_in ** -0.5).astype(dtype),
    }


def _causal_conv(xbc, w, bias):
    """Depthwise causal conv.  xbc: (B,S,C); w: (W,C)."""
    width = w.shape[0]
    pad = jnp.pad(xbc, ((0, 0), (width - 1, 0), (0, 0)))
    out = sum(pad[:, i:i + xbc.shape[1], :] * w[i][None, None]
              for i in range(width))
    return out + bias[None, None]


def _split_proj(zxbcdt, d_in, g_n, h):
    z = zxbcdt[..., :d_in]
    xbc = zxbcdt[..., d_in:d_in + d_in + 2 * g_n]
    dt_raw = zxbcdt[..., -h:]
    return z, xbc, dt_raw


def mamba_seq(x, p, cfg: SSMConfig, d_model: int, eps: float, h0=None,
              conv0=None, lora=None, scale: float = 1.0,
              backend: str = "jnp"):
    """Full-sequence mamba2 mixer.  Returns (y, (h_final, conv_state)).
    ``lora``/``scale``/``backend``: factored-LoRA side channel (module
    docstring) — in_proj/out_proj stay unmerged."""
    b, s, _ = x.shape
    d_in = cfg.expand * d_model
    h = d_in // cfg.headdim
    g_n = cfg.n_groups * cfg.state
    zxbcdt = lora_proj(x, p["in_proj"], _lf(lora, "in_proj"), scale=scale,
                       backend=backend)
    z, xbc, dt_raw = _split_proj(zxbcdt, d_in, g_n, h)
    if conv0 is not None:
        xbc_ext = jnp.concatenate([conv0, xbc], axis=1)
        conv_out = _causal_conv(xbc_ext, p["conv_w"], p["conv_b"])[:, conv0.shape[1]:]
    else:
        conv_out = _causal_conv(xbc, p["conv_w"], p["conv_b"])
    conv_state = jax.lax.dynamic_slice_in_dim(
        jnp.concatenate([jnp.zeros((b, cfg.conv_width - 1, xbc.shape[-1]),
                                   xbc.dtype), xbc], axis=1),
        s, cfg.conv_width - 1, axis=1)
    xbc = jax.nn.silu(conv_out)
    xs = xbc[..., :d_in].reshape(b, s, h, cfg.headdim)
    bmat = xbc[..., d_in:d_in + g_n].reshape(b, s, cfg.n_groups, cfg.state)
    cmat = xbc[..., d_in + g_n:].reshape(b, s, cfg.n_groups, cfg.state)
    rep = h // cfg.n_groups
    bmat = jnp.repeat(bmat, rep, axis=2)
    cmat = jnp.repeat(cmat, rep, axis=2)
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + p["dt_bias"][None, None])
    a_coef = -jnp.exp(p["a_log"])
    y, h_final = ssd_chunk_scan(xs, dt, a_coef, bmat, cmat, cfg.chunk, h0=h0)
    y = y + (p["d_skip"][None, None, :, None] * xs.astype(jnp.float32)).astype(y.dtype)
    y = y.reshape(b, s, d_in)
    y = rmsnorm((y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))).astype(x.dtype),
                p["gate_norm"]["scale"], eps)
    y = lora_proj(y, p["out_proj"], _lf(lora, "out_proj"), scale=scale,
                  backend=backend)
    return y, (h_final, conv_state)


def mamba_decode(x, p, cfg: SSMConfig, d_model: int, eps: float, h_state,
                 conv_state, lora=None, scale: float = 1.0,
                 backend: str = "jnp"):
    """Single-token mamba2 step.  x: (B,1,d).  Returns (y, (h, conv))."""
    b = x.shape[0]
    d_in = cfg.expand * d_model
    h = d_in // cfg.headdim
    g_n = cfg.n_groups * cfg.state
    zxbcdt = lora_proj(x[:, 0], p["in_proj"], _lf(lora, "in_proj"),
                       scale=scale, backend=backend)
    z = zxbcdt[..., :d_in]
    xbc_t = zxbcdt[..., d_in:d_in + d_in + 2 * g_n]
    dt_raw = zxbcdt[..., -h:]
    # conv ring: conv_state holds the previous (W-1) inputs
    window = jnp.concatenate([conv_state, xbc_t[:, None]], axis=1)  # (B,W,C)
    conv_out = jnp.einsum("bwc,wc->bc", window, p["conv_w"]) + p["conv_b"]
    new_conv = window[:, 1:]
    xbc = jax.nn.silu(conv_out)
    xs = xbc[..., :d_in].reshape(b, h, cfg.headdim)
    bmat = xbc[..., d_in:d_in + g_n].reshape(b, cfg.n_groups, cfg.state)
    cmat = xbc[..., d_in + g_n:].reshape(b, cfg.n_groups, cfg.state)
    rep = h // cfg.n_groups
    bmat = jnp.repeat(bmat, rep, axis=1)
    cmat = jnp.repeat(cmat, rep, axis=1)
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + p["dt_bias"][None])
    a_coef = -jnp.exp(p["a_log"])
    y, hnew = ssd_decode_step(xs, dt, a_coef, bmat, cmat, h_state)
    y = y + (p["d_skip"][None, :, None] * xs.astype(jnp.float32)).astype(y.dtype)
    y = y.reshape(b, d_in)
    y = rmsnorm((y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))).astype(x.dtype),
                p["gate_norm"]["scale"], eps)
    y = lora_proj(y, p["out_proj"], _lf(lora, "out_proj"), scale=scale,
                  backend=backend)
    return y[:, None], (hnew, new_conv)


# ---------------------------------------------------------------------------
# Sequence-parallel SSD (§Perf optimization B2 — recurrent-scan sharding)
# ---------------------------------------------------------------------------
#
# The TP layout forces every mamba layer to all-gather seq-sharded boundary
# activations before in_proj (and reduce them after) — the dominant
# collective for hybrid stacks.  But the SSD recurrence is associative: each
# device can scan its own sequence shard with h0=0, exchange only the tiny
# per-shard (decay, state) summaries (H·P·N floats), compute its incoming
# state with an exclusive prefix over devices, and add the linear correction
# term locally.  Activations stay seq-sharded through the entire layer; the
# only collectives are a (W-1)-token conv halo exchange and the state
# all-gather (~2 MB vs ~0.5 GB of activation gathers per layer).


def _sp_body(x, in_proj, conv_w, conv_b, a_log, d_skip, dt_bias, gate_scale,
             out_proj, *, cfg: SSMConfig, d_model: int, eps: float,
             model_axis: str, n_dev: int):
    b, s_loc, _ = x.shape
    d_in = cfg.expand * d_model
    h = d_in // cfg.headdim
    g_n = cfg.n_groups * cfg.state

    zxbcdt = x @ in_proj
    z, xbc, dt_raw = _split_proj(zxbcdt, d_in, g_n, h)

    # causal conv with halo from the previous device (ring shift)
    halo = xbc[:, -(cfg.conv_width - 1):, :]
    prev = jax.lax.ppermute(halo, model_axis,
                            [(i, i + 1) for i in range(n_dev - 1)])
    idx = jax.lax.axis_index(model_axis)
    prev = jnp.where(idx > 0, prev, jnp.zeros_like(prev))
    xbc_ext = jnp.concatenate([prev, xbc], axis=1)
    conv_out = _causal_conv(xbc_ext, conv_w, conv_b)[:, cfg.conv_width - 1:]
    xbc = jax.nn.silu(conv_out)

    xs = xbc[..., :d_in].reshape(b, s_loc, h, cfg.headdim)
    bmat = xbc[..., d_in:d_in + g_n].reshape(b, s_loc, cfg.n_groups, cfg.state)
    cmat = xbc[..., d_in + g_n:].reshape(b, s_loc, cfg.n_groups, cfg.state)
    rep = h // cfg.n_groups
    bmat = jnp.repeat(bmat, rep, axis=2)
    cmat = jnp.repeat(cmat, rep, axis=2)
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + dt_bias[None, None])
    a_coef = -jnp.exp(a_log)

    # local scan from zero state → y_local + this shard's state contribution
    y, s_dev = ssd_chunk_scan(xs, dt, a_coef, bmat, cmat, cfg.chunk)

    # cross-device exclusive prefix over (decay, state)
    cs_full = jnp.cumsum(dt * a_coef[None, None, :], axis=1)   # (B,S_loc,H)
    d_dev = jnp.exp(cs_full[:, -1])                            # (B,H)
    d_all = jax.lax.all_gather(d_dev, model_axis)              # (M,B,H)
    s_all = jax.lax.all_gather(s_dev, model_axis)              # (M,B,H,P,N)

    def pscan(carry, js):
        dj, sj = js
        out = carry
        return dj[..., None, None] * carry + sj, out
    _, h_in_all = jax.lax.scan(pscan,
                               jnp.zeros_like(s_dev), (d_all, s_all))
    h_in = h_in_all[idx]                                       # (B,H,P,N)

    # linear correction: contribution of the incoming state to local outputs
    y_corr = jnp.einsum("blhn,bhpn,blh->blhp", cmat.astype(jnp.float32),
                        h_in, jnp.exp(cs_full))
    y = (y.astype(jnp.float32) + y_corr)
    y = y + d_skip[None, None, :, None] * xs.astype(jnp.float32)
    y = y.reshape(b, s_loc, d_in).astype(x.dtype)
    y = rmsnorm((y.astype(jnp.float32)
                 * jax.nn.silu(z.astype(jnp.float32))).astype(x.dtype),
                gate_scale, eps)
    return y @ out_proj


def mamba_seq_sp(x, p, cfg: SSMConfig, d_model: int, eps: float, meshctx):
    """Sequence-parallel mamba2 mixer: x (B, S, d) with S sharded over the
    model axis.  Weights are gathered (small) — activations never are."""
    import functools
    from jax.sharding import PartitionSpec as P

    msize = meshctx.model_size
    if msize <= 1 or x.shape[1] % msize != 0:
        return mamba_seq(x, p, cfg, d_model, eps)[0]
    batch_ax = meshctx.dim_axis(x.shape[0], meshctx.batch_axes)
    bspec = P(batch_ax, meshctx.model_axis, None)
    body = functools.partial(_sp_body, cfg=cfg, d_model=d_model, eps=eps,
                             model_axis=meshctx.model_axis, n_dev=msize)
    rep = P(None, None)
    return jax.shard_map(
        body, mesh=meshctx.mesh,
        in_specs=(bspec, rep, rep, P(None), P(None), P(None), P(None),
                  P(None), rep),
        out_specs=bspec, check_vma=False,
    )(x, p["in_proj"], p["conv_w"], p["conv_b"], p["a_log"], p["d_skip"],
      p["dt_bias"], p["gate_norm"]["scale"], p["out_proj"])
