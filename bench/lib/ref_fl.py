"""Plain reference of the first rounds of a PFTT population run.

It restates, client by client and step by step, what the timed path is
meant to compute, from the seed alone:

- the cohort: ``RandomState(sampler seed).choice(N, K, replace=False)``,
  sorted, one draw per round (the uniform sampler);
- the uplink: per round ``RandomState(channel seed).exponential(1, N)``
  fading draws; a client whose SNR (mean SNR x draw, in dB) is below the
  outage threshold is not aggregated;
- each sampled client starts from the server's global adapters and head,
  with its own LoRA factors and optimizer state, and takes its local
  AdamW steps on its own batches (``lib/data.py``);
- the server takes the plain mean of the delivered clients' adapters and
  head (all weights 1: no faults, no staleness), and every sampled client
  receives it; with nothing delivered the clients keep their own values
  and the global stays;
- the pending payload of a client is its trained upload, before the
  aggregation;
- each sampled client's held-out rows are scored after the round.

Trainable paths follow the program's layout (``shared/...`` uploaded,
``local/lora/...`` kept), so each can be compared leaf by leaf.
"""
from __future__ import annotations

import functools
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from lib import data, ref_models, weights

SHARED, LORA = "shared/", "local/lora/"


def is_shared(cfg, path: str) -> bool:
    return "/adapter/" in path or path == "cls_head"


def adamw(tr, opt, g, *, lr, b1, b2, eps, dtype):
    """One AdamW step (no weight decay) on flat dicts; enable-mask leaves
    keep their value."""
    step = opt["step"] + 1
    c1 = 1.0 - b1 ** step
    c2 = 1.0 - b2 ** step
    mu, nu, out = {}, {}, {}
    for k, p in tr.items():
        gk = g[k].astype(dtype)
        mu[k] = (b1 * opt["mu"][k] + (1 - b1) * gk).astype(dtype)
        nu[k] = (b2 * opt["nu"][k] + (1 - b2) * gk * gk).astype(dtype)
        upd = lr * (mu[k] / c1) / (jnp.sqrt(nu[k] / c2) + eps)
        out[k] = p if k.endswith("/mask") else (p - upd).astype(dtype)
    return out, {"mu": mu, "nu": nu, "step": step}


@functools.partial(jax.jit, static_argnames=("cfg_items", "dtype"))
def _client_step(base, tr, opt, tokens, labels, *, cfg_items, dtype):
    cfg = weights.thaw(cfg_items)
    peft = cfg["peft"]
    scale = peft["lora_alpha"] / peft["lora_rank"]
    opt_cfg = cfg["optimizer"]

    def loss_fn(t):
        P = dict(base)
        lora = {}
        for k, v in t.items():
            if k.startswith(SHARED):
                P[k[len(SHARED):]] = v
            else:
                lora[k[len(LORA):]] = v
        return ref_models.cls_loss(P, lora, tokens, labels, cfg,
                                   scale=scale, dtype=dtype)

    loss, g = jax.value_and_grad(loss_fn)(tr)
    tr, opt = adamw(tr, opt, g, lr=opt_cfg["lr"], b1=opt_cfg["b1"],
                    b2=opt_cfg["b2"], eps=opt_cfg["eps"], dtype=dtype)
    return tr, opt, loss


@functools.partial(jax.jit, static_argnames=("cfg_items", "dtype"))
def _client_predict(base, tr, tokens, *, cfg_items, dtype):
    cfg = weights.thaw(cfg_items)
    peft = cfg["peft"]
    P = dict(base)
    lora = {}
    for k, v in tr.items():
        if k.startswith(SHARED):
            P[k[len(SHARED):]] = v
        else:
            lora[k[len(LORA):]] = v
    return ref_models.cls_predict(P, lora, tokens, cfg,
                                  scale=peft["lora_alpha"] / peft["lora_rank"],
                                  dtype=dtype)


def reference_rounds(cfg, traffic, streams: Dict[str, int], n_rounds: int,
                     dtype=jnp.float32) -> Dict:
    """Run ``n_rounds`` rounds from the seed streams. Returns per round the
    cohort ``ids``, ``losses`` (K, steps), delivery weights ``agg_w`` and
    ``eval_correct`` (K,); ``mu1`` (client -> flat first moments after
    round 0); ``rows0`` (client -> flat trainable as first sampled);
    after the last round ``rows`` (client -> flat trainable), ``pending``
    (client -> flat upload) and ``global``; and ``shared0``. ``dtype`` is
    the storage and compute type (bfloat16 for the control)."""
    N, K = traffic["population"], traffic["cohort"]
    steps, B, S = traffic["local_steps"], traffic["batch"], traffic["seq"]
    V, C = cfg["vocab_size"], cfg["num_labels"]
    items = weights.freeze(cfg)
    base = weights.make_params(cfg, weights.jax_key(streams["weights"]))
    shared0 = {SHARED + k: v for k, v in base.items() if is_shared(cfg, k)}
    base = {k: v.astype(dtype) for k, v in base.items()}
    glob = {k: v.astype(dtype) for k, v in shared0.items()}
    sampler = np.random.RandomState(streams["sampler"])
    channel = np.random.RandomState(streams["channel"])
    snr_lin = 10 ** (traffic["snr_db"] / 10.0)
    clients: Dict[int, Dict] = {}
    out = {"ids": [], "losses": [], "agg_w": [], "eval_correct": [],
           "mu1": {}, "rows0": {}, "shared0": {k: np.asarray(v) for k, v in
                                  shared0.items()}}
    pending: Dict[int, Dict] = {}
    for rnd in range(n_rounds):
        ids = np.sort(sampler.choice(N, size=K, replace=False))
        gains = channel.exponential(1.0, size=N)
        snr_db = 10 * np.log10(np.maximum(snr_lin * gains, 1e-12))
        w = (snr_db >= traffic["outage_snr_db"]).astype(np.float32)[ids]
        trained, losses = [], []
        for cid in ids:
            cid = int(cid)
            st = clients.get(cid)
            if st is None:
                lora = weights.make_lora(cfg, weights.jax_key(
                    streams["clients"]), [cid])
                tr = {LORA + k: v[0].astype(dtype) for k, v in lora.items()}
                tr.update(glob)
                zeros = {k: jnp.zeros(v.shape, dtype) for k, v in tr.items()}
                st = {"tr": tr, "opt": {"mu": zeros, "nu": dict(zeros),
                                        "step": jnp.zeros((), jnp.int32)}}
                out["rows0"][cid] = {k: np.asarray(v, np.float32)
                                     for k, v in tr.items()}
            tr = dict(st["tr"], **glob)
            opt = st["opt"]
            toks, labels = data.train_batches(
                streams["data"], cid, rnd, steps=steps, batch=B, seq=S,
                vocab=V, n_labels=C)
            ls = []
            for s in range(steps):
                tr, opt, loss = _client_step(base, tr, opt, toks[s],
                                             labels[s], cfg_items=items,
                                             dtype=dtype)
                ls.append(loss)
            clients[cid] = {"tr": tr, "opt": opt}
            trained.append(tr)
            losses.append(jnp.stack(ls))
            if rnd == 0:
                out["mu1"][cid] = {k: np.asarray(v, np.float32)
                                   for k, v in opt["mu"].items()}
        for cid, tr in zip(ids, trained):
            pending[int(cid)] = {k: v for k, v in tr.items()
                                 if k.startswith(SHARED)}
        if w.sum() > 0:
            wn = w / w.sum()
            glob = {k: sum(float(wn[i]) * trained[i][k].astype(jnp.float32)
                           for i in range(K)).astype(dtype)
                    for k in glob}
            for cid in ids:
                clients[int(cid)]["tr"] = dict(clients[int(cid)]["tr"],
                                               **glob)
        correct = []
        for cid in ids:
            toks, labels = data.test_rows(
                streams["data"], int(cid), rows=traffic["eval_rows"], seq=S,
                vocab=V, n_labels=C)
            pred = _client_predict(base, clients[int(cid)]["tr"], toks,
                                   cfg_items=items, dtype=dtype)
            correct.append(int((np.asarray(pred) == labels).sum()))
        out["ids"].append(ids)
        out["losses"].append(np.asarray(jnp.stack(losses), np.float32))
        out["agg_w"].append(w)
        out["eval_correct"].append(np.asarray(correct))
    out["rows"] = {c: {k: np.asarray(v, np.float32)
                       for k, v in st["tr"].items()}
                   for c, st in clients.items()}
    out["pending"] = {c: {k: np.asarray(v, np.float32) for k, v in p.items()}
                      for c, p in pending.items()}
    out["global"] = {k: np.asarray(v, np.float32) for k, v in glob.items()}
    return out
