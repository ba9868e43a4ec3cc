"""Seeded random weights and client adapters, made by the benchmark.

Every leaf is named by its path in the program's parameter layout
(``stages/0/layers/0/mixer/wq``; layers of a stage are stacked on a
leading axis) and drawn from ``fold_in(key, crc32(path))``, so the values
depend only on the seed and the path. The program gets them nested in its
layout (``nest``); the plain reference reads the same flat dict. All of a
model's leaves are made on the device in one jitted call; clients' LoRA
factors are one vmapped draw over their ids.
"""
from __future__ import annotations

import zlib
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

LN_NOISE = 0.05     # layer-norm scales 1 + 0.05·N(0,1), biases 0.05·N(0,1)
SMALL_STD = 0.02    # embeddings, heads, LoRA B, adapter up-projection


def seed_streams(seed: int, n: int = 4):
    """``n`` independent 32-bit seeds from one benchmark seed of any size."""
    return [int(v) for v in np.random.SeedSequence(seed).generate_state(n)]


def jax_key(seed32: int):
    return jax.random.fold_in(jax.random.PRNGKey(seed32 & 0x7FFFFFFF),
                              seed32 >> 31)


def _leaf_key(key, path: str):
    return jax.random.fold_in(key, zlib.crc32(path.encode()) & 0x7FFFFFFF)


def model_leaves(cfg) -> Dict[str, Tuple[tuple, str, float]]:
    """path -> (shape, init, std) of the base model. ``init`` is
    ``normal`` (mean 0) or ``scale`` (mean 1)."""
    d = cfg["hidden_size"]
    L = cfg["num_hidden_layers"]
    dff = cfg["intermediate_size"]
    v = cfg["vocab_size"]
    out = {"embed": ((v, d), "normal", SMALL_STD),
           "pos_embed": ((cfg["max_position_embeddings"], d), "normal",
                         SMALL_STD),
           "final_norm/scale": ((d,), "scale", LN_NOISE),
           "final_norm/bias": ((d,), "normal", LN_NOISE)}
    if not cfg.get("tie_word_embeddings", False):
        out["lm_head"] = ((d, v), "normal", SMALL_STD)
    if cfg.get("num_labels"):
        out["cls_head"] = ((d, cfg["num_labels"]), "normal", SMALL_STD)
    lp = "stages/0/layers/0/"
    for n in ("norm1", "norm2"):
        out[lp + n + "/scale"] = ((L, d), "scale", LN_NOISE)
        out[lp + n + "/bias"] = ((L, d), "normal", LN_NOISE)
    for w in ("wq", "wk", "wv", "wo"):
        out[lp + "mixer/" + w] = ((L, d, d), "normal", d ** -0.5)
    out[lp + "ff/wu"] = ((L, d, dff), "normal", d ** -0.5)
    out[lp + "ff/wd"] = ((L, dff, d), "normal", dff ** -0.5)
    peft = cfg.get("peft", {})
    if peft.get("adapters"):
        m = peft["adapter_dim"]
        out[lp + "adapter/wd"] = ((L, d, m), "normal", d ** -0.5)
        out[lp + "adapter/wu"] = ((L, m, d), "normal", SMALL_STD)
    return out


def _draw(key, path, shape, init, std, dtype):
    x = jax.random.normal(_leaf_key(key, path), shape, jnp.float32) * std
    if init == "scale":
        x = x + 1.0
    return x.astype(dtype)


def make_params(cfg, key, dtype=jnp.float32) -> Dict[str, jax.Array]:
    """The base model's flat path -> array dict, in one jitted call."""
    leaves = model_leaves(cfg)

    def build(k):
        return {p: _draw(k, p, *spec, dtype) for p, spec in leaves.items()}

    return jax.jit(build)(key)


def lora_leaves(cfg) -> Dict[str, Tuple[tuple, tuple]]:
    """Target weight path -> (A shape, B shape) of one client's LoRA."""
    r = cfg["peft"]["lora_rank"]
    L = cfg["num_hidden_layers"]
    d = cfg["hidden_size"]
    return {"stages/0/layers/0/" + t: ((L, d, r), (L, r, d))
            for t in cfg["peft"]["lora_targets"]}


def client_lora(key, cid, cfg, dtype=jnp.float32):
    """One client's flat ``<target>/{a,b,mask}`` factors, drawn from
    ``fold_in(key, cid)``."""
    L = cfg["num_hidden_layers"]
    kc = jax.random.fold_in(key, cid)
    out = {}
    for path, (sa, sb) in lora_leaves(cfg).items():
        out[path + "/a"] = (jax.random.normal(_leaf_key(kc, path + "/a"), sa)
                            * sa[-2] ** -0.5).astype(dtype)
        out[path + "/b"] = (jax.random.normal(_leaf_key(kc, path + "/b"), sb)
                            * SMALL_STD).astype(dtype)
        out[path + "/mask"] = jnp.ones((L, 1, 1), dtype)
    return out


def make_lora(cfg, key, client_ids, dtype=jnp.float32):
    """Flat ``<target>/{a,b,mask}`` dict of the given clients' LoRA
    factors, stacked on a leading client axis: ``client_lora`` vmapped
    over the ids, op by op as the program's ``stacked_client_init`` runs
    it, so that both give the same bits. B is drawn too (not zero), as a
    trained client's is, so that every factor moves the output from the
    first step."""
    ids = jnp.asarray(np.asarray(client_ids, np.int32))
    return jax.vmap(lambda c: client_lora(key, c, cfg, dtype))(ids)


def freeze(x):
    """Hashable form of a JSON config (a static argument of a jit)."""
    if isinstance(x, dict):
        return ("__dict__",) + tuple((k, freeze(v))
                                     for k, v in sorted(x.items()))
    if isinstance(x, list):
        return tuple(freeze(v) for v in x)
    return x


def thaw(x):
    """The JSON config ``freeze`` was given."""
    if isinstance(x, tuple) and x[:1] == ("__dict__",):
        return {k: thaw(v) for k, v in x[1:]}
    if isinstance(x, tuple):
        return [thaw(v) for v in x]
    return x


def nest(flat: Dict[str, object]):
    """Flat ``a/0/b`` paths -> nested dicts, with lists where a path
    component is an index."""
    root: dict = {}
    for path, v in flat.items():
        parts = path.split("/")
        node = root
        for p in parts[:-1]:
            node = node.setdefault(p, {})
        node[parts[-1]] = v

    def fix(node):
        if not isinstance(node, dict):
            return node
        if node and all(k.isdigit() for k in node):
            return [fix(node[str(i)]) for i in range(len(node))]
        return {k: fix(v) for k, v in node.items()}

    return fix(root)


def mirror(tree, fn, path: str = ""):
    """Map ``fn(path, leaf) -> value or None`` over a nested dict/list
    tree, keeping its structure (the program's select/mirror layouts hold
    ``None`` where a leaf is not part of a subtree)."""
    if isinstance(tree, dict):
        return {k: mirror(v, fn, f"{path}/{k}" if path else k)
                for k, v in tree.items()}
    if isinstance(tree, list):
        return [mirror(v, fn, f"{path}/{i}" if path else str(i))
                for i, v in enumerate(tree)]
    return fn(path, tree)


def flatten(tree, path: str = "") -> Dict[str, object]:
    """Nested dict/list tree -> flat path dict, skipping ``None``."""
    out: Dict[str, object] = {}
    if isinstance(tree, dict):
        items = tree.items()
    elif isinstance(tree, (list, tuple)):
        items = ((str(i), v) for i, v in enumerate(tree))
    else:
        if tree is not None:
            out[path] = tree
        return out
    for k, v in items:
        out.update(flatten(v, f"{path}/{k}" if path else k))
    return out
