"""The harness's one adapter to the program's model registry (the plain
references never import this module)."""
from __future__ import annotations

import dataclasses


def program_config(cfg):
    """The program's registered ``ModelConfig`` for ``cfg["program_config"]``
    with the sizes that ``cfg`` states (the registered sizes themselves at
    published widths; smaller ones in the CPU tests)."""
    from repro.configs import get_config
    m = get_config(cfg["program_config"])
    L = cfg["num_hidden_layers"]
    return dataclasses.replace(
        m, d_model=cfg["hidden_size"], n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_attention_heads"],
        head_dim=cfg["hidden_size"] // cfg["num_attention_heads"],
        d_ff=cfg["intermediate_size"], vocab_size=cfg["vocab_size"],
        n_classes=cfg.get("num_labels", 0), norm_eps=cfg["layer_norm_eps"],
        max_position=cfg["max_position_embeddings"],
        stages=tuple(dataclasses.replace(st, repeats=L) for st in m.stages))


def check_layout(flat, want_tree):
    """Raise where the benchmark's parameter paths differ from the
    program's (``want_tree``: the program's own init, as shapes)."""
    from lib import weights
    want = weights.flatten(want_tree)
    if set(want) != set(flat):
        raise ValueError(f"parameter layout differs from the program's: "
                         f"{sorted(set(want) ^ set(flat))}")
