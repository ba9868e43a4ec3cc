"""Shared set-up of the benchmark's own tests (CPU, reduced sizes).

    JAX_PLATFORMS=cpu python3 -m pytest bench/tests -q

They import the harness from ``bench/`` and the program from ``src/``."""
import json
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))


def _tiny(cfg: dict) -> dict:
    cfg = dict(cfg)
    cfg.update(hidden_size=64, num_hidden_layers=2, num_attention_heads=4,
               intermediate_size=128, vocab_size=512,
               max_position_embeddings=64)
    return cfg


@pytest.fixture
def tiny_roberta():
    return _tiny(json.loads((BENCH / "configs" / "roberta-base.json")
                            .read_text()))


@pytest.fixture
def tiny_gpt2():
    return _tiny(json.loads((BENCH / "configs" / "gpt2-small.json")
                            .read_text()))


def tiny_traffic(name: str, **over) -> dict:
    tr = json.loads((BENCH / "traffic" / f"{name}.json").read_text())
    tr.update(over)
    return tr
