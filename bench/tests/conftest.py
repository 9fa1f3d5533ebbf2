"""A cell small enough for the CPU: the served path at reduced widths
(``configs.base.reduced``), with configuration and mix files of the
same widths, so the harness and its check run end to end in interpret
mode."""

from __future__ import annotations

import json
import pathlib
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

#: granite3moe at the widths ``reduced(..., d_model=64, vocab=512)`` runs
TINY_WIDTHS = {"hidden_size": 64, "num_attention_heads": 4,
               "num_key_value_heads": 4, "head_dim": 16,
               "intermediate_size": 128, "num_local_experts": 8,
               "num_experts_per_tok": 2, "vocab_size": 512,
               "prefill_chunk": 16}


def load_json(rel: str) -> dict:
    return json.loads((ROOT / rel).read_text())


#: minitron4b at the widths the same reduction runs
TINY_DENSE_WIDTHS = {"hidden_size": 64, "num_attention_heads": 4,
                     "num_key_value_heads": 4, "head_dim": 16,
                     "intermediate_size": 256, "vocab_size": 512,
                     "prefill_chunk": 16}


def _tiny(monkeypatch, name, config, mix, widths):
    from repro.configs import registry
    from repro.configs.base import reduced

    real = registry.get_config
    monkeypatch.setattr(registry, "get_config", lambda arch: reduced(
        real(arch), layers=1, d_model=64, vocab=512))
    config = dict(load_json(f"bench/configs/{config}.json"), **widths)
    # short outputs, so sessions finish and are replaced inside the window
    mix = dict(load_json(f"bench/traffic/{mix}.json"), kv_extent=32,
               prompt_tokens={"dist": "fixed", "value": 16},
               output_tokens={"dist": "uniform", "min": 3, "max": 9})
    return {"name": name, "chips": 1, "config": config, "mix": mix}


@pytest.fixture
def tiny_spec(monkeypatch):
    return _tiny(monkeypatch, "granite3moe.decode", "granite3moe",
                 "long_decode4", TINY_WIDTHS)


@pytest.fixture
def tiny_dense_spec(monkeypatch):
    """minitron4b.decode's cell, held to its own number."""
    return _tiny(monkeypatch, "minitron4b.decode", "minitron4b",
                 "long_decode", TINY_DENSE_WIDTHS)


@pytest.fixture
def bench_spec():
    return load_json("BENCHMARK.json")
