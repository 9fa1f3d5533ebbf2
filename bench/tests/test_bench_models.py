"""A configuration is added as new files only: the shared harness
drives a toy configuration's module (``toy_decoder.py``, of a family,
depth and compared number that the GEMM stream refuses) end to end and
decides ``correct`` by the module's own check; and the harness's core
reaches a configuration's module only through ``bench.models``."""

import ast
import sys
import time

import pytest

from conftest import ROOT, load_json

MIX = {"loop": "closed", "sessions": 2,
       "prompt_tokens": {"dist": "fixed", "value": 6},
       "output_tokens": {"dist": "uniform", "min": 3, "max": 9},
       "max_concurrent": 2}


@pytest.fixture
def toy_spec(monkeypatch):
    import toy_decoder
    monkeypatch.setitem(sys.modules, "bench.models.toy_decoder",
                        toy_decoder)
    return {"name": "toy.decode", "chips": 1,
            "config": load_json("bench/tests/toy_decoder.json"),
            "mix": dict(MIX)}


def _run(spec, seed=2**31 + 7):
    import jax

    from bench import harness
    return harness.run(spec, load_json("BENCHMARK.json"), seed=seed,
                       seconds=1.0, trace=False, devices=jax.devices()[:1],
                       t_start=time.perf_counter())


def test_a_toy_configuration_runs_through_the_harness(toy_spec):
    from bench.models import gemm_stream
    from bench.models.gemm_stream import reference
    config = toy_spec["config"]
    assert config["num_hidden_layers"] == 4
    with pytest.raises(ValueError, match="no reference stream"):
        reference.stream(dict(config, num_attention_heads=4,
                              num_key_value_heads=4, hidden_act="silu"),
                         "decode", 32)
    result, compared = _run(toy_spec)
    assert set(compared) == {"logits_rel_err"}
    assert result["correct"], compared
    assert 0 < compared["logits_rel_err"]["value"] < 1e-5
    assert set(result["metrics"]) == {"tpot_p95_ms", "output_tokens_per_s",
                                      "setup_s"}
    assert result["attempted"] > 2 and result["failed"] == 0
    assert set(gemm_stream.NUMBERS).isdisjoint(compared)


def test_a_toy_run_with_an_altered_output_is_not_correct(toy_spec,
                                                         monkeypatch):
    import toy_decoder
    step = toy_decoder._step

    def altered(*args, **kw):
        h, logits = step(*args, **kw)
        return h, logits.at[0, 0].add(1.0)
    monkeypatch.setattr(toy_decoder, "_step", altered)
    result, compared = _run(toy_spec)
    assert result["correct"] is False, compared
    assert compared["logits_rel_err"]["value"] > 1e-2


CORE = ["harness.py", "layers.py", "control.py", "run.py"]
STAND_IN = {"bench.reference", "bench.correctness", "bench.cell",
            "bench.models.gemm_stream"}


def _imports(path):
    names = set()
    for node in ast.walk(ast.parse(path.read_text())):
        if isinstance(node, ast.Import):
            names |= {a.name for a in node.names}
        elif isinstance(node, ast.ImportFrom) and node.module:
            names.add(node.module)
            names |= {f"{node.module}.{a.name}" for a in node.names}
    return names


@pytest.mark.parametrize("name", CORE)
def test_the_core_imports_no_configuration_module(name):
    names = _imports(ROOT / "bench" / name)
    assert names, name
    assert not {n for n in names for s in STAND_IN
                if n == s or n.startswith(s + ".")}, names
