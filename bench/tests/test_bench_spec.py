"""``BENCHMARK.json`` against the benchmark's contract, and each
configuration file against the program's own configuration."""

import importlib
import json
import re

import pytest

from conftest import ROOT, load_json

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
BENCH = load_json("BENCHMARK.json")


def _line(s):
    return isinstance(s, str) and 1 <= len(s) <= 200 and "\n" not in s \
        and "\t" not in s


def test_top_level_keys_and_command():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= len(BENCH["command"]) <= 32
    assert all(_line(w) for w in BENCH["command"])
    for p in BENCH["paths"]:
        assert re.fullmatch(r"[A-Za-z0-9_./\-]{1,200}", p)
        assert not p.startswith("/") and ".." not in p
    script = BENCH["command"][1]
    assert any(script.startswith(p + "/") for p in BENCH["paths"])
    assert (ROOT / script).is_file()
    assert isinstance(BENCH["run_seconds"], int)
    assert 1 <= BENCH["run_seconds"] <= 51
    assert len(json.dumps(BENCH)) < 64 * 1024


def test_names_units_and_keys():
    names = set()
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and _line(c["why"])
        assert _line(c["source"]) and len(c["reduced"]) <= 16
        assert all(NAME.match(k) for k in c["reduced"])
        assert c["file"].startswith("bench/")
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and _line(w["why"])
        assert (ROOT / "bench" / "traffic" / f"{w['traffic']}.json").is_file()
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
        names.add(m["name"])
    assert len(names) == len(BENCH["end_to_end"]) + len(BENCH["per_layer"])
    cells = [w["name"] for w in BENCH["workloads"]]
    assert len(set(cells)) == len(cells)
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(set(pairs)) == len(pairs)


def test_metrics_are_reported_where_they_move():
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e
    cells = [w["name"] for w in BENCH["workloads"]]
    for m in BENCH["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
    for m in BENCH["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert _line(m["layer"]) and m["moves"] in e2e
        moved = e2e[m["moves"]].get("workloads", cells)
        assert set(m.get("workloads", cells)) <= set(moved)
        assert (ROOT / "bench" / "metrics" / f"{m['name']}.py").is_file()
        if m["name"].endswith("_roofline") or "mfu" in m["name"]:
            assert m["unit"] == "%"
    for cell in cells:
        got = [m for m in BENCH["end_to_end"]
               if cell in m.get("workloads", cells)]
        assert len(got) >= 2
        assert any(cell in m.get("workloads", cells)
                   for m in BENCH["per_layer"])


@pytest.mark.parametrize("entry", BENCH["configs"], ids=lambda c: c["name"])
def test_config_file_is_well_formed(entry):
    """What every configuration file holds, whatever its module."""
    from bench import models
    cfg = load_json(entry["file"])
    assert cfg["name"] == entry["name"]
    assert sorted(cfg["reduced"]) == sorted(entry["reduced"])
    assert cfg["source"] == entry["source"]
    assert {"assumed", "deployment", "precision"} <= set(cfg)
    model = models.load(cfg)
    assert cfg["correct_limits"]
    assert set(cfg["correct_limits"]) <= set(model.NUMBERS)


def test_a_config_without_a_model_is_refused():
    from bench import models
    cfg = load_json(BENCH["configs"][0]["file"])
    for bad in (None, "", "gemm_stream.reference", "../x"):
        broken = dict(cfg, model=bad)
        if bad is None:
            del broken["model"]
        with pytest.raises(ValueError, match="names no module"):
            models.load(broken)


GEMM_STREAM = [c for c in BENCH["configs"]
               if load_json(c["file"])["model"] == "gemm_stream"]


@pytest.mark.parametrize("entry", GEMM_STREAM, ids=lambda c: c["name"])
def test_config_widths_match_the_program(entry):
    from bench.models import gemm_stream
    cfg = load_json(entry["file"])
    gemm_stream.validate(cfg)
    # every run is held to a decode number (``required``)
    assert any(gemm_stream.NUMBERS[k] == "decode"
               for k in cfg["correct_limits"])
    for key, value in (("hidden_size", cfg["hidden_size"] + 8),
                       ("family", "ssm"), ("num_hidden_layers", 4)):
        with pytest.raises(ValueError, match=key):
            gemm_stream.validate(dict(cfg, **{key: value}))


@pytest.mark.parametrize("entry", GEMM_STREAM, ids=lambda c: c["name"])
def test_reference_stream_is_the_served_stream(entry, monkeypatch):
    """The reference's own stream has the program's shapes, names and
    draw order at both passes (built on the CPU, nothing runs)."""
    from bench.models import gemm_stream
    from bench.models.gemm_stream import reference
    cfg = load_json(entry["file"])
    gemm_stream.validate(cfg)
    stream = reference.stream
    monkeypatch.setattr(reference, "stream",
                        lambda *a: stream(*a)[:-1])
    with pytest.raises(ValueError, match="stream"):
        gemm_stream.validate(cfg)


def test_every_cell_has_a_config_and_mix_on_disk():
    configs = {c["name"] for c in BENCH["configs"]}
    for w in BENCH["workloads"]:
        assert w["config"] in configs
    used = {w["config"] for w in BENCH["workloads"]}
    assert used == configs
    files = [c["file"] for c in BENCH["configs"]]
    assert len(set(files)) == len(files)
    for f in files:
        assert (ROOT / f).is_file()
    assert importlib.import_module("bench.peaks")
