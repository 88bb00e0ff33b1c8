"""BENCHMARK.json against the benchmark's contract, and each configuration
file against the program's published configuration."""
import json
import re
from pathlib import Path

import pytest

from bench import archs, harness

ROOT = Path(__file__).resolve().parents[2]
MANIFEST = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
ONE_LINE = re.compile(r"^[^\n\t]{1,200}$")


def test_top_level_keys():
    assert set(MANIFEST) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert 1 <= MANIFEST["run_seconds"] <= 51
    assert len((ROOT / "BENCHMARK.json").read_bytes()) <= 64 * 1024
    for p in MANIFEST["paths"]:
        assert re.fullmatch(r"[A-Za-z0-9_./-]{1,200}", p) and ".." not in p
    for word in MANIFEST["command"]:
        assert ONE_LINE.match(word) and not word.startswith("/")


@pytest.mark.parametrize("section", ["configs", "workloads", "end_to_end",
                                     "per_layer"])
def test_names_and_units(section):
    entries = MANIFEST[section]
    names = [e["name"] for e in entries]
    assert len(names) == len(set(names))
    for e in entries:
        assert NAME.match(e["name"]), e["name"]
        if "unit" in e:
            assert UNIT.match(e["unit"]), e["unit"]
            assert e["better"] in ("lower", "higher")
        for k in ("why", "layer", "source"):
            if k in e and section != "end_to_end" and section != "per_layer":
                assert ONE_LINE.match(e[k]), (e["name"], k)
        if section in ("configs", "workloads"):
            assert ONE_LINE.match(e["why"])


def test_cells_and_configs():
    configs = {c["name"]: c for c in MANIFEST["configs"]}
    used = set()
    pairs = set()
    for w in MANIFEST["workloads"]:
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert NAME.match(w["traffic"])
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
        used.add(w["config"])
        # every cell's data files exist, found by name
        harness.load_cell(w["name"])
    assert used == set(configs)
    assert len({c["file"] for c in configs.values()}) == len(configs)


def test_every_cell_reports_setup_an_end_to_end_and_a_layer_metric():
    e2e = MANIFEST["end_to_end"]
    assert any(m["name"] == "setup_s" for m in e2e)
    for m in e2e:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0 < m["bound"] <= 0.25
    for w in MANIFEST["workloads"]:
        cell = harness.load_cell(w["name"])
        names = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in names and len(names) >= 2
        assert cell.per_layer


def test_per_layer_metric_moves_an_end_to_end_metric_its_cells_report():
    e2e = {m["name"]: m for m in MANIFEST["end_to_end"]}
    layers = {}
    for m in MANIFEST["per_layer"]:
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert m["moves"] in e2e
        assert ONE_LINE.match(m["layer"])
        layers.setdefault(m["layer"], []).append(m["name"])
        assert (ROOT / "bench" / "metrics" / f"{m['name']}.py").is_file()
        for cell in m.get("workloads", []):
            reported = {x["name"] for x in harness.load_cell(cell).end_to_end}
            assert m["moves"] in reported, (m["name"], cell)


@pytest.mark.parametrize("entry", MANIFEST["configs"], ids=lambda c: c["name"])
def test_config_file_is_the_published_config_but_for_reduced(entry):
    from repro.configs import get_config
    cfile = json.loads((ROOT / entry["file"]).read_text())
    assert cfile["source"] == entry["source"]
    assert sorted(cfile["reduced"]) == sorted(entry["reduced"])
    built = harness.model_config(cfile)
    published = get_config(cfile["arch"])
    hf_keys = archs.for_config(cfile).HF_KEYS
    for key, (fld, _) in hf_keys.items():
        if key in entry["reduced"]:
            assert cfile["model"][key] == cfile["reduced"][key]["here"]
            assert getattr(published, fld) == cfile["reduced"][key]["published"]
        else:
            assert getattr(built, fld) == getattr(published, fld), key
    changed = {f for f in published.__dataclass_fields__
               if getattr(built, f) != getattr(published, f)}
    assert changed == {hf_keys[k][0] for k in entry["reduced"]}
