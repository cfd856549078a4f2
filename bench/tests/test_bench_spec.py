"""BENCHMARK.json keeps to the benchmark's contract, and every file it names
by name exists."""

from __future__ import annotations

import json
import re

import pytest

from bench import harness
from bench.traffic import generator

SPEC = harness.load_spec()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_./-]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
TOP_KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"}
ENTRY_KEYS = {
    "configs": {"name", "source", "file", "reduced", "why"},
    "workloads": {"name", "config", "traffic", "chips", "why"},
    "end_to_end": {"name", "unit", "better", "bound", "source"},
    "per_layer": {"name", "unit", "better", "source", "layer", "moves"},
}


def _line(text: str) -> bool:
    return 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_keys_and_sizes():
    assert set(SPEC) == TOP_KEYS
    assert len((harness.ROOT / "BENCHMARK.json").read_bytes()) <= 64 * 1024
    assert isinstance(SPEC["run_seconds"], int) and 1 <= SPEC["run_seconds"] <= 51
    assert 1 <= len(SPEC["paths"]) <= 16
    for p in SPEC["paths"]:
        assert PATH.match(p) and not p.startswith("/") and ".." not in p.split("/")
    assert len(SPEC["command"]) <= 32 and all(_line(w) for w in SPEC["command"])
    for group, keys in ENTRY_KEYS.items():
        for e in SPEC[group]:
            extra = {"workloads"} if group in ("end_to_end", "per_layer") else set()
            assert keys <= set(e) <= keys | extra, (group, e)


@pytest.mark.parametrize("group", sorted(ENTRY_KEYS))
def test_names_and_units_use_allowed_characters(group):
    names = [e["name"] for e in SPEC[group]]
    assert len(names) == len(set(names))
    for e in SPEC[group]:
        assert NAME.match(e["name"]), e["name"]
        if "unit" in e:
            assert UNIT.match(e["unit"]), e["unit"]
            assert e["better"] in ("lower", "higher")
            assert e["source"] in SOURCES
        for key in ("why", "layer"):
            if key in e:
                assert _line(e[key]), (e["name"], key)
        if group == "configs":
            assert _line(e["source"])
        for key in ("config", "traffic"):
            if key in e:
                assert NAME.match(e[key])
        for key in e.get("reduced", []):
            assert NAME.match(key)


def test_cells_and_configs():
    configs = {c["name"]: c for c in SPEC["configs"]}
    used = {w["config"] for w in SPEC["workloads"]}
    assert used == set(configs)
    assert 1 <= len(SPEC["workloads"]) <= 24
    pairs = [(w["config"], w["traffic"]) for w in SPEC["workloads"]]
    assert len(pairs) == len(set(pairs))
    assert sum(w["chips"] == 4 for w in SPEC["workloads"]) <= max(
        1, len(SPEC["workloads"]) // 2)
    for c in configs.values():
        assert c["file"].startswith(tuple(p + "/" for p in SPEC["paths"]))
        assert (harness.ROOT / c["file"]).is_file()
        assert c["file"] == f"bench/configs/{c['name']}.json"
        assert c["reduced"] == json.loads((harness.ROOT / c["file"]).read_text())["reduced"]
    for w in SPEC["workloads"]:
        assert w["chips"] in (1, 4)
        generator.load(w["traffic"])
        assert (harness.BENCH / "limits" / f"{w['name']}.json").is_file()


def test_every_metric_has_a_reader_and_every_cell_reports_enough():
    e2e = {m["name"]: m for m in SPEC["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for m in SPEC["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert harness.reader_path(harness.BENCH / "metrics", m["name"]).is_file()
    layers = {}
    for m in SPEC["per_layer"]:
        assert m["moves"] in e2e
        layers.setdefault(m["name"].split(".")[0], set()).add(m["layer"])
        for cell in m.get("workloads", []):
            assert cell in e2e[m["moves"]].get("workloads", [cell])
    for w in SPEC["workloads"]:
        name = w["name"]
        reported = [m["name"] for m in harness.cell_metrics(SPEC, name, False)]
        assert "setup_s" in reported and len(reported) >= 2, name
        assert harness.cell_metrics(SPEC, name, True), name
    # a quantity split by the metric it moves stays in one layer
    assert all(len(v) == 1 for v in layers.values()), layers


def test_shares_have_a_step_mfu_beside_them():
    for m in SPEC["per_layer"]:
        if m["name"].split(".")[0].endswith("_roofline"):
            assert m["unit"] == "%"
            mfu = [x for x in SPEC["per_layer"] if "mfu" in x["name"]
                   and x["moves"] == m["moves"]]
            assert mfu and set(m["workloads"]) <= set(mfu[0]["workloads"])
