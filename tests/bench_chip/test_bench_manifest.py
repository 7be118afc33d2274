"""BENCHMARK.json against the benchmark's contract, and every file a cell
needs found by name."""
import json
import re

import pytest

from benchmarks.chip import run as bench

ROOT = bench.ROOT
HERE = bench.HERE
MANIFEST = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}")
UNIT = re.compile(r"[A-Za-z0-9_/%.\-]{1,16}")
PATH = re.compile(r"[A-Za-z0-9_./\-]{1,200}")
KEYS = {
    "top": {"command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer"},
    "config": {"name", "source", "file", "reduced", "why"},
    "workload": {"name", "config", "traffic", "chips", "why"},
    "end_to_end": {"name", "unit", "better", "bound", "source"},
    "per_layer": {"name", "unit", "better", "source", "layer", "moves"},
}
CELLS = [w["name"] for w in MANIFEST["workloads"]]


def _text(s):
    return isinstance(s, str) and 1 <= len(s) <= 200 and "\n" not in s and "\t" not in s


def test_top_level_keys_and_sizes():
    assert set(MANIFEST) == KEYS["top"]
    assert (ROOT / "BENCHMARK.json").stat().st_size <= 64 * 1024
    assert isinstance(MANIFEST["run_seconds"], int) and 1 <= MANIFEST["run_seconds"] <= 51
    assert 1 <= len(MANIFEST["paths"]) <= 16
    for p in MANIFEST["paths"]:
        assert PATH.fullmatch(p) and not p.startswith("/") and ".." not in p.split("/")
        assert (ROOT / p).is_dir()
    cmd = MANIFEST["command"]
    assert 1 <= len(cmd) <= 32 and all(_text(w) for w in cmd)
    for word in cmd:
        if "/" in word:  # a file of the repo: only under paths
            assert any(word.startswith(p + "/") for p in MANIFEST["paths"]), word


@pytest.mark.parametrize("kind,section", [("config", "configs"), ("workload", "workloads"),
                                          ("end_to_end", "end_to_end"),
                                          ("per_layer", "per_layer")])
def test_entry_keys_and_names(kind, section):
    entries = MANIFEST[section]
    names = [e["name"] for e in entries]
    assert len(names) == len(set(names)) and entries
    for e in entries:
        allowed = KEYS[kind] | ({"workloads"} if kind in ("end_to_end", "per_layer") else set())
        assert KEYS[kind] <= set(e) <= allowed, e["name"]
        assert NAME.fullmatch(e["name"]), e["name"]
        if "unit" in e:
            assert UNIT.fullmatch(e["unit"]) and e["better"] in ("lower", "higher")
        for key in ("why", "layer", "source"):
            if key in e and kind in ("config", "workload", "per_layer"):
                assert _text(e[key]), (e["name"], key)


def test_metric_names_are_unique_across_kinds():
    names = [m["name"] for m in MANIFEST["end_to_end"] + MANIFEST["per_layer"]]
    assert len(names) == len(set(names))


def test_bounds_and_sources():
    names = {m["name"] for m in MANIFEST["end_to_end"]}
    assert "setup_s" in names
    for m in MANIFEST["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25 and m["source"] in ("host_clock", "device_trace")
    for m in MANIFEST["per_layer"]:
        assert m["source"] in ("device_trace", "program_span", "program_counter", "host_clock")
        assert m["moves"] in names


def test_four_chip_cells_at_most_half():
    four = sum(1 for w in MANIFEST["workloads"] if w["chips"] == 4)
    assert all(w["chips"] in (1, 4) for w in MANIFEST["workloads"])
    assert four <= max(1, len(CELLS) // 2)


@pytest.mark.parametrize("cell", CELLS)
def test_cell_files_are_found_by_name(cell):
    w = next(w for w in MANIFEST["workloads"] if w["name"] == cell)
    assert NAME.fullmatch(w["config"]) and NAME.fullmatch(w["traffic"])
    conf = next(c for c in MANIFEST["configs"] if c["name"] == w["config"])
    assert conf["file"] == f"benchmarks/chip/configs/{w['config']}.json"
    config = json.loads((ROOT / conf["file"]).read_text())
    traffic = json.loads((HERE / "traffic" / f"{w['traffic']}.json").read_text())
    assert (HERE / "traffic" / f"{traffic['generator']}.py").is_file()
    assert (HERE / "drivers" / f"{traffic['driver']}.py").is_file()
    assert (HERE / config.get("reference", "reference.py")).is_file()
    for m in bench.per_layer_for(MANIFEST, cell):
        assert (HERE / "metrics" / f"{m['name']}.py").is_file(), m["name"]
    assert len(bench.per_layer_for(MANIFEST, cell)) >= 1
    e2e = {m["name"] for m in bench.e2e_for(MANIFEST, cell)}
    assert "setup_s" in e2e and len(e2e) >= 2


@pytest.mark.parametrize("cell", CELLS)
def test_per_layer_moves_are_reported_by_their_cells(cell):
    e2e = {m["name"] for m in bench.e2e_for(MANIFEST, cell)}
    for m in MANIFEST["per_layer"]:
        if cell in m.get("workloads", []):
            assert m["moves"] in e2e, (m["name"], cell)


def test_every_config_is_used_and_files_are_distinct():
    used = {w["config"] for w in MANIFEST["workloads"]}
    assert used == {c["name"] for c in MANIFEST["configs"]}
    files = [c["file"] for c in MANIFEST["configs"]]
    assert len(files) == len(set(files))
    for c in MANIFEST["configs"]:
        assert any(c["file"].startswith(p + "/") for p in MANIFEST["paths"])
        assert c["source"].startswith("https://") and _text(c["why"])
        assert len(c["reduced"]) <= 16 and all(NAME.fullmatch(k) for k in c["reduced"])


@pytest.mark.parametrize("name", [c["name"] for c in MANIFEST["configs"]])
def test_config_file_agrees_with_the_program(name):
    config = json.loads((HERE / "configs" / f"{name}.json").read_text())
    conf = next(c for c in MANIFEST["configs"] if c["name"] == name)
    assert config["reduced"] == conf["reduced"]
    assert config["source"].startswith(conf["source"])
    model = bench.model_numbers(config)
    cfg = bench.program_config(config, model)  # raises on any disagreement
    assert cfg.num_layers == config["published"]["num_hidden_layers"]


def test_per_layer_layers_are_named_in_perf_md():
    perf = (ROOT / "PERF.md").read_text()
    for m in MANIFEST["per_layer"]:
        assert f"`{m['layer']}`" in perf, m["layer"]
