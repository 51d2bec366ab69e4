"""BENCHMARK.json keeps to the benchmark's format, and every name in it
resolves to a file of its own under bench/."""
import json
import re
from pathlib import Path

import loadgen
import run

ROOT = Path(__file__).resolve().parents[2]
SPEC_TEXT = (ROOT / "BENCHMARK.json").read_text()
SPEC = json.loads(SPEC_TEXT)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def _line(text):
    return isinstance(text, str) and 1 <= len(text) <= 200 and "\n" not in text \
        and "\t" not in text


def test_top_level_shape():
    assert len(SPEC_TEXT.encode()) <= 64 * 1024
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs", "workloads",
                         "end_to_end", "per_layer"}
    assert SPEC["command"] == ["python3", "bench/run.py"]
    assert SPEC["paths"] == ["bench"]
    assert isinstance(SPEC["run_seconds"], int) and 1 <= SPEC["run_seconds"] <= 51


def test_configs_resolve_and_match_their_files():
    names = [c["name"] for c in SPEC["configs"]]
    assert len(names) == len(set(names))
    used = {w["config"] for w in SPEC["workloads"]}
    for c in SPEC["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and _line(c["source"]) and _line(c["why"])
        assert c["name"] in used
        assert c["file"] == f"bench/configs/{c['name']}.json"
        doc = loadgen.load_json("configs", c["name"])
        assert doc["name"] == c["name"] and doc["reduced"] == c["reduced"]
        assert {"source", "peers", "keys_per_side", "guarantee", "assumed", "pbs"} <= set(doc)


def test_workloads_resolve():
    configs = {c["name"] for c in SPEC["configs"]}
    pairs = set()
    for w in SPEC["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"]) and _line(w["why"])
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert loadgen.load_json("traffic", w["traffic"])["mode"] in loadgen.MODES
        pairs.add((w["config"], w["traffic"]))
    assert len(pairs) == len(SPEC["workloads"])
    assert len({w["name"] for w in SPEC["workloads"]}) == len(SPEC["workloads"])


def test_metrics_resolve_to_readers():
    cells = {w["name"] for w in SPEC["workloads"]}
    e2e = {m["name"] for m in SPEC["end_to_end"]}
    assert "setup_s" in e2e
    names = [m["name"] for m in SPEC["end_to_end"] + SPEC["per_layer"]]
    assert len(names) == len(set(names))
    for m in SPEC["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert m["source"] in {"host_clock", "device_trace"}
        assert 0.01 <= m["bound"] <= 0.25
    for m in SPEC["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                           "layer", "moves"}
        assert m["source"] in SOURCES and _line(m["layer"]) and m["moves"] in e2e
        assert set(m.get("workloads", cells)) <= cells
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert callable(run.metric_reader(m["name"]))
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"
