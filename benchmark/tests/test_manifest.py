"""``BENCHMARK.json`` at the root against the rules it is held to before any
run: exact keys, names, lengths, every file found by name, every metric's cells."""

import json
import os
import re

import harness
import run as bench_run

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
MANIFEST = os.path.join(harness.ROOT, "BENCHMARK.json")


def _line(text: str) -> bool:
    return 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_the_manifest_keeps_to_the_contract():
    m = json.load(open(MANIFEST))
    assert set(m) == {"command", "paths", "run_seconds", "configs", "workloads",
                      "end_to_end", "per_layer"}
    assert os.path.getsize(MANIFEST) <= 64 * 1024
    assert m["paths"] == ["benchmark"] and m["command"] == ["python3", "benchmark/run.py"]
    assert isinstance(m["run_seconds"], int) and 1 <= m["run_seconds"] <= 51

    configs = {c["name"]: c for c in m["configs"]}
    assert len(configs) == len(m["configs"])
    for c in m["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and _line(c["source"]) and _line(c["why"])
        assert c["file"].startswith("benchmark/") and os.path.isfile(
            os.path.join(harness.ROOT, c["file"]))
        held = json.load(open(os.path.join(harness.ROOT, c["file"])))
        assert held["reduced"] == c["reduced"] and held["source"] == c["source"]
        assert not [k for k in c["reduced"] if k.endswith(("_dim", "_rank", "_size"))]
    assert len({c["file"] for c in m["configs"]}) == len(configs)

    cells = {w["name"]: w for w in m["workloads"]}
    assert len(cells) == len(m["workloads"])
    assert len({(w["config"], w["traffic"]) for w in m["workloads"]}) == len(cells)
    for w in m["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"]) and _line(w["why"])
        assert w["name"] == f"{w['config']}.{w['traffic']}" and w["chips"] in (1, 4)
        loaded = bench_run.load_cell(MANIFEST, w["name"])  # config, mix and limits by name
        assert loaded["limits"]["bad_replies"] == 0
    assert {w["config"] for w in m["workloads"]} == set(configs)

    e2e = {e["name"]: e for e in m["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.1
    for e in m["end_to_end"]:
        assert set(e) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert e["source"] in {"host_clock", "device_trace"}
        assert 0.01 <= e["bound"] <= 0.1
    for p in m["per_layer"]:
        assert set(p) - {"workloads"} == {"name", "unit", "better", "source", "layer", "moves"}
        assert p["source"] in SOURCES and p["moves"] in e2e and _line(p["layer"])
        bench_run.layer_reader(p["name"], os.path.dirname(MANIFEST))
        if p["name"].endswith("_roofline") or "mfu" in p["name"]:
            assert p["unit"] == "%"
    names = [x["name"] for x in m["end_to_end"] + m["per_layer"]]
    assert len(set(names)) == len(names)
    for x in m["end_to_end"] + m["per_layer"]:
        assert NAME.match(x["name"]) and UNIT.match(x["unit"])
        assert x["better"] in ("lower", "higher")
        assert set(x.get("workloads", cells)) <= set(cells)
    for w in cells:  # every cell: set-up, another end-to-end metric, a per-layer one
        assert len(bench_run.metrics_of(m, "end_to_end", w)) >= 2
        assert bench_run.metrics_of(m, "per_layer", w)
