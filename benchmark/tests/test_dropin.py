"""A second configuration, traffic mix, cell and per-layer metric dropped in as
files of their own are found by name, with no edit to any harness file."""

import json
import os
import shutil

import harness
import run as bench_run


def _drop_in(tmp_path):
    """A manifest directory of its own: the rehearsal's files, copied under new
    names, plus a new layer metric."""
    src = os.path.join(harness.TESTS, "rehearsal")
    for kind in ("configs", "traffic", "limits", "layers"):
        os.makedirs(tmp_path / kind)
    cfg = json.load(open(os.path.join(src, "configs", "tiny-bert.json")))
    cfg["rows"] = cfg["reserved_space"] = 1024
    json.dump(cfg, open(tmp_path / "configs" / "second-bert.json", "w"))
    mix = json.load(open(os.path.join(src, "traffic", "read-c4.json")))
    mix["clients"] = 2
    json.dump(mix, open(tmp_path / "traffic" / "read-c2.json", "w"))
    shutil.copy(os.path.join(src, "limits", "tiny-bert.read-c4.json"),
                tmp_path / "limits" / "second-bert.read-c2.json")
    (tmp_path / "layers" / "searches_in_window.py").write_text(
        '"""How many wrapped searches the window held."""\n\n\n'
        "def read(trace, spans, counts, cell):\n"
        "    n = sum(s['name'] == 'search' for s in spans)\n"
        "    return float(n) if n else None\n"
    )
    manifest = json.load(open(harness.REHEARSAL))
    manifest["configs"] = [{"name": "second-bert", "source": cfg["source"],
                            "file": "configs/second-bert.json", "reduced": ["rows"],
                            "why": "drop-in test"}]
    manifest["workloads"] = [{"name": "second-bert.read-c2", "config": "second-bert",
                              "traffic": "read-c2", "chips": 1, "why": "drop-in test"}]
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        if "workloads" in m:
            m["workloads"] = ["second-bert.read-c2"] if "clean" in m["name"] else []
    manifest["per_layer"].append(
        {"name": "searches_in_window", "unit": "searches", "better": "higher",
         "source": "program_span", "layer": "index engine", "moves": "retrieve_qps"})
    path = tmp_path / "BENCHMARK.json"
    json.dump(manifest, open(path, "w"))
    return str(path)


def test_files_are_found_by_name(tmp_path):
    manifest = _drop_in(tmp_path)
    loaded = bench_run.load_cell(manifest, "second-bert.read-c2")
    assert loaded["config"]["rows"] == 1024 and loaded["mix"]["clients"] == 2
    read = bench_run.layer_reader("searches_in_window", loaded["manifest_dir"])
    assert read(None, [{"name": "search"}] * 3, {}, {}) == 3.0
    # the layers the benchmark already has are still found, beside the new one
    assert bench_run.layer_reader("queries_per_search", loaded["manifest_dir"])


def test_a_dropped_in_cell_runs(tmp_path):
    manifest = _drop_in(tmp_path)
    code, result, err = harness.run_cell("second-bert.read-c2", seconds=2, trace=1,
                                         manifest=manifest)
    assert code == 0, err[-3000:]
    assert result["correct"] is True, result["compared"]
    assert result["metrics"]["searches_in_window"]["value"] > 0
    assert "search_clean_ms" in result["metrics"]
