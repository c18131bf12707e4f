"""A second configuration, traffic mix, cell and per-layer metric dropped in as
files of their own are found by name, with no edit to any harness file; so is
a scoped cell: folders in the configuration, a filter in the mix, its limits."""

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


def _drop_in_scoped(tmp_path):
    """A scoped cell as files alone: the other field and the other binding
    than the rehearsal's, six folders, every request filtered."""
    src = os.path.join(harness.TESTS, "rehearsal")
    for kind in ("configs", "traffic", "limits"):
        os.makedirs(tmp_path / kind)
    cfg = json.load(open(os.path.join(src, "configs", "tiny-bert.json")))
    cfg["rows"] = cfg["reserved_space"] = 1024
    cfg["metadata"] = {"tenants": 6, "tenant_zipf_s": 0.5, "path": "drive/f{tenant}/d{doc}.md"}
    json.dump(cfg, open(tmp_path / "configs" / "shared-drive.json", "w"))
    mix = json.load(open(os.path.join(src, "traffic", "read-c4.json")))
    mix["clients"] = mix["warm_batch_max"] = 2
    mix["scope"] = {"field": "metadata_filter", "bind": "request", "client_zipf_s": 0.5,
                    "template": "starts_with(path, 'drive/f{tenant}/') && ver == `0`",
                    "share_unscoped": 0.0, "passage_in_scope": 0.5}
    json.dump(mix, open(tmp_path / "traffic" / "read-folder-c2.json", "w"))
    json.dump({"rank_gap": 0.003, "score_err": 0.003, "bad_replies": 0, "out_of_scope": 0},
              open(tmp_path / "limits" / "shared-drive.read-folder-c2.json", "w"))
    manifest = json.load(open(harness.REHEARSAL))
    manifest["configs"] = [{"name": "shared-drive", "source": cfg["source"],
                            "file": "configs/shared-drive.json", "reduced": ["rows"],
                            "why": "scoped drop-in test"}]
    manifest["workloads"] = [{"name": "shared-drive.read-folder-c2", "config": "shared-drive",
                              "traffic": "read-folder-c2", "chips": 1, "why": "scoped drop-in test"}]
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        if "workloads" in m:
            m["workloads"] = []
    path = tmp_path / "BENCHMARK.json"
    json.dump(manifest, open(path, "w"))
    return str(path)


def test_a_dropped_in_scoped_cell_runs(tmp_path):
    manifest = _drop_in_scoped(tmp_path)
    code, result, err = harness.run_cell("shared-drive.read-folder-c2", seconds=2, trace=1,
                                         manifest=manifest)
    assert code == 0, err[-3000:]
    assert result["correct"] is True, result["compared"]
    assert result["compared"]["out_of_scope"] == {"value": 0.0, "limit": 0}
    assert result["failed"] == 0 and result["attempted"] > 20
    # every request carried the filter, one of the six folders each
    sent = [json.loads(line) for line in open(os.path.join(
        harness.BENCH, "out", "shared-drive.read-folder-c2", "requests.jsonl"))]
    assert {r["scope"] for r in sent} <= set(range(6)) and len({r["scope"] for r in sent}) > 1
