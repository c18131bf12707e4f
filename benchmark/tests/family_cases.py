"""A model family is one file (``lib/family.py``, ``models/<model_type>.py``).

The BERT family's file gives what the harness made before the family was a
file of its own: the golden values below were taken from the commit before,
whose harness wrote BERT into ``datagen``, ``reference`` and ``workcount``, at
``SEED``. Weights, vocabulary, token rows and work counts are held to the byte;
the reference's vectors, which come out of the CPU's matrix products, to
within rounding. A family added as one new file, beside a manifest of its own,
is taken with no edit to any other file; one that has no file stops the run
before anything starts.

Not a ``test_`` file: ``test_scope.py`` takes these cases in, so that tier-1
counts them through ``tests/test_benchmark_scope.py`` and
``pytest benchmark/tests`` collects each once."""

import glob
import hashlib
import json
import os
import re
import shutil

import harness
import numpy as np
import pytest
from lib import datagen, family, reference

SEED = 2147483659
REHEARSAL = os.path.join(harness.TESTS, "rehearsal")
CONFIGS = os.path.join(harness.BENCH, "configs")
BERT = family.load({"model_type": "bert"})


def _config(path):
    with open(path) as f:
        return json.load(f)


def _tiny():
    return _config(os.path.join(REHEARSAL, "configs", "tiny-bert.json"))


def _model(cfg, fam=BERT):
    return {k: cfg[k] for k in fam.MODEL_KEYS}


def _digest(weights):
    h = hashlib.sha256()
    for name in sorted(weights):
        a = weights[name]
        h.update(name.encode())
        h.update(str(a.shape).encode())
        h.update(str(a.dtype).encode())
        h.update(a.tobytes())
    return h.hexdigest()


def test_the_weights_are_the_parents_at_tiny_bert_widths():
    weights = BERT.make_weights(SEED, _model(_tiny()))
    assert len(weights) == 37
    assert _digest(weights) == \
        "bed453980c45c35c9c5816b24bf59cd42d78a07347a00fae6fab2a8cf41a41fa"


def test_three_weights_are_the_parents_at_e5_large_widths():
    cfg = _config(os.path.join(CONFIGS, "e5-large-v2-1200k.json"))
    weights = BERT.make_weights(SEED, _model(cfg))
    assert len(weights) == 389
    for name, shape, digest in [
        ("embeddings.position_embeddings.weight", (512, 1024),
         "a210cb5994d48d64e019693be8af99e08dcd429d82627e734e934aa1274b0c51"),
        ("encoder.layer.7.output.LayerNorm.weight", (1024,),
         "e4e0c05b9e428b8d440d1e8718d168b71a1b4576874d67b4dd80be58b0268afb"),
        ("encoder.layer.23.intermediate.dense.weight", (4096, 1024),
         "65a53409caacc0cf373c026c56b481d6270dc7db4755b34253bbed1f4038107d"),
    ]:
        assert weights[name].shape == shape
        assert hashlib.sha256(weights[name].tobytes()).hexdigest() == digest, name


def test_the_vocabulary_is_the_parents():
    lines, words = datagen.vocabulary(SEED, 30522, BERT)
    assert (len(lines), len(words)) == (30522, 25707)
    assert hashlib.sha256("\n".join(lines).encode()).hexdigest() == \
        "351f41725cd35bd0b28c25066be17082f194f82ad08748e03b4cb219678dd6a8"


def _ids(fam=BERT):
    cfg = _tiny()
    lines, words = datagen.vocabulary(SEED, cfg["vocab_size"], fam)
    texts = datagen.make_texts(SEED, 2, words, 24, 1, 60, 20)
    return reference.token_ids(texts, {w: i for i, w in enumerate(lines)}, 64, fam)


#: the commit before's reference vectors of ``_ids()`` at tiny-bert size
#: (``reference.encode``, float32 and fp8), stored as arrays
RECORDED = os.path.join(harness.TESTS, "recorded", "bert_tiny_reference.npz")
#: how far the vectors may move on a CPU that rounds its products otherwise:
#: moving half of the weights by one float32 step, at random, six times, moved
#: the float32 vectors by at most 1.04e-7, and the fp8 control's (whose
#: roundings to 3 bits then flip here and there) by a median of at most
#: 2.3e-8 and a largest of 2.8e-4; the control itself lies from float32 by a
#: median of 3.3e-4 and by at least 1.27e-3 in every row
F32_ATOL, FP8_MEDIAN, FP8_MAX = 1e-6, 1e-6, 1e-3


def test_the_reference_vectors_are_the_parents():
    assert BERT.frame([7, 8]) == [101, 7, 8, 102] and BERT.FRAMING == 2
    ids = _ids()
    assert hashlib.sha256(ids.tobytes()).hexdigest() == \
        "6ce6dd132c19cc96a73666ef06dc9d957c56c1b22a3a92ffa56a3472fe955665"
    model = _model(_tiny())
    params = BERT.reference_params(BERT.make_weights(SEED, model))
    recorded = np.load(RECORDED)
    got = {p: BERT.encode(params, ids, model, p) for p in ("float32", "fp8")}
    for vec in got.values():
        assert vec.shape == (24, 256) and vec.dtype == np.float32
    np.testing.assert_allclose(got["float32"], recorded["float32"], rtol=0, atol=F32_ATOL)
    moved = np.abs(got["fp8"] - recorded["fp8"])
    assert np.median(moved) <= FP8_MEDIAN and moved.max() <= FP8_MAX
    # the control, held so, is told apart from the reference
    control = np.abs(recorded["fp8"] - recorded["float32"])
    assert np.median(control) > 100 * FP8_MEDIAN and control.max(1).min() > FP8_MAX


def test_the_program_tests_entry_points_are_the_bert_familys():
    """``tests/test_embedder_packed.py`` calls ``datagen`` and ``reference`` by
    their old names with BERT model dicts; those give BERT's, and no harness
    file calls them, so a caller of the harness always names its family."""
    model = _model(_tiny())
    assert datagen.make_vocab(SEED, 30522) == datagen.vocabulary(SEED, 30522, BERT)
    assert _digest(datagen.make_state_dict(SEED, model)) == \
        _digest(BERT.make_weights(SEED, model))
    lines, words = datagen.make_vocab(SEED, model["vocab_size"])
    index = {w: i for i, w in enumerate(lines)}
    np.testing.assert_array_equal(reference.tokenize(words[:3], index, 8),
                                  reference.token_ids(words[:3], index, 8, BERT))
    ids = _ids()[:4]
    weights = BERT.make_weights(SEED, model)
    assert np.array_equal(reference.encode(reference.to_device(weights), ids, model),
                          BERT.encode(BERT.reference_params(weights), ids, model))
    old = re.compile(r"\b(datagen\.(make_vocab|make_state_dict)"
                     r"|reference\.(tokenize|to_device|encode))\(")
    harness_files = [os.path.join(harness.BENCH, "run.py")] + [
        f for d in ("lib", "layers", "models")
        for f in glob.glob(os.path.join(harness.BENCH, d, "*.py"))]
    assert len(harness_files) > 20
    for path in harness_files:
        with open(path) as f:
            assert not old.search(f.read()), path


#: the parent's ``workcount.encoder_flops_per_token`` at 16, 227 and 512 tokens
LARGE_FLOPS = [605552640.0, 626294784.0, 654311424.0]
BASE_FLOPS = [170459136.0, 178237440.0, 188743680.0]
PARENT_FLOPS = {
    "e5-large-v2-1200k": LARGE_FLOPS, "e5-large-v2-longq-1200k": LARGE_FLOPS,
    "e5-large-v2-scoped-1200k": LARGE_FLOPS,
    "e5-base-v2-1600k": BASE_FLOPS, "e5-base-v2-live-1600k": BASE_FLOPS,
}


@pytest.mark.parametrize("config", sorted(PARENT_FLOPS))
@pytest.mark.parametrize("at", range(3))
def test_flops_per_token_is_the_parents(config, at):
    cfg = _config(os.path.join(CONFIGS, config + ".json"))
    fam = family.load(cfg)
    assert fam is BERT
    assert fam.flops_per_token(cfg, (16, 227, 512)[at]) == PARENT_FLOPS[config][at]


def _copy_family(tmp_path, model_type="bert_copy"):
    """``bert.py`` copied under another ``model_type`` into a manifest
    directory of its own: a new family, as one file."""
    os.makedirs(tmp_path / "models", exist_ok=True)
    shutil.copy(os.path.join(harness.BENCH, "models", "bert.py"),
                tmp_path / "models" / f"{model_type}.py")
    return dict(_tiny(), model_type=model_type)


def test_a_family_added_as_one_file_is_taken_and_gives_the_same_vectors(tmp_path):
    cfg = _copy_family(tmp_path)
    fam = family.load(cfg, str(tmp_path))
    assert fam is not BERT
    assert fam.__file__ == str(tmp_path / "models" / "bert_copy.py")
    model = _model(cfg, fam)
    weights = fam.make_weights(SEED, model)
    assert _digest(weights) == _digest(BERT.make_weights(SEED, model))
    ids = _ids(fam)
    np.testing.assert_array_equal(ids, _ids())
    got = fam.encode(fam.reference_params(weights), ids, model)
    want = BERT.encode(BERT.reference_params(weights), ids, model)
    assert np.array_equal(got, want)


def test_a_family_with_no_file_fails_at_load_naming_the_file(tmp_path):
    with pytest.raises(family.NoFamily) as e:
        family.load({"model_type": "no_such_family"}, str(tmp_path))
    assert str(tmp_path / "models" / "no_such_family.py") in str(e.value)
    assert os.path.join(harness.BENCH, "models", "no_such_family.py") in str(e.value)
    with pytest.raises(family.NoFamily, match="names no model_type"):
        family.load({})


def _manifest(tmp_path, config_name, cfg, cell):
    """The rehearsal's manifest with one cell of ``cfg`` under ``read-c4``."""
    for kind in ("configs", "traffic", "limits"):
        os.makedirs(tmp_path / kind, exist_ok=True)
    with open(tmp_path / "configs" / f"{config_name}.json", "w") as f:
        json.dump(cfg, f)
    shutil.copy(os.path.join(REHEARSAL, "traffic", "read-c4.json"), tmp_path / "traffic")
    shutil.copy(os.path.join(REHEARSAL, "limits", "tiny-bert.read-c4.json"),
                tmp_path / "limits" / f"{cell}.json")
    manifest = _config(harness.REHEARSAL)
    manifest["configs"] = [{"name": config_name, "source": cfg["source"],
                            "file": f"configs/{config_name}.json", "reduced": ["rows"],
                            "why": "family test"}]
    manifest["workloads"] = [{"name": cell, "config": config_name, "traffic": "read-c4",
                              "chips": 1, "why": "family test"}]
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        if "workloads" in m:
            m["workloads"] = [cell] if "clean" in m["name"] else []
    path = tmp_path / "BENCHMARK.json"
    with open(path, "w") as f:
        json.dump(manifest, f)
    return str(path)


def _out(config_name):
    """A cell name of this process alone (``test_scope.py`` takes these cases
    in, so two workers can run one at once) and its ``out/`` directory."""
    cell = f"{config_name}-{os.getpid()}.read-c4"
    return cell, os.path.join(harness.BENCH, "out", cell)


def test_a_configuration_whose_family_has_no_file_stops_before_the_child(tmp_path):
    cell, out = _out("no-family-bert")
    cfg = dict(_tiny(), model_type="no_such_family")
    manifest = _manifest(tmp_path, cell.split(".")[0], cfg, cell)
    code, result, err = harness.run_cell(cell, seconds=2, manifest=manifest, timeout=60)
    assert code != 0 and result is None
    assert os.path.join("models", "no_such_family.py") in err.strip().splitlines()[-1]
    assert not os.path.exists(out)


def test_a_family_added_as_one_file_runs(tmp_path):
    cell, out = _out("copied-bert")
    cfg = _copy_family(tmp_path)
    cfg["rows"] = cfg["reserved_space"] = 1024
    manifest = _manifest(tmp_path, cell.split(".")[0], cfg, cell)
    try:
        code, result, err = harness.run_cell(cell, seconds=2, trace=1, manifest=manifest)
        assert code == 0, err[-3000:]
        assert result["correct"] is True, result["compared"]
        assert "search_clean_ms" in result["metrics"]
        with open(os.path.join(out, "spec.json")) as f:
            assert json.load(f)["family"] == str(tmp_path / "models" / "bert_copy.py")
    finally:
        shutil.rmtree(out, ignore_errors=True)
