"""``workcount`` against counts worked out by hand at the cells' shapes, with
the BERT family's work per token (``models/bert.py::flops_per_token``)."""

import functools

import harness  # noqa: F401  (puts benchmark/ on the path)
import pytest
from lib import family, peaks, workcount

V5E = peaks.peaks_for("TPU v5 lite")
BERT = family.load({"model_type": "bert"})


def test_topk_scores_at_the_read_cells_shape():
    # 16 queries over 1,200,000 live rows of 1024, 10 answers
    flops, nbytes = workcount.topk_scores_work(16, 1_200_000, 1024, 10)
    assert flops == 2 * 16 * 1_200_000 * 1024 == 39_321_600_000
    assert nbytes == 1_200_000 * 1024 * 2 + 16 * 1024 * 4 + 16 * 10 * 8 == 2_457_666_816
    least, bound = workcount.least_time(flops, nbytes, V5E)
    assert bound == "memory"
    assert least == pytest.approx(3.0008e-3, rel=1e-3)  # 2.4577 GB / 819 GB/s
    assert flops / V5E["bf16_flops"] == pytest.approx(0.1996e-3, rel=1e-3)


def test_topk_scores_at_the_live_cells_shape():
    flops, nbytes = workcount.topk_scores_work(20, 1_600_000, 768, 10)
    assert flops == 2 * 20 * 1_600_000 * 768
    assert nbytes == 1_600_000 * 768 * 2 + 20 * 768 * 4 + 20 * 10 * 8


@pytest.mark.parametrize("model,seq,expect", [
    # layers * (2*d*3d + 2*d*d + 4*d*h + 4*s*d)
    (dict(hidden_size=1024, intermediate_size=4096, num_hidden_layers=24), 8,
     24 * (6 * 1024**2 + 2 * 1024**2 + 4 * 1024 * 4096 + 4 * 8 * 1024)),
    (dict(hidden_size=768, intermediate_size=3072, num_hidden_layers=12), 16,
     12 * (6 * 768**2 + 2 * 768**2 + 4 * 768 * 3072 + 4 * 16 * 768)),
])
def test_encoder_flops_per_token(model, seq, expect):
    assert BERT.flops_per_token(model, seq) == expect


def test_retrieve_flops_is_encoder_over_real_tokens_plus_scan():
    model = dict(hidden_size=1024, intermediate_size=4096, num_hidden_layers=24)
    per_token = functools.partial(BERT.flops_per_token, model)
    got = workcount.retrieve_flops(8, 1_200_000, 1024, per_token)
    assert got == 8 * BERT.flops_per_token(model, 8) + 2 * 1_200_000 * 1024
    # 8 tokens: 4.84 GFLOP of encoder, 2.46 GFLOP of scan
    assert got == pytest.approx(7.30e9, rel=5e-3)


def test_scoped_counts_by_hand_at_one_small_shape():
    # 3 queries of one search over a store of 1,000 live rows of width 64, k = 10:
    # scopes of 100, 100 (the same folder) and 300 rows, so the union is 400
    flops, nbytes = workcount.scoped_topk_scores_work(3, 100 + 100 + 300, 400, 64, 10)
    assert flops == 2 * 64 * 500 == 64_000
    assert nbytes == 400 * 64 * 2 + 3 * 64 * 4 + 3 * 10 * 8 == 52_208
    # a scope that is the whole store is the old count: which returns what it did
    assert workcount.scoped_topk_scores_work(3, 3 * 1000, 1000, 64, 10) == \
        workcount.topk_scores_work(3, 1000, 64, 10) == (384_000.0, 129_008.0)
    model = dict(hidden_size=64, intermediate_size=128, num_hidden_layers=2)
    per_token = functools.partial(BERT.flops_per_token, model)
    assert workcount.scoped_retrieve_flops(8, 100, 64, per_token) == \
        8 * BERT.flops_per_token(model, 8) + 2 * 100 * 64
    assert workcount.scoped_retrieve_flops(8, 1000, 64, per_token) == \
        workcount.retrieve_flops(8, 1000, 64, per_token)


@pytest.mark.parametrize("program_reads", ["every row under a mask", "only the scopes"])
def test_a_scoped_share_cannot_pass_100(program_reads):
    # the large widths, 16 queries each in a folder of a tenth of 1.2M rows;
    # the layer reader counts the union as ONE mean scope, its lower bound
    n, d, k, q, scope = 1_200_000, 1024, 10, 16, 120_000
    flops, nbytes = workcount.scoped_topk_scores_work(q, q * scope, scope, d, k)
    least, bound = workcount.least_time(flops, nbytes, V5E)
    assert bound == "memory"  # 0.30 ms for the rows against 0.02 ms of products
    # the fastest a program could be: its own bytes at the chip's full bandwidth
    # (every row once under a mask; or each folder's rows once, all 16 distinct)
    rows_read = n if program_reads == "every row under a mask" else q * scope
    device = max((rows_read * d * 2 + q * d * 4 + q * k * 8) / V5E["hbm_bytes_per_s"],
                 flops / V5E["bf16_flops"])
    share = 100 * least / device
    assert share <= 100
    assert share == pytest.approx(10.0 if rows_read == n else 6.25, rel=2e-3)
    # and only a program that reads one shared folder once reaches the whole of it
    one_folder = (scope * d * 2 + q * d * 4 + q * k * 8) / V5E["hbm_bytes_per_s"]
    assert 100 * least / one_folder == pytest.approx(100.0)


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        peaks.peaks_for("TPU v9 imaginary")
    assert V5E["bf16_flops"] == 197e12 and V5E["hbm_bytes_per_s"] == 819e9
    assert V5E["hbm_bytes"] == 16 * 2**30
