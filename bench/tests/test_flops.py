"""The counts behind every share, against values worked by hand for one
stablelm-1.6b layer and one mistral-large-123b layer. A count that is too
high would let a share pass 100%."""
import json
import os

import pytest

from bench.lib import peaks
from bench.models.dense_decoder import work as flops
from bench.tests.tiny import ROOT


def _cfg(name):
    return json.load(open(os.path.join(ROOT, f"bench/configs/{name}.json")))


def _traffic(name):
    return json.load(open(os.path.join(ROOT, f"bench/traffic/{name}.json")))


STABLELM = _cfg("stablelm-1.6b")
MISTRAL = _cfg("mistral-large-123b")


def test_layer_matmuls_by_hand():
    # stablelm: q, k, v, o are 2048 x 2048; gate, up 2048 x 5632, down back
    assert flops.matmul_per_token(STABLELM) == 2 * (
        4 * 2048 * 2048 + 3 * 2048 * 5632) == 102_760_448
    # mistral: q, o 12288 x 12288; k, v 12288 x 1024 (8 heads of 128);
    # gate, up 12288 x 28672, down back: 1.384 B weights per layer
    assert flops.matmul_per_token(MISTRAL) == 2 * (
        2 * 12288 * 12288 + 2 * 12288 * 1024 + 3 * 12288 * 28672) \
        == 2 * 1_384_120_320


def test_adapter_by_hand():
    qv = _traffic("serve-decode")["adapter"]
    assert flops.adapter_per_token(STABLELM, qv) == 2 * 8 * (4096 + 4096)
    all7 = _traffic("train-4k")["adapter"]
    widths = (24576 + 13312 + 13312 + 24576 + 40960 + 40960 + 40960)
    assert flops.adapter_per_token(MISTRAL, all7) == 2 * 8 * widths


def test_attention_and_head_by_hand():
    assert float(flops.attn_per_query(STABLELM, 10)) == 4 * 32 * 64 * 10
    assert float(flops.attn_per_query(MISTRAL, 10)) == 4 * 96 * 128 * 10
    assert flops.head_per_token(STABLELM) == 2 * 2048 * 100352
    assert flops.head_per_token(MISTRAL) == 2 * 12288 * 32768


def test_train_step_by_hand():
    ad = _traffic("train-4k")["adapter"]
    w = flops.train_step(MISTRAL, ad, 1, 4096, {})
    mat, a = 2 * 1_384_120_320, flops.adapter_per_token(MISTRAL, ad)
    att = 4 * 96 * 128 * 4096 * 4097 / 2
    head = 2 * 12288 * 32768
    assert w["model_flops"] == pytest.approx(
        4096 * 3 * (2 * mat + 3 * a) + 3 * 3 * att + 2 * 4096 * head)
    # per token about 19 GFLOP (forward ~9.6 with the head, backward ~9.7)
    assert 18e9 < w["model_flops"] / 4096 < 20e9
    assert w["flash_flops"] == pytest.approx(3 * 3 * att)
    # every matmul is adapted: tt_linear carries all base matmuls, twice
    assert w["tt_linear_flops"] == pytest.approx(
        2 * 3 * 4096 * (mat + a))


def test_serve_forward_by_hand():
    ad = _traffic("serve-decode")["adapter"]
    # one request: prompt 3, 2 tokens generated, prefill chunk 2.
    # positions 0, 1, 2 (prompt) and 3 (first token fed back) run forward;
    # two readouts; contexts 1..4
    w = flops.serve_forward(STABLELM, ad, [(3, 2, 3)], chunk=2,
                            counts={})
    per = 24 * (flops.matmul_per_token(STABLELM)
                + flops.adapter_per_token(STABLELM, ad))
    att = 24 * 4 * 32 * 64 * (1 + 2 + 3 + 4)
    assert w["model_flops"] == pytest.approx(
        4 * per + att + 2 * 2 * 2048 * 100352)
    assert w["paged_flops"] == pytest.approx(att)
    # K/V bytes: prompt chunks end at 2 and 3; the decode step reads 4
    kv_row, q_row = 2 * 2048 * 2, 2 * 2048 * 2
    assert w["paged_bytes"] == pytest.approx(
        24 * ((2 + 3 + 4) * kv_row + 4 * q_row))
    # a request cut before its first token shows no work
    assert flops.serve_forward(STABLELM, ad, [(100, 0, 0)], 8, {})[
        "model_flops"] == 0


def test_peaks_table():
    assert peaks.peak("TPU v5 lite")["bf16_flops"] == 197e12
    assert peaks.roofline_s(197e12, 0, "TPU v5 lite") == pytest.approx(1.0)
    assert peaks.roofline_s(0, 819e9, "TPU v5 lite") == pytest.approx(1.0)
    with pytest.raises(KeyError):
        peaks.peak("TPU v9")
