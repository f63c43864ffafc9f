"""opcount.py against counts made by hand."""
import json
import os

import pytest

import tiny  # noqa: F401  (puts benchmark/ on the path)
import opcount

MISTRAL = dict(json.load(open(os.path.join(tiny.BENCH, "configs",
                                           "mistral-7b-v0.3.json"))),
               num_hidden_layers=5)           # counted by hand at depth 5
DEEPSEEK = dict(json.load(open(os.path.join(tiny.BENCH, "configs",
                                            "deepseek-llm-7b.json"))),
                num_hidden_layers=12)         # counted by hand at depth 12
PEAKS = json.load(open(os.path.join(tiny.BENCH, "peaks.json")))["devices"]


def test_parameters_by_hand():
    # q 4096x4096, k and v 4096x1024, o 4096x4096, three 4096x14336, 2 norms
    assert opcount.layer_params(MISTRAL) == (
        2 * 4096 * 4096 + 2 * 4096 * 1024 + 3 * 4096 * 14336 + 2 * 4096)
    assert round(opcount.layer_params(MISTRAL) / 1e6, 1) == 218.1
    assert round(opcount.layer_params(DEEPSEEK) / 1e6, 1) == 202.4
    assert opcount.head_params(DEEPSEEK) == 4096 * 102400
    # depth 5: 1.359 B in all, 1.225 B outside the embedding table
    assert round(opcount.total_params(MISTRAL) / 1e9, 3) == 1.359
    assert round(opcount.matmul_params(MISTRAL) / 1e9, 3) == 1.225


def test_train_step_flops_by_hand():
    tokens = 2 * 2048
    attention = 3 * (2 * 2 * 2048 * 2048 * 128 * 32 / 2) * 2 * 5
    want = 6 * opcount.matmul_params(MISTRAL) * tokens + attention
    assert opcount.train_step_flops(MISTRAL, 2, 2048) == pytest.approx(want)
    assert 3.0e13 < want < 3.2e13


def test_flash_attention_by_hand():
    fwd, bwd = opcount.flash_attention_flops(MISTRAL, 1, 2048)
    assert fwd == 2 * (2 * 2048 * 2048 * 128 * 32) / 2
    assert bwd == 2.5 * fwd
    fb, bb = opcount.flash_attention_bytes(MISTRAL, 1, 2048)
    q, kv = 2048 * 32 * 128 * 2, 2048 * 8 * 128 * 2
    assert fb == 2 * q + 2 * kv and bb == 4 * q + 4 * kv


def test_adamw_and_serve_bytes_by_hand():
    # fp32 parameter and gradient, bf16 moments: 4+4 read, 4 written, 2x2+2x2
    assert opcount.fused_adamw_bytes(10, 4, 4, 2) == 10 * 20
    assert opcount.kv_bytes_per_token(DEEPSEEK) == 2 * 32 * 128 * 2
    # 12 layers x 16384 B a token: the ISSUE's 196,608 B a token
    assert opcount.kv_bytes_per_token(DEEPSEEK) * 12 == 196608
    live = [100, 17]
    weights = (12 * opcount.layer_params(DEEPSEEK) + 4096 * 102400) * 2
    assert opcount.serve_step_bytes(DEEPSEEK, live) == weights + 117 * 196608
    # pages of 16: 100 -> 7 pages, 17 -> 2 pages
    assert opcount.paged_attention_bytes(DEEPSEEK, live, 16) == \
        9 * 16 * 196608


def test_serve_flops_and_roofline():
    got = opcount.serve_flops(DEEPSEEK, 10, 3, 1000)
    want = (2 * 12 * opcount.layer_params(DEEPSEEK) * 10
            + 2 * 4096 * 102400 * 3 + 4 * 128 * 32 * 12 * 1000)
    assert got == pytest.approx(want)
    peaks = PEAKS["TPU v5 lite"]
    assert opcount.roofline_seconds(197e12, 1.0, peaks) == (1.0, "compute")
    assert opcount.roofline_seconds(1.0, 819e9 * 2, peaks, chips=2) == \
        (1.0, "memory")
