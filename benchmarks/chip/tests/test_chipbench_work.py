"""Shape-derived work of the dense GQA family against hand figures."""

import json

import numpy as np
import pytest

from chipbench_tiny import BENCH, harness

fam = harness.load_module(BENCH / "families" / "dense_gqa.py",
                          "chipbench_family_dense_gqa")


def dims(name):
    return fam.dims(json.loads((BENCH / "configs" / f"{name}.json")
                               .read_text()))


def test_qwen2_7b_hand_figures():
    d = dims("qwen2-7b")
    # 14 of 28 layers with embedding and untied head: 4.35 B parameters
    assert fam.n_params(d) == pytest.approx(4.35e9, rel=2e-3)
    # one (1, 512) chunk: 2 x 3.26 B layer weights x 512 tokens = 3.3 TFLOP
    assert 2 * d.L * fam.layer_params(d) * 512 == pytest.approx(3.34e12,
                                                                 rel=5e-3)
    assert fam.chunk_flops(d, 0, 512) == pytest.approx(3.3e12, rel=0.03)
    # 28 KB of K and V per token over the 14 layers (bf16)
    assert fam.kv_bytes_per_token(d) == 28 * 1024
    conf = json.loads((BENCH / "configs" / "qwen2-7b.json").read_text())
    assert conf["memory"]["params"] == fam.n_params(d)


def test_mistral_nemo_hand_figures():
    d = dims("mistral-nemo-12b")
    assert fam.n_params(d) == pytest.approx(4.07e9, rel=2e-3)
    assert fam.kv_bytes_per_token(d) == 40 * 1024
    assert d.H * d.hd == 4096 and d.D == 5120


def test_token_and_chunk_flops_agree():
    d = dims("qwen2-7b")
    start, n = 1000, 37
    per_token = sum(fam.token_flops(d, start + i + 1, False)
                    for i in range(n)) + 2 * d.D * d.V
    assert fam.chunk_flops(d, start, n) == per_token


def test_decode_attention_work():
    d = dims("qwen2-7b")
    kv = np.array([1, 100, 4096])
    flops, byts = fam.decode_attention_work(d, kv)
    assert flops == 4 * d.H * d.hd * kv.sum()
    # K and V rows of each slot's context, plus q and out, bf16
    assert byts == 2 * d.KV * d.hd * 2 * kv.sum() + 2 * 3 * d.H * d.hd * 2
