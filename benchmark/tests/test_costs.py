"""Each ``costs/*.py`` against numbers worked by hand."""

from __future__ import annotations

import json

import pytest

from benchmark.tests import toy
from benchmark.costs import flash_attention as fa
from benchmark.costs import mpt_train
from benchmark.costs import ragged_paged_attention as rpa

MPT_125M = json.loads((toy.ROOT / "benchmark/configs/mpt-125m.json").read_text())["model"]


def test_flash_forward_mpt125m_b2_s2048():
    # causal pairs 2048 * 2049 / 2 = 2,098,176; QK^T and PV at d_head 64 (not
    # the 128 lanes it is padded to): 4 * 64 = 256 per pair; 2 rows x 12 heads
    assert fa.forward_flops(batch=2, heads=12, seq=2048, d_head=64) == 2_098_176 * 256 * 24
    assert fa.forward_flops(2, 12, 2048, 64) == 12_891_193_344
    # backward: four products where forward has two; the recomputed scores are
    # not required work
    assert fa.training_flops(2, 12, 2048, 64) == 3 * 12_891_193_344
    # q, k, v read and o written in bf16, plus the fp32 log-sum-exp
    assert fa.forward_bytes(2, 12, 2048, 64) == 24 * 2048 * (4 * 64 * 2 + 4)
    assert fa.backward_bytes(2, 12, 2048, 64) == 24 * 2048 * (8 * 64 * 2 + 4)


def test_mpt125m_training_flops_per_token():
    # weights: 12 * (3 + 1 + 8) * 768^2 + 768 * 50368 = 123,617,280
    # dense 6 * that = 741,703,680; attention 12 layers * 3 * 12 heads *
    # (2049 / 2 pairs per token) * 256 = 113,301,504
    assert mpt_train.flops_per_token(MPT_125M) == pytest.approx(855_005_184)


def test_ragged_decode_and_prefill():
    # one token at context 1000 of mpt-1b: 24 layers * 1000 tokens * (K + V)
    # * 2048 * 2 bytes
    assert rpa.decode_bytes(1000, 2048, 24) == 24 * 1000 * 8192 == 196_608_000
    assert rpa.decode_flops(1000, 2048, 24) == 24 * 4 * 2048 * 1000
    assert rpa.prefill_flops(384, 2048, 24) == 24 * 4 * 2048 * 384 * 385 / 2
    assert rpa.prefill_bytes(384, 2048, 24) == 24 * 384 * 8192
