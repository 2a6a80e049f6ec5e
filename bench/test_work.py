"""Operation and byte counts against hand arithmetic at published widths."""

import json
import os

import pytest

from bench import spec, work
from bench.serve_cell import Dispatch

dense, ssm = spec.family("dense"), spec.family("ssm")

HERE = os.path.dirname(__file__)
PEAKS = {"bf16_flops": 197e12, "int8_ops": 393e12, "hbm_bytes": 819e9}


def _model(name):
    with open(os.path.join(HERE, "configs", name + ".json")) as fh:
        return spec.model(json.load(fh))


def test_stablelm_linears_and_token_ops():
    m = _model("stablelm-3b-w4a4")
    assert dense.linears(m) == [(2560, 2560)] * 4 + [(2560, 6912)] * 2 + [(6912, 2560)]
    # 32 x (4 x 2560^2 + 3 x 2560 x 6912) = 2,537,553,920 weights
    i8, bf = dense.token_ops(m, context=99)
    assert i8 == 2 * 2_537_553_920
    # attention: 4 x 100 keys x 32 heads x 80 per layer; head 2 x 2560 x 50304
    assert bf == 32 * 4 * 100 * 32 * 80 + 2 * 2560 * 50304


def test_linear_counts():
    ops, nbytes = work.linear(32, 2560, 6912, w_bits=4)
    assert ops == 2 * 32 * 2560 * 6912
    # int4 weights, f32 scale per output, bf16 x in and y out
    assert nbytes == 2560 * 6912 / 2 + 4 * 6912 + 2 * 32 * 2560 + 2 * 32 * 6912
    # at 32 rows the weights bound it: bytes / 819 GB/s
    t = work.linear_seconds(Dispatch("decode", 32, 1, [1] * 32, [0] * 32),
                            _model("stablelm-3b-w4a4"), 4, PEAKS)
    per_layer = sum(work.linear(32, k, n, 4)[1] for k, n in
                    dense.linears(_model("stablelm-3b-w4a4")))
    assert t == pytest.approx(32 * per_layer / 819e9)


def test_paged_attention_counts_live_positions_only():
    m = _model("stablelm-3b-w4a4")
    ops, nbytes = dense.paged_attention([100, 200], m, kv_bits=8)
    assert ops == 4 * 300 * 32 * 80
    # per position: K and V int8 (2 x 32 x 80) + two f32 scales per head
    assert nbytes == 300 * 32 * (2 * 80 + 8) + 2 * 32 * 80 * 2 * 2


def test_mamba2_counts():
    m = _model("mamba2-780m-w4a4")
    # in_proj: z, x (2 x 3072), B, C (2 x 128), dt (48 heads)
    assert ssm.linears(m) == [(1536, 6448), (3072, 1536)]
    i8, bf = ssm.token_ops(m, context=5000)
    assert i8 == 2 * 48 * (1536 * 6448 + 3072 * 1536)
    # conv over 3328 channels x 4 taps; state update and readout 6 h p n
    assert bf == 48 * (2 * 4 * 3328 + 6 * 48 * 64 * 128) + 2 * 1536 * 50280


def test_model_seconds_sums_every_served_token():
    m = _model("mamba2-780m-w4a4")
    d = [Dispatch("prefill", 1, 256, [3], [0]),
         Dispatch("decode", 64, 1, [1, 1], [7, 9])]
    i8, bf = ssm.token_ops(m, 0)
    assert work.model_seconds(m, d, PEAKS) == pytest.approx(
        5 * (i8 / 393e12 + bf / 197e12))
