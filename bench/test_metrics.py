"""Each metric reader over a made-up run whose answer is known."""

import numpy as np
import pytest

from bench import spec, tracefile as T
from bench.serve_cell import Dispatch, Run

PEAKS = {"bf16_flops": 197e12, "int8_ops": 393e12, "hbm_bytes": 819e9}


def _run(**kw):
    c = spec.cell_of("stablelm-3b-w4a4", "decode-long")
    r = Run(cell=c, m=spec.model(c.config), seed=0, seconds=10.0,
            t0=100.0, t_end=110.0, peaks=PEAKS)
    for k, v in kw.items():
        setattr(r, k, v)
    return r


def read(name, run):
    return spec.read_metric(name, run)


def test_output_tok_s_counts_tokens_inside_the_window():
    r = _run(tokens={1: [99.0, 100.5, 101.0], 2: [109.9, 110.5]})
    assert read("output_tok_s", r) == pytest.approx(3 / 10.0)
    assert read("setup_s", _run(setup_s=42.5)) == 42.5


def test_ttft_is_timed_from_the_due_time():
    # request 1 waited 2.0 s from its due time; request 2 never got a token
    # and counts with the wait so far (110 - 108); request 3 fell due early
    due = {1: 101.0, 2: 108.0, 3: 99.0}
    tokens = {1: [103.0, 103.1], 3: [100.5]}
    r = _run(due=due, tokens=tokens)
    assert read("ttft_p95_s", r) == pytest.approx(np.percentile([2.0, 2.0], 95))
    tokens[1] = [104.0]
    assert read("ttft_p95_s", r) == pytest.approx(np.percentile([3.0, 2.0], 95))


def test_itl_gaps_inside_the_window():
    r = _run(tokens={1: [99.0, 100.2, 100.25, 100.35], 2: [105.0, 105.5]})
    assert read("itl_p95_ms", r) == pytest.approx(
        1e3 * np.percentile([0.05, 0.1, 0.5], 95))


def test_engine_event_readers():
    ev = [{"ev": "admit", "ts": 102.0, "uid": 1},
          {"ev": "admit", "ts": 103.0, "uid": 2},
          {"ev": "admit", "ts": 99.0, "uid": 3},
          {"ev": "tick", "ts": 101.0, "n_active": 30},
          {"ev": "tick", "ts": 102.0, "n_active": 32},
          {"ev": "tick", "ts": 111.0, "n_active": 2}]
    r = _run(events=ev, due={1: 101.5, 2: 101.0, 3: 98.0})
    assert read("queue_wait_p95_s", r) == pytest.approx(np.percentile([0.5, 2.0], 95))
    assert read("decode_rows_mean", _run(events=ev)) == pytest.approx(31.0)


def _traced(**kw):
    ev = T.Event
    lin = ('custom-call(bf16[32,2560] %x, s8[1280,2560] %w), '
           'custom_call_target="tpu_custom_call"')
    trace = T.Trace(
        modules=[ev("jit_a(1)", 1.0, 1.05), ev("jit__argmax(2)", 1.06, 1.061),
                 ev("jit_a(1)", 1.1, 1.15)],
        ops=[ev("%c.1 = " + lin, 1.0, 1.02), ev("%c.2 = " + lin, 1.1, 1.12)],
        spans=[ev("bench.step", 0.99, 1.2), ev("bench.decode", 0.995, 0.996),
               ev("bench.decode", 1.095, 1.096)],
        n_devices=1)
    return _run(trace=trace, **kw)


def test_trace_readers():
    d = [Dispatch("decode", 32, 1, [1] * 32, [100] * 32)] * 2
    r = _traced(dispatches=d)
    assert read("decode_step_ms.batch", r) == pytest.approx(50.0)
    assert read("device_idle_share", r) == pytest.approx(
        100 * (1 - 0.101 / 0.21))
    ideal = 2 * sum(max(2 * 32 * k * n / 393e12,
                        (k * n / 2 + 4 * n + 64 * k + 64 * n) / 819e9)
                    for k, n in [(2560, 2560)] * 4 + [(2560, 6912)] * 2
                    + [(6912, 2560)]) * 32
    assert read("fused_qlinear_roofline", r) == pytest.approx(100 * ideal / 0.04)
    assert read("model_step_mfu", r) > 0
    # no paged-attention kernel in this trace: the reader finds nothing
    assert read("paged_attention_roofline", r) is None


def test_readers_without_a_trace_return_nothing():
    r = _run()
    for name in ("decode_step_ms.batch", "device_idle_share", "model_step_mfu",
                 "fused_qlinear_roofline", "paged_attention_roofline"):
        assert read(name, r) is None
