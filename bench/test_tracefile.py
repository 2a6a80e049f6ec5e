"""The trace reduction, on a recorded chip trace and on made-up events.

``testdata/stablelm-3b-2layer.xplane.pb.gz`` is a profiler trace of six
engine steps of stablelm-3b at published widths, two layers deep, eight
slots, W4A4 with an int8 paged pool, on one TPU v5e (``bench/trace_dump.py``).
"""

import os

import pytest

from bench import tracefile as T

TRACE = os.path.join(os.path.dirname(__file__), "testdata",
                     "stablelm-3b-2layer.xplane.pb.gz")


@pytest.fixture(scope="module")
def chip_trace():
    return T.load(TRACE)


def test_recorded_trace_planes(chip_trace):
    assert chip_trace.n_devices == 1
    assert len([e for e in chip_trace.spans if e.name == "bench.step"]) == 6
    # the step spans dispatched six runs of one decode program, ~2.9 ms each
    progs = T.program_runs(chip_trace, "bench.step")
    assert len(progs) == 6
    assert all(p.name.startswith("jit__lambda(") for p in progs)
    assert all(2.8e-3 < p.dur < 3.1e-3 for p in progs)
    assert len({p.name for p in progs}) == 1


def test_recorded_trace_busy_and_idle(chip_trace):
    lo, hi = chip_trace.window()
    assert hi - lo == pytest.approx(0.0405228, rel=1e-6)
    busy = T.busy_seconds(chip_trace)
    assert busy == pytest.approx(0.017656109, rel=1e-6)
    gaps = dict(T.idle_gaps(chip_trace))
    # every idle gap falls inside a step span, and busy + idle = window
    assert set(gaps) == {"bench.step"}
    assert busy + sum(gaps.values()) == pytest.approx(hi - lo, rel=1e-9)


def test_recorded_trace_kernels(chip_trace):
    # 6 steps x 2 layers: 7 quantized linears and one paged attention each
    kinds = [T.op_kind(e.name) for e in chip_trace.ops]
    assert kinds.count("fused_qlinear") == 6 * 2 * 7
    assert kinds.count("paged_attention") == 6 * 2
    assert T.kernel_seconds(chip_trace, "fused_qlinear") == pytest.approx(
        0.009834414, rel=1e-6)
    assert T.kernel_seconds(chip_trace, "paged_attention") == pytest.approx(
        0.001074569, rel=1e-6)
    top = dict(T.top_ops(chip_trace))
    assert max(top, key=top.get) == "fused_qlinear"
    # self times never double-count the layer scan around the kernels
    assert sum(top.values()) <= T.busy_seconds(chip_trace) * 1.001


def _trace(modules, spans, ops=()):
    ev = lambda n, s, e: T.Event(n, s, e)
    return T.Trace(modules=[ev(*m) for m in modules], ops=[ev(*o) for o in ops],
                   spans=[ev(*s) for s in spans], n_devices=1)


def test_union_and_idle_by_span():
    tr = _trace(modules=[("a", 1.0, 2.0), ("b", 1.5, 3.0), ("c", 5.0, 6.0)],
                spans=[("bench.step", 0.0, 8.0), ("bench.sample", 3.0, 4.0)])
    assert T.union([(1.0, 2.0), (1.5, 3.0), (5.0, 6.0)]) == [(1.0, 3.0), (5.0, 6.0)]
    assert T.busy_seconds(tr) == pytest.approx(3.0)
    # gaps 0-1, 3-5 and 6-8; each goes to the innermost span open at its
    # middle (a span is open on [start, end), so 4.0 is past the sample)
    assert dict(T.idle_gaps(tr)) == {"bench.step": pytest.approx(5.0)}
    tr.spans[1] = T.Event("bench.sample", 3.0, 4.5)
    assert dict(T.idle_gaps(tr)) == {"bench.step": pytest.approx(3.0),
                                     "bench.sample": pytest.approx(2.0)}


def test_self_time_of_nested_ops():
    ops = [T.Event("%while.1 = ...", 0.0, 10.0), T.Event("%k.1 = ...", 1.0, 3.0),
           T.Event("%k.2 = ...", 4.0, 5.0), T.Event("%copy.3 = ...", 11.0, 12.0)]
    got = {e.name: t for e, t in T.self_times(ops)}
    assert got["%while.1 = ..."] == pytest.approx(7.0)
    assert got["%copy.3 = ..."] == pytest.approx(1.0)


def test_op_kind_names():
    assert T.op_kind("%copy.96 = f32[64] copy(f32[64] %x)") == "copy"
    paged = ('%closed_call.81 = bf16[8,32,1,80] custom-call(s32[8,8]{1,0:T(8,128)} '
             '%t, s32[8]{0:T(128)} %l, s8[64,64,32,80] %k), '
             'custom_call_target="tpu_custom_call"')
    lin = ('%closed_call.78 = bf16[8,2560] custom-call(bf16[8,2560] %x, '
           's8[1280,2560] %w), custom_call_target="tpu_custom_call"')
    assert T.op_kind(paged) == "paged_attention"
    assert T.op_kind(lin) == "fused_qlinear"
