"""Program runs tied to the engine span that launched them, and idle
time by engine span, on made-up events and on a recorded chip trace.

``testdata/stablelm-3b-2layer-spans.xplane.pb.gz`` is a profiler trace of
six engine steps of stablelm-3b at published widths, two layers deep,
eight slots, W4A4 with an int8 paged pool, on one TPU v5e, taken by
``bench/trace_dump.py`` with the engine's own spans in it.
"""

import os

import pytest

from bench import engine_trace as E
from bench import spec
from bench import tracefile as T
from bench.serve_cell import Run

TRACE = os.path.join(os.path.dirname(__file__), "testdata",
                     "stablelm-3b-2layer-spans.xplane.pb.gz")


def _h(name, s, e, line, **stats):
    return E.HostEvent(name, s, e, line, stats)


def _made_up():
    """Three runs: one reached through three flow hops from a worker
    thread, one enqueued from the engine's own thread, one no flow leads
    back from."""
    spans = [_h("engine.step", 0.0, 10.0, "py"),
             _h("engine.decode", 1.0, 2.0, "py"),
             _h("engine.sample", 5.0, 6.0, "py")]
    host = {
        "py": [_h("Execute linkage", 1.3, 1.31, "py", _p="L1"),
               _h("Enqueue", 5.2, 5.3, "py", _p="R7")],
        "main": [_h("Execute", 1.3, 1.6, "main", _c="L1"),
                 _h("System::Execute", 1.4, 1.45, "main", _p="P1")],
        "worker": [_h("IssueSequencedEvent", 2.9, 3.2, "worker", _c="P1"),
                   _h("DoEnqueueProgram", 3.0, 3.1, "worker", _p="Q1")],
    }
    runs = [E.ProgramRun("jit_decode_step", 3.2, 4.0, "Q1"),
            E.ProgramRun("jit__argmax", 6.0, 6.5, "R7"),
            E.ProgramRun("jit_add", 8.0, 8.2, "Z9")]
    trace = T.Trace(
        modules=[T.Event(r.name + "(1)", r.start, r.end) for r in runs],
        ops=[], spans=[T.Event("bench.step", 0.0, 10.0)], n_devices=1)
    return E.EngineTrace(trace, spans, runs, host)


def test_runs_join_their_launching_span_by_ids():
    et = _made_up()
    got = [(r.name, s.name if s else None) for r, s in E.launches(et)]
    assert got == [("jit_decode_step", "engine.decode"),
                   ("jit__argmax", "engine.sample"), ("jit_add", None)]
    progs = {(s, p): (n, t) for s, p, n, t in E.programs_by_span(et)}
    assert progs[("engine.decode", "jit_decode_step")] == (1, pytest.approx(0.8))
    assert progs[("none", "jit_add")] == (1, pytest.approx(0.2))


def test_idle_by_engine_span_sums_to_the_breakdowns_idle():
    et = _made_up()
    # gaps 0-3.2 (middle in the decode span), 4-6 (sample), 6.5-8 and
    # 8.2-10 (only the step open)
    assert dict(E.idle_by_span(et)) == {
        "engine.decode": pytest.approx(3.2), "engine.sample": pytest.approx(2.0),
        "engine.step": pytest.approx(3.3)}
    total = sum(t for _, t in E.idle_by_span(et))
    assert total == pytest.approx(sum(t for _, t in T.idle_gaps(et.trace)))
    lines = E.report(et)
    assert lines[0].startswith("idle by engine span (s): total 8.500000;")
    assert "engine.decode > jit_decode_step x1 800.000" in lines[1]


@pytest.fixture(scope="module")
def spans_trace():
    return E.load(TRACE)


def test_recorded_trace_names_programs_and_kernels(spans_trace):
    tr = spans_trace.trace
    names = {r.name for r in spans_trace.runs}
    assert "jit_decode_step" in names and "jit_prefill_paged" not in names
    assert not [n for n in names if n == "jit__lambda"]
    # the kernels' ops are named after the kernels
    custom = [e.name for e in tr.ops if "tpu_custom_call" in e.name]
    assert custom
    for name in custom:
        head = name.split(" = ", 1)[0]
        assert head.startswith(("%fused_qlinear", "%paged_attention")), head
    kinds = [T.op_kind(n) for n in custom]
    assert kinds.count("fused_qlinear") == 6 * 2 * 7
    assert kinds.count("paged_attention") == 6 * 2


def test_recorded_trace_joins_every_run(spans_trace):
    got = E.launches(spans_trace)
    assert got and all(s is not None for _, s in got)
    decode = [(r, s) for r, s in got if r.name == "jit_decode_step"]
    assert len(decode) == 6
    assert all(s.name == "engine.decode" for _, s in decode)
    # one decode span per decode run
    assert len({(s.start, s.end) for _, s in decode}) == 6
    steps = [s for s in spans_trace.engine_spans if s.name == "engine.step"]
    assert len(steps) == 6
    idle = sum(t for _, t in E.idle_by_span(spans_trace))
    assert idle == pytest.approx(
        sum(t for _, t in T.idle_gaps(spans_trace.trace)), rel=1e-9)


def test_recorded_trace_decode_copy(spans_trace):
    c = spec.cell_of("stablelm-3b-w4a4", "decode-long")
    run = Run(cell=c, m=spec.model(c.config), seed=0, seconds=1.0,
              trace=spans_trace.trace)
    # the copies of two layers' pool (8 slots) per decode run
    assert spec.read_metric("decode_copy_ms", run) == pytest.approx(
        0.443162, rel=1e-5)
