"""The readers of the engine's own records, on made-up runs whose answer
is known, and the shapes those records carry against what the
harness's patches record from outside, on one CPU run."""

import tempfile
import time

import pytest

from bench import serve_cell as sc
from bench import spec
from bench import tracefile as T
from bench.serve_cell import Dispatch, Run
from bench.test_check import tiny


def _run(**kw):
    c = spec.cell_of("stablelm-3b-w4a4", "decode-long")
    r = Run(cell=c, m=spec.model(c.config), seed=0, seconds=10.0,
            t0=100.0, t_end=110.0)
    for k, v in kw.items():
        setattr(r, k, v)
    return r


def read(name, run):
    return spec.read_metric(name, run)


def test_decode_copy_ms_reads_copies_inside_the_decode_runs():
    ev = T.Event
    mods = [ev("jit_decode_step(1)", 1.0, 1.1), ev("jit_prefill_chunk(2)", 1.1, 1.2),
            ev("jit_decode_step(1)", 1.2, 1.3)]
    ops = [ev("%while.1 = ...", 1.0, 1.09),
           ev("%copy.4 = s8[2] copy(s8[2] %a)", 1.01, 1.03),    # nested: 20 ms
           ev("%copy-start.2 = (...) copy-start(...)", 1.091, 1.092),
           ev("%copy-done.2 = s8[2] copy-done(...)", 1.093, 1.096),
           ev("%copy.9 = s8[2] copy(s8[2] %b)", 1.12, 1.15),    # in the prefill
           ev("%fusion.3 = ...", 1.2, 1.25),
           ev("%copy.4 = s8[2] copy(s8[2] %a)", 1.25, 1.29)]
    trace = T.Trace(modules=mods, ops=ops,
                    spans=[ev("bench.step", 0.99, 1.31)], n_devices=1)
    # (20 + 1 + 3) ms in the first run, 40 ms in the second
    assert read("decode_copy_ms", _run(trace=trace)) == pytest.approx(32.0)
    # a program of another name (the parent's jit__lambda) reads nothing
    for m in mods:
        m.name = "jit__lambda(7)"
    assert read("decode_copy_ms", _run(trace=trace)) is None
    assert read("decode_copy_ms", _run()) is None


def _prefill(t0, ts, n, padded, **kw):
    return {"ev": "prefill", "t0": t0, "ts": ts, "n_tokens": n,
            "padded_tokens": padded, **kw}


def test_prefill_readers_over_spans_starting_in_the_window():
    ev = [_prefill(101.0, 101.5, 200, 256),
          _prefill(105.0, 106.0, 300, 512, chunked=True),
          _prefill(99.0, 100.5, 1000, 1024),         # started before
          {"ev": "prefill", "ts": 103.0, "n_tokens": 50, "dur_s": 0.1},
          {"ev": "tick", "ts": 102.0, "n_active": 32}]
    r = _run(events=ev)
    assert read("prefill_ms_per_ktok", r) == pytest.approx(1500.0 / 0.5)
    assert read("prefill_useful_share", r) == pytest.approx(100 * 500 / 768)
    # events without the span fields (the parent's engine) read nothing
    old = _run(events=[{"ev": "prefill", "ts": 103.0, "n_tokens": 50,
                        "dur_s": 0.1}])
    assert read("prefill_ms_per_ktok", old) is None
    assert read("prefill_useful_share", old) is None
    assert read("prefill_ms_per_ktok", _run()) is None


def test_engine_events_carry_the_dispatch_shapes():
    """What ``serve_cell.instrument`` records around the engine's
    private methods, the engine's own ``prefill`` and ``tick`` events
    hold too, in the same order."""
    cell = tiny("stablelm-3b-w4a4")
    with tempfile.TemporaryDirectory() as d:
        run, eng = sc.serve(cell, 2**31 + 5, 3.0, trace=True,
                            t_start=time.perf_counter(), trace_dir=d)
    assert run.dispatches
    kinds = {d.kind for d in run.dispatches}
    assert kinds == {"decode", "prefill"}
    seq = []
    for e in run.events:
        if e["ev"] == "prefill":
            seq.append(Dispatch("prefill", e["rows"], e["padded_len"],
                                e["lens"], e["starts"]))
        elif e["ev"] == "tick":
            seq.append(Dispatch("decode", eng.max_slots, 1,
                                [1] * len(e["ctx"]), e["ctx"]))
    d = run.dispatches
    assert any(seq[i:i + len(d)] == d for i in range(len(seq) - len(d) + 1))
    for e in run.events:
        if e["ev"] == "prefill":
            assert e["padded_tokens"] == e["rows"] * e["padded_len"]
            assert sum(e["lens"]) == e["n_tokens"]
            assert e["t0"] < e["ts"]
