"""Observability layer (repro.obs + docs/observability.md).

Pins the contracts the serving stack leans on:

  * metrics primitives under a fake clock — counters, gauges,
    fixed-bucket histograms with EXACT nearest-rank percentiles;
  * trace JSONL round trip: emit → load_trace → summarize reproduces
    the in-memory summary byte-for-byte;
  * deterministic span math: hand-built event streams give exact TTFT /
    per-token / queue-wait numbers (no wall clock involved);
  * tracing is FREE to turn on and off: with obs attached the engines'
    greedy tokens are IDENTICAL to an untraced run (all three engines,
    paged included), and with obs off they emit zero events and issue
    exactly the same jitted dispatches;
  * the three engines report ONE run_stats schema (satellite of the
    obs PR: stats() lives once in _EngineBase);
  * kernels.ops.dispatch_resolutions tallies every resolve_backend
    outcome;
  * quant-health sampling reports per-layer absmax / clip-fraction /
    Eq.-2 difficulty keyed like the autoplan telemetry.
"""

import functools
import json

import jax
import numpy as np
import pytest

from repro.configs.base import get_config
from repro.kernels import ops
from repro.models.api import get_model
from repro.obs import (ManualClock, MetricsRegistry, Observability,
                       QuantHealthSampler, Tracer, exact_percentile,
                       format_summary, load_trace, percentile_summary,
                       summarize)
from repro.serving.engine import (EngineConfig, PagedServingEngine,
                                  PerSlotServingEngine, Request,
                                  ServingEngine)

KEY = jax.random.PRNGKey(0)

ENGINES = {
    "per_slot": PerSlotServingEngine,
    "batched": ServingEngine,
    "paged": functools.partial(PagedServingEngine, page_size=4,
                               prefill_bucket=8),
}


@functools.lru_cache(maxsize=None)
def _setup():
    cfg = get_config("stablelm_3b").reduced()
    model = get_model(cfg)
    return cfg, model, model.init(KEY, cfg)


def _requests(cfg, n=4, max_new=5):
    return [Request(uid=i,
                    prompt=np.random.default_rng(i).integers(
                        0, cfg.vocab_size, size=(3 + i,)),
                    max_new_tokens=max_new) for i in range(n)]


# ---------------------------------------------------------------------------
# metrics primitives
# ---------------------------------------------------------------------------


def test_manual_clock():
    clk = ManualClock()
    t0 = clk()
    clk.advance(1.5)
    assert clk() - t0 == pytest.approx(1.5)


def test_counter_and_gauge():
    reg = MetricsRegistry()
    c = reg.counter("c")
    c.inc()
    c.inc(2.5)
    assert c.value == pytest.approx(3.5)
    with pytest.raises(ValueError):
        c.inc(-1)
    # create-on-first-use returns the SAME instrument
    assert reg.counter("c") is c
    g = reg.gauge("g")
    g.set(7)
    g.set(-2)
    assert g.value == -2


def test_exact_percentiles_nearest_rank():
    xs = sorted(float(v) for v in range(1, 101))    # 1..100
    assert exact_percentile(xs, 50) == 50.0
    assert exact_percentile(xs, 90) == 90.0
    assert exact_percentile(xs, 99) == 99.0
    assert exact_percentile(xs, 100) == 100.0
    s = percentile_summary(list(reversed(xs)))
    assert s["count"] == 100 and s["p50"] == 50.0 and s["p99"] == 99.0
    assert s["min"] == 1.0 and s["max"] == 100.0
    assert percentile_summary([])["count"] == 0


def test_histogram_buckets_and_summary():
    reg = MetricsRegistry()
    h = reg.histogram("h", buckets=(0.1, 1.0, 10.0))
    for v in (0.05, 0.5, 0.5, 5.0, 50.0):
        h.observe(v)
    # per-bucket ≤-upper-bound counts; the trailing slot catches overflow
    assert h.bucket_counts == [1, 2, 1, 1]
    s = h.summary()
    assert s["count"] == 5 and s["max"] == 50.0
    assert s["p50"] == 0.5
    assert s["overflow"] == 1
    assert reg.histogram("h") is h


def test_registry_prefix_and_snapshot():
    reg = MetricsRegistry()
    reg.counter("dispatch.decode.xla").inc(3)
    reg.counter("dispatch.prefill.xla").inc()
    reg.counter("other").inc()
    assert reg.counters_with_prefix("dispatch.") == {
        "decode.xla": 3.0, "prefill.xla": 1.0}
    snap = reg.snapshot()
    assert snap["counters"]["other"] == 1.0
    assert set(snap) == {"counters", "gauges", "histograms"}


# ---------------------------------------------------------------------------
# tracing + summary math
# ---------------------------------------------------------------------------


def _hand_events():
    """Two requests with hand-picked timestamps (no clock involved)."""
    return [
        {"ev": "submit", "ts": 0.0, "uid": 1, "prompt_len": 4},
        {"ev": "submit", "ts": 1.0, "uid": 2, "prompt_len": 6},
        {"ev": "admit", "ts": 2.0, "uid": 1, "slot": 0, "queue_wait_s": 2.0,
         "resumed": False},
        {"ev": "prefill", "ts": 3.0, "n_requests": 1, "n_tokens": 4,
         "rows": 1, "padded_len": 4, "dur_s": 1.0},
        {"ev": "first_token", "ts": 3.0, "uid": 1, "ttft_s": 3.0},
        {"ev": "admit", "ts": 4.0, "uid": 2, "slot": 1, "queue_wait_s": 3.0,
         "resumed": True},
        {"ev": "first_token", "ts": 5.0, "uid": 2, "ttft_s": 4.0},
        {"ev": "tick", "ts": 7.0, "tick": 1, "n_active": 2, "uids": [1, 2],
         "dur_s": 2.0, "alloc_dur_s": 0.5},
        {"ev": "tick", "ts": 10.0, "tick": 2, "n_active": 1, "uids": [1],
         "dur_s": 3.0, "alloc_dur_s": 1.0},
        {"ev": "preempt", "ts": 10.5, "uid": 2, "slot": 1, "n_generated": 2},
        {"ev": "retire", "ts": 11.0, "uid": 1, "prompt_len": 4,
         "decode_tokens": 3, "e2e_s": 11.0},
        # uid 2 resumes: queue wait runs from the REQUEUE at 10.5 (not
        # the original submit at 1.0), and the resume-prefill token gets
        # its own ``token`` event joining the per-token chain
        {"ev": "admit", "ts": 12.0, "uid": 2, "slot": 0, "queue_wait_s": 1.5,
         "resumed": True},
        {"ev": "token", "ts": 13.0, "uid": 2, "resumed": True},
        # front-end events ride the same schema
        {"ev": "shed", "ts": 13.5, "queue_depth": 5, "occupancy": 0.8,
         "score": 4.0},
        {"ev": "deadline", "ts": 14.0, "uid": 2, "deadline_s": 10.0,
         "n_streamed": 3},
        {"ev": "retire", "ts": 15.0, "uid": 2, "prompt_len": 6,
         "decode_tokens": 3, "e2e_s": 14.0, "cancelled": True},
    ]


def test_summarize_exact_numbers():
    s = summarize(_hand_events())
    assert s["counts"] == {"submitted": 2, "admitted": 3, "retired": 2,
                           "preemptions": 1, "resumes": 2, "decode_tokens": 4,
                           "prefill_tokens": 4, "ticks": 2, "cancelled": 1,
                           "deadline_expired": 1, "shed": 1, "failed": 0,
                           "faults_injected": 0, "guard_trips": 0,
                           "breaker_trips": 0, "breaker_recoveries": 0,
                           "watchdog_restarts": 0, "disconnects": 0}
    assert s["ttft_s"]["count"] == 2
    assert s["ttft_s"]["p50"] == 3.0 and s["ttft_s"]["max"] == 4.0
    # uid 1 token ts: 3, 7, 10 → deltas 4, 3;  uid 2: 5, 7, 13 → 2, 6
    assert s["per_token_s"]["count"] == 4
    assert s["per_token_s"]["min"] == 2.0 and s["per_token_s"]["max"] == 6.0
    assert s["queue_wait_s"]["mean"] == pytest.approx(6.5 / 3)
    assert s["tick_alloc_s"]["count"] == 2
    assert s["tick_decode_s"]["max"] == pytest.approx(2.0)  # 3.0 - 1.0
    assert s["e2e_s"]["count"] == 2 and s["e2e_s"]["max"] == 14.0
    # the human table renders without error and carries the counts,
    # front-end outcome, and end-to-end rows
    table = format_summary(s)
    assert "2 submitted" in table
    assert "front-end: 1 shed, 1 deadline-expired, 1 cancelled" in table
    assert "| end-to-end | 2 |" in table


def test_format_summary_no_frontend_line_when_clean():
    """Offline runs (no sheds/deadlines/cancels) keep the pre-front-end
    table layout: no front-end outcome line appears."""
    events = [ev for ev in _hand_events()
              if ev["ev"] not in ("shed", "deadline")
              and not ev.get("cancelled")]
    assert "front-end:" not in format_summary(summarize(events))


def test_tracer_jsonl_roundtrip(tmp_path):
    path = tmp_path / "trace.jsonl"
    with Tracer(str(path)) as tr:
        for ev in _hand_events():
            kind = ev.pop("ev")
            tr.emit(kind, **ev)
        mem = summarize(tr.events)
    loaded = load_trace(str(path))
    assert summarize(loaded) == mem
    # every line is standalone JSON with the schema fields
    with open(path) as fh:
        for line in fh:
            rec = json.loads(line)
            assert "ev" in rec and "ts" in rec


def test_tracer_rejects_unknown_event():
    tr = Tracer()
    with pytest.raises(ValueError):
        tr.emit("not_an_event", ts=0.0)


# ---------------------------------------------------------------------------
# engines under observability
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("name", sorted(ENGINES))
def test_tracing_token_identical_and_zero_overhead(name):
    """obs on/off must not change a single sampled token, and obs OFF
    must cost nothing: zero trace events, identical dispatch counts."""
    cfg, model, params = _setup()
    cls = ENGINES[name]

    def serve(obs):
        eng = cls(model, params, cfg, max_slots=2, max_len=64, obs=obs)
        for r in _requests(cfg):
            eng.submit(r)
        done = eng.run(max_ticks=500)
        return eng, {r.uid: list(r.out_tokens) for r in done}

    eng_off, toks_off = serve(None)
    obs = Observability(clock=ManualClock())
    eng_on, toks_on = serve(obs)
    assert toks_on == toks_off
    # same jitted work either way
    assert eng_on.decode_dispatches == eng_off.decode_dispatches
    assert eng_on.prefill_dispatches == eng_off.prefill_dispatches
    assert eng_on.ticks == eng_off.ticks
    # obs off: nothing was traced anywhere
    assert eng_off.obs is None and eng_off._tracer is None
    # obs on: the trace tells the full request story
    s = obs.summary()
    assert s["counts"]["submitted"] == s["counts"]["retired"] == 4
    assert s["counts"]["decode_tokens"] == sum(
        len(t) for t in toks_on.values()) - 4   # first tokens from prefill
    assert s["ttft_s"]["count"] == 4
    assert s["per_token_s"]["count"] == s["counts"]["decode_tokens"]


def test_run_stats_schema_identical_across_engines():
    """ONE stats() implementation: every engine reports the same keys
    (the paged engine adds only its page-pool block on top)."""
    cfg, model, params = _setup()
    schemas = {}
    for name, cls in ENGINES.items():
        eng = cls(model, params, cfg, max_slots=2, max_len=64)
        for r in _requests(cfg, n=2, max_new=3):
            eng.submit(r)
        eng.run(max_ticks=200)
        schemas[name] = set(eng.run_stats)
    assert schemas["per_slot"] == schemas["batched"]
    pool_keys = {"page_size", "n_pages", "table_width", "pages_in_use",
                 "peak_pages_in_use", "page_occupancy",
                 "page_occupancy_peak", "paged_attention_backend",
                 "prefill_chunk", "chunked_prefill", "prefix", "spec"}
    assert schemas["paged"] == schemas["batched"] | pool_keys
    base_keys = {"requests", "prefill_tokens", "decode_tokens",
                 "per_request", "ticks", "decode_dispatches",
                 "prefill_dispatches", "dispatches_per_tick",
                 "kernel_backend", "dispatch_backends"}
    assert base_keys <= schemas["batched"]


def test_engine_dispatch_attribution():
    """Per-backend dispatch counters match the legacy dispatch counts;
    no analytic byte model rides along (bench/work.py counts bytes)."""
    cfg, model, params = _setup()
    obs = Observability(clock=ManualClock())
    eng = ENGINES["paged"](model, params, cfg, max_slots=2, max_len=64,
                           obs=obs)
    for r in _requests(cfg, n=3, max_new=4):
        eng.submit(r)
    eng.run(max_ticks=200)
    st = eng.run_stats
    assert st["dispatch_backends"]["decode.bf16"] == st["decode_dispatches"]
    assert st["dispatch_backends"]["prefill.bf16"] == st["prefill_dispatches"]
    # the paged engine also attributes its decode-attention executor
    pa = st["paged_attention_backend"]
    assert st["dispatch_backends"][f"paged_attention.{pa}"] == st["ticks"]
    assert "hbm_modeled_bytes" not in st


def _preemption_run():
    """Tiny-pool paged run under a ManualClock: both prompts fill the
    pool exactly, so decode growth forces a preemption + resume."""
    cfg, model, params = _setup()
    clk = ManualClock()
    obs = Observability(clock=clk)
    eng = PagedServingEngine(model, params, cfg, max_slots=2, max_len=32,
                             page_size=4, prefill_bucket=8, n_pages=2,
                             obs=obs)
    for r in (Request(uid=0, prompt=np.arange(1, 5), max_new_tokens=3),
              Request(uid=1, prompt=np.arange(3, 7), max_new_tokens=3)):
        eng.submit(r)
    done = []
    for _ in range(100):
        clk.advance(1.0)
        eng.step()
        done += eng.pop_retired()
        if not eng.queue and not any(eng.slots):
            break
    assert not eng.queue and not any(eng.slots), "run did not drain"
    return eng, obs, done


def test_resumed_queue_wait_measured_from_requeue():
    """The preemption-era latency fix: a resumed request's queue wait
    runs from the REQUEUE (the preempt tick), not the original submit —
    otherwise the first service period is double-counted."""
    _, obs, _ = _preemption_run()
    events = obs.tracer.events
    preempts = [e for e in events if e["ev"] == "preempt"]
    assert preempts, "workload no longer preempts"
    for pre in preempts:
        resumed = next(e for e in events
                       if e["ev"] == "admit" and e["uid"] == pre["uid"]
                       and e.get("resumed") and e["ts"] >= pre["ts"])
        # submits happened at t=0, preempts strictly later: measuring
        # from the original submit would give queue_wait == ts
        assert resumed["queue_wait_s"] == pytest.approx(
            resumed["ts"] - pre["ts"])
        assert resumed["queue_wait_s"] < resumed["ts"]


def test_trace_token_counts_match_engine_under_preemption():
    """The resume-prefill token gets a ``token`` event, so the
    trace-derived token count equals the engine's: first_token +
    decode_tokens events == every token every client streamed."""
    eng, obs, done = _preemption_run()
    s = obs.summary()
    streamed = sum(len(r.out_tokens) for r in done)
    assert streamed == eng.stats()["decode_tokens"]
    assert s["counts"]["decode_tokens"] + s["ttft_s"]["count"] == streamed
    assert s["counts"]["resumes"] >= 1
    # every non-first streamed token contributes one inter-token gap
    assert s["per_token_s"]["count"] == s["counts"]["decode_tokens"]


# ---------------------------------------------------------------------------
# speculative-decoding accounting (docs/speculative.md)
# ---------------------------------------------------------------------------


def _spec_run(spec_k=4):
    cfg, model, params = _setup()
    clk = ManualClock()
    obs = Observability(clock=clk)
    eng = PagedServingEngine(
        model, params, cfg,
        config=EngineConfig(max_slots=2, max_len=32, page_size=4,
                            prefill_bucket=8, spec_k=spec_k, obs=obs))
    for r in _requests(cfg, n=3, max_new=5):
        eng.submit(r)
    done = []
    for _ in range(200):
        clk.advance(1.0)
        eng.step()
        done += eng.pop_retired()
        if not eng.queue and not any(eng.slots):
            break
    assert not eng.queue and not any(eng.slots), "run did not drain"
    return eng, obs, done


def test_spec_trace_token_counts_match_engine():
    """Accepted tokens past a tick's first ride extra ``token`` events,
    so trace-derived token accounting stays exact under speculation:
    first_token + decode_tokens events == every token every client
    streamed, per-uid per-token chains cover each ACCEPTED token, and
    the trace's spec block reconciles with the engine counters."""
    eng, obs, done = _spec_run()
    s = obs.summary()
    streamed = sum(len(r.out_tokens) for r in done)
    assert streamed == eng.stats()["decode_tokens"]
    assert s["counts"]["decode_tokens"] + s["ttft_s"]["count"] == streamed
    # every non-first streamed token contributes one inter-token gap
    assert s["per_token_s"]["count"] == s["counts"]["decode_tokens"]
    # a verify tick with > 1 accepted token actually occurred (otherwise
    # this test pins nothing beyond the plain-path one above)
    assert s["spec"]["emitted"] > s["spec"]["ticks"]
    # decode-phase tokens all went through verify ticks
    assert s["spec"]["emitted"] == s["counts"]["decode_tokens"]
    est = eng.stats()["spec"]
    assert s["spec"]["emitted"] == est["emitted_tokens"]
    assert s["spec"]["ticks"] == est["verify_dispatches"]
    assert s["spec"]["accepted"] == est["accepted"]


def test_summarize_spec_exact_and_table_line():
    """Hand-built spec events: exact aggregation plus the conditional
    ``spec:`` table line — both absent from plain-run summaries."""
    events = _hand_events()
    assert "spec" not in summarize(events)
    assert "spec:" not in format_summary(summarize(events))
    events += [
        {"ev": "spec", "ts": 7.0, "tick": 1, "drafted": 4, "accepted": 3,
         "rejected": 1, "emitted": 5, "n_rows": 2},
        {"ev": "spec", "ts": 10.0, "tick": 2, "drafted": 2, "accepted": 2,
         "rejected": 0, "emitted": 3, "n_rows": 1},
    ]
    s = summarize(events)
    assert s["spec"] == {"ticks": 2, "drafted": 6, "accepted": 5,
                         "rejected": 1, "emitted": 8,
                         "acceptance_rate": 5 / 6}
    assert ("spec: 6 drafted, 5 accepted (rate 0.833), 1 rejected, "
            "8 emitted over 2 verify ticks") in format_summary(s)


def test_dispatch_resolutions_tally():
    ops.dispatch_resolutions(reset=True)
    ops.resolve_backend("never")
    ops.resolve_backend("never")
    ops.resolve_backend("interpret")
    ops.resolve_backend("auto")
    counts = ops.dispatch_resolutions(reset=True)
    assert counts["xla"] >= 2 and counts["interpret"] == 1
    assert sum(counts.values()) == 4
    assert ops.dispatch_resolutions() == {}


# ---------------------------------------------------------------------------
# quant-health sampling
# ---------------------------------------------------------------------------


def test_quant_health_sampler_smoke():
    cfg, model, params = _setup()
    qh = QuantHealthSampler(model, params, cfg, every=2, bucket=8)
    assert qh.due(0) and qh.due(2) and not qh.due(3)
    ctx = np.arange(5) % cfg.vocab_size
    rec = qh.sample(2, 7, ctx)
    assert rec["uid"] == 7 and rec["context_len"] == 5
    assert rec["modules"], "no linear-input taps collected"
    for m, sig in rec["modules"].items():
        assert len(sig["absmax"]) == len(sig["difficulty"]) >= 1
        assert all(v >= 0 for v in sig["absmax"])
        assert sig["clip_fraction"] is None      # no calibration reference
    assert qh.samples == [rec]


def test_quant_health_clip_fraction_with_reference():
    from repro.serving.fold import collect_calibration

    cfg, model, params = _setup()
    toks = jax.random.randint(KEY, (2, 16), 0, cfg.vocab_size)
    stats = collect_calibration(model, params, cfg, [{"tokens": toks}])
    qh = QuantHealthSampler(model, params, cfg, every=1, reference=stats,
                            bucket=8)
    rec = qh.sample(1, 0, np.arange(6) % cfg.vocab_size)
    clips = [c for sig in rec["modules"].values()
             for c in (sig["clip_fraction"] or [])]
    assert clips, "calibration reference given but no clip fractions"
    assert all(0.0 <= c <= 1.0 for c in clips)


@pytest.mark.parametrize("tap_forward", ["calibration", "quant_health"])
def test_tap_forward_takes_params_as_argument(tap_forward):
    """The with-taps forwards of calibration and quant-health sampling
    pass the params to their jit as an argument.  Closed over, the
    params are embedded in the program as constants: at stablelm-3b's
    full width that is 5.3 GB of literals, and compiling it exhausts a
    TPU host's memory."""
    import warnings

    from repro.serving.fold import collect_calibration

    cfg, model, params = _setup()
    nbytes = sum(a.nbytes for a in jax.tree.leaves(params))
    toks = jax.random.randint(KEY, (2, 16), 0, cfg.vocab_size)
    prev = jax.config.jax_captured_constants_warn_bytes
    jax.config.update("jax_captured_constants_warn_bytes", nbytes // 2)
    try:
        with warnings.catch_warnings():
            warnings.filterwarnings("error", message=".*constants were "
                                    "captured")
            if tap_forward == "calibration":
                stats = collect_calibration(model, params, cfg,
                                            [{"tokens": toks}])
                assert stats
            else:
                qh = QuantHealthSampler(model, params, cfg, every=1, bucket=8)
                assert qh.sample(1, 0, np.arange(6) % cfg.vocab_size)
    finally:
        jax.config.update("jax_captured_constants_warn_bytes", prev)


def test_quant_health_in_engine_and_summary():
    """--quant-health wiring end to end: due() gates on ticks, events
    land in the trace, the summary aggregates per module."""
    cfg, model, params = _setup()
    qh = QuantHealthSampler(model, params, cfg, every=2, bucket=8)
    obs = Observability(clock=ManualClock(), quant_health=qh)
    eng = ENGINES["batched"](model, params, cfg, max_slots=2, max_len=64,
                             obs=obs)
    for r in _requests(cfg, n=2, max_new=4):
        eng.submit(r)
    eng.run(max_ticks=200)
    assert qh.samples, "sampler never fired"
    s = obs.summary()
    assert "quant_health" in s
    for m, agg in s["quant_health"].items():
        assert agg["samples"] >= 1 and agg["absmax_max"] >= 0
    assert "| module |" in format_summary(s)
