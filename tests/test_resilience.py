"""Resilience subsystem (docs/resilience.md): fault plane, numerical
guards, kernel circuit breaker, chaos invariants.

The contracts this suite pins:

  * the fault plane is DETERMINISTIC — per-spec arrival windows with
    uid/op filters, a seeded ``FaultPlan.random`` that replays
    identically, JSON round trip for ``serve.py --fault-plan``;
  * zero overhead when off — ``faults=None`` + ``nan_guard`` runs are
    token-identical to the seed engine with identical dispatch counts;
  * per-request guard isolation — a NaN-poisoned slot retires ``failed``
    with its pages freed and a quant-health-style escalation, while
    every surviving request's tokens are BIT-IDENTICAL to the
    fault-free run;
  * the kernel circuit breaker trips ONE failing dispatch to the XLA
    fallback jit (the tick completes), rides the fallback through the
    cooldown, then recovers on the half-open probe — with pinned
    counters, trace events and ``stats()`` surfacing;
  * chaos: under seeded random fault schedules + cancels, no request is
    lost or double-retired, accounting is exact, every page returns to
    the free list, and the JSONL trace replays to the identical summary
    through ``repro.obs`` — across all four model families.
"""

import functools
import json
import os
import tempfile

import jax
import numpy as np
import pytest

from repro.configs.base import get_config
from repro.core.qlinear import QuantPolicy
from repro.kernels import ops
from repro.models.api import get_model
from repro.obs import Observability, QuantHealthSampler, load_trace, summarize
from repro.resilience.faults import SITES, FaultInjected, FaultPlan, FaultSpec
from repro.serving.engine import (CompileError, PagedServingEngine,
                                  PerSlotServingEngine, Request,
                                  ServingEngine)
from repro.serving.fold import collect_calibration, fold_quantize
from tests._hypothesis_support import given, settings, st

KEY = jax.random.PRNGKey(0)

FAMILY_ARCHS = {
    "dense": "stablelm_3b",
    "moe": "deepseek_v2_lite_16b",
    "ssm": "mamba2_780m",
    "hybrid": "zamba2_12b",
}


@functools.lru_cache(maxsize=None)
def _setup(arch: str = "stablelm_3b", use_kernels: str | None = None):
    """(cfg, model, params, policy); ``use_kernels=None`` → bf16."""
    cfg = get_config(arch).reduced()
    model = get_model(cfg)
    params = model.init(KEY, cfg)
    policy = None
    if use_kernels is not None:
        toks = jax.random.randint(KEY, (2, 16), 0, cfg.vocab_size)
        stats = collect_calibration(model, params, cfg, [{"tokens": toks}])
        policy = QuantPolicy(weight_bits=8, act_bits=8, pack_weights=False,
                             use_kernels=use_kernels)
        params = fold_quantize(params, cfg, policy=policy, stats=stats)
    return cfg, model, params, policy


def _engine(cls=PagedServingEngine, arch="stablelm_3b", use_kernels=None,
            **kw):
    cfg, model, params, policy = _setup(arch, use_kernels)
    kw.setdefault("max_slots", 4)
    kw.setdefault("max_len", 32)
    if cls is PagedServingEngine:
        kw.setdefault("page_size", 4)
        kw.setdefault("prefill_bucket", 8)
    return cls(model, params, cfg, policy=policy, **kw)


def _prompts(n, arch="stablelm_3b"):
    cfg, _, _, _ = _setup(arch)
    return [np.random.default_rng(100 + i).integers(
        0, cfg.vocab_size, size=(3 + i % 4,)) for i in range(n)]


def _reqs(n, max_new=5, arch="stablelm_3b"):
    return [Request(uid=i, prompt=p, max_new_tokens=max_new)
            for i, p in enumerate(_prompts(n, arch))]


def _run(eng, reqs, max_ticks=300):
    for r in reqs:
        eng.submit(r)
    return {r.uid: r for r in eng.run(max_ticks=max_ticks)}


@functools.lru_cache(maxsize=None)
def _ref_tokens(cls_name: str, use_kernels: str | None, n=4, max_new=5,
                arch="stablelm_3b", **kw):
    """Fault-free reference tokens for the IDENTICAL engine shape (batch
    shape perturbs reduction order → greedy near-ties, so the twin run
    must match max_slots etc. exactly)."""
    cls = {"paged": PagedServingEngine, "batched": ServingEngine,
           "perslot": PerSlotServingEngine}[cls_name]
    done = _run(_engine(cls, arch, use_kernels, **kw),
                _reqs(n, max_new, arch))
    return {u: tuple(r.out_tokens) for u, r in done.items()}


@pytest.fixture
def clean_breaker():
    """Process-wide breaker: isolate and restore around breaker tests."""
    ops.breaker.reset()
    saved = ops.breaker.cooldown
    yield ops.breaker
    ops.breaker.cooldown = saved
    ops.breaker.reset()


# -- fault plane -----------------------------------------------------------


def test_fault_spec_validates_site():
    with pytest.raises(ValueError, match="unknown fault site"):
        FaultSpec("flux_capacitor")


def test_fire_arrival_windows_and_filters():
    plan = FaultPlan([
        FaultSpec("nan_logits", at=2, count=2, uid=7),
        FaultSpec("dispatch_raise", op="decode"),
    ])
    # uid filter: non-matching uids never advance the spec's arrivals
    assert plan.fire("nan_logits", uid=3) is None
    assert plan.fire("nan_logits", uid=7) is None          # arrival 0
    assert plan.fire("nan_logits", uid=7) is None          # arrival 1
    assert plan.fire("nan_logits", uid=7) is not None      # arrival 2: fires
    assert plan.fire("nan_logits", uid=7) is not None      # arrival 3: fires
    assert plan.fire("nan_logits", uid=7) is None          # window closed
    # op filter + default window (at=0, count=1): first match only
    assert plan.fire("dispatch_raise", op="prefill") is None
    spec = plan.fire("dispatch_raise", op="decode")
    assert spec is not None and spec.op == "decode"
    assert plan.fire("dispatch_raise", op="decode") is None
    assert [f["site"] for f in plan.fired] == ["nan_logits", "nan_logits",
                                               "dispatch_raise"]
    assert plan.fired[0]["arrival"] == 2 and plan.fired[2]["arrival"] == 0


def test_plan_json_round_trip_and_seeded_random():
    plan = FaultPlan.random(seed=42, n_faults=4, uids=(0, 1, 2),
                            delay_s=0.25)
    again = FaultPlan.random(seed=42, n_faults=4, uids=(0, 1, 2),
                             delay_s=0.25)
    assert plan.specs == again.specs                  # same seed, same plan
    assert plan.specs != FaultPlan.random(seed=43, n_faults=4,
                                          uids=(0, 1, 2)).specs
    back = FaultPlan.from_json(plan.to_json())
    assert back.specs == plan.specs
    assert all(s.site in SITES for s in back.specs)
    # serde is declarative only: arrival state does not travel
    assert back._arrivals == [0] * len(back.specs) and back.fired == []


def test_fault_injected_carries_site():
    exc = FaultInjected("dispatch_raise", "decode")
    assert exc.site == "dispatch_raise"
    assert "injected fault at dispatch_raise: decode" in str(exc)


# -- circuit breaker unit --------------------------------------------------


def test_breaker_state_machine(clean_breaker):
    b = clean_breaker
    b.cooldown = 2
    assert b.allow_native("decode")                   # closed
    assert b.record_success("decode") is False        # success while closed
    b.record_failure("decode")
    st_ = b.state()["decode"]
    assert st_["state"] == "open" and st_["trips"] == 1
    assert not b.allow_native("decode")               # cooldown 2 → refuse
    assert b.allow_native("decode")                   # countdown → half_open
    assert b.state()["decode"]["state"] == "half_open"
    assert b.record_success("decode") is True         # probe → recovery
    assert b.state()["decode"] == {"state": "closed", "trips": 1,
                                   "recoveries": 1, "until_probe": 0}
    # a failed probe re-opens and restarts the cooldown
    b.record_failure("decode")
    b.allow_native("decode")
    b.allow_native("decode")
    b.record_failure("decode")                        # half-open probe fails
    assert b.state()["decode"]["state"] == "open"
    assert b.state()["decode"]["trips"] == 3


def test_resolve_backend_consults_breaker(clean_breaker):
    clean_breaker.cooldown = 2
    ops.dispatch_resolutions(reset=True)
    assert ops.resolve_backend("interpret", op="decode") == "interpret"
    clean_breaker.record_failure("decode")
    assert ops.resolve_backend("interpret", op="decode") == "xla"
    assert ops.dispatch_resolutions()["breaker_fallback"] == 1
    # legacy op-less resolutions never consult the breaker
    assert ops.resolve_backend("interpret") == "interpret"
    # the forced-xla resolution counted down: next call is the probe
    assert ops.resolve_backend("interpret", op="decode") == "interpret"
    assert clean_breaker.state()["decode"]["state"] == "half_open"
    ops.dispatch_resolutions(reset=True)


# -- zero overhead when off ------------------------------------------------


def test_zero_overhead_when_off():
    """faults=None + nan_guard (clean logits) change nothing: tokens and
    dispatch counters identical to the seed engine."""
    ref = _run(_engine(), _reqs(4))
    guarded = _engine(nan_guard=True)
    got = _run(guarded, _reqs(4))
    for u in ref:
        assert list(got[u].out_tokens) == list(ref[u].out_tokens)
        assert not got[u].failed
    plain = _engine()
    _run(plain, _reqs(4))
    assert guarded.stats()["requests_failed"] == 0
    for k in ("decode_dispatches", "prefill_dispatches", "ticks"):
        assert getattr(guarded, k) == getattr(plain, k)


# -- numerical guard -------------------------------------------------------


@pytest.mark.parametrize("cls_name,cls", [
    ("paged", PagedServingEngine), ("batched", ServingEngine),
    ("perslot", PerSlotServingEngine)])
def test_guard_isolates_poisoned_request(cls_name, cls):
    """nan_logits on ONE uid: that request retires failed (pages freed),
    every survivor's tokens are bit-identical to the fault-free run."""
    ref = _ref_tokens(cls_name, None)
    obs = Observability()
    plan = FaultPlan([FaultSpec("nan_logits", uid=1, at=2)])
    eng = _engine(cls, obs=obs, faults=plan, nan_guard=True)
    done = _run(eng, _reqs(4))
    # prefill token + 2 decode ticks before arrival 2 fires
    assert done[1].failed and len(done[1].out_tokens) == 3
    for u in (0, 2, 3):
        assert tuple(done[u].out_tokens) == ref[u]    # bit-identical
        assert not done[u].failed
    assert not any(eng.slots)
    if cls is PagedServingEngine:
        assert eng.pages_in_use == 0
        assert sorted(eng._free) == list(range(eng.n_pages))
    assert eng.stats()["requests_failed"] == 1
    kinds = [e["ev"] for e in obs.tracer.events]
    assert "fault" in kinds and "guard" in kinds
    guard = next(e for e in obs.tracer.events if e["ev"] == "guard")
    assert guard["uid"] == 1 and guard["reason"] == "nonfinite_logits"
    retire = next(e for e in obs.tracer.events
                  if e["ev"] == "retire" and e["uid"] == 1)
    assert retire["failed"] is True
    c = obs.summary()["counts"]
    assert c["failed"] == 1 and c["guard_trips"] == 1
    assert c["faults_injected"] == 1
    # failed rows stream no token: decode accounting stays exact
    streamed = sum(len(r.out_tokens) for r in done.values())
    assert c["decode_tokens"] + obs.summary()["ttft_s"]["count"] == streamed


def test_guard_escalation_cites_worst_difficulty_layer():
    """With the quant-health sampler attached, the guard event escalates
    the (module, layer) whose Eq.-2 difficulty is worst for the failing
    request's context — the runtime counterpart of the passive
    sampler."""
    cfg, model, params, _ = _setup()
    obs = Observability(
        quant_health=QuantHealthSampler(model, params, cfg, every=10_000,
                                        bucket=8))
    plan = FaultPlan([FaultSpec("nan_logits", uid=0, at=1)])
    eng = _engine(obs=obs, faults=plan, nan_guard=True)
    done = _run(eng, _reqs(2))
    assert done[0].failed
    guard = next(e for e in obs.tracer.events if e["ev"] == "guard")
    assert guard["module"] and isinstance(guard["layer"], int)
    assert np.isfinite(guard["difficulty"])


def test_unguarded_engine_ignores_poison():
    """nan_guard off: the poisoned run completes without failing anyone
    (the guard is strictly opt-in)."""
    plan = FaultPlan([FaultSpec("nan_logits", uid=1, at=2)])
    done = _run(_engine(faults=plan), _reqs(4))
    assert not any(r.failed for r in done.values())
    assert len(plan.fired) == 1


# -- circuit breaker through the engine ------------------------------------


def test_breaker_trips_to_xla_and_recovers(clean_breaker):
    """An injected decode-dispatch failure on the interpret path: the
    tick completes on the XLA fallback jit (tokens identical to the
    fault-free run), the breaker rides the fallback through the
    cooldown, then the half-open probe recovers — counters pinned."""
    clean_breaker.cooldown = 2
    ref = _ref_tokens("paged", "interpret", max_slots=2)
    ops.dispatch_resolutions(reset=True)
    obs = Observability()
    plan = FaultPlan([FaultSpec("dispatch_raise", op="decode", at=2)])
    # max_slots=2 + cooldown=2: the trip, the open-circuit tick and the
    # recovering probe all land while uids 0/1 are in flight — at
    # positions where the never-lowered fallback jit and the interpret
    # path agree exactly (they DO diverge on greedy near-ties: uid 3's
    # trajectory differs between backends, which is why the schedule
    # closes the circuit before uids 2/3 ever decode).  That makes the
    # whole run bit-identical to the fault-free twin — the strongest
    # form of "the tick was never lost".
    eng = _engine(use_kernels="interpret", obs=obs, faults=plan,
                  max_slots=2)
    done = _run(eng, _reqs(4))
    for u, r in done.items():
        assert tuple(r.out_tokens) == ref[u]          # tick never lost
        assert not r.failed
    st_ = eng.stats()
    assert st_["breaker"]["decode"] == {"state": "closed", "trips": 1,
                                        "recoveries": 1, "until_probe": 0}
    disp = st_["dispatch_backends"]
    # the trip + ONE open resolution ride the fallback, both tallied
    # under dispatch.fallback.decode AND dispatch.decode.xla
    assert disp["fallback.decode"] == 2
    assert disp["decode.xla"] == 2
    assert disp["decode.interpret"] == eng.decode_dispatches - 2
    evs = [e for e in obs.tracer.events if e["ev"] == "breaker"]
    assert [e["action"] for e in evs] == ["trip", "recover"]
    assert all(e["op"] == "decode" for e in evs)
    c = obs.summary()["counts"]
    assert c["breaker_trips"] == 1 and c["breaker_recoveries"] == 1
    assert ops.dispatch_resolutions()["breaker_fallback"] == 1
    ops.dispatch_resolutions(reset=True)


def test_compile_failure_propagates_without_tripping(clean_breaker,
                                                     monkeypatch):
    """A native program that fails to lower — the fused qlinear's
    ``pallas_call`` refused while tracing, the way the TPU lowering
    refuses an illegal block — raises CompileError out of the engine:
    no trip, no fallback dispatch, so no XLA tick can hide the fault.
    Runtime faults still trip and recover
    (test_breaker_trips_to_xla_and_recovers pins that)."""
    from repro.kernels import fused_qlinear as fq

    def refuse(*a, **k):
        raise ValueError("block shape refused by the TPU lowering")

    monkeypatch.setattr(fq, "_pallas_call", refuse)
    obs = Observability()
    # a max_len no other test uses: its programs are not yet compiled
    eng = _engine(use_kernels="interpret", obs=obs, max_len=28)
    for r in _reqs(2):
        eng.submit(r)
    with pytest.raises(CompileError, match="refused by the TPU lowering"):
        eng.run(max_ticks=50)
    assert clean_breaker.state() == {}
    disp = eng.stats()["dispatch_backends"]
    assert not any(k.startswith("fallback.") for k in disp)
    assert obs.summary()["counts"]["breaker_trips"] == 0


def test_dispatch_raise_without_fallback_propagates():
    """bf16 engine (no fallback jit): the injected dispatch failure
    escapes step() — containment is the front-end watchdog's job
    (tests/test_frontend.py)."""
    plan = FaultPlan([FaultSpec("dispatch_raise", op="decode")])
    eng = _engine(faults=plan)
    for r in _reqs(2):
        eng.submit(r)
    with pytest.raises(FaultInjected, match="dispatch_raise"):
        eng.run(max_ticks=50)


def test_page_alloc_fail_defers_without_corruption():
    """An injected empty-pool report at admission defers the head of the
    queue one round; the request still completes token-identically."""
    ref = _ref_tokens("paged", None)
    obs = Observability()
    plan = FaultPlan([FaultSpec("page_alloc_fail", uid=2, op="admit")])
    eng = _engine(obs=obs, faults=plan)
    done = _run(eng, _reqs(4))
    for u, r in done.items():
        assert tuple(r.out_tokens) == ref[u]
    assert len(plan.fired) == 1
    assert eng.pages_in_use == 0


def test_slow_tick_delays_but_preserves_tokens():
    ref = _ref_tokens("paged", None)
    plan = FaultPlan([FaultSpec("slow_tick", at=1, delay_s=0.05)])
    eng = _engine(faults=plan)
    done = _run(eng, _reqs(4))
    for u, r in done.items():
        assert tuple(r.out_tokens) == ref[u]
    assert len(plan.fired) == 1


# -- chaos -----------------------------------------------------------------


def _chaos_run(seed: int, trace_path: str):
    """One seeded chaos episode on the quantized-interpret paged engine:
    a random fault schedule + a deterministic mid-run cancel."""
    rng = np.random.default_rng(seed)
    plan = FaultPlan.random(seed, n_faults=4,
                            sites=("nan_logits", "dispatch_raise",
                                   "page_alloc_fail", "slow_tick"),
                            uids=range(4), max_at=12)
    obs = Observability(trace_path=trace_path)
    eng = _engine(use_kernels="interpret", obs=obs, faults=plan,
                  nan_guard=True, max_slots=2, n_pages=10)
    reqs = _reqs(4)
    for r in reqs:
        eng.submit(r)
    cancel_uid = int(rng.integers(4))
    cancel_tick = int(rng.integers(1, 6))
    for _ in range(300):
        if not (eng.queue or any(s is not None for s in eng.slots)):
            break
        eng.step()
        if eng.ticks == cancel_tick:
            eng.cancel(cancel_uid)
    done = {r.uid: r for r in eng.pop_retired()}
    return eng, obs, done, plan


@settings(max_examples=5, deadline=None)
@given(st.integers(min_value=0, max_value=10_000))
def test_chaos_accounting_pages_and_replay(seed):
    """Chaos invariants (the tentpole's cap): every submitted request
    retires exactly once with exact accounting, every page returns to
    the free list, surviving requests are BIT-IDENTICAL to the
    fault-free run, and the JSONL trace replays to the identical
    summary through the ``repro.obs`` pipeline."""
    ops.breaker.reset()
    fd, path = tempfile.mkstemp(suffix=".jsonl")
    os.close(fd)
    try:
        eng, obs, done, plan = _chaos_run(seed, path)
        # exact accounting: completed + failed + cancelled == submitted
        assert sorted(done) == list(range(4))         # nobody lost/duped
        failed = sum(r.failed for r in done.values())
        cancelled = sum(r.cancelled and not r.failed
                        for r in done.values())
        completed = 4 - failed - cancelled
        assert completed + failed + cancelled == 4
        c = obs.summary()["counts"]
        assert c["submitted"] == 4 and c["retired"] == 4
        assert c["failed"] == failed and c["cancelled"] == cancelled
        # every page back in the free list, no slot occupied
        assert not any(eng.slots)
        assert eng.pages_in_use == 0
        assert sorted(eng._free) == list(range(eng.n_pages))
        # no lost/duplicated tokens for survivors; when no dispatch ever
        # rode the fallback jit the survivors are BIT-IDENTICAL to the
        # fault-free twin (a dispatch_raise legitimately switches the
        # executing backend for a tick, and interpret/xla diverge on
        # greedy near-ties — the breaker test pins that case)
        ref = _ref_tokens("paged", "interpret", max_slots=2, n_pages=10)
        clean = not any(f["site"] == "dispatch_raise" for f in plan.fired)
        for u, r in done.items():
            if not r.failed and not r.cancelled:
                assert len(r.out_tokens) == 5, (seed, u)
                if clean:
                    assert tuple(r.out_tokens) == ref[u], (seed, u)
        # trace replay: python -m repro.obs on the JSONL reproduces the
        # in-memory summary byte for byte
        mem = obs.summary()
        obs.close()
        assert summarize(load_trace(path)) == mem
    finally:
        os.unlink(path)
        ops.breaker.reset()


@pytest.mark.parametrize("family", sorted(FAMILY_ARCHS))
def test_chaos_accounting_every_family(family):
    """The guard + fault plane hold their accounting invariants on every
    cache family (dense / MoE-MLA / SSM / hybrid): all requests retire,
    pages restore, the poisoned request alone fails."""
    arch = FAMILY_ARCHS[family]
    obs = Observability()
    plan = FaultPlan([FaultSpec("nan_logits", uid=1, at=1),
                      FaultSpec("slow_tick", at=2, delay_s=0.01),
                      FaultSpec("page_alloc_fail", at=0, op="admit",
                                uid=2)])
    eng = _engine(arch=arch, obs=obs, faults=plan, nan_guard=True,
                  max_slots=2)
    done = _run(eng, _reqs(3, max_new=4, arch=arch))
    assert sorted(done) == [0, 1, 2]
    assert done[1].failed and not done[0].failed and not done[2].failed
    assert not any(eng.slots)
    assert eng.pages_in_use == 0
    assert sorted(eng._free) == list(range(eng.n_pages))
    c = obs.summary()["counts"]
    assert c["retired"] == 3 and c["failed"] == 1
    assert c["guard_trips"] == 1
