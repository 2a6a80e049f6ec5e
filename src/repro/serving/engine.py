"""Batched serving engine: continuous batching over a fixed slot grid.

The engine owns ONE slot-major cache pytree (``max_slots`` sequences ×
``max_len`` positions — ``common.batch_slot_cache`` over the family's
``make_cache``) and runs two jitted programs:

  * ``prefill``     — admit one request into a free slot (prompt → a
    batch-1 cache, copied into the slot with ``common.write_slot``)
  * ``decode_step`` — ONE ``(max_slots, 1)`` program per tick for EVERY
    active slot (the batched shape the roofline decode cells model),
    with per-slot positions threaded through the cache ``length``
    vector and vectorized sampling over the slot axis.

Requests are queued, admitted as slots free up, sampled greedily or by
temperature, and retired on EOS/max_tokens — vLLM-style continuous
batching reduced to its JAX-native core.  Weights may be the bf16 train
params or the fold+quantized serving params (the paper's pipeline).

``PagedServingEngine`` rebuilds the memory and admission layers on top
of the batched tick: the dense per-slot ``(max_slots, max_len)`` extents
become fixed-size PAGES from a shared pool (``common.PagedKVCache``) so
slots grow on demand and freed pages return to the pool, and admission
runs ONE jitted ``(n_admit, padded_prompt_len)`` ``prefill_paged``
dispatch that writes straight into the assigned pages — replacing the
per-request batch-1 prefill + ``write_slot`` copy.  Mixed prompt lengths
share the dispatch through length-bucketed padding.

``PerSlotServingEngine`` preserves the original one-dispatch-per-slot
loop as the equivalence/throughput baseline: batched AND paged greedy
output are token-identical to it (tests/test_serving_batched.py,
tests/test_serving_paged.py), while issuing ``1`` decode dispatch per
tick instead of ``n_active``.

jit caches are shared process-wide per (model, cfg, policy), so
constructing many engines (property tests, benchmarks) does not retrace.

Every program is a named function, so a profiler trace shows
``jit_decode_step``, ``jit_prefill_paged``, ``jit_prefill_chunk`` ... and
not ``jit__lambda``.  The engine opens ``jax.profiler`` spans
(``engine.step``, ``engine.admit``, ``engine.prefill``, ``engine.decode``,
``engine.sample`` ...; docs/observability.md has the table) whether or
not a profiler runs: without one each is an inactive TraceMe costing
about a microsecond, and with one the host spans land on the same clock
as the device's program runs.
"""

from __future__ import annotations

import contextvars
import dataclasses
import functools
import time
from collections import deque

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core.qlinear import QuantPolicy
from repro.launch.roofline import (
    serving_prefill_flops,
    serving_prefill_hbm_bytes,
)
from repro.models import common as cm
from repro.obs import MetricsRegistry
from repro.resilience.faults import FaultInjected
from repro.serving.config import EngineConfig, resolve_engine_config

__all__ = ["Request", "EngineConfig", "ServingEngine", "PagedServingEngine",
           "PerSlotServingEngine", "CompileError"]


class CompileError(RuntimeError):
    """An engine program failed to lower or compile.  Unlike a fault
    raised while a program runs, this is never contained by the kernel
    circuit breaker: a kernel the compiler refuses must surface, not be
    served on the XLA fallback behind the caller's back."""


# the ``engine.compiles`` counter of the engine whose step() is running:
# the jitted programs are shared process-wide, their compiles are counted
# by the engine that asked for them
_compile_counter: contextvars.ContextVar = contextvars.ContextVar(
    "engine_compile_counter", default=None)

_span = jax.profiler.TraceAnnotation


class _StagedJit:
    """A ``jax.jit`` function whose first call with each input signature
    lowers and compiles before anything runs, so that a failure there
    raises :class:`CompileError` and stays apart from runtime faults.
    Later calls take jit's own fast path, which reuses the executable
    compiled here.  Each compile runs in an ``engine.compile`` span and
    counts once in the stepping engine's ``engine.compiles``."""

    def __init__(self, fn):
        self._fn = fn
        self._compiled: set = set()

    def __call__(self, *args):
        leaves, tree = jax.tree.flatten(args)
        key = (tree, tuple(jax.typeof(x) for x in leaves))
        if key not in self._compiled:
            with _span("engine.compile", program=self._fn.__name__):
                try:
                    self._fn.lower(*args).compile()
                except Exception as exc:  # noqa: BLE001 — any lowering failure
                    raise CompileError(f"{exc.__class__.__name__}: {exc}"
                                       ) from exc
            self._compiled.add(key)
            counter = _compile_counter.get()
            if counter is not None:
                counter.inc()
        return self._fn(*args)


@dataclasses.dataclass
class Request:
    uid: int
    prompt: np.ndarray                   # (prompt_len,) int32
    max_new_tokens: int = 32
    temperature: float = 0.0             # 0 → greedy
    out_tokens: list = dataclasses.field(default_factory=list)
    done: bool = False
    cancelled: bool = False              # set by engine.cancel()
    failed: bool = False                 # set by the numerical guard


@functools.lru_cache(maxsize=None)
def _jitted(model, cfg: ModelConfig, policy: QuantPolicy | None):
    """Process-wide (model, cfg, policy) → jitted (prefill, decode_step)."""
    def prefill(p, t, c):
        return model.prefill(p, cfg, t, c, policy=policy)

    def decode_step(p, t, c):
        return model.decode_step(p, cfg, t, c, policy=policy)

    return _StagedJit(jax.jit(prefill)), _StagedJit(jax.jit(decode_step))


@functools.lru_cache(maxsize=None)
def _jitted_paged_prefill(model, cfg: ModelConfig, policy: QuantPolicy | None):
    """Process-wide jitted in-engine batched prefill; the engine cache is
    donated (the pool is the engine's largest buffer — no tick-time copy)."""
    def prefill_paged(p, t, ln, c, s):
        return model.prefill_paged(p, cfg, t, ln, c, s, policy=policy)

    return _StagedJit(jax.jit(prefill_paged, donate_argnums=3))


@functools.lru_cache(maxsize=None)
def _jitted_chunked_prefill(model, cfg: ModelConfig,
                            policy: QuantPolicy | None):
    """Chunked-prefill CONTINUATION dispatch: each row writes its next
    prompt chunk into its pages at per-row ``start`` offsets and attends
    over its already-written pool prefix (docs/serving.md).  Only model
    families with ``supports_chunked_prefill`` expose the ``start``
    parameter; the cache is donated exactly like the whole-prompt path."""
    def prefill_chunk(p, t, ln, st, c, s):
        return model.prefill_paged(p, cfg, t, ln, c, s, policy=policy,
                                   start=st)

    return _StagedJit(jax.jit(prefill_chunk, donate_argnums=4))


@functools.lru_cache(maxsize=None)
def _jitted_verify(model, cfg: ModelConfig, policy: QuantPolicy | None):
    """Speculative VERIFY dispatch (docs/speculative.md): one batched
    ragged call scores k+1 candidate positions per slot at per-row
    ``start`` offsets — the chunked-prefill continuation shape, but
    logits come back for ALL positions so greedy acceptance can compare
    the target's argmax against every draft.  The cache is donated like
    the prefill paths (closures re-materialize host state on retry)."""
    def verify_paged(p, t, ln, st, c, s):
        return model.verify_paged(p, cfg, t, ln, c, s, st, policy=policy)

    return _StagedJit(jax.jit(verify_paged, donate_argnums=4))


def page_copy(buf, src, dst):
    """Copy one physical page's data inside a pool leaf (page axis 1)."""
    return buf.at[:, dst].set(buf[:, src])


# copy-on-write page clone: one donated jit per pool-leaf shape
_page_copy = jax.jit(page_copy, donate_argnums=0)


def _sample_key(step: int, uid: int) -> jax.Array:
    """Per-(tick, request) PRNG key.  Folding in the uid is load-bearing:
    a step-only fold hands every slot in a tick the SAME key, i.e.
    identical draws across concurrent requests at temperature > 0."""
    return jax.random.fold_in(
        jax.random.fold_in(jax.random.PRNGKey(7), step), uid)


def _sample_one(logits: jax.Array, temperature: float, step: int,
                uid: int) -> jax.Array:
    """Sample one token from (1, V) logits (the admit/prefill path)."""
    if temperature <= 0:
        return jnp.argmax(logits, -1)
    return jax.random.categorical(_sample_key(step, uid),
                                  logits / temperature, axis=-1)


# slot writes run jitted with the batched cache donated: one fused program
# per (shape, slot) that updates the slot in place instead of eagerly
# re-materializing every cache leaf on each admission
_write_slot = jax.jit(cm.write_slot, static_argnums=2, donate_argnums=0)


class _EngineBase:
    """Shared scheduling state + request bookkeeping.

    Counters live in a :class:`repro.obs.MetricsRegistry` — the engine
    always carries one (``run_stats``/``stats()`` read from it), and an
    ``obs=Observability(...)`` argument swaps in a shared registry plus
    the OPT-IN layers: span tracing (submit/admit/prefill/first-token/
    tick/preempt/retire events + TTFT/queue-wait/tick histograms, and
    the shapes of each prefill and decode dispatch) and quant-health
    sampling.  Per-backend dispatch counts and ``engine.compiles`` are
    always kept.  With ``obs=None`` the engine takes no
    timestamps, emits no events, and issues exactly the same jitted
    dispatches (tests/test_obs.py pins zero overhead and token
    identity)."""

    def __init__(self, model, params, cfg: ModelConfig, *,
                 config: EngineConfig | None = None, **legacy):
        # ONE EngineConfig carries every knob (docs/api.md); the legacy
        # per-kwarg form builds an equivalent config through a shim that
        # warns once per process (serving/config.py)
        config = resolve_engine_config(config, legacy)
        self.config = config
        policy, obs = config.policy, config.obs
        self.model, self.params, self.cfg = model, params, cfg
        self.policy = policy
        self.max_slots, self.max_len = config.max_slots, config.max_len
        self.eos_id = config.eos_id
        self.kv_bits = config.kv_bits
        self.obs = obs
        faults, nan_guard = config.faults, config.nan_guard
        # resilience layer (docs/resilience.md): both OPT-IN with the
        # obs-hook zero-overhead contract — faults=None / nan_guard=False
        # cost one attribute check per site and change nothing else
        self._faults = faults
        self._nan_guard = nan_guard
        self._metrics = obs.registry if obs is not None else MetricsRegistry()
        self._tracer = obs.tracer if obs is not None else None
        self._qhealth = obs.quant_health if obs is not None else None
        self._clock = obs.clock if obs is not None else time.perf_counter
        self._c_decode = self._metrics.counter("engine.decode_dispatches")
        self._c_prefill = self._metrics.counter("engine.prefill_dispatches")
        self._c_ticks = self._metrics.counter("engine.ticks")
        self._c_prefill_tokens = self._metrics.counter("engine.prefill_tokens")
        self._c_compiles = self._metrics.counter("engine.compiles")
        self._submit_ts: dict[int, float] = {}    # uid → ORIGINAL submit ts
        self._wait_from: dict[int, float] = {}    # uid → submit OR requeue ts
        self._seen_uids: set[int] = set()         # first-token bookkeeping
        # streaming hooks (the async front-end installs these; both run
        # on the engine thread and must not block)
        self.on_token = None                      # fn(req, tok) per token
        self.on_retire = None                     # fn(req) at retirement
        self.queue: deque[Request] = deque()
        self.slots: list[Request | None] = [None] * self.max_slots
        self.retired: list[Request] = []
        self._prefill, self._decode = _jitted(model, cfg, policy)
        self._step = 0
        self._per_request: dict[int, dict] = {}   # uid → token counts
        self.run_stats: dict = {}        # filled by run()
        self._backend = self.kernel_backend     # resolved once: attribution
        # circuit-breaker fallback jits (same math, use_kernels="never"
        # lowering) — only built when the native path is a kernel path,
        # so a trip has somewhere safe to land (docs/resilience.md)
        self._fb_policy = None
        if policy is not None and self._backend in ("pallas", "interpret"):
            self._fb_policy = dataclasses.replace(policy,
                                                  use_kernels="never")
        if self._fb_policy is not None:
            self._prefill_fb, self._decode_fb = _jitted(model, cfg,
                                                        self._fb_policy)
        else:
            self._prefill_fb = self._decode_fb = None
        self._init_caches()

    # registry-backed views of the legacy counter attributes (run_stats
    # keys and these names are unchanged for backward compatibility)
    @property
    def decode_dispatches(self) -> int:
        """Jitted decode calls issued."""
        return int(self._c_decode.value)

    @property
    def prefill_dispatches(self) -> int:
        """Jitted prefill calls issued."""
        return int(self._c_prefill.value)

    @property
    def ticks(self) -> int:
        """step() calls that decoded."""
        return int(self._c_ticks.value)

    def _init_caches(self):
        """Build this engine's cache storage (layout differs per engine)."""
        raise NotImplementedError

    @property
    def kernel_backend(self) -> str:
        """Resolved matmul backend for this engine's policy ("bf16" when
        no quantization policy is attached).  repro.kernels.ops is the
        single dispatch authority (docs/kernels.md): on TPU hosts the
        quantized decode tick runs the one-pass fused Pallas qlinear."""
        if self.policy is None:
            return "bf16"
        from repro.kernels import ops

        return ops.resolve_backend(self.policy.use_kernels)

    def submit(self, req: Request):
        self.queue.append(req)
        if self.obs is not None:
            self._submit_ts[req.uid] = self._clock()
            self._wait_from[req.uid] = self._submit_ts[req.uid]
            self._tracer.emit("submit", ts=self._submit_ts[req.uid],
                              uid=req.uid, prompt_len=len(req.prompt))

    def resubmit(self, req: Request):
        """Re-admit a request that already streamed tokens on a PREVIOUS
        engine instance — the front-end watchdog's recovery path.
        Admission re-prefills ``_resume_ctx`` (prompt + tokens so far)
        exactly like a preemption resume, so the greedy continuation is
        token-identical and already-streamed tokens are neither repeated
        nor lost; obs bookkeeping marks the re-admission ``resumed``."""
        if req.out_tokens:
            self._seen_uids.add(req.uid)
        self.submit(req)

    @staticmethod
    def _resume_ctx(req: Request) -> np.ndarray:
        """Full re-prefill context for a (possibly resumed) request: the
        ORIGINAL prompt plus every token generated so far.  Computed at
        admission time — ``_preempt_youngest`` used to fold
        ``out_tokens`` into ``req.prompt`` in place, which corrupted the
        caller-visible Request (retired requests came back with a prompt
        they never submitted, and retire-event ``prompt_len`` inflated),
        and a SECOND preemption of the same request re-folded the
        already-folded tokens, duplicating context."""
        if not req.out_tokens:
            return np.asarray(req.prompt, np.int64)
        return np.concatenate([np.asarray(req.prompt, np.int64),
                               np.asarray(req.out_tokens, np.int64)])

    def _finished(self, req: Request, tok: int) -> bool:
        return tok == self.eos_id or len(req.out_tokens) >= req.max_new_tokens

    def _install_slot_cache(self, slot: int, cache):
        """Store an admitted request's prefilled batch-1 cache for
        ``slot`` (layout differs per engine)."""
        raise NotImplementedError

    def _count_prefill(self, req: Request, n_tokens: int):
        self._c_prefill_tokens.inc(n_tokens)
        rec = self._per_request.setdefault(req.uid,
                                           {"prefill": 0, "decode": 0})
        rec["prefill"] += n_tokens

    def _append_token(self, req: Request, tok: int):
        """Every sampled token flows through here so the streaming hook
        sees it the instant it exists (the async front-end forwards it
        to the client's open response)."""
        req.out_tokens.append(tok)
        if self.on_token is not None:
            self.on_token(req, tok)

    def _retire(self, req: Request):
        req.done = True
        self.retired.append(req)
        rec = self._per_request.setdefault(req.uid,
                                           {"prefill": 0, "decode": 0})
        rec["decode"] = len(req.out_tokens)
        self._metrics.counter("engine.requests_retired").inc()
        if self.obs is not None:
            now = self._clock()
            e2e = now - self._submit_ts.get(req.uid, now)
            extra = {"cancelled": True} if req.cancelled else {}
            if req.failed:
                extra["failed"] = True
            self._tracer.emit("retire", ts=now, uid=req.uid,
                              prompt_len=len(req.prompt),
                              decode_tokens=len(req.out_tokens), e2e_s=e2e,
                              **extra)
            # retired uids never re-admit: drop their timestamp entries
            # so a long-lived front-end engine doesn't grow unboundedly
            self._submit_ts.pop(req.uid, None)
            self._wait_from.pop(req.uid, None)
            self._seen_uids.discard(req.uid)
        if self.on_retire is not None:
            self.on_retire(req)

    def cancel(self, uid: int) -> bool:
        """Cancel a queued or in-flight request (the front-end's
        deadline path).  The request retires immediately with
        ``cancelled=True`` — its already-streamed tokens stand — and an
        occupied slot is evicted (the paged engine returns its pages to
        the pool).  Returns False when the uid is not present (already
        retired: the caller lost the race, which is fine)."""
        for i, r in enumerate(self.queue):
            if r.uid == uid:
                del self.queue[i]
                r.cancelled = True
                self._retire(r)
                return True
        for i, r in enumerate(self.slots):
            if r is not None and r.uid == uid:
                r.cancelled = True
                self._retire(r)
                self._evict_slot(i)
                return True
        return False

    def _evict_slot(self, slot: int):
        """Clear a cancelled request's slot.  The dense engines just
        vacate it (admission overwrites the slot cache wholesale)."""
        self.slots[slot] = None

    # -- resilience layer (docs/resilience.md) ------------------------------

    def _fire(self, site: str, **ctx):
        """Advance the fault plan at one site (callers pre-check
        ``self._faults is not None``); a triggered spec also lands a
        ``fault`` trace event so chaos-run traces are self-describing."""
        spec = self._faults.fire(site, **ctx)
        if spec is not None and self.obs is not None:
            self._tracer.emit("fault", ts=self._clock(), site=site, **ctx)
        return spec

    def _fire_slow_tick(self):
        spec = self._fire("slow_tick", tick=self._step)
        if spec is not None and spec.delay_s > 0:
            time.sleep(spec.delay_s)

    def _dispatch_guarded(self, op: str, native, fallback):
        """Issue ONE jitted dispatch through the fault plane and the
        process-wide kernel circuit breaker (``repro.kernels.ops``).

        ``native``/``fallback`` are zero-arg closures capturing their
        argument pytrees late — a retry of a donating dispatch (paged
        prefill) must re-materialize the donated cache.  An injected
        ``dispatch_raise`` fires BEFORE the call for the same reason: no
        donated buffer is ever half-consumed by a scheduled fault.
        Returns ``(outputs, executed_backend)``.

        With no fallback (bf16 engines, ``use_kernels="never"``, auto on
        a non-TPU host) a failure propagates: containment moves up to
        the front-end watchdog.  Otherwise a native runtime failure trips
        the breaker for ``op`` and the tick completes on the XLA fallback
        jit; while the circuit is open every dispatch rides the fallback
        (counted under ``dispatch.fallback.*``) until a half-open probe
        succeeds and closes it again.  A :class:`CompileError` (the
        native program failed to lower or compile) always propagates.
        """
        if fallback is None:
            if self._faults is not None and self._fire("dispatch_raise",
                                                       op=op):
                raise FaultInjected("dispatch_raise", op)
            return native(), self._backend
        from repro.kernels import ops

        mode = ops.resolve_backend(self.policy.use_kernels, op=op)
        if mode == "xla":
            # circuit open: ride the fallback until the breaker re-probes
            # (no native call is made, so the dispatch_raise site, which
            # stands for a native failure, is not reached)
            self._metrics.counter(f"dispatch.fallback.{op}").inc()
            return fallback(), "xla"
        try:
            if self._faults is not None and self._fire("dispatch_raise",
                                                       op=op):
                raise FaultInjected("dispatch_raise", op)
            out = native()
        except CompileError:
            raise   # a kernel the compiler refuses is a bug, not a fault
        except Exception as exc:  # noqa: BLE001 — any runtime failure trips
            ops.breaker.record_failure(op)
            self._metrics.counter("engine.breaker_trips").inc()
            self._metrics.counter(f"dispatch.fallback.{op}").inc()
            if self.obs is not None:
                self._tracer.emit("breaker", ts=self._clock(), op=op,
                                  action="trip", error=repr(exc))
            return fallback(), "xla"
        if ops.breaker.record_success(op):
            self._metrics.counter("engine.breaker_recoveries").inc()
            if self.obs is not None:
                self._tracer.emit("breaker", ts=self._clock(), op=op,
                                  action="recover")
        return out, mode

    def _poison_logits(self, logits, active: list[int]):
        """``nan_logits`` fault site: poison scheduled slots' logits
        rows ON DEVICE, post-dispatch — other rows' values are the exact
        arrays the fault-free run produced, which is what makes the
        chaos suite's bit-identical-survivors invariant provable."""
        for i in active:
            if self._fire("nan_logits", uid=self.slots[i].uid,
                          tick=self._step):
                logits = logits.at[i].set(jnp.nan)
        return logits

    def _guard_rows(self, logits, active: list[int]) -> list[int]:
        """Opt-in per-tick finite check over the active rows' last-token
        logits; returns the slots to fail this tick (empty when the
        guard is off — the common path costs one attribute check)."""
        if not self._nan_guard or not active:
            return []
        finite = np.isfinite(np.asarray(logits[:, -1],
                                        np.float32)).all(axis=-1)
        return [i for i in active if not finite[i]]

    def _fail_slot(self, slot: int, reason: str = "nonfinite_logits"):
        """Numerical-guard containment: retire ONE slot with status
        ``failed`` — pages freed through ``_evict_slot`` — and escalate
        a quant-health-style ``guard`` trace event citing the layer
        whose Eq.-2 difficulty is worst over this request's context (the
        runtime counterpart of the passive ``quant_health`` sampler).
        Every other slot is untouched: the guard reads only the failing
        row."""
        req = self.slots[slot]
        req.failed = True
        self._metrics.counter("engine.requests_failed").inc()
        if self.obs is not None:
            self._tracer.emit("guard", ts=self._clock(), uid=req.uid,
                              slot=slot, tick=self.ticks, reason=reason,
                              **self._guard_escalation(req))
        self._retire(req)
        self._evict_slot(slot)

    def _guard_escalation(self, req: Request) -> dict:
        """Name the worst-difficulty (module, layer) for the failing
        request's context via the quant-health tap forward — only when
        the sampler is attached (obs opt-in), else the guard event
        carries just uid/slot/reason."""
        if self._qhealth is None:
            return {}
        ctx = np.concatenate([np.asarray(req.prompt, np.int64),
                              np.asarray(req.out_tokens, np.int64)])
        rec = self._qhealth.sample(self.ticks, req.uid, ctx)
        worst = (None, -1, float("-inf"))
        for mod, sig in rec["modules"].items():
            for layer, diff in enumerate(sig["difficulty"]):
                if diff > worst[2]:
                    worst = (mod, layer, diff)
        if worst[0] is None:
            return {}
        return {"module": worst[0], "layer": worst[1],
                "difficulty": float(worst[2])}

    @property
    def prompt_capacity(self) -> int:
        """Longest prompt this engine can ever admit — the front-end
        rejects over-capacity submissions with an HTTP 400 instead of
        letting ``submit`` raise on the engine thread."""
        return self.max_len

    # -- obs hooks (all no-ops costing one attribute check when disabled) --

    def _obs_admitted(self, req: Request, slot: int) -> float:
        """Emit admit (+ queue-wait) for one request; returns 'now'.

        Queue wait is measured from ``_wait_from`` — the ORIGINAL submit
        for a fresh request, the REQUEUE time for a preemption-resumed
        one (``_preempt_youngest`` stamps it).  Measuring resumes from
        the original submit would double-count the first service period
        and inflate ``engine.queue_wait_s``; end-to-end latency keeps
        the original submit via ``_submit_ts``."""
        now = self._clock()
        wait = now - self._wait_from.get(req.uid, now)
        self._metrics.histogram("engine.queue_wait_s").observe(wait)
        self._tracer.emit("admit", ts=now, uid=req.uid, slot=slot,
                          queue_wait_s=wait,
                          resumed=req.uid in self._seen_uids)
        return now

    def _obs_prefill_token(self, req: Request):
        """Timestamp the token sampled from prefill logits.  For a
        freshly admitted request that is the FIRST token (TTFT); a
        preemption-resumed request already streamed its first token, but
        the resume-prefill token is still a real streamed token — it
        gets a ``token`` event so the per-token chain and trace-derived
        ``decode_tokens`` stay complete (summarize counts it)."""
        now = self._clock()
        if req.uid in self._seen_uids:
            self._tracer.emit("token", ts=now, uid=req.uid, resumed=True)
            return
        self._seen_uids.add(req.uid)
        ttft = now - self._submit_ts.get(req.uid, now)
        self._metrics.histogram("engine.ttft_s").observe(ttft)
        self._tracer.emit("first_token", ts=now, uid=req.uid, ttft_s=ttft)

    def _attr_dispatch(self, op: str, backend: str | None = None):
        """Per-backend dispatch count of ``op`` (decode or prefill).
        ``backend`` is the EXECUTED mode when the circuit breaker may
        have rerouted the dispatch (default: the engine's native)."""
        self._metrics.counter(
            f"dispatch.{op}.{backend or self._backend}").inc()

    def _sample_first(self, logits: jax.Array, req: Request) -> int:
        """Sample a request's next token from its prefill logits row and
        read it to the host."""
        with _span("engine.sample", rows=1):
            return int(_sample_one(logits, req.temperature, self._step,
                                   req.uid)[0])

    def _maybe_quant_health(self):
        """Opt-in every-N-ticks activation health probe over the active
        request with the deepest context (repro.obs.quant_health)."""
        qh = self._qhealth
        if qh is None or not qh.due(self.ticks):
            return
        reqs = [r for r in self.slots if r is not None]
        if not reqs:
            return
        req = max(reqs, key=lambda r: len(r.prompt) + len(r.out_tokens))
        ctx = np.concatenate([np.asarray(req.prompt, np.int64),
                              np.asarray(req.out_tokens, np.int64)])
        rec = qh.sample(self.ticks, req.uid, ctx)
        self._tracer.emit("quant_health", **rec)

    def _pool_stats(self) -> dict:
        """Page-pool occupancy; non-paged engines have no pool."""
        return {}

    def stats(self) -> dict:
        """Aggregate + per-request token counts (so callers stop
        re-deriving them from the retired Request lists by hand).
        Counter-backed fields read from the obs metrics registry — ONE
        implementation for all three engines, keys unchanged."""
        # a truncated run (max_ticks exhausted) leaves requests in slots
        # or requeued: fold their in-flight decode counts in so the
        # aggregate never under-reports work actually done
        from repro.kernels import ops

        for req in list(self.slots) + list(self.queue):
            if req is not None and req.uid in self._per_request:
                self._per_request[req.uid]["decode"] = len(req.out_tokens)
        return {
            "requests_failed": int(
                self._metrics.counter("engine.requests_failed").value),
            "breaker": ops.breaker.state(),
            "requests": len(self._per_request),
            "prefill_tokens": int(self._c_prefill_tokens.value),
            "decode_tokens": sum(r["decode"]
                                 for r in self._per_request.values()),
            "per_request": {uid: dict(rec)
                            for uid, rec in self._per_request.items()},
            "ticks": self.ticks,
            "decode_dispatches": self.decode_dispatches,
            "prefill_dispatches": self.prefill_dispatches,
            "dispatches_per_tick": self.decode_dispatches / max(self.ticks, 1),
            "kernel_backend": self._backend,
            "dispatch_backends": self._metrics.counters_with_prefix(
                "dispatch."),
            **self._pool_stats(),
        }

    def _admit(self):
        for i in range(self.max_slots):
            while self.slots[i] is None and self.queue:
                req = self.queue.popleft()
                if self.obs is not None:
                    t0 = self._obs_admitted(req, i)
                # prefill the RESUME context (prompt + generated) — for a
                # fresh request that is just the prompt; a watchdog
                # re-admission replays its streamed tokens too
                ctx = self._resume_ctx(req)
                fresh = self.model.make_cache(self.cfg, 1, self.max_len,
                                              bits=self.kv_bits)
                toks = jnp.asarray(ctx[None, :], jnp.int32)
                (logits, slot_cache), used = self._dispatch_guarded(
                    "prefill",
                    lambda t=toks, c=fresh: self._prefill(self.params, t, c),
                    None if self._prefill_fb is None else
                    (lambda t=toks, c=fresh: self._prefill_fb(self.params,
                                                              t, c)))
                self._c_prefill.inc()
                self._attr_dispatch("prefill", used)
                self._count_prefill(req, len(ctx))
                nxt = self._sample_first(logits[:, -1], req)
                if self.obs is not None:
                    # nxt materialized ⇒ the prefill dispatch completed
                    now = self._clock()
                    self._metrics.histogram("engine.prefill_s").observe(
                        now - t0)
                    self._tracer.emit("prefill", ts=now, t0=t0,
                                      n_requests=1, n_tokens=len(ctx),
                                      rows=1, padded_len=len(ctx),
                                      padded_tokens=len(ctx),
                                      lens=[len(ctx)], starts=[0],
                                      dur_s=now - t0)
                    self._obs_prefill_token(req)
                self._append_token(req, nxt)
                # the prefill-sampled token can already finish the request
                # (EOS or max_new_tokens=1): retire without occupying the
                # slot, and keep admitting into it
                if self._finished(req, nxt):
                    self._retire(req)
                else:
                    self.slots[i] = req
                    self._install_slot_cache(i, slot_cache)

    def pop_retired(self) -> list[Request]:
        """Drain and return retired requests (callers driving step()
        directly should call this periodically — the engine does not
        retain retired requests once handed out)."""
        out, self.retired = self.retired, []
        return out

    def step(self) -> int:
        """One engine tick; returns the number of rows that decoded.
        The tick runs in an ``engine.step`` step span (the profiler's
        step markers), and compiles it triggers count in this engine's
        ``engine.compiles``."""
        token = _compile_counter.set(self._c_compiles)
        try:
            with jax.profiler.StepTraceAnnotation("engine.step",
                                                  step_num=self._step + 1):
                return self._tick()
        finally:
            _compile_counter.reset(token)

    def _tick(self) -> int:
        """The tick itself (scheduling differs per engine)."""
        raise NotImplementedError

    def run(self, max_ticks: int = 1000) -> list[Request]:
        """Tick until queue and slots drain (or the tick budget runs out);
        returns every retired request not yet handed out — including ones
        already occupying a slot beforehand or submitted mid-run.  The
        aggregate/per-request token counts and (paged engines) page-pool
        occupancy land in ``self.run_stats``."""
        while (self.queue or any(self.slots)) and max_ticks > 0:
            self.step()
            max_ticks -= 1
        self.run_stats = self.stats()
        return self.pop_retired()


class ServingEngine(_EngineBase):
    """Slot-batched continuous batching: one decode dispatch per tick."""

    def _init_caches(self):
        # ONE slot-major cache: data leaves (layer, slot, ...), lengths
        # vectorized to (max_slots,) so each slot decodes at its own depth
        self.cache = cm.batch_slot_cache(
            self.model.make_cache(self.cfg, self.max_slots, self.max_len,
                                  bits=self.kv_bits))

    def _install_slot_cache(self, slot: int, cache):
        # full-extent copy: no stale KV/scales from the slot's previous
        # occupant survive admission
        self.cache = _write_slot(self.cache, cache, slot)

    def _sample_batch(self, logits: jax.Array, temps: np.ndarray,
                      uids: np.ndarray) -> jax.Array:
        """Vectorized over slots: greedy rows take argmax; temperature
        rows draw categorically with a per-(tick, uid) key."""
        greedy = jnp.argmax(logits, -1)
        if not (temps > 0).any():
            return greedy
        keys = jax.vmap(lambda u: _sample_key(self._step, u))(
            jnp.asarray(uids, jnp.int32))
        scaled = logits / jnp.maximum(jnp.asarray(temps), 1e-6)[:, None]
        drawn = jax.vmap(jax.random.categorical)(keys, scaled)
        return jnp.where(jnp.asarray(temps) > 0, drawn, greedy)

    # -- one engine tick ----------------------------------------------------

    def _tick(self) -> int:
        """Admit + decode one token for every active slot with a SINGLE
        (max_slots, 1) jitted dispatch. Returns the number of active
        sequences."""
        if self._faults is not None:
            self._fire_slow_tick()
        self._admit()
        self._step += 1
        active = [i for i, r in enumerate(self.slots) if r is not None]
        if not active:
            return 0
        last = np.zeros((self.max_slots, 1), np.int32)
        temps = np.zeros((self.max_slots,), np.float32)
        uids = np.zeros((self.max_slots,), np.int32)
        for i in active:
            req = self.slots[i]
            last[i, 0] = req.out_tokens[-1]
            temps[i] = req.temperature
            uids[i] = req.uid
        # inactive slots ride along masked: their rows decode garbage that
        # is never sampled into a request, and admission overwrites their
        # slot cache wholesale
        t0 = self._clock() if self.obs is not None else 0.0
        last_j = jnp.asarray(last)
        (logits, self.cache), used = self._dispatch_guarded(
            "decode",
            lambda: self._decode(self.params, last_j, self.cache),
            None if self._decode_fb is None else
            (lambda: self._decode_fb(self.params, last_j, self.cache)))
        self._c_decode.inc()
        self._c_ticks.inc()
        self._attr_dispatch("decode", used)
        if self._faults is not None:
            logits = self._poison_logits(logits, active)
        failed = self._guard_rows(logits, active)
        toks = np.asarray(self._sample_batch(logits[:, -1], temps, uids))
        if self.obs is not None:
            # toks materialized ⇒ the decode dispatch completed
            now = self._clock()
            self._metrics.histogram("engine.tick_s").observe(now - t0)
            # failed rows stream no token, so they are excluded from the
            # tick's uid list (summarize counts one decode token per uid)
            self._tracer.emit("tick", ts=now, tick=self.ticks,
                              n_active=len(active),
                              uids=[self.slots[i].uid for i in active
                                    if i not in failed],
                              dur_s=now - t0)
        for i in active:
            req = self.slots[i]
            if i in failed:
                # guard containment: no token appended from a non-finite
                # row — the request retires failed, others are untouched
                self._fail_slot(i)
                continue
            nxt = int(toks[i])
            self._append_token(req, nxt)
            if self._finished(req, nxt):
                self._retire(req)
                self.slots[i] = None
        self._maybe_quant_health()
        return len(active)


def _paged_part(cache) -> cm.PagedKVCache | None:
    """The PagedKVCache component of a family cache, if any (the SSM
    family's O(1) state has nothing to page)."""
    if isinstance(cache, cm.PagedKVCache):
        return cache
    attn = getattr(cache, "attn", None)
    return attn if isinstance(attn, cm.PagedKVCache) else None


class PagedServingEngine(ServingEngine):
    """Continuous batching over a PAGED KV pool with in-engine batched
    prefill.

    Memory layer: ``model.make_paged_cache`` backs attention KV with
    fixed-size pages from a shared pool (``n_pages``; default sized for
    zero overcommit).  The HOST owns allocation: a numpy page table +
    free list, synced into the cache pytree before every dispatch.
    Slots grow one page at a time as they decode; retirement returns
    pages to the pool (stale page contents are never read — validity is
    the per-slot length prefix, and positions are overwritten before
    they become valid).

    Admission layer: each round admits every FIFO request that fits
    (free slot + enough free pages for its prompt, else the head of the
    queue WAITS — pool backpressure), then prefills the whole batch with
    ONE jitted ``(n_admit_padded, padded_prompt_len)`` dispatch that
    scatter-writes straight into the assigned pages.  Prompt lengths are
    padded to a shared ``prefill_bucket`` multiple and the row count to a
    power of two, so mixed lengths share a dispatch and the jit cache
    stays small.

    A slot whose next page cannot be allocated mid-decode simply sits
    out ticks until pages free up (its tokens are unaffected — decode
    depends only on its own cache); if EVERY active slot is stalled, the
    youngest is preempted back to the queue (greedy continuation after
    re-prefill is token-identical).  Sizing the pool below
    ``ceil(max_prompt / page_size)`` can therefore starve admission —
    ``run()``'s tick budget still bounds the loop.

    Decode keeps the batched engine's contract: ONE ``(max_slots, 1)``
    dispatch per tick, greedy output token-identical to
    ``PerSlotServingEngine``.

    Prefix caching (``EngineConfig(prefix_cache=True)``, docs/serving.md
    §Prefix caching): prompts are chain-hashed in page-size chunks into
    a host-side map from chunk chain → physical page.  A cache-hit
    admission points its page-table row at the shared pages (per-page
    refcounts) and re-prefills ONLY the non-shared suffix through the
    chunked-continuation dispatch; freed pages park in an LRU tier
    reclaimed under pool pressure, and copy-on-write clones any shared
    page before a write could touch it.  Off (the default) the engine
    runs the pre-cache allocator byte for byte.
    """

    def __init__(self, model, params, cfg: ModelConfig, *,
                 config: EngineConfig | None = None, **legacy):
        config = resolve_engine_config(config, legacy)
        self.page_size = config.page_size
        self.prefill_bucket = config.prefill_bucket
        self._n_pages_arg = config.n_pages
        super().__init__(model, params, cfg, config=config)
        policy = config.policy
        self._prefill_paged = _jitted_paged_prefill(model, cfg, policy)
        self._prefill_paged_fb = (
            _jitted_paged_prefill(model, cfg, self._fb_policy)
            if self._fb_policy is not None else None)
        self._admit_seq = 0
        self._admitted_at = [0] * self.max_slots
        # chunked prefill: prompts longer than ``prefill_chunk`` stream
        # through bounded (n, chunk) continuation dispatches interleaved
        # with decode ticks, so a long admit can't stall a tick's worth
        # of streaming tokens.  Requires per-row start offsets in the
        # family's prefill_paged — families without the continuation
        # path (SSM scan state, per-invocation hybrid KV, MLA latent
        # pools) fall back to whole-prompt prefill, recorded in stats().
        self.prefill_chunk = config.prefill_chunk
        self._chunked = (bool(config.prefill_chunk) and self._pt is not None
                         and getattr(model, "supports_chunked_prefill",
                                     False))
        # the prefix cache's suffix re-prefill rides the same per-row
        # ``start=`` continuation jit, so it is built for either feature
        if self._chunked or self._prefix_on:
            self._prefill_cont = _jitted_chunked_prefill(model, cfg, policy)
            self._prefill_cont_fb = (
                _jitted_chunked_prefill(model, cfg, self._fb_policy)
                if self._fb_policy is not None else None)
        # speculative decoding (docs/speculative.md): a draft model
        # autoregressively proposes spec_k tokens per ready slot against
        # its OWN slot-major dense cache, the target scores all k+1
        # positions in ONE batched ragged verify dispatch per tick, and
        # greedy acceptance (longest matching prefix + one corrected
        # token) keeps output bit-identical to the plain path.  Gated
        # like the prefix cache on the per-row ``start`` continuation
        # machinery (verify IS a continuation dispatch returning
        # all-position logits); families without ``verify_paged`` serve
        # identically with ``stats()["spec"]["enabled"] is False``.
        self._spec_on = (config.spec_k > 0 and self._pt is not None
                         and getattr(model, "supports_chunked_prefill",
                                     False)
                         and getattr(model, "verify_paged", None)
                         is not None)
        if self._spec_on:
            self._verify = _jitted_verify(model, cfg, policy)
            self._verify_fb = (_jitted_verify(model, cfg, self._fb_policy)
                               if self._fb_policy is not None else None)
            dcfg = config.spec_draft_config
            if dcfg is None:
                # self-draft: the target drafts for itself through the
                # dense batch-slot decode path (the per-slot oracle's
                # numerics, including the int8-KV roundtrip), so every
                # draft matches the verify argmax and each dispatch
                # emits k+1 tokens — the bench's acceptance ceiling
                self.draft_model, self.draft_cfg = model, cfg
                self.draft_params = params
                self._draft_policy = policy
                self._draft_bits = self.kv_bits
            else:
                if dcfg.vocab_size != cfg.vocab_size:
                    raise ValueError(
                        "spec_draft_config vocab_size "
                        f"{dcfg.vocab_size} != target vocab_size "
                        f"{cfg.vocab_size}: draft and target must share "
                        "a token space")
                from repro.models.api import get_model

                self.draft_model = get_model(dcfg)
                self.draft_cfg = dcfg
                self.draft_params = self.draft_model.init(
                    jax.random.PRNGKey(11), dcfg)
                self._draft_policy = None
                self._draft_bits = None
            self._draft_prefill, self._draft_decode = _jitted(
                self.draft_model, self.draft_cfg, self._draft_policy)
            # headroom past max_len: a fully accepted run's draft length
            # reaches _len + spec_k + 1 before the next tick's writes
            self._draft_max_len = self.max_len + config.spec_k + 1
            self._draft_cache = cm.batch_slot_cache(
                self.draft_model.make_cache(self.draft_cfg, self.max_slots,
                                            self._draft_max_len,
                                            bits=self._draft_bits))

    # -- memory layer -------------------------------------------------------

    def _init_caches(self):
        self.cache = self.model.make_paged_cache(
            self.cfg, self.max_slots, self.max_len, page_size=self.page_size,
            n_pages=self._n_pages_arg, bits=self.kv_bits)
        part = _paged_part(self.cache)
        if part is None:                 # ssm: O(1) state, nothing to page
            self.n_pages, self.table_width = 0, 0
            self._pt, self._free = None, []
        else:
            self.n_pages = part.n_pages
            self.table_width = part.page_table.shape[1]
            self._pt = np.full((self.max_slots, self.table_width), -1,
                               np.int32)
            self._free = list(range(self.n_pages - 1, -1, -1))  # pop() → 0 first
        self._len = np.zeros((self.max_slots,), np.int32)
        # speculative draft sync state: slot i's draft cache holds KV
        # for positions [0, _draft_len[i]); in-sync means equal to
        # _len[i] (lazy — _spec_step re-prefills on mismatch)
        self._draft_len = np.zeros((self.max_slots,), np.int32)
        self.peak_pages_in_use = 0
        self._prefilling: dict[int, int] = {}   # slot → prompt tokens done
        # prefix cache (docs/serving.md §Prefix caching): content-chained
        # chunk hashes → physical pages, per-page slot refcounts, and an
        # LRU tier of cached-but-unreferenced pages reclaimed under pool
        # pressure.  Gated on the chunked-prefill continuation dispatch
        # (the suffix re-prefill needs per-row ``start`` offsets):
        # families without it admit every request as a miss, and the
        # knob defaults OFF — a cache-off engine runs the pre-cache
        # allocator byte for byte.
        self._prefix_on = (bool(self.config.prefix_cache)
                           and self._pt is not None
                           and getattr(self.model,
                                       "supports_chunked_prefill", False))
        # refcounts are maintained whenever a pool exists (cache on or
        # off — with the map empty they reduce to the old free list)
        self._ref = (np.zeros((self.n_pages,), np.int64)
                     if self._pt is not None else None)
        self._cache_map: dict[int, int] = {}   # chain key → physical page
        self._page_key: dict[int, int] = {}    # physical page → chain key
        self._lru: dict[int, int] = {}         # chain key → last-use seq
        self._lru_seq = 0

    def _host_state_cache(self):
        """Cache pytree with the HOST-authoritative page table + per-slot
        lengths pushed in (stalled/inactive rows never advance)."""
        with _span("engine.host_sync"):
            c = self.cache
            if isinstance(c, cm.PagedKVCache):
                return dataclasses.replace(
                    c, page_table=jnp.asarray(self._pt),
                    length=jnp.asarray(self._len))
            if _paged_part(c) is not None:   # hybrid: paged attn component
                # NOTE: distinct length buffers — prefill donates the
                # cache, and one array aliased into two leaves donates twice
                return dataclasses.replace(
                    c, attn=dataclasses.replace(
                        c.attn, page_table=jnp.asarray(self._pt),
                        length=jnp.asarray(np.array(self._len))),
                    length=jnp.asarray(np.array(self._len, copy=True)))
            return dataclasses.replace(c, length=jnp.asarray(self._len))

    @property
    def pages_in_use(self) -> int:
        return self.n_pages - len(self._free) if self._pt is not None else 0

    def _note_occupancy(self):
        self.peak_pages_in_use = max(self.peak_pages_in_use,
                                     self.pages_in_use)

    @property
    def paged_attention_backend(self) -> str:
        """Resolved executor for decode attention over the paged pool —
        "pallas"/"interpret" (the in-VMEM Pallas kernel), "xla" (the
        ``paged_view`` gather fallback; always the case for MLA latent
        pools), or "none" (pure-SSM: nothing to page).
        ``common.paged_attn_backend`` is the single dispatch authority
        (docs/paged_attention.md)."""
        return cm.paged_attn_backend(self.cfg, self.policy)

    def _pool_stats(self) -> dict:
        n = max(self.n_pages, 1)
        c = self._metrics.counter
        hits = int(c("prefix.hits").value)
        misses = int(c("prefix.misses").value)
        return {"page_size": self.page_size, "n_pages": self.n_pages,
                "table_width": self.table_width,
                "pages_in_use": self.pages_in_use,
                "peak_pages_in_use": self.peak_pages_in_use,
                "page_occupancy": self.pages_in_use / n,
                "page_occupancy_peak": self.peak_pages_in_use / n,
                "paged_attention_backend": self.paged_attention_backend,
                "prefill_chunk": self.prefill_chunk or 0,
                "chunked_prefill": self._chunked,
                "prefix": {
                    "enabled": self._prefix_on,
                    "hits": hits, "misses": misses,
                    "hit_rate": hits / max(hits + misses, 1),
                    "shared_pages": int(c("prefix.shared_pages").value),
                    "cow_copies": int(c("prefix.cow_copies").value),
                    "evictions": int(c("prefix.evictions").value),
                    "cached_pages": len(self._page_key),
                    "saved_prefill_tokens": int(
                        c("prefix.saved_prefill_tokens").value),
                    "saved_prefill_flops": int(
                        c("prefix.saved_prefill_flops").value),
                    "saved_hbm_bytes": int(
                        c("prefix.saved_hbm_bytes").value)},
                "spec": self._spec_stats()}

    def _spec_stats(self) -> dict:
        c = self._metrics.counter
        drafted = int(c("spec.drafted").value)
        accepted = int(c("spec.accepted").value)
        emitted = int(c("spec.emitted_tokens").value)
        verifies = int(c("spec.verify_dispatches").value)
        return {"enabled": getattr(self, "_spec_on", False),
                "k": self.config.spec_k,
                "self_draft": self.config.spec_draft_config is None,
                "drafted": drafted, "accepted": accepted,
                "rejected": int(c("spec.rejected").value),
                "acceptance_rate": accepted / max(drafted, 1),
                "emitted_tokens": emitted,
                "verify_dispatches": verifies,
                "draft_dispatches": int(c("spec.draft_dispatches").value),
                "draft_prefill_dispatches": int(
                    c("spec.draft_prefill_dispatches").value),
                "accepted_per_dispatch": emitted / max(verifies, 1)}

    def _pages_needed(self, n_tokens: int) -> int:
        if self._pt is None:
            return 0
        return cm.pages_per_slot(n_tokens, self.page_size)

    @property
    def prompt_capacity(self) -> int:
        cap = self.max_len
        if self._pt is not None:
            cap = min(cap, self.table_width * self.page_size,
                      self.n_pages * self.page_size)
        return cap

    def submit(self, req: Request):
        """Reject prompts that could NEVER be admitted up front: the
        dense engines clamp out-of-range cache writes, but a paged slot
        cannot outgrow its page-table width or the whole pool — such a
        request would starve the FIFO queue forever."""
        cap = self.prompt_capacity
        if len(req.prompt) > cap:
            raise ValueError(
                f"prompt of {len(req.prompt)} tokens exceeds the paged "
                f"engine's capacity of {cap} (max_len={self.max_len}, "
                f"page_size={self.page_size}, n_pages={self.n_pages})")
        super().submit(req)

    def _release_slot(self, slot: int):
        """Free the slot and drop its page references.  A page returns
        to the free list only at refcount 0 AND out of the cache map —
        prefix-shared pages survive a co-resident's retirement, and
        cached pages park in the LRU eviction tier instead."""
        if self._pt is not None:
            for p in self._pt[slot]:
                if p >= 0:
                    self._decref(int(p))
            self._pt[slot] = -1
        self._len[slot] = 0
        # a reused slot's draft cache is stale by construction: zeroing
        # the sync mark forces a draft re-prefill before it drafts again
        self._draft_len[slot] = 0
        self.slots[slot] = None
        self._prefilling.pop(slot, None)

    def _evict_slot(self, slot: int):
        self._release_slot(slot)

    # -- page allocator (refcounts + prefix-cache LRU tier) -----------------

    def _decref(self, p: int):
        self._ref[p] -= 1
        if self._ref[p] == 0 and p not in self._page_key:
            self._free.append(p)

    def _alloc_page(self) -> int | None:
        """Pop a free page, reclaiming an LRU cached-but-unreferenced
        page first when the free list is dry.  Returns None when nothing
        can be reclaimed (the caller stalls or backpressures)."""
        if not self._free and self._prefix_on:
            self._evict_lru()
        return self._free.pop() if self._free else None

    def _evict_lru(self) -> bool:
        """Reclaim the least-recently-used cached page no slot
        references.  Evicting a mid-chain entry orphans its descendants
        (their keys stop being reachable by any match walk) — they age
        out of the LRU the same way, a deliberate simplification over
        cascading the eviction."""
        for key in sorted(self._lru, key=self._lru.get):
            p = self._cache_map[key]
            if self._ref[p] == 0:
                del self._cache_map[key]
                del self._page_key[p]
                del self._lru[key]
                self._free.append(p)
                self._metrics.counter("prefix.evictions").inc()
                return True
        return False

    def _avail_pages(self) -> int:
        """Pages an admission could obtain: the free list plus the LRU
        eviction tier (cached pages no slot references)."""
        n = len(self._free)
        if self._prefix_on:
            n += sum(1 for p in self._page_key if self._ref[p] == 0)
        return n

    def _page_shared(self, p: int) -> bool:
        """A write must never mutate this page in place: another slot
        still references it, or the cache map could hand it to a future
        admission."""
        return self._ref[p] > 1 or p in self._page_key

    def _cow_slot_page(self, slot: int, pi: int) -> bool:
        """Copy-on-write: clone the slot's logical page ``pi`` into a
        fresh physical page before a write would hit pool memory other
        rows (or the cache map) still reference — ``paged_update``
        itself never mutates a shared page.  Returns False when no page
        can be allocated for the clone (the caller stalls, exactly like
        an allocation failure)."""
        dst = self._alloc_page()
        if dst is None:
            return False
        src = int(self._pt[slot, pi])
        self._clone_pool_page(src, dst)
        self._pt[slot, pi] = dst
        self._ref[dst] += 1
        self._decref(src)
        self._metrics.counter("prefix.cow_copies").inc()
        return True

    def _clone_pool_page(self, src: int, dst: int):
        """Copy one physical page across every pool data leaf (k/v +
        int8 scales) with a donated jit per leaf, so the pool updates in
        place instead of re-materializing."""
        part = _paged_part(self.cache)
        src_j, dst_j = jnp.asarray(src), jnp.asarray(dst)
        rep = {name: _page_copy(getattr(part, name), src_j, dst_j)
               for name in ("k", "v", "k_scale", "v_scale")
               if getattr(part, name) is not None}
        new_part = dataclasses.replace(part, **rep)
        if isinstance(self.cache, cm.PagedKVCache):
            self.cache = new_part
        else:
            self.cache = dataclasses.replace(self.cache, attn=new_part)

    # -- prefix cache (chunk-chain hashing over the page pool) --------------

    def _chain_keys(self, ctx: np.ndarray) -> list[int]:
        """Chain hash per FULL page-size chunk of ``ctx``: key_k folds
        key_{k-1} with chunk k's tokens, so a chunk only matches under
        an identical full prefix — page k's KV depends on positions
        ``[0, k*page)`` as much as on its own tokens.  A partial tail
        chunk gets no key (only whole pages are ever shared)."""
        ps = self.page_size
        keys, prev = [], 0
        for k in range(len(ctx) // ps):
            prev = hash((prev,
                         np.asarray(ctx[k * ps:(k + 1) * ps],
                                    np.int64).tobytes()))
            keys.append(prev)
        return keys

    def _touch(self, key: int):
        self._lru_seq += 1
        self._lru[key] = self._lru_seq

    def _match_prefix(self, ctx: np.ndarray) -> tuple[list[int], int]:
        """Longest cached chunk chain prefixing ``ctx`` → (physical
        pages, matched token count).  Touches every matched entry, so
        LRU eviction drops the deepest chain nodes first."""
        if not self._prefix_on:
            return [], 0
        pages = []
        for key in self._chain_keys(ctx):
            p = self._cache_map.get(key)
            if p is None:
                break
            self._touch(key)
            pages.append(p)
        return pages, len(pages) * self.page_size

    def _register_prefix(self, slot: int, ctx: np.ndarray):
        """Publish the slot's freshly prefilled FULL prompt pages into
        the chain map (the partial tail page stays exclusive — decode
        keeps writing into it).  Runs AFTER the prefill dispatch that
        wrote the pages completes, so a same-round co-admission can
        never point at not-yet-written pool memory.  Existing entries
        win: the content is identical, and re-pointing would strand the
        old page's cache hold."""
        if not self._prefix_on:
            return
        for k, key in enumerate(self._chain_keys(ctx)):
            p = int(self._pt[slot, k])
            if p < 0:
                break
            self._touch(key)
            if key in self._cache_map:
                continue
            self._cache_map[key] = p
            self._page_key[p] = key

    def _note_prefix_hit(self, req: Request, slot: int, ctx: np.ndarray,
                         start: int, n_shared: int):
        """Attribution for one cache-hit admission: hit counters, the
        roofline-modeled prefill work the shared pages avoided, and the
        ``prefix_hit`` trace event."""
        self._metrics.counter("prefix.hits").inc()
        self._metrics.counter("prefix.shared_pages").inc(n_shared)
        self._metrics.counter("prefix.saved_prefill_tokens").inc(start)
        self._metrics.counter("prefix.saved_prefill_flops").inc(
            serving_prefill_flops(self.cfg, 1, start))
        self._metrics.counter("prefix.saved_hbm_bytes").inc(
            serving_prefill_hbm_bytes(
                self.cfg, 1, start,
                weight_bits=self.policy.weight_bits if self.policy else None,
                kv_bits=self.kv_bits))
        if self.obs is not None:
            self._tracer.emit("prefix_hit", ts=self._clock(), uid=req.uid,
                              slot=slot, matched_tokens=start,
                              shared_pages=n_shared,
                              suffix_tokens=len(ctx) - start)

    # -- admission layer ----------------------------------------------------

    def _admit(self):
        # rounds: a request finishing at prefill frees its slot and pages
        # for the same tick's next round (matches the per-slot oracle's
        # keep-admitting-into-the-slot behaviour)
        more = True
        while more:
            with _span("engine.admit"):
                more = self._admit_round()

    def _admit_round(self) -> bool:
        free_slots = [i for i in range(self.max_slots)
                      if self.slots[i] is None]
        batch: list[tuple[int, Request, np.ndarray]] = []
        hits: list[tuple[int, Request, np.ndarray, int]] = []
        admitted_deferred = False
        while free_slots and self.queue:
            req = self.queue[0]
            ctx = self._resume_ctx(req)
            total = self._pages_needed(len(ctx))
            if self._pt is not None and total > min(self.n_pages,
                                                    self.table_width):
                # a resumed context that can NEVER fit again (watchdog
                # re-admission can outgrow a small pool): retire
                # truncated, exactly like _preempt_youngest — leaving
                # it at the FIFO head would starve everything behind
                self.queue.popleft()
                self._retire(req)
                continue
            shared, matched = self._match_prefix(ctx)
            # a FULL match still re-prefills the last token for its
            # next-token logits; that write lands inside the final
            # shared page, so admission reserves one page for the COW
            # clone and the suffix start backs up to len-1
            full = shared and matched == len(ctx)
            need = total - len(shared) + (1 if full else 0)
            if self._pt is not None:
                if need > self._avail_pages():
                    break                # backpressure: FIFO head waits
                if (self._faults is not None
                        and self._fire("page_alloc_fail", uid=req.uid,
                                       op="admit")):
                    break                # injected exhaustion: head waits
            self.queue.popleft()
            slot = free_slots.pop(0)
            if self._pt is not None:
                # shared pages first: the incref pins them against any
                # eviction the fresh allocations below may trigger
                for j, p in enumerate(shared):
                    self._pt[slot, j] = p
                    self._ref[p] += 1
                for j in range(len(shared), total):
                    p = self._alloc_page()
                    self._pt[slot, j] = p
                    self._ref[p] += 1
            if shared:
                start = min(matched, len(ctx) - 1)
                self._note_prefix_hit(req, slot, ctx, start, len(shared))
                if full:
                    self._cow_slot_page(slot, total - 1)
                self.slots[slot] = req
                self._len[slot] = start
                self._admitted_at[slot] = self._admit_seq
                self._admit_seq += 1
                if self.obs is not None:
                    self._obs_admitted(req, slot)
                if self._chunked and len(ctx) - start > self.prefill_chunk:
                    # long suffix: ride the chunked-prefill continuation
                    # machinery from the matched offset
                    self._prefilling[slot] = start
                    admitted_deferred = True
                else:
                    hits.append((slot, req, ctx, start))
                continue
            if self._prefix_on:
                self._metrics.counter("prefix.misses").inc()
            if self._chunked and len(ctx) > self.prefill_chunk:
                # chunked-prefill path: the slot and ALL its prompt pages
                # are assigned now (backpressure semantics unchanged) but
                # the prompt streams through bounded per-tick chunks
                # (_advance_prefill) instead of this round's dispatch
                self.slots[slot] = req
                self._len[slot] = 0
                self._prefilling[slot] = 0
                self._admitted_at[slot] = self._admit_seq
                self._admit_seq += 1
                if self.obs is not None:
                    self._obs_admitted(req, slot)
                admitted_deferred = True
                continue
            batch.append((slot, req, ctx))
        if not batch:
            if hits:
                self._prefill_suffix(hits)
            self._note_occupancy()
            return bool(hits) or admitted_deferred
        # ONE (n_pad, s_pad) prefill dispatch for the whole batch:
        # prompt lengths bucket-padded, row count padded to a power of
        # two (sentinel rows' writes drop in the kernel)
        n_pad = 1 << (len(batch) - 1).bit_length()
        s_max = max(len(ctx) for _, _, ctx in batch)
        s_pad = min(self.max_len,
                    -(-s_max // self.prefill_bucket) * self.prefill_bucket)
        t0 = self._clock() if self.obs is not None else 0.0
        with _span("engine.prefill", rows=n_pad,
                   tokens=sum(len(ctx) for _, _, ctx in batch),
                   padded=n_pad * s_pad):
            toks = np.zeros((n_pad, s_pad), np.int32)
            lens = np.zeros((n_pad,), np.int32)
            rows = np.full((n_pad,), self.max_slots, np.int32)
            for r, (slot, req, ctx) in enumerate(batch):
                toks[r, :len(ctx)] = ctx
                lens[r] = len(ctx)
                rows[r] = slot
            if self.obs is not None:
                for slot, req, _ in batch:
                    self._obs_admitted(req, slot)
            toks_j, lens_j = jnp.asarray(toks), jnp.asarray(lens)
            rows_j = jnp.asarray(rows)
            # the cache is DONATED: each closure materializes its own host-
            # state pytree, so a breaker retry never touches consumed buffers
            (logits, self.cache), used = self._dispatch_guarded(
                "prefill",
                lambda: self._prefill_paged(self.params, toks_j, lens_j,
                                            self._host_state_cache(), rows_j),
                None if self._prefill_paged_fb is None else
                (lambda: self._prefill_paged_fb(self.params, toks_j, lens_j,
                                                self._host_state_cache(),
                                                rows_j)))
        self._c_prefill.inc()
        self._attr_dispatch("prefill", used)
        if self.obs is not None:
            self._obs_prefill(t0, logits, n_pad, s_pad, lens,
                              np.zeros_like(lens))
        for r, (slot, req, ctx) in enumerate(batch):
            self._count_prefill(req, int(lens[r]))
            # register AFTER the dispatch wrote the pages; BEFORE the
            # finish check, so a one-shot request (the system-prompt
            # seeding shape) still populates the cache as it retires
            self._register_prefix(slot, ctx)
            nxt = self._sample_first(logits[r], req)
            if self.obs is not None:
                self._obs_prefill_token(req)
            self._append_token(req, nxt)
            if self._finished(req, nxt):
                self._retire(req)
                self._release_slot(slot)
            else:
                self.slots[slot] = req
                self._len[slot] = int(lens[r])
                self._admitted_at[slot] = self._admit_seq
                self._admit_seq += 1
        if hits:
            self._prefill_suffix(hits)
        self._note_occupancy()
        return True

    def _dispatch_cont(self, toks, lens, starts, rows):
        """ONE continuation prefill dispatch (per-row ``start`` offsets)
        through the breaker; the cache is donated, so each closure
        materializes its own host-state pytree.  Returns (logits,
        executed backend)."""
        toks_j, lens_j = jnp.asarray(toks), jnp.asarray(lens)
        starts_j, rows_j = jnp.asarray(starts), jnp.asarray(rows)
        (logits, self.cache), used = self._dispatch_guarded(
            "prefill",
            lambda: self._prefill_cont(self.params, toks_j, lens_j, starts_j,
                                       self._host_state_cache(), rows_j),
            None if self._prefill_cont_fb is None else
            (lambda: self._prefill_cont_fb(self.params, toks_j, lens_j,
                                           starts_j,
                                           self._host_state_cache(),
                                           rows_j)))
        return logits, used

    def _obs_prefill(self, t0: float, logits, n_pad: int, s_pad: int,
                     lens: np.ndarray, starts: np.ndarray, **extra):
        """The ``prefill`` span event of one batched dispatch, once its
        logits exist: from ``t0`` (before the host arrays were built) to
        now, with the dispatch's shape and each real row's tokens and
        start offset."""
        logits.block_until_ready()
        now = self._clock()
        self._metrics.histogram("engine.prefill_s").observe(now - t0)
        keep = lens > 0
        self._tracer.emit("prefill", ts=now, t0=t0, n_requests=int(keep.sum()),
                          n_tokens=int(lens.sum()), rows=n_pad,
                          padded_len=s_pad, padded_tokens=n_pad * s_pad,
                          lens=lens[keep].tolist(),
                          starts=starts[keep].tolist(), dur_s=now - t0,
                          **extra)

    def _prefill_suffix(self, hits: list):
        """ONE batched continuation dispatch for this round's cache-hit
        admissions: each row re-prefills ONLY its non-shared suffix at
        its per-row ``start`` offset, attending over the shared pages
        through its page table (prefix caching requires
        ``supports_chunked_prefill`` for exactly this dispatch).  Suffix
        lengths are bucket-padded and the row count padded to a power of
        two like the whole-prompt path.  Only the dispatched suffix
        tokens are counted as prefill work — the acceptance pin for "the
        second admit prefills only the non-shared suffix"."""
        n_pad = 1 << (len(hits) - 1).bit_length()
        s_max = max(len(ctx) - start for _, _, ctx, start in hits)
        s_pad = min(self.max_len,
                    -(-s_max // self.prefill_bucket) * self.prefill_bucket)
        t0 = self._clock() if self.obs is not None else 0.0
        with _span("engine.prefill", rows=n_pad,
                   tokens=sum(len(ctx) - start for _, _, ctx, start in hits),
                   padded=n_pad * s_pad):
            toks = np.zeros((n_pad, s_pad), np.int32)
            lens = np.zeros((n_pad,), np.int32)
            starts = np.zeros((n_pad,), np.int32)
            rows = np.full((n_pad,), self.max_slots, np.int32)
            for r, (slot, req, ctx, start) in enumerate(hits):
                suffix = ctx[start:]
                toks[r, :len(suffix)] = suffix
                lens[r] = len(suffix)
                starts[r] = start
                rows[r] = slot
            logits, used = self._dispatch_cont(toks, lens, starts, rows)
        self._c_prefill.inc()
        self._attr_dispatch("prefill", used)
        if self.obs is not None:
            self._obs_prefill(t0, logits, n_pad, s_pad, lens, starts,
                              prefix=True)
        for r, (slot, req, ctx, start) in enumerate(hits):
            took = int(lens[r])
            self._count_prefill(req, took)
            self._len[slot] = start + took
            # deepen the chain: matched entries are skipped, the hit's
            # own full suffix pages register as new descendants
            self._register_prefix(slot, ctx)
            nxt = self._sample_first(logits[r], req)
            if self.obs is not None:
                self._obs_prefill_token(req)
            self._append_token(req, nxt)
            if self._finished(req, nxt):
                self._retire(req)
                self._release_slot(slot)

    def _advance_prefill(self):
        """Advance every chunk-prefilling slot by ONE bounded chunk with
        a single batched continuation dispatch (row count padded to a
        power of two, chunk length fixed — the jit cache stays small).
        Rows whose prompt completes sample their first token from the
        final chunk's logits and become decode-active; the interleave
        with ``step()``'s decode dispatch is what bounds the per-token
        gap concurrent streams see during a long admit."""
        if not self._prefilling:
            return
        items = sorted(self._prefilling.items())
        chunk = self.prefill_chunk
        n_pad = 1 << (len(items) - 1).bit_length()
        t0 = self._clock() if self.obs is not None else 0.0
        # resume contexts are stable mid-chunking: a chunk-prefilling
        # slot sits out decode, so out_tokens cannot grow under it
        srcs = [self._resume_ctx(self.slots[slot]) for slot, _ in items]
        takes = [min(chunk, len(src) - done)
                 for src, (_, done) in zip(srcs, items)]
        with _span("engine.prefill_chunk", rows=n_pad, tokens=sum(takes),
                   padded=n_pad * chunk):
            toks = np.zeros((n_pad, chunk), np.int32)
            lens = np.zeros((n_pad,), np.int32)
            starts = np.zeros((n_pad,), np.int32)
            rows = np.full((n_pad,), self.max_slots, np.int32)
            for r, ((slot, done), src, take) in enumerate(
                    zip(items, srcs, takes)):
                toks[r, :take] = src[done:done + take]
                lens[r] = take
                starts[r] = done
                rows[r] = slot
            logits, used = self._dispatch_cont(toks, lens, starts, rows)
        self._c_prefill.inc()
        self._attr_dispatch("prefill", used)
        if self.obs is not None:
            self._obs_prefill(t0, logits, n_pad, chunk, lens, starts,
                              chunked=True)
        for r, (slot, done) in enumerate(items):
            req = self.slots[slot]
            took = int(lens[r])
            self._count_prefill(req, took)
            self._len[slot] = done + took
            if done + took < len(self._resume_ctx(req)):
                self._prefilling[slot] = done + took
                continue
            del self._prefilling[slot]
            # the prompt is fully written: publish its full pages (a
            # cache-hit slot chunking from a matched offset deepens the
            # chain — its matched entries are skipped)
            self._register_prefix(slot, self._resume_ctx(req))
            nxt = self._sample_first(logits[r], req)
            if self.obs is not None:
                self._obs_prefill_token(req)
            self._append_token(req, nxt)
            if self._finished(req, nxt):
                self._retire(req)
                self._release_slot(slot)

    def _preempt_youngest(self, active: list[int]):
        """Deadlock breaker: every active slot needs a page and none are
        free.  The youngest occupant requeues; re-admission re-prefills
        its full context (``_resume_ctx``: prompt + generated tokens) —
        that reproduces the pending decode input's logits, so the greedy
        continuation is token-identical.  ``req.prompt`` itself is NOT
        touched: the caller's Request must come back exactly as
        submitted.  A context that can NEVER fit again (more pages than
        the whole pool / table width — the pool is simply too small for
        the request) retires truncated instead of requeueing: leaving it
        at the FIFO head would starve every request behind it forever."""
        i = max(active, key=lambda j: self._admitted_at[j])
        req = self.slots[i]
        self._metrics.counter("engine.preemptions").inc()
        if self.obs is not None:
            self._tracer.emit("preempt", ts=self._clock(), uid=req.uid,
                              slot=i, n_generated=len(req.out_tokens))
        self._release_slot(i)
        ctx_len = len(req.prompt) + len(req.out_tokens)
        if self._pages_needed(ctx_len) > min(self.n_pages,
                                             self.table_width):
            self._retire(req)
        else:
            self.queue.appendleft(req)
            if self.obs is not None:
                # queue wait for the resumed admission is measured from
                # HERE, not the original submit (see _obs_admitted)
                self._wait_from[req.uid] = self._clock()

    # -- one engine tick ----------------------------------------------------

    def _tick(self) -> int:
        if self._faults is not None:
            self._fire_slow_tick()
        self._admit()
        self._step += 1
        # one bounded prefill chunk per tick, BEFORE the decode dispatch:
        # decoding slots and a chunk-prefilling long prompt make progress
        # in the same tick (slots mid-chunking sit out the decode)
        self._advance_prefill()
        active = [i for i, r in enumerate(self.slots)
                  if r is not None and i not in self._prefilling]
        if not active:
            return 0
        if self._spec_on:
            with _span("engine.spec", tick=self._step):
                return self._spec_step(active)
        t0 = self._clock() if self.obs is not None else 0.0
        # on-demand growth: a slot whose next write starts a new page
        # allocates it now; allocation failure stalls the slot this tick
        # (its write would have no destination and is dropped anyway)
        ready = []
        for i in active:
            if self._pt is not None:
                pi = self._len[i] // self.page_size
                if pi < self.table_width and self._pt[i, pi] < 0:
                    # an injected allocation failure behaves exactly like
                    # a genuinely exhausted pool: the slot stalls this
                    # tick (its tokens are unaffected — decode depends
                    # only on its own cache), and the existing stall /
                    # preempt machinery takes over
                    if (self._faults is not None
                            and self._fire("page_alloc_fail",
                                           uid=self.slots[i].uid,
                                           op="grow")):
                        continue
                    p = self._alloc_page()
                    if p is None:
                        continue
                    self._pt[i, pi] = p
                    self._ref[p] += 1
                elif (self._prefix_on and pi < self.table_width
                      and self._page_shared(int(self._pt[i, pi]))
                      and not self._cow_slot_page(i, pi)):
                    # shared/cached pages are always FULL pages, so a
                    # decode write (position >= the prefilled length)
                    # structurally lands in an exclusive tail or a fresh
                    # page — this guard is the paged_update contract's
                    # backstop, and a failed clone stalls like an
                    # allocation failure
                    continue
            ready.append(i)
        self._note_occupancy()
        if not ready:
            self._preempt_youngest(active)
            return 0
        t_alloc = self._clock() if self.obs is not None else 0.0
        with _span("engine.decode", rows=len(ready), tick=self._step):
            last = np.zeros((self.max_slots, 1), np.int32)
            temps = np.zeros((self.max_slots,), np.float32)
            uids = np.zeros((self.max_slots,), np.int32)
            for i in ready:
                req = self.slots[i]
                last[i, 0] = req.out_tokens[-1]
                temps[i] = req.temperature
                uids[i] = req.uid
            before = self._host_state_cache()
            last_j = jnp.asarray(last)
            # decode is NOT donated, so ``before`` stays valid for both the
            # breaker's fallback retry and the ssm rollback below
            (logits, self.cache), used = self._dispatch_guarded(
                "decode",
                lambda: self._decode(self.params, last_j, before),
                None if self._decode_fb is None else
                (lambda: self._decode_fb(self.params, last_j, before)))
        self._c_decode.inc()
        self._c_ticks.inc()
        self._attr_dispatch("decode", used)
        self._metrics.counter(
            f"dispatch.paged_attention.{self.paged_attention_backend}").inc()
        stalled = [i for i in active if i not in ready]
        if stalled and hasattr(self.cache, "ssm"):
            # paged-KV writes of stalled rows drop (no destination page),
            # but the hybrid family's recurrent state leaves DID advance
            # on the garbage tick — roll those rows back
            sl = np.asarray(stalled)
            self.cache = dataclasses.replace(
                self.cache,
                ssm=self.cache.ssm.at[:, sl].set(before.ssm[:, sl]),
                conv=self.cache.conv.at[:, sl].set(before.conv[:, sl]))
        if self._faults is not None:
            logits = self._poison_logits(logits, ready)
        failed = self._guard_rows(logits, ready)
        with _span("engine.sample", rows=len(ready)):
            toks = np.asarray(self._sample_batch(logits[:, -1], temps, uids))
        if self.obs is not None:
            # toks materialized ⇒ the decode dispatch completed; failed
            # rows stream no token so they leave the tick's uid list;
            # ``ctx`` is each live row's context as the dispatch read it
            now = self._clock()
            self._metrics.histogram("engine.tick_s").observe(now - t0)
            self._tracer.emit("tick", ts=now, tick=self.ticks,
                              n_active=len(ready),
                              uids=[self.slots[i].uid for i in ready
                                    if i not in failed],
                              ctx=[int(self._len[i]) for i in active],
                              n_stalled=len(stalled), dur_s=now - t0,
                              alloc_dur_s=t_alloc - t0)
        for i in ready:
            req = self.slots[i]
            if i in failed:
                # guard containment: retire failed, pages back to the
                # pool — co-scheduled slots' tokens are bit-identical to
                # a fault-free run (the guard read only this row)
                self._fail_slot(i)
                continue
            self._len[i] += 1
            nxt = int(toks[i])
            self._append_token(req, nxt)
            if self._finished(req, nxt):
                self._retire(req)
                self._release_slot(i)
        self._maybe_quant_health()
        return len(ready)

    # -- speculative decoding (docs/speculative.md) -------------------------

    def _draft_sync(self, slot: int):
        """Re-prefill one slot's context into its draft-cache slot (lazy:
        fresh admissions, preemption resumes, and slot reuse all land
        here the first tick they draft).  Batch-1 through the draft's
        own jit — counted under ``spec.*``, NOT the engine prefill
        counters (dispatch attribution stays target-only)."""
        req = self.slots[slot]
        ctx = self._resume_ctx(req)[:int(self._len[slot])]
        fresh = self.draft_model.make_cache(self.draft_cfg, 1,
                                            self._draft_max_len,
                                            bits=self._draft_bits)
        _, slot_cache = self._draft_prefill(
            self.draft_params, jnp.asarray(ctx[None, :], jnp.int32), fresh)
        # full-extent copy: no stale KV/scales from the slot's previous
        # occupant survive into the draft pass
        self._draft_cache = _write_slot(self._draft_cache, slot_cache, slot)
        self._draft_len[slot] = self._len[slot]
        self._metrics.counter("spec.draft_prefill_dispatches").inc()

    def _spec_budget(self, ready: list[int], active: list[int]) -> dict:
        """Per-slot draft depth + page allocation for the verify write
        range.  Row i's verify writes positions ``[L, L+k_i]`` into the
        pool, so every page covering that span is allocated (or COW'd
        out of sharing) NOW; a dry pool shrinks ``k_i`` to the allocated
        range, and a slot whose FIRST page can't be had stalls exactly
        like the plain path.  Temperature rows draft nothing (k_i = 0 —
        the verify row degenerates to the plain single-position decode,
        sampled with the same per-(tick, uid) key)."""
        ks: dict[int, int] = {}
        for i in active:
            req = self.slots[i]
            L = int(self._len[i])
            ki = 0
            if req.temperature <= 0:
                # one token is always emitted; drafts beyond the request's
                # remaining budget could never be accepted into out_tokens
                ki = max(0, min(self.config.spec_k,
                                req.max_new_tokens - len(req.out_tokens) - 1,
                                self.table_width * self.page_size - 1 - L))
            ok = True
            for pi in range(L // self.page_size,
                            (L + ki) // self.page_size + 1):
                if self._pt[i, pi] < 0:
                    p = None
                    if not (self._faults is not None
                            and self._fire("page_alloc_fail",
                                           uid=req.uid, op="grow")):
                        p = self._alloc_page()
                    if p is None:
                        if pi == L // self.page_size:
                            ok = False
                        else:
                            ki = pi * self.page_size - 1 - L
                        break
                    self._pt[i, pi] = p
                    self._ref[p] += 1
                elif (self._prefix_on
                      and self._page_shared(int(self._pt[i, pi]))
                      and not self._cow_slot_page(i, pi)):
                    if pi == L // self.page_size:
                        ok = False
                    else:
                        ki = pi * self.page_size - 1 - L
                    break
            if ok:
                ready.append(i)
                ks[i] = ki
        return ks

    def _spec_step(self, active: list[int]) -> int:
        """One speculative tick: draft up to k tokens per ready slot
        against the slot-major draft cache, verify every candidate
        position in ONE batched ragged target dispatch, emit the longest
        draft prefix the target's argmax agrees with plus one corrected
        token, and roll back rejected-suffix lengths/pages.  Keeps the
        plain tick's contracts: one decode dispatch, host-authoritative
        state pushed per dispatch, stall/preempt/guard semantics."""
        t0 = self._clock() if self.obs is not None else 0.0
        ready: list[int] = []
        ks = self._spec_budget(ready, active)
        self._note_occupancy()
        if not ready:
            self._preempt_youngest(active)
            return 0
        # -- draft phase: k_max+1 batched (max_slots, 1) dense dispatches.
        # Dispatch j consumes the previous token and WRITES its KV, so
        # the (k_max+1)-th writes the deepest draft's KV — on full
        # acceptance the draft cache is exactly in sync at the new
        # length and the next tick drafts with no re-prefill.
        kbig = max(ks.values())
        drafts: dict[int, list[int]] = {i: [] for i in ready}
        if kbig > 0:
            for i in ready:
                if ks[i] > 0 and self._draft_len[i] != self._len[i]:
                    self._draft_sync(i)
            last = np.zeros((self.max_slots, 1), np.int32)
            for i in ready:
                last[i, 0] = self.slots[i].out_tokens[-1]
            dlen = np.array(self._draft_len)
            for j in range(kbig + 1):
                cache = dataclasses.replace(self._draft_cache,
                                            length=jnp.asarray(dlen))
                logits, self._draft_cache = self._draft_decode(
                    self.draft_params, jnp.asarray(last), cache)
                self._metrics.counter("spec.draft_dispatches").inc()
                if j == kbig:
                    break               # KV-write-only: logits discarded
                toks = np.asarray(jnp.argmax(logits[:, -1], -1))
                for i in ready:
                    if len(drafts[i]) < ks[i]:
                        drafts[i].append(int(toks[i]))
                        last[i, 0] = int(toks[i])
                dlen += 1
        # -- verify phase: ONE (max_slots, spec_k+1) ragged dispatch.
        # Row i scores [out[-1], d_1 .. d_k_i] at start _len[i]; stalled
        # and empty rows ride as sentinels (slot id max_slots → writes
        # drop), exactly like batched-prefill padding rows.
        with _span("engine.verify", rows=len(ready), tick=self._step):
            W = self.config.spec_k + 1
            toks = np.zeros((self.max_slots, W), np.int32)
            lens = np.zeros((self.max_slots,), np.int32)
            starts = np.zeros((self.max_slots,), np.int32)
            rows = np.full((self.max_slots,), self.max_slots, np.int32)
            temps = np.zeros((self.max_slots,), np.float32)
            uids = np.zeros((self.max_slots,), np.int32)
            for i in ready:
                req = self.slots[i]
                toks[i, 0] = req.out_tokens[-1]
                toks[i, 1:1 + ks[i]] = drafts[i]
                lens[i] = 1 + ks[i]
                starts[i] = self._len[i]
                rows[i] = i
                temps[i] = req.temperature
                uids[i] = req.uid
            t_alloc = self._clock() if self.obs is not None else 0.0
            toks_j, lens_j = jnp.asarray(toks), jnp.asarray(lens)
            starts_j, rows_j = jnp.asarray(starts), jnp.asarray(rows)
            # the verify jit DONATES the pool (like the prefill paths): each
            # closure materializes its own host-state pytree so a breaker
            # retry never touches consumed buffers
            (logits, self.cache), used = self._dispatch_guarded(
                "decode",
                lambda: self._verify(self.params, toks_j, lens_j, starts_j,
                                     self._host_state_cache(), rows_j),
                None if self._verify_fb is None else
                (lambda: self._verify_fb(self.params, toks_j, lens_j, starts_j,
                                         self._host_state_cache(), rows_j)))
        self._c_decode.inc()
        self._c_ticks.inc()
        self._attr_dispatch("decode", used)
        self._metrics.counter(
            f"dispatch.paged_attention.{self.paged_attention_backend}").inc()
        self._metrics.counter("spec.verify_dispatches").inc()
        if self._faults is not None:
            logits = self._poison_logits(logits, ready)
        failed = []
        if self._nan_guard:
            # the guard spans each row's VALID positions (the plain
            # tick's last-position check would read verify padding)
            lg = np.asarray(logits, np.float32)
            failed = [i for i in ready
                      if not np.isfinite(lg[i, :int(lens[i])]).all()]
        with _span("engine.sample", rows=len(ready)):
            greedy = np.asarray(jnp.argmax(logits, -1))
            samp = (np.asarray(self._sample_batch(logits[:, 0], temps, uids))
                    if (temps > 0).any() else None)
        now = 0.0
        if self.obs is not None:
            now = self._clock()
            self._metrics.histogram("engine.tick_s").observe(now - t0)
            self._tracer.emit("tick", ts=now, tick=self.ticks,
                              n_active=len(ready),
                              uids=[self.slots[i].uid for i in ready
                                    if i not in failed],
                              ctx=[int(self._len[i]) for i in active],
                              n_stalled=len(active) - len(ready),
                              dur_s=now - t0, alloc_dur_s=t_alloc - t0)
        n_drafted = n_accepted = n_emitted_total = 0
        for i in ready:
            req = self.slots[i]
            if i in failed:
                self._fail_slot(i)
                continue
            if temps[i] > 0:
                emit = [int(samp[i])]
            else:
                emit = cm.spec_accept_greedy(drafts[i], greedy[i])
            n_drafted += ks[i]
            n_accepted += len(emit) - 1
            new_len, done = int(self._len[i]), False
            for n, tok in enumerate(emit):
                if n > 0 and self.obs is not None:
                    # each accepted token past the tick's first gets its
                    # own trace event, so per-uid token chains and
                    # trace-derived decode_tokens count ACCEPTED tokens
                    self._tracer.emit("token", ts=now, uid=req.uid)
                self._append_token(req, tok)
                new_len += 1
                n_emitted_total += 1
                if self._finished(req, tok):
                    done = True
                    break
            if done:
                self._retire(req)
                self._release_slot(i)
                continue
            self._len[i] = new_len
            # rejected-suffix rollback: pages past the accepted length
            # were allocated for verify writes that are now invalid —
            # return them (valid-prefix pages, including every
            # prefix-shared page, always sit below this range)
            for pi in range((new_len - 1) // self.page_size + 1,
                            self.table_width):
                p = int(self._pt[i, pi])
                if p < 0:
                    break
                self._decref(p)
                self._pt[i, pi] = -1
            if ks[i] > 0:
                self._draft_len[i] = new_len
        self._metrics.counter("spec.drafted").inc(n_drafted)
        self._metrics.counter("spec.accepted").inc(n_accepted)
        self._metrics.counter("spec.rejected").inc(n_drafted - n_accepted)
        self._metrics.counter("spec.emitted_tokens").inc(n_emitted_total)
        if self.obs is not None:
            self._tracer.emit("spec", ts=self._clock(), tick=self.ticks,
                              drafted=n_drafted, accepted=n_accepted,
                              rejected=n_drafted - n_accepted,
                              emitted=n_emitted_total,
                              n_rows=len(ready))
        self._maybe_quant_health()
        return len(ready)


class PerSlotServingEngine(_EngineBase):
    """The original per-slot loop: one (1, 1) decode dispatch per active
    slot per tick.  Kept as the equivalence oracle and the throughput
    baseline (benchmarks/serving_throughput.py); the batched engine must
    match its greedy tokens exactly."""

    def _init_caches(self):
        self.caches = [self.model.make_cache(self.cfg, 1, self.max_len,
                                             bits=self.kv_bits)
                       for _ in range(self.max_slots)]

    def _install_slot_cache(self, slot: int, cache):
        self.caches[slot] = cache

    def _tick(self) -> int:
        if self._faults is not None:
            self._fire_slow_tick()
        self._admit()
        self._step += 1
        active = 0
        t0 = self._clock() if self.obs is not None else 0.0
        uids = []
        for i, req in enumerate(self.slots):
            if req is None:
                continue
            active += 1
            tok = jnp.asarray([[req.out_tokens[-1]]], jnp.int32)
            (logits, self.caches[i]), used = self._dispatch_guarded(
                "decode",
                lambda t=tok, c=self.caches[i]: self._decode(self.params,
                                                             t, c),
                None if self._decode_fb is None else
                (lambda t=tok, c=self.caches[i]: self._decode_fb(
                    self.params, t, c)))
            self._c_decode.inc()
            self._attr_dispatch("decode", used)
            if (self._faults is not None
                    and self._fire("nan_logits", uid=req.uid,
                                   tick=self._step)):
                logits = logits.at[0].set(jnp.nan)
            if self._nan_guard and not np.isfinite(
                    np.asarray(logits[:, -1], np.float32)).all():
                self._fail_slot(i)
                continue
            uids.append(req.uid)
            nxt = int(_sample_one(logits[:, -1], req.temperature, self._step,
                                  req.uid)[0])
            self._append_token(req, nxt)
            if self._finished(req, nxt):
                self._retire(req)
                self.slots[i] = None
        if active:
            self._c_ticks.inc()
            if self.obs is not None:
                now = self._clock()
                self._metrics.histogram("engine.tick_s").observe(now - t0)
                self._tracer.emit("tick", ts=now, tick=self.ticks,
                                  n_active=active, uids=uids, dur_s=now - t0)
            self._maybe_quant_health()
        return active
