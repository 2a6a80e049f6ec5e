"""Serving driver: load (or train-and-fold) a model, quantize per the
paper's pipeline, and run the continuous-batching engine over a request
stream.

Usage:
    PYTHONPATH=src python -m repro.launch.serve --arch stablelm-3b \
        --reduced --requests 8 --max-new 16 --weight-bits 4

Observability (docs/observability.md): ``--trace-out t.jsonl`` streams
per-request span events (replayable offline with ``python -m repro.obs
t.jsonl``), ``--metrics-out m.json`` dumps the metrics-registry
snapshot, ``--quant-health N`` probes live activation health every N
ticks against the calibrated ranges, and ``--json`` swaps the human
report for one structured JSON document on stdout.

``--speculate K`` turns on draft-verify speculative decoding in the
paged engine (docs/speculative.md): K drafted tokens per slot verify in
ONE batched ragged dispatch per tick, greedy outputs bit-identical to
the plain path.

``--serve-http`` routes the same workload through the async streaming
front-end (repro.serving.frontend) over loopback — per-request
deadlines (``--deadline-s``), admission control (``--shed-queue-depth``
/ ``--shed-score``) and chunked prefill (``--prefill-chunk``) — and
prints the same reports from the same trace schema (docs/serving.md).

On a real cluster this runs under the production mesh with the sharding
rules from launch/sharding.py; the CPU path uses a (1,1) mesh with the
same code.

``main`` is ``run(args, *build(args))`` under that mesh: ``build`` makes
the (folded) params and ``run`` serves them and returns the report dict
(``chip_smoke.py`` calls the two itself to check the folded params
further on the chip).
"""

from __future__ import annotations

import argparse
import json
import os
import time
from typing import NamedTuple

import jax
import numpy as np

from repro.checkpoint import Checkpointer
from repro.configs.base import get_config
from repro.core.qlinear import QuantPolicy
from repro.core.transforms import TransformPlan
from repro.data import calibration_stream
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_test_mesh
from repro.models.api import get_model
from repro.obs import Observability, QuantHealthSampler, format_summary
from repro.serving.engine import (EngineConfig, PagedServingEngine,
                                  PerSlotServingEngine, Request,
                                  ServingEngine)
from repro.serving.fold import collect_calibration, fold_quantize


class Built(NamedTuple):
    """What ``build`` hands to ``run``."""

    cfg: object
    model: object
    params: dict
    policy: QuantPolicy | None
    stats: dict | None


def _parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="stablelm-3b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--checkpoint", default="",
                    help="checkpoint dir (else random init)")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--max-slots", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=256)
    ap.add_argument("--weight-bits", type=int, default=4, choices=[4, 8])
    ap.add_argument("--act-bits", type=int, default=4, choices=[4, 8])
    ap.add_argument("--kv-bits", type=int, default=8, choices=[0, 8])
    ap.add_argument("--no-quant", action="store_true",
                    help="serve bf16 (baseline)")
    ap.add_argument("--alpha", type=float, default=0.5,
                    help="smoothing migration strength (paper Eq. 4)")
    ap.add_argument("--auto-plan", action="store_true",
                    help="search a per-layer transform/α plan from the "
                         "calibration stream (repro.autoplan)")
    ap.add_argument("--plan-json", default="",
                    help="load a saved LayerwisePlan JSON instead of the "
                         "fixed §V plan (overridden by --auto-plan)")
    ap.add_argument("--use-kernels", default="auto",
                    choices=["auto", "never", "interpret"],
                    help="matmul backend (resolved by kernels.ops: auto = "
                         "fused Pallas qlinear on TPU, XLA elsewhere)")
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--engine", default="paged",
                    choices=["paged", "batched", "per-slot"],
                    help="paged: paged KV pool + in-engine batched prefill "
                         "(default); batched: dense slot-major cache, ONE "
                         "(max_slots, 1) decode dispatch per tick; "
                         "per-slot: the original one-dispatch-per-active-"
                         "slot baseline")
    ap.add_argument("--page-size", type=int, default=64,
                    help="paged engine: tokens per KV page")
    ap.add_argument("--prefill-chunk", type=int, default=0,
                    help="paged engine: interleave bounded prefill chunks "
                         "of this many tokens with decode ticks so a long "
                         "admit can't stall streaming tokens (0 = whole-"
                         "prompt prefill; dense-transformer family only)")
    ap.add_argument("--serve-http", action="store_true",
                    help="serve the workload through the async HTTP "
                         "front-end (repro.serving.frontend) over loopback "
                         "instead of the offline run() loop — same engine, "
                         "same trace schema, same report")
    ap.add_argument("--http-port", type=int, default=0,
                    help="--serve-http bind port (0 = ephemeral)")
    ap.add_argument("--deadline-s", type=float, default=0.0,
                    help="--serve-http per-request deadline in seconds "
                         "(0 = none); expired requests cancel mid-stream")
    ap.add_argument("--shed-queue-depth", type=int, default=64,
                    help="--serve-http admission control: hard queue-depth "
                         "cap before requests shed with HTTP 503")
    ap.add_argument("--shed-score", type=float, default=32.0,
                    help="--serve-http admission control: shed when queue "
                         "depth × pool occupancy crosses this bound")
    ap.add_argument("--pool-pages", type=int, default=0,
                    help="paged engine: shared pool size in pages (0 = "
                         "zero-overcommit sizing, max_slots × pages/slot; "
                         "smaller pools overcommit and rely on admission "
                         "backpressure)")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="paged engine: share identical prompt prefixes "
                         "page-granularly across requests (refcounted "
                         "pages, copy-on-write on divergence, LRU "
                         "eviction under pool pressure — docs/serving.md "
                         "§Prefix caching; dense-transformer family only)")
    ap.add_argument("--speculate", type=int, default=0, metavar="K",
                    help="paged engine: draft K tokens per slot per tick "
                         "(self-draft) and verify all K+1 positions in ONE "
                         "batched ragged dispatch; greedy outputs stay "
                         "bit-identical to K=0 (docs/speculative.md; "
                         "dense-transformer family only)")
    ap.add_argument("--trace-out", default="",
                    help="stream per-request span events (submit/admit/"
                         "prefill/first-token/tick/preempt/retire) to this "
                         "JSONL file; summarize offline with "
                         "`python -m repro.obs <file>`")
    ap.add_argument("--metrics-out", default="",
                    help="write the metrics-registry snapshot (counters/"
                         "gauges/histograms) to this JSON file")
    ap.add_argument("--quant-health", type=int, default=0, metavar="N",
                    help="every N engine ticks, probe one live request's "
                         "activations against the calibrated ranges "
                         "(absmax / clip fraction / Eq.-2 difficulty); "
                         "0 = off (no extra dispatches)")
    ap.add_argument("--nan-guard", action="store_true",
                    help="per-tick finite check on decode logits: a "
                         "NaN/Inf row retires its request with status "
                         "failed (pages freed, guard trace event) instead "
                         "of streaming garbage (docs/resilience.md)")
    ap.add_argument("--fault-plan", default="",
                    help="deterministic fault-injection schedule: a "
                         "FaultPlan JSON file (or inline JSON list) "
                         "replayed at the instrumented sites "
                         "(docs/resilience.md)")
    ap.add_argument("--json", action="store_true",
                    help="emit ONE structured JSON report on stdout "
                         "instead of the human tables")
    return ap


def parse_args(argv=None) -> argparse.Namespace:
    return _parser().parse_args(argv)


def _say(args):
    return (lambda *a, **k: None) if args.json else print


def build(args: argparse.Namespace) -> Built:
    """Init (or restore) the params; unless ``--no-quant``, calibrate and
    fold them per the plan into the quantized serving params."""
    say = _say(args)
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    model = get_model(cfg)
    key = jax.random.PRNGKey(0)
    params = model.init(key, cfg)
    if args.checkpoint:
        ck = Checkpointer(args.checkpoint)
        restored = ck.restore_latest({"p": params})
        if restored:
            params = restored[0]["p"]
            say(f"restored checkpoint step {restored[1]}")

    policy = stats = None
    if not args.no_quant:
        t0 = time.time()
        stats = collect_calibration(
            model, params, cfg,
            list(calibration_stream(cfg, n_batches=2, batch=2, seq=64)),
            keep_samples=128 if args.auto_plan else 0)
        policy = QuantPolicy(
            weight_bits=args.weight_bits, act_bits=args.act_bits,
            kv_cache_bits=args.kv_bits or None,
            use_kernels=args.use_kernels)
        if args.auto_plan:
            from repro.autoplan import SearchConfig, search_plan

            plan, _ = search_plan(
                params, cfg, stats,
                search=SearchConfig(weight_bits=args.weight_bits,
                                    act_bits=args.act_bits),
                base=TransformPlan(alpha=args.alpha))
            plan_desc = "searched per-layer plan (repro.autoplan)"
        elif args.plan_json:
            from repro.autoplan import LayerwisePlan

            plan = LayerwisePlan.load(args.plan_json)
            # a mismatched plan would silently fall back to its base
            # for every stack — fail loudly instead (the planned stack
            # excludes MoE leading dense layers)
            planned_stack = cfg.num_layers - cfg.first_dense_layers
            if plan.num_layers != planned_stack:
                _parser().error(f"{args.plan_json} plans {plan.num_layers} "
                                f"layers but {cfg.name}'s planned stack "
                                f"has {planned_stack} — searched on a "
                                "different config?")
            if plan.arch and plan.arch != cfg.name:
                say(f"WARNING: plan searched on {plan.arch!r}, "
                    f"serving {cfg.name!r}")
            plan_desc = f"LayerwisePlan from {args.plan_json}"
        else:
            plan = TransformPlan(alpha=args.alpha)
            plan_desc = "SmoothRotation on down_proj — paper §V"
        params = fold_quantize(params, cfg, policy=policy, plan=plan,
                               stats=stats)
        say(f"calibrated + folded W{args.weight_bits}A{args.act_bits} "
            f"in {time.time() - t0:.1f}s (plan: {plan_desc})")
    return Built(cfg, model, params, policy, stats)


def run(args: argparse.Namespace, cfg, model, params, policy, stats) -> dict:
    """Serve the request stream on ``params``; print the report (human
    tables, or ONE JSON document with ``--json``) and return it."""
    say = _say(args)
    qh = None
    if args.quant_health:
        qh = QuantHealthSampler(
            model, params, cfg, policy=policy, every=args.quant_health,
            reference=stats if not args.no_quant else None,
            max_context=args.max_len)
    obs = Observability(trace_path=args.trace_out or None,
                        quant_health=qh)
    faults = None
    if args.fault_plan:
        from repro.resilience.faults import FaultPlan

        text = args.fault_plan
        if os.path.exists(text):
            with open(text) as fh:
                text = fh.read()
        faults = FaultPlan.from_json(text)
        say(f"fault plan armed: {faults}")
    # ONE EngineConfig carries every engine knob (docs/api.md); the
    # non-paged engines ignore the page-pool fields
    econfig = EngineConfig(
        max_slots=args.max_slots, max_len=args.max_len, policy=policy,
        kv_bits=args.kv_bits or None, page_size=args.page_size,
        n_pages=args.pool_pages or None,
        prefill_chunk=args.prefill_chunk or None, obs=obs,
        faults=faults, nan_guard=args.nan_guard,
        prefix_cache=args.prefix_cache, spec_k=args.speculate)
    engine_cls = {"paged": PagedServingEngine, "batched": ServingEngine,
                  "per-slot": PerSlotServingEngine}[args.engine]
    eng = engine_cls(model, params, cfg, config=econfig)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size, size=(4 + i % 13,))
               for i in range(args.requests)]
    if args.prefix_cache:
        # the workload shape the cache exists for: every request
        # opens with the same "system prompt", unique tail per user;
        # whole pages, capped so prompt + tail + decode fit max_len
        max_tail = max(len(p) for p in prompts)
        headroom = args.max_len - max_tail - args.max_new
        sys_len = max(args.page_size,
                      min(4, headroom // args.page_size) * args.page_size)
        system = rng.integers(0, cfg.vocab_size, size=(sys_len,))
        prompts = [np.concatenate([system, p]) for p in prompts]
    if args.serve_http:
        import asyncio

        from repro.serving.frontend import ServingFrontend, http_generate

        async def _drive():
            fe = ServingFrontend(
                eng, port=args.http_port,
                max_queue_depth=args.shed_queue_depth,
                shed_score=args.shed_score,
                default_deadline_s=args.deadline_s or None)
            async with fe:
                say(f"HTTP front-end on {fe.host}:{fe.port}")
                return await asyncio.gather(*[
                    http_generate(fe.host, fe.port, {
                        "prompt": p.tolist(),
                        "max_new_tokens": args.max_new,
                        "temperature": args.temperature})
                    for p in prompts])

        t0 = time.time()
        results = asyncio.run(_drive())
        dt = time.time() - t0
        done = [Request(uid=r["body"]["uid"], prompt=prompts[i],
                        out_tokens=r["body"]["tokens"],
                        done=True, cancelled=r["body"]["cancelled"])
                for i, r in enumerate(results) if r["status"] == 200]
        eng.run_stats = st = eng.stats()
    else:
        for i, p in enumerate(prompts):
            eng.submit(Request(uid=i, prompt=p,
                               max_new_tokens=args.max_new,
                               temperature=args.temperature))
        t0 = time.time()
        done = eng.run(max_ticks=10_000)
        dt = time.time() - t0
        st = eng.run_stats
    obs.close()
    if args.metrics_out:
        with open(args.metrics_out, "w") as fh:
            json.dump(obs.registry.snapshot(), fh, indent=1,
                      sort_keys=True)
    from repro.kernels import ops

    summary = obs.summary()
    dev = jax.devices()[0]
    # one machine-readable document: run stats (minus the bulky
    # per-request map), each request's prompt and output tokens, the obs
    # latency summary, and the process-wide dispatch-resolution tally
    report = {
        "arch": cfg.name, "engine": args.engine,
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(jax.devices())},
        "requests_served": len(done),
        "requests": [{"uid": r.uid, "prompt": [int(t) for t in r.prompt],
                      "tokens": [int(t) for t in r.out_tokens],
                      "failed": r.failed} for r in done],
        "wall_s": dt,
        "decode_tok_per_s": st["decode_tokens"] / max(dt, 1e-9),
        "run_stats": {k: v for k, v in st.items() if k != "per_request"},
        "obs": summary,
        "dispatch_resolutions": ops.dispatch_resolutions(),
    }
    if args.json:
        print(json.dumps(report, indent=1, sort_keys=True))
        return report
    print(f"served {len(done)}/{args.requests} requests, "
          f"{st['decode_tokens']} tokens in {dt:.2f}s "
          f"({st['decode_tokens'] / max(dt, 1e-9):.1f} tok/s, "
          f"{args.engine} engine: {st['decode_dispatches']} decode "
          f"dispatches over {st['ticks']} ticks = "
          f"{st['dispatches_per_tick']:.2f}/tick, plus "
          f"{st['prefill_dispatches']} prefill dispatches, "
          f"kernel backend: {eng.kernel_backend})")
    if "n_pages" in st:
        print(f"  page pool: {st['peak_pages_in_use']}/{st['n_pages']} "
              f"pages at peak ({100 * st['page_occupancy_peak']:.0f}% "
              f"occupancy, page size {st['page_size']}), "
              f"paged attention: {st['paged_attention_backend']}")
    if st.get("prefix", {}).get("enabled"):
        px = st["prefix"]
        print(f"  prefix cache: {px['hits']}/{px['hits'] + px['misses']} "
              f"hits ({100 * px['hit_rate']:.0f}%), "
              f"{px['shared_pages']} shared pages, "
              f"{px['saved_prefill_tokens']} prefill tokens saved, "
              f"{px['cow_copies']} COW copies, "
              f"{px['evictions']} evictions")
    if st.get("spec", {}).get("enabled"):
        sp = st["spec"]
        print(f"  speculative (k={sp['k']}, "
              f"{'self' if sp['self_draft'] else 'separate'}-draft): "
              f"{sp['accepted']}/{sp['drafted']} drafts accepted "
              f"({100 * sp['acceptance_rate']:.0f}%), "
              f"{sp['emitted_tokens']} tokens over "
              f"{sp['verify_dispatches']} verify dispatches = "
              f"{sp['accepted_per_dispatch']:.2f} tokens/dispatch")
    for r in done[:3]:
        print(f"  req {r.uid}: {r.out_tokens[:12]}...")
    print()
    print(format_summary(summary))
    print(f"backend resolutions (kernels.ops): "
          f"{ops.dispatch_resolutions()}")
    if args.trace_out:
        print(f"trace: {args.trace_out} "
              f"(summarize: python -m repro.obs {args.trace_out})")
    return report


def main(argv=None) -> dict:
    args = parse_args(argv)
    enable_compile_cache()
    with jax.set_mesh(make_test_mesh()):
        return run(args, *build(args))


if __name__ == "__main__":
    main()
