"""Build the served model, warm every shape the cell's traffic can
produce, and drive ``PagedServingEngine`` through the measured window.

The weights are the benchmark's, made by the configuration's family
(``bench/families/<family>.py``); calibration and the fold are the
functions ``serve.build`` calls.  The window drives the engine directly
in this process: requests are submitted as they fall due (open loop) or
as a client's last one retires (closed loop), and ``step()`` runs in a
loop.  The engine admits as many requests as it has
free slots and pages, so a prefill could take up to ``max_slots`` rows;
a small front queue in the benchmark hands it at most ``ADMISSION_CAP``
requests that are not yet decoding (queued or chunk-prefilling), so that
every prefill dispatch has 1, 2 or 4 rows and the set of compiled shapes
stays the small one warmed here.  Time spent in that queue counts in the
request's latency, which runs from its due time.

Host spans (``jax.profiler.TraceAnnotation``, names ``bench.*``) wrap
the calls into each layer the benchmark can reach: the engine step,
admission, chunked prefill, the prefill and decode dispatches and
sampling.  They are only installed in a traced run.
"""

from __future__ import annotations

import contextlib
import dataclasses
import gc
import time
from collections import deque

import jax
import numpy as np

from bench import spec as specmod
from bench.traffic import RequestSpec, Traffic

# most requests handed to the engine that are not yet decoding: a limit
# on the shapes warmed in set-up, not a setting of the deployment
ADMISSION_CAP = 4


@dataclasses.dataclass
class Dispatch:
    """One program dispatch in the traced steps, as the work counters
    read it: ``rows`` x ``seq`` tokens through the linears; per row, the
    real tokens and the context each attends over."""

    kind: str                 # "decode" | "prefill"
    rows: int
    seq: int
    tokens: list[int]
    starts: list[int]


@dataclasses.dataclass
class Run:
    """What one run of a cell recorded; the metric readers read this."""

    cell: object
    m: dict
    seed: int
    seconds: float
    setup_s: float = 0.0
    t0: float = 0.0
    t_end: float = 0.0
    due: dict = dataclasses.field(default_factory=dict)
    fed: dict = dataclasses.field(default_factory=dict)
    tokens: dict = dataclasses.field(default_factory=dict)
    finished: list = dataclasses.field(default_factory=list)
    failed: int = 0
    compiles_in_window: int = 0
    events: list = dataclasses.field(default_factory=list)
    dispatches: list = dataclasses.field(default_factory=list)
    trace: object = None
    peaks: dict = dataclasses.field(default_factory=dict)
    live: list = dataclasses.field(default_factory=list)
    snapshot: dict = dataclasses.field(default_factory=dict)
    memory_peak_bytes: int = 0
    recording: bool = False
    step_s: list = dataclasses.field(default_factory=list)
    gc_in_window: tuple = (0, 0.0)

    def in_window(self, t: float) -> bool:
        return self.t0 <= t <= self.t_end


def _span(name: str, fn):
    def wrapped(*a, **k):
        with jax.profiler.TraceAnnotation(name):
            return fn(*a, **k)
    return wrapped


class _Compiles:
    """Counts JAX compilation events while ``on`` is set."""

    def __init__(self):
        self.on = False
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._listen)

    def _listen(self, name, secs, **kw):
        if self.on and ("compile" in name or "trace_duration" in name):
            self.n += 1


class _GcTime:
    """Seconds the interpreter's garbage collector ran while ``on`` is
    set, and how many collections: a host stall it could explain."""

    def __init__(self):
        self.on = False
        self.n, self.s, self._t = 0, 0.0, 0.0
        gc.callbacks.append(self._cb)

    def _cb(self, phase, info):
        if phase == "start":
            self._t = time.perf_counter()
        elif self.on:
            self.n += 1
            self.s += time.perf_counter() - self._t

    def close(self):
        gc.callbacks.remove(self._cb)


def build(cell, seed: int, *, obs: bool):
    """Make the weights, calibrate and fold them (the family's
    ``served_params``), and construct the engine."""
    from repro.core.qlinear import QuantPolicy
    from repro.models.api import get_model
    from repro.obs import Observability
    from repro.serving.engine import EngineConfig, PagedServingEngine

    conf, q, e = cell.config, cell.config["quant"], cell.config["engine"]
    m = specmod.model(conf)
    cfg = specmod.program_config(conf, m)
    model = get_model(cfg)
    policy = QuantPolicy(weight_bits=q["weight_bits"], act_bits=q["act_bits"],
                         kv_cache_bits=q["kv_bits"] or None, use_kernels="auto")
    params = specmod.family(m["family"]).served_params(
        model, cfg, conf, m, seed, policy)
    eng = PagedServingEngine(model, params, cfg, config=EngineConfig(
        max_slots=e["max_slots"], max_len=e["max_len"], policy=policy,
        kv_bits=q["kv_bits"] or None, page_size=e["page_size"],
        n_pages=e.get("n_pages"), prefill_bucket=e["prefill_bucket"],
        prefill_chunk=e.get("prefill_chunk"),
        obs=Observability() if obs else None))
    return m, cfg, eng


def shape_set(conf: dict, traffic: dict, chunked: bool) -> list[tuple[int, int]]:
    """Every (rows, prompt length) group the window can admit at once:
    rows are powers of two up to the admission cap; lengths are the
    bucket multiples the traffic's prompts pad to, and one past the
    chunk where prompts are chunked (its dispatches are (rows, chunk)
    whatever the length)."""
    e = conf["engine"]
    cap, b = ADMISSION_CAP, e["prefill_bucket"]
    lo, hi = traffic["prompt"]["min"], traffic["prompt"]["max"]
    chunk = e.get("prefill_chunk") if chunked else None
    whole_hi = min(hi, chunk) if chunk else hi
    lengths = sorted({min(e["max_len"], -(-n // b) * b)
                      for n in range(lo, whole_hi + 1)})
    if chunk and hi > chunk:
        lengths.append(chunk + 1)
    rows = [1 << i for i in range(cap.bit_length()) if 1 << i <= cap]
    return [(r, n) for n in lengths for r in rows]


def warm(eng, shapes, vocab: int) -> None:
    """Drive each (rows, length) group once through admission, prefill,
    one decode tick and sampling, so that every program the window uses
    is compiled (or loaded from the cache) before it opens."""
    from repro.serving.engine import Request

    rng = np.random.default_rng(0)
    uid = -1
    for rows, n in shapes:
        for _ in range(rows):
            eng.submit(Request(uid=uid, prompt=rng.integers(0, vocab, n),
                               max_new_tokens=2))
            uid -= 1
        while eng.queue or any(s is not None for s in eng.slots):
            eng.step()
        eng.pop_retired()


class Load:
    """Feeds requests to the engine and records every token."""

    def __init__(self, eng, run: Run, traffic: Traffic, cap: int):
        from repro.serving.engine import Request

        self.Request = Request
        self.eng, self.run, self.traffic, self.cap = eng, run, traffic, cap
        self.front: deque[RequestSpec] = deque()
        self.clock = time.perf_counter
        self.next_uid = 0
        eng.on_token = self._on_token

    def _on_token(self, req, tok) -> None:
        self.run.tokens.setdefault(req.uid, []).append(self.clock())

    def enqueue(self, r: RequestSpec, due: float) -> None:
        self.run.due[r.uid] = due
        self.front.append(r)

    def feed(self) -> None:
        eng = self.eng
        pending = len(eng.queue) + len(eng._prefilling)
        while self.front and pending < self.cap:
            r = self.front.popleft()
            self.run.fed[r.uid] = self.clock()
            eng.submit(self.Request(uid=r.uid, prompt=r.prompt,
                                    max_new_tokens=r.max_new, temperature=0.0))
            pending += 1

    def busy(self) -> bool:
        return bool(self.front or self.eng.queue
                    or any(s is not None for s in self.eng.slots))

    def step(self) -> list:
        t = self.clock()
        self.feed()
        self.eng.step()
        done = self.eng.pop_retired()
        now = self.clock()
        if self.run.in_window(t):
            self.run.step_s.append(now - t)
        for req in done:
            self.run.finished.append((req, now))
            self.run.failed += int(req.failed)
        return done

    def all_decoding(self) -> bool:
        eng = self.eng
        return (not self.front and not eng.queue and not eng._prefilling
                and all(s is not None for s in eng.slots))


def instrument(eng, run: Run) -> None:
    """Host spans around the engine's layers, and a record of each
    dispatch's shape for the work counters (traced runs only)."""
    decode, sample = eng._decode, eng._sample_batch
    eng._admit = _span("bench.admit", eng._admit)
    eng._advance_prefill = _span("bench.prefill_chunk", eng._advance_prefill)
    eng._sample_batch = _span("bench.sample", sample)
    slots = eng.max_slots

    def rec_decode(*a):
        if not run.recording:
            return decode(*a)
        live = [i for i, r in enumerate(eng.slots)
                if r is not None and i not in eng._prefilling]
        run.dispatches.append(Dispatch(
            "decode", slots, 1, [1] * len(live),
            [int(eng._len[i]) for i in live]))
        return decode(*a)

    eng._decode = _span("bench.decode", rec_decode)

    def rec_prefill(fn, has_start):
        def call(*a):
            if not run.recording:
                return fn(*a)
            toks, lens = a[1], np.asarray(a[2])
            starts = np.asarray(a[3]) if has_start else np.zeros_like(lens)
            keep = lens > 0
            run.dispatches.append(Dispatch(
                "prefill", toks.shape[0], toks.shape[1],
                lens[keep].tolist(), starts[keep].tolist()))
            return fn(*a)
        return _span("bench.prefill", call)

    eng._prefill_paged = rec_prefill(eng._prefill_paged, False)
    if hasattr(eng, "_prefill_cont"):
        eng._prefill_cont = rec_prefill(eng._prefill_cont, True)


FAULTS = ("state_unchanged", "token_altered", "half_batch", "layer_skipped")


def inject(eng, fault: str | None, vocab: int) -> None:
    """Break the timed path underneath the harness, to show that the
    check catches it (tests, and fault readings on the chip):

    - ``state_unchanged``: the decode step returns its cache as it came in;
    - ``token_altered``: every sampled token is moved to the next id;
    - ``half_batch``: the second half of the rows is sampled from the
      first half's logits, so those slots serve other slots' tokens;
    - ``layer_skipped``: the middle layer's attention output and
      down_proj contribute nothing (their weight scales zeroed).
    """
    if fault is None:
        return
    if fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}")
    if fault == "state_unchanged":
        decode = eng._decode
        eng._decode = lambda p, t, c: (decode(p, t, c)[0], c)
    elif fault == "token_altered":
        sample = eng._sample_batch
        eng._sample_batch = lambda lg, *a: (sample(lg, *a) + 1) % vocab
    elif fault == "half_batch":
        sample = eng._sample_batch

        def halved(lg, *a):
            h = lg.shape[0] // 2
            return sample(lg.at[lg.shape[0] - h:].set(lg[:h]), *a)
        eng._sample_batch = halved
    else:
        layers = eng.params["layers"]
        mid = layers["attn"]["wo"]["qw"].scale.shape[0] // 2
        for grp, name in (("attn", "wo"), ("mlp", "wd")):
            qw = layers[grp][name]["qw"]
            layers[grp][name]["qw"] = dataclasses.replace(
                qw, scale=qw.scale.at[mid].set(0.0))


def serve(cell, seed: int, seconds: float, *, trace: bool, t_start: float,
          trace_dir: str | None = None, fault: str | None = None) -> tuple:
    """Set up, warm, and run the window.  Returns (run, engine)."""
    from repro.launch.mesh import make_test_mesh

    compiles = _Compiles()
    with jax.set_mesh(make_test_mesh()):
        m, cfg, eng = build(cell, seed, obs=trace)
        run = Run(cell=cell, m=m, seed=seed, seconds=seconds)
        conf, tr = cell.config, cell.traffic
        warm(eng, shape_set(conf, tr, eng._chunked), m["vocab_size"])
        inject(eng, fault, m["vocab_size"])
        if trace:
            instrument(eng, run)
        traffic = Traffic(tr, seed, m["vocab_size"])
        drv = Load(eng, run, traffic, ADMISSION_CAP)
        clients = eng.max_slots if tr.get("clients") == "slots" else tr.get("clients")
        if traffic.closed:
            now = time.perf_counter()
            for r in traffic.initial(clients):
                drv.enqueue(r, now)
            drv.next_uid = clients
            while not drv.all_decoding():
                for _ in drv.step():
                    drv.enqueue(traffic.request(drv.next_uid), time.perf_counter())
                    drv.next_uid += 1
        run.t0 = time.perf_counter()
        run.setup_s = run.t0 - t_start
        run.t_end = run.t0 + seconds
        gct = _GcTime()
        compiles.on = gct.on = True
        _window(drv, traffic, run, trace, trace_dir)
        compiles.on = gct.on = False
        gct.close()
        run.compiles_in_window = compiles.n
        run.gc_in_window = (gct.n, gct.s)
        if eng.obs is not None:
            run.events = list(eng.obs.tracer.events)
        dev = jax.devices()[0]
        run.memory_peak_bytes = int((dev.memory_stats() or {}).get(
            "peak_bytes_in_use", 0))
    return run, eng


def _window(drv: Load, traffic: Traffic, run: Run, trace: bool,
            trace_dir: str | None) -> None:
    """The measured window; with ``trace``, a profiler trace over its
    middle third (at most 5 seconds) of whole engine steps."""
    clock = drv.clock
    t0, t_end = run.t0, run.t_end
    tr_lo = t0 + max(0.0, (run.seconds - min(5.0, run.seconds / 3)) / 2)
    tr_hi = tr_lo + min(5.0, run.seconds / 3)
    tracing = False
    i = 0
    step = _span("bench.step", drv.step) if trace else drv.step
    while True:
        now = clock()
        if now >= t_end:
            break
        if trace and not tracing and trace_dir and tr_lo <= now < tr_hi:
            jax.profiler.start_trace(trace_dir, profiler_options=_profile_options())
            run.recording = tracing = True
        elif tracing and now >= tr_hi:
            jax.profiler.stop_trace()
            run.recording = tracing = False
            trace_dir = None
        if not traffic.closed:
            n = traffic.due_before(now - t0)
            while i < n:
                drv.enqueue(traffic.request(i), t0 + float(traffic.arrivals[i]))
                i += 1
        if drv.busy():
            for _ in step():
                if traffic.closed:
                    drv.enqueue(traffic.request(drv.next_uid), clock())
                    drv.next_uid += 1
        else:
            nxt = (t0 + float(traffic.arrivals[i])
                   if not traffic.closed and i < len(traffic.arrivals) else t_end)
            with (jax.profiler.TraceAnnotation("bench.wait") if trace
                  else contextlib.nullcontext()):
                time.sleep(max(0.0, min(nxt, t_end) - clock()))
    if tracing:
        jax.profiler.stop_trace()
        run.recording = False


def _profile_options():
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    return opts


def snapshot(eng, run: Run, keep_layers: tuple[int, ...], n_live: int) -> None:
    """Copy, for a sample of the slots still decoding at the close, the
    cache the timed path wrote (the layers the check compares, as the
    family's ``snapshot`` reads them), then let the engine go."""
    fam = specmod.family(run.m["family"])
    live = [(i, r) for i, r in enumerate(eng.slots)
            if r is not None and i not in eng._prefilling and r.out_tokens]
    live.sort(key=lambda ir: -(len(ir[1].prompt) + len(ir[1].out_tokens)))
    rng = np.random.default_rng([run.seed, 4])
    rest = rng.permutation(len(live) - 1)[:n_live - 1] + 1 if live else []
    pick = live[:1] + [live[j] for j in sorted(rest)]
    for i, r in pick:
        seq = np.concatenate([np.asarray(r.prompt), np.asarray(r.out_tokens)])
        n = len(seq) - 1                       # the last token is not yet fed
        per = {l: fam.snapshot(eng, i, l, n) for l in keep_layers}
        run.live.append((seq, len(r.prompt), n))
        run.snapshot[len(run.live) - 1] = per
