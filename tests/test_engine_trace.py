"""The engine's own measurement: named programs, profiler spans around
its phases (always installed, no flag), and the compile counter.

Spans are read back from a real ``jax.profiler`` trace of a tiny paged
engine on the CPU, the way a trace of the chip is read.
"""

import glob
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.configs.base import get_config
from repro.models.api import get_model
from repro.serving.engine import (
    EngineConfig,
    PagedServingEngine,
    Request,
    _jitted_chunked_prefill,
    _page_copy,
)

KEY = jax.random.PRNGKey(0)


@pytest.fixture(scope="module")
def tiny():
    cfg = get_config("stablelm_3b").reduced()
    model = get_model(cfg)
    return cfg, model, model.init(KEY, cfg)


def _engine(tiny, max_len):
    cfg, model, params = tiny
    return PagedServingEngine(model, params, cfg, config=EngineConfig(
        max_slots=4, max_len=max_len, page_size=8, prefill_bucket=8,
        prefill_chunk=16))


def _submit(eng, cfg, lens, max_new=4, uid0=0):
    for i, n in enumerate(lens):
        eng.submit(Request(uid=uid0 + i, prompt=np.random.default_rng(i)
                           .integers(0, cfg.vocab_size, (n,)),
                           max_new_tokens=max_new))


def _traced(fn) -> list[dict]:
    """Run ``fn`` under the profiler; return its ``engine.*`` host spans
    as dicts of name, start, end (ns) and args."""
    d = tempfile.mkdtemp()
    jax.profiler.start_trace(d)
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(f"{d}/**/*.xplane.pb", recursive=True)[0]
    out = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("engine."):
                    out.append({"name": e.name, "start": e.start_ns,
                                "end": e.start_ns + e.duration_ns,
                                "args": {k: v for k, v in e.stats}})
    return sorted(out, key=lambda s: s["start"])


def _inside(s, outer) -> bool:
    return outer["start"] <= s["start"] and s["end"] <= outer["end"]


def test_spans_nest_under_the_step_with_their_args(tiny):
    cfg = tiny[0]
    eng = _engine(tiny, 96)
    # two short prompts share a whole-prompt prefill; the long one
    # streams through chunks of 16
    _submit(eng, cfg, [5, 9, 40])
    spans = _traced(lambda: [eng.step() for _ in range(6)])
    steps = [s for s in spans if s["name"] == "engine.step"]
    assert len(steps) == 6
    assert [s["args"]["step_num"] for s in steps] == list(range(1, 7))
    names = {s["name"] for s in spans}
    assert {"engine.admit", "engine.prefill", "engine.prefill_chunk",
            "engine.host_sync", "engine.decode", "engine.sample"} <= names
    for s in spans:
        if s["name"] != "engine.step":
            assert any(_inside(s, st) for st in steps), s["name"]
    for s in spans:
        a = s["args"]
        if s["name"] in ("engine.prefill", "engine.prefill_chunk"):
            assert 0 < a["tokens"] <= a["padded"], s
            assert a["padded"] % a["rows"] == 0
        elif s["name"] == "engine.decode":
            assert 1 <= a["rows"] <= 4 and a["tick"] >= 1
            # the page table and lengths go up inside the dispatch span
            assert any(h["name"] == "engine.host_sync" and _inside(h, s)
                       for h in spans)
        elif s["name"] == "engine.sample":
            assert a["rows"] >= 1
    first = [s for s in spans if s["name"] == "engine.prefill"][0]
    assert (first["args"]["rows"], first["args"]["tokens"],
            first["args"]["padded"]) == (2, 14, 2 * 16)
    chunk = [s for s in spans if s["name"] == "engine.prefill_chunk"][0]
    assert (chunk["args"]["tokens"], chunk["args"]["padded"]) == (16, 16)


def test_compile_spans_once_per_signature_and_never_warm(tiny):
    cfg = tiny[0]
    counter = lambda e: int(e._metrics.counter("engine.compiles").value)  # noqa: E731
    # a max_len no other test uses: its programs are not compiled yet
    eng = _engine(tiny, 120)
    _submit(eng, cfg, [6, 7])
    cold = _traced(lambda: eng.run(max_ticks=50))
    compiles = [s for s in cold if s["name"] == "engine.compile"]
    assert compiles and len(compiles) == counter(eng)
    progs = [s["args"]["program"] for s in compiles]
    assert progs.count("decode_step") == 1
    assert set(progs) <= {"prefill_paged", "prefill_chunk", "decode_step"}
    steps = [s for s in cold if s["name"] == "engine.step"]
    assert all(any(_inside(c, st) for st in steps) for c in compiles)
    # the same shapes again: nothing compiles, the counter stands still
    n = counter(eng)
    _submit(eng, cfg, [6, 7], uid0=10)
    warm = _traced(lambda: eng.run(max_ticks=50))
    assert not [s for s in warm if s["name"] == "engine.compile"]
    assert [s for s in warm if s["name"] == "engine.step"]
    assert counter(eng) == n


def test_programs_are_named(tiny):
    cfg, model, params = tiny
    eng = _engine(tiny, 96)
    cache = eng._host_state_cache()
    toks = jnp.zeros((2, 16), jnp.int32)
    lens = jnp.array([5, 16], jnp.int32)
    rows = jnp.array([0, 1], jnp.int32)
    decode = eng._decode._fn.lower(
        params, jnp.zeros((4, 1), jnp.int32), cache).as_text()
    assert decode.startswith("module @jit_decode_step")
    prefill = eng._prefill_paged._fn.lower(
        params, toks, lens, cache, rows).as_text()
    assert prefill.startswith("module @jit_prefill_paged")
    chunk = _jitted_chunked_prefill(model, cfg, None)._fn.lower(
        params, toks, lens, jnp.zeros((2,), jnp.int32), cache, rows).as_text()
    assert chunk.startswith("module @jit_prefill_chunk")
    pool = jnp.zeros((2, 4, 8), jnp.float32)
    assert _page_copy.lower(pool, 0, 1).as_text().startswith(
        "module @jit_page_copy")
