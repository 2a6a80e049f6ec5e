"""The model families behind one interface.

Each ``bench/families/<family>.py`` provides the names in ``INTERFACE``;
every configuration file names a family; no other module of ``bench/``
branches on a family's name or on what a cache holds.  The golden
digests in ``testdata/families-golden.json`` were recorded at the CPU
test sizes before the families moved into their own files: the weights
(whole tree and each layer), calibration, the reference's final states,
caches and logit gaps for two seeded sequences, and the work counts of a
fixed list of dispatches at published widths.  The move has to keep
every one of them bit for bit.
"""

import functools
import hashlib
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import reference as ref
from bench import spec, work
from bench.check import keep_layers
from bench.serve_cell import Dispatch
from bench.test_check import tiny
from bench.weights import calibration_tokens

FAMILIES = sorted(os.path.splitext(f)[0]
                  for f in os.listdir(os.path.join(spec.BENCH, "families"))
                  if f.endswith(".py"))
CONFIGS = sorted(os.path.splitext(f)[0]
                 for f in os.listdir(os.path.join(spec.BENCH, "configs"))
                 if f.endswith(".json"))
INTERFACE = (
    # description
    "FIELDS", "program_fields", "shapes", "TINY",
    # weights by layer, and the program's served parameters
    "weights", "served_params",
    # reference
    "calibrate", "fold_layer", "layer", "BATCHED",
    # the timed path's cache against the reference's
    "snapshot", "cache_numbers",
    # work
    "linear_work", "attention_work", "token_ops",
)

with open(os.path.join(spec.BENCH, "testdata", "families-golden.json")) as fh:
    GOLDEN = json.load(fh)
SEED = GOLDEN["seed"]


def _config(name: str) -> dict:
    with open(os.path.join(spec.BENCH, "configs", name + ".json")) as fh:
        return json.load(fh)


def sha(a) -> str:
    a = np.ascontiguousarray(np.asarray(a))
    return hashlib.sha256(f"{a.dtype}{a.shape}".encode() + a.tobytes()).hexdigest()


def leaves(tree) -> dict:
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return {jax.tree_util.keystr(p): sha(v) for p, v in flat}


@pytest.mark.parametrize("name", FAMILIES)
def test_family_provides_the_interface(name):
    fam = spec.family(name)
    missing = [k for k in INTERFACE if not hasattr(fam, k)]
    assert not missing, f"families/{name}.py lacks {missing}"
    assert set(fam.TINY) == {"model", "engine"}
    assert set(fam.TINY["model"]) <= set(fam.FIELDS)
    assert isinstance(fam.BATCHED, bool)


@pytest.mark.parametrize("name", CONFIGS)
def test_every_config_names_a_family(name):
    """Each configuration loads its family, whose sizes give the program
    its preset exactly: nothing derived is set that the family does not
    derive (no ``head_dim = hidden // heads`` for a family whose heads
    are not that)."""
    from repro.configs.base import get_config

    conf = _config(name)
    assert conf["family"] in FAMILIES
    m = spec.model(conf)
    assert spec.program_config(conf, m) == get_config(conf["model"])
    assert set(spec.family(m["family"]).TINY["engine"]) <= set(conf["engine"])


def _bench_modules():
    for dirpath, _, files in os.walk(spec.BENCH):
        if os.path.basename(dirpath) in ("families", "__pycache__"):
            continue
        for f in files:
            if f.endswith(".py"):
                yield os.path.join(dirpath, f)


def test_no_family_branches_outside_families():
    names = "|".join(FAMILIES)
    quoted = rf"""["']({names})["']"""
    bad = [re.compile(rf"(==|!=)\s*{quoted}"),
           re.compile(rf"{quoted}\s*(==|!=)"),
           re.compile(rf"\bin\s*[(\[{{]\s*{quoted}"),
           re.compile(r"hasattr\([^,]*cache[^,]*,")]
    found = []
    for path in _bench_modules():
        with open(path) as fh:
            for i, line in enumerate(fh, 1):
                if any(p.search(line) for p in bad):
                    found.append(f"{os.path.relpath(path, spec.ROOT)}:{i}: "
                                 f"{line.strip()}")
    assert not found, "\n".join(found)


@pytest.mark.parametrize("name", FAMILIES)
def test_layer_at_a_time(name):
    """Layer i made from the seed alone: the same leaves in whatever
    order the layers are asked for, and each layer its own."""
    conf = next(c for c in CONFIGS if _config(c)["family"] == name)
    m = spec.model(tiny(conf).config)
    n = m["num_hidden_layers"]
    fam = spec.family(name)
    a, b = fam.weights(m, SEED), fam.weights(m, SEED)
    forward = [leaves(a.layer(i)) for i in range(n)]
    backward = [leaves(b.layer(i)) for i in reversed(range(n))][::-1]
    assert forward == backward
    assert all(forward[i] != forward[0] for i in range(1, n))
    assert leaves(a.top) == leaves(b.top)
    assert leaves(fam.weights(m, SEED + 1).layer(0)) != forward[0]


@functools.lru_cache(maxsize=None)
def _golden_run(name: str):
    """The reference at the CPU test sizes, as the digests were recorded:
    weights, calibration, both precisions over two seeded sequences."""
    c = tiny(GOLDEN["families"][name]["config"])
    m, q = spec.model(c.config), c.config["quant"]
    fam = spec.family(name)
    w = fam.weights(m, SEED)
    calib = fam.calibrate(w, m, q, np.concatenate(
        calibration_tokens(c.config, SEED)))
    rng = np.random.default_rng([SEED, 9])
    seqs = [rng.integers(0, m["vocab_size"], n) for n in (37, 70)]
    valid, n_prompt = [36, 61], [20, 50]
    precs = (ref.SOUND, ref.CONTROL)
    with jax.default_matmul_precision("highest"):
        f = ref.forward(w, m, q, seqs, valid, precs, keep_layers(m), calib)
        gaps = [[sha(g) for g in ref.gaps(w, m, f[ref.SOUND]["x"][j],
                                          f[ref.CONTROL]["x"][j], s,
                                          n_prompt[j])]
                for j, s in enumerate(seqs)]
    return m, w, calib, f, gaps


@pytest.mark.parametrize("name", sorted(GOLDEN["families"]))
def test_golden_weights(name):
    want = GOLDEN["families"][name]["model"]
    m, w, _, _, _ = _golden_run(name)
    n = m["num_hidden_layers"]
    layers = [w.layer(i) for i in reversed(range(n))][::-1]
    assert [leaves(x) for x in layers] == want["layers"]
    stacked = jax.tree.map(lambda *a: jnp.stack(a), *layers)
    assert leaves({**w.top, "layers": stacked}) == want["weights"]


@pytest.mark.parametrize("name", sorted(GOLDEN["families"]))
def test_golden_calibration(name):
    _, _, calib, _, _ = _golden_run(name)
    got = None if calib is None else [sha(a) for a in calib]
    assert got == GOLDEN["families"][name]["model"]["calibrate"]


@pytest.mark.parametrize("name", sorted(GOLDEN["families"]))
def test_golden_reference(name):
    want = GOLDEN["families"][name]["model"]
    _, _, _, f, gaps = _golden_run(name)
    for tag, p in (("sound", ref.SOUND), ("control", ref.CONTROL)):
        got = {"x": [sha(x) for x in f[p]["x"]],
               "cache": {str(l): [[sha(a) for a in c] for c in cs]
                         for l, cs in f[p]["cache"].items()}}
        assert got == want["forward"][tag], tag
    assert gaps == want["gaps"]


@pytest.mark.parametrize("name", sorted(GOLDEN["families"]))
def test_golden_work(name):
    """Work counts at published widths, exactly as before the move."""
    want = GOLDEN["families"][name]["work"]
    conf = _config(GOLDEN["families"][name]["config"])
    m, q = spec.model(conf), conf["quant"]
    fam, peaks = spec.family(name), spec.peaks("TPU v5 lite")
    ds = [Dispatch(*d) for d in GOLDEN["dispatches"]]
    L, wb = m["num_hidden_layers"], q["weight_bits"]
    assert [work.linear_seconds(d, m, wb, peaks) for d in ds] == want["linear_seconds"]
    assert [fam.linear_work(m, d, wb) for d in ds] == [
        [(L, [tuple(c) for c in calls])] for calls in want["linear_work"]]
    assert [list(fam.token_ops(m, c)) for c in (0, 99, 5000)] == want["token_ops"]
    assert work.model_seconds(m, ds, peaks) == want["model_seconds"]
    dec = [d for d in ds if d.kind == "decode"]
    kv = q["kv_bits"] or 16
    if "attention_seconds" in want:
        assert [work.paged_attention_seconds(d, m, kv, peaks)
                for d in dec] == want["attention_seconds"]
        assert [fam.attention_work(m, d, kv) for d in dec] == [
            [(L, [tuple(c)])] for c in want["attention_work"]]
    else:
        assert all(fam.attention_work(m, d, kv) == [] for d in dec)
