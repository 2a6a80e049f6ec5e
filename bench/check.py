"""Decide ``correct``: what the timed path produced, against the plain
reference (``bench/reference.py``), after the window.

The sample, drawn from the seed: up to ``N_LIVE`` of the requests still
decoding at the close (the longest among them) and up to ``N_DONE`` of
those that finished in the window (the longest among them).  The
reference runs once, teacher-forced over each sampled prompt with its
served tokens, and gives these numbers:

- the family's cache numbers (``cache_numbers`` in
  ``bench/families/<family>.py``), for the layers ``keep_layers`` names:
  ``kv_l<i>`` (dense) is the relative L2 error, over every cached
  position of the live sample, of the K and V that the timed path wrote
  into the paged pool at layer i (the larger of the two); ``state_l<i>``
  and ``conv_l<i>`` (ssm) the same for the recurrent state that decode
  carried to the close, and for its conv tail (the raw in_proj outputs
  of the last ``conv_kernel - 1`` positions);
- ``gap_p50`` and ``gap_max``: how far below the reference's best logit
  the served token's logit lies, median and widest over every served
  token of the sample;
- ``gap_worst_p50``: the same gap's median over each sampled request's
  own served tokens, the largest over the requests: a request served
  wrong tokens (altered, or another slot's) reads the gap of a token the
  model does not favour, though the others' tokens are sound.

Layer 0's cache is written from the embedding through the first norm,
the K/V (or in_proj) quantized linears and the cache write; layer 1's
is written after the whole of layer 0 (every linear kind, attention over
the served pool or the SSM scan, the MLP or the gated norm), by prefill
for the prompt and by decode for the served tokens.  The served tokens'
gaps cover the whole depth.

A number is compared where ``bench/limits/<config>.json`` gives it a
limit; the rest are printed as readings.  With ``control``, the control
(the reference in the precision below the configuration's, see
``reference.CONTROL``) is read in the program's place on the same
sequences, and its numbers are printed and judged by the same limits.
"""

from __future__ import annotations

import gc

import jax
import numpy as np

from bench import reference as ref
from bench import spec
from bench.weights import calibration_tokens

N_LIVE = 8
N_DONE = 8


def keep_layers(m: dict) -> tuple[int, ...]:
    n = m["num_hidden_layers"]
    return tuple(sorted({0, 1, n // 2, n - 1} & set(range(n))))


def rel(a, b) -> float:
    """Relative L2 error of the arrays ``a`` against ``b``, taken whole."""
    a = np.concatenate([np.ravel(x) for x in a]).astype(np.float64)
    b = np.concatenate([np.ravel(x) for x in b]).astype(np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


def _sample_done(run) -> list[tuple[np.ndarray, int]]:
    done = [r for r, _ in run.finished if r.out_tokens and not r.failed
            and r.uid >= 0]
    done.sort(key=lambda r: -(len(r.prompt) + len(r.out_tokens)))
    rng = np.random.default_rng([run.seed, 5])
    rest = rng.permutation(max(len(done) - 1, 0))[:N_DONE - 1] + 1
    pick = done[:1] + [done[j] for j in sorted(rest)]
    return [(np.concatenate([np.asarray(r.prompt), np.asarray(r.out_tokens)]),
             len(r.prompt)) for r in pick]


def numbers(run, control: bool = False) -> dict:
    """Every number the comparison reads; ``control.<name>`` beside
    each with ``control``."""
    m, q = run.m, run.cell.config["quant"]
    fam = spec.family(m["family"])
    gc.collect()
    weights = fam.weights(m, run.seed)
    calib = fam.calibrate(weights, m, q, np.concatenate(
        calibration_tokens(run.cell.config, run.seed)))
    live = run.live
    done = _sample_done(run)
    seqs = [s for s, _, _ in live] + [s for s, _ in done]
    n_prompt = [p for _, p, _ in live] + [p for _, p in done]
    valid = [n for _, _, n in live] + [len(s) - 1 for s, _ in done]
    precs = (ref.SOUND, ref.CONTROL) if control else (ref.SOUND,)
    layers = keep_layers(m)
    with jax.default_matmul_precision("highest"):
        out = ref.forward(weights, m, q, seqs, valid, precs, layers, calib)
        served, alt = [], []
        xa = out[precs[-1]]["x"]
        for j, s in enumerate(seqs):
            g, a = ref.gaps(weights, m, out[ref.SOUND]["x"][j], xa[j], s,
                            n_prompt[j])
            served.append(g)
            alt.append(a)
    nl = len(live)
    res = {}
    if nl:
        sound = {l: out[ref.SOUND]["cache"][l][:nl] for l in layers}
        res.update(fam.cache_numbers(run.snapshot, sound, layers))
        if control:
            ctrl = {j: {l: out[ref.CONTROL]["cache"][l][j] for l in layers}
                    for j in range(nl)}
            res.update({"control." + k: v for k, v in
                        fam.cache_numbers(ctrl, sound, layers).items()})
    res["gap_worst_p50"] = max(float(np.median(g)) for g in served if len(g))
    if control:
        res["control.gap_worst_p50"] = max(float(np.median(a))
                                           for a in alt if len(a))
    served, alt = np.concatenate(served), np.concatenate(alt)
    res["gap_p50"] = float(np.median(served))
    res["gap_max"] = float(np.max(served))
    if control:
        res["control.gap_p50"] = float(np.median(alt))
        res["control.gap_max"] = float(np.max(alt))
    res["served_tokens_compared"] = int(len(served))
    res["live_compared"] = nl
    del weights, out
    return res


def judge(nums: dict, limits: dict, prefix: str = "") -> tuple[bool, dict]:
    """(correct, {name: {"value", "limit"}}) over the limited numbers."""
    checks = {}
    for name, lim in limits["limits"].items():
        v = nums.get(prefix + name)
        checks[name] = {"value": v, "limit": lim["limit"]}
    ok = all(c["value"] is not None and np.isfinite(c["value"])
             and c["value"] <= c["limit"] for c in checks.values())
    return ok, checks
