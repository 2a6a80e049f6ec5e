"""Serve-time quant-health sampling: what real traffic does to the
activations the offline calibration planned for.

The autoplan subsystem scores transforms OFFLINE on a calibration
stream (difficulty profiles in ``repro.autoplan.telemetry``); the
SmoothQuant-style folded scales bake those observed ranges into the
weights.  This sampler closes the loop at serving time: every N engine
ticks it re-runs the family's ``forward_with_taps`` over one active
request's full context (prompt + generated tokens — i.e. the exact
token stream the engine is serving) under the SERVING policy, and
reduces each quantized linear's input tap to three per-layer signals:

  * ``absmax``       — the live activation absolute maximum;
  * ``clip_fraction``— fraction of live values whose magnitude exceeds
    the CALIBRATED per-channel absmax (the range the folded smoothing
    scales / quantizer Δ were derived from).  A drifting workload shows
    up here before it shows up in output quality;
  * ``difficulty``   — the paper's Eq.-2-correlated metric (std of
    channel magnitudes, §II-B) of the observed ranges, directly
    comparable to the pre/post profiles in the autoplan telemetry
    artifacts (same ``modules`` keying as
    :mod:`repro.autoplan.telemetry`).

Sampling is OPT-IN (``--quant-health N`` in launch/serve.py): each
sample costs one extra tap-forward dispatch per bucketed context
length.  With sampling off the engines issue no extra dispatches
(tests/test_obs.py pins this).
"""

from __future__ import annotations

import numpy as np

__all__ = ["QuantHealthSampler"]


def _difficulty(x2: np.ndarray) -> float:
    """std of per-channel Frobenius norms of (tokens, C) samples — the
    numpy twin of ``repro.core.difficulty.quantization_difficulty``
    (host-side so sampling adds no device dispatches beyond the tap
    forward itself)."""
    mags = np.sqrt(np.sum(np.square(x2.astype(np.float64)), axis=0))
    return float(np.std(mags))


class QuantHealthSampler:
    """Every-N-ticks activation health probe over live request contexts."""

    def __init__(self, model, params, cfg, *, policy=None, every: int = 32,
                 reference=None, max_context: int = 256, bucket: int = 16):
        """``reference``: the calibration ``dict[str, CalibStats]`` the
        fold consumed — enables the clip-fraction-vs-calibrated-Δ lens;
        without it only absmax and difficulty are reported.
        ``max_context`` caps the probed PREFIX (a prefix forward is a
        faithful replay; a clipped suffix would not be); ``bucket``
        pads context lengths so the jitted tap forward compiles once
        per bucket, not once per length."""
        import jax

        self.model, self.params, self.cfg = model, params, cfg
        self.policy = policy
        self.every = max(int(every), 1)
        self.max_context = max_context
        self.bucket = max(int(bucket), 1)
        self.samples: list[dict] = []
        self.reference = {
            name: np.asarray(st.act_absmax, np.float32)
            for name, st in (reference or {}).items()
        } or None
        # params are an argument, not captured as program constants
        self._tap_fn = jax.jit(
            lambda p, toks: model.forward_with_taps(p, cfg, toks,
                                                    policy=policy)[1])

    def due(self, tick: int) -> bool:
        return tick % self.every == 0

    def sample(self, tick: int, uid: int, context: np.ndarray) -> dict:
        """Probe one request's context; returns (and stores) the record
        ``{"tick", "uid", "context_len", "modules": {m: {"absmax",
        "clip_fraction", "difficulty"} per layer}}``."""
        ctx = np.asarray(context, np.int64)[: self.max_context]
        t = len(ctx)
        pad = -(-t // self.bucket) * self.bucket
        toks = np.zeros((1, pad), np.int32)
        toks[0, :t] = ctx
        taps = self._tap_fn(self.params, toks)
        modules: dict[str, dict] = {}
        for name in sorted(taps):
            arr = np.asarray(taps[name], np.float32)
            if arr.ndim == 3:          # unscanned (B, T, C) → one layer
                arr = arr[None]
            arr = arr[:, :, :t, :]     # (L, B, t, C): drop pad tokens
            L = arr.shape[0]
            flat = arr.reshape(L, -1, arr.shape[-1])
            absmax = np.max(np.abs(flat), axis=(1, 2))
            diff = [_difficulty(flat[l]) for l in range(L)]
            clip = None
            ref = (self.reference or {}).get(name)
            if ref is not None:
                ref_l = np.broadcast_to(
                    ref.reshape(-1, ref.shape[-1]), (L, ref.shape[-1]))
                clip = [float(np.mean(np.abs(flat[l]) > ref_l[l]))
                        for l in range(L)]
            modules[name] = {
                "absmax": [float(v) for v in absmax],
                "clip_fraction": clip,
                "difficulty": diff,
            }
        rec = {"tick": int(tick), "uid": int(uid), "context_len": t,
               "modules": modules}
        self.samples.append(rec)
        return rec
