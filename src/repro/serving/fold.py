"""Offline calibrate→fold→quantize pipeline (the paper as a deployment step).

Turns bf16 training params into a serving param tree where every linear
leaf is a folded, RTN-quantized :class:`QuantizedWeight`:

    smooth       : W ← diag(s)·W   (runtime divides x by s;  Eq. 4)
    rotate       : W ← Rᵀ·W        (runtime applies x·R online — fast
                                    Kronecker apply / fused Pallas kernel)
    smooth_rotate: both, scaling FIRST (the paper's hybrid, §IV-E)

    The runtime side of every folded leaf is the ONE-pass fused qlinear
    kernel (docs/kernels.md); mixed layerwise stacks emit a traced
    ``had_mask`` gate that the kernel multiplexes in-VMEM, so searched
    plans stay on the fast path.  The folded tree serves unchanged from
    every engine — including the paged engine's batched
    ``prefill_paged`` dispatch and its int8 paged KV pool
    (docs/serving.md): quantization state lives entirely in the leaves,
    never in the cache layout.

The per-module policy is a :class:`repro.core.transforms.TransformPlan`;
the default follows the paper's §V recommendation (SmoothRotation on
down_proj-type inputs, rotation elsewhere).  Calibration stats come from
``collect_calibration`` (a with-taps forward over a calibration stream).

MoE experts are quantized per-expert (storage savings); their ragged
compute path dequantizes to bf16 before the grouped einsum — dense
linears use the full int8-MXU path (DESIGN.md §5).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.autoplan.plan import LayerwisePlan, ModuleChoice
from repro.configs.base import ModelConfig
from repro.core.calibration import (
    CalibStats,
    collect_stats,
    smoothing_scales_from_stats,
)
from repro.core.hadamard import apply_hadamard
from repro.core.qlinear import QuantPolicy, QuantizedWeight, quantize_weight
from repro.core.transforms import TransformKind, TransformPlan

Params = dict[str, Any]
PlanLike = Union[TransformPlan, LayerwisePlan]


# ---------------------------------------------------------------------------
# calibration driver
# ---------------------------------------------------------------------------


def collect_calibration(model, params, cfg: ModelConfig, batches,
                        keep_samples: int = 0) -> dict[str, CalibStats]:
    """Run the model's with-taps forward over calibration batches and
    accumulate per-module per-channel absmax (taps stacked over layers).

    ``keep_samples > 0`` also retains that many raw activation tokens per
    module per layer (CalibStats.act_samples) for the autoplan search.
    """
    # params are an argument, not a closure: a captured pytree would be
    # embedded in the program as constants (gigabytes at full width)
    tap_fn = jax.jit(
        lambda p, toks=None, embeds=None: model.forward_with_taps(
            p, cfg, toks, embeds=embeds)[1])
    return collect_stats(
        lambda batch: tap_fn(params, batch.get("tokens"),
                             batch.get("embeds")),
        batches, keep_samples)


# ---------------------------------------------------------------------------
# single-linear fold
# ---------------------------------------------------------------------------


def _fold_one(w: jax.Array, kind: TransformKind, act_absmax: jax.Array | None,
              *, alpha: float, policy: QuantPolicy) -> QuantizedWeight:
    """w: (c_in, c_out). act_absmax: (c_in,) or None."""
    w = w.astype(jnp.float32)
    s = None
    if kind in ("smooth", "smooth_rotate"):
        if act_absmax is None:
            raise ValueError(f"'{kind}' needs calibration stats")
        s = smoothing_scales_from_stats(act_absmax, w, alpha)
        w = w * s[:, None]
    had = 0
    if kind in ("rotate", "smooth_rotate"):
        w = apply_hadamard(w, axis=0)
        had = w.shape[0]
    return quantize_weight(w, bits=policy.weight_bits,
                           pack=policy.pack_weights, had_dim=had, smooth=s)


def _fold_stacked(w: jax.Array, kind: TransformKind,
                  act_absmax: jax.Array | None, *, alpha: float,
                  policy: QuantPolicy, bias: jax.Array | None = None) -> Params:
    """Fold a (L, c_in, c_out) or (L, E, c_in, c_out) stacked linear.
    Returns the params leaf {"qw": QuantizedWeight[, "b": bias]}.

    The layer axis is folded one layer at a time (any expert axis is
    vmapped): the fold's f32 temporaries then stay one layer's size
    instead of several copies of the whole stack, which at full width
    (stablelm-3b's gate_proj stack is 2.1 GiB in f32) do not fit in a
    16 GB chip beside the bf16 params."""
    def fn(wl, al):
        return _fold_one(wl, kind, al, alpha=alpha, policy=policy)

    n_lead = w.ndim - 2
    for _ in range(n_lead - 1):
        fn = jax.vmap(fn)
    am = act_absmax
    if am is not None:
        # broadcast stats over expert axis if weights have one more lead dim
        while am.ndim < n_lead + 1:
            am = jnp.broadcast_to(am[..., None, :],
                                  (*am.shape[:-1], w.shape[am.ndim - 1], am.shape[-1]))
    if n_lead == 0:
        qw = fn(w, am)
    else:
        per_layer = [fn(w[i], None if am is None else am[i])
                     for i in range(w.shape[0])]
        qw = jax.tree.map(lambda *xs: jnp.stack(xs), *per_layer)
    out: Params = {"qw": qw}
    if bias is not None:
        out["b"] = bias
    return out


def _stat(stats: dict[str, CalibStats] | None, name: str):
    if stats is None or name not in stats:
        return None
    return stats[name].act_absmax


def _need_stats(kind: TransformKind) -> bool:
    return kind in ("smooth", "smooth_rotate")


def _effective(kind: TransformKind, stat) -> TransformKind:
    """Degrade smooth→rotate when stats are unavailable (logged policy)."""
    if _need_stats(kind) and stat is None:
        return "rotate" if "rotate" in kind else "none"
    return kind


# ---------------------------------------------------------------------------
# plan resolution (global TransformPlan | per-layer LayerwisePlan)
# ---------------------------------------------------------------------------


def _base_plan(plan: PlanLike) -> TransformPlan:
    return plan.base if isinstance(plan, LayerwisePlan) else plan


def _resolve(plan: PlanLike, module: str, w: jax.Array):
    """(kind, alpha) for a uniform fold, or per-layer ModuleChoices when
    the plan is layer-dependent AND matches this stack's layer count.

    Mismatched stacks (MoE leading dense layers, hybrid shared blocks,
    unstacked linears) fall back to the plan's global base — the same
    conservative reuse the uniform path always applied.
    """
    if isinstance(plan, LayerwisePlan):
        choices = plan.modules.get(module)
        if (choices is not None and w.ndim >= 3
                and w.shape[0] == len(choices)):
            if len(set(choices)) > 1:
                return tuple(choices)
            return choices[0].kind, choices[0].alpha
        base = plan.base
        return base.kind_for(module), base.alpha
    return plan.kind_for(module), plan.alpha


def _fold_stacked_layerwise(w: jax.Array, choices: tuple[ModuleChoice, ...],
                            act_absmax: jax.Array | None, *,
                            policy: QuantPolicy,
                            bias: jax.Array | None = None) -> Params:
    """Mixed per-layer kinds/αs on a (L, c_in, c_out) stack.

    The scan shares ONE QuantizedWeight structure across layers, so the
    static metadata must be uniform: rotated and un-rotated layers
    coexist through the traced ``had_mask`` gate, and smoothing uses
    identity scales on layers that don't smooth.  Layers are grouped by
    (kind, α) and each group folds through the same vmapped math as the
    uniform path.
    """
    if w.ndim != 3:
        raise ValueError("layerwise fold expects a (L, c_in, c_out) stack")
    L, c_in, _ = w.shape
    wf = w.astype(jnp.float32)
    smooth = jnp.ones((L, c_in), jnp.float32)
    rot = np.zeros(L, bool)
    any_smooth = False

    groups: dict[tuple[TransformKind, float], list[int]] = {}
    for l, c in enumerate(choices):
        eff = _effective(c.kind, act_absmax)
        groups.setdefault((eff, c.alpha), []).append(l)

    for (kind, alpha), idx in groups.items():
        ia = jnp.asarray(idx)
        wi = wf[ia]
        if kind in ("smooth", "smooth_rotate"):
            am = (act_absmax[ia] if act_absmax.ndim == 2
                  else jnp.broadcast_to(act_absmax, (len(idx), c_in)))
            s = smoothing_scales_from_stats(am, wi, alpha)
            wi = wi * s[..., None]
            smooth = smooth.at[ia].set(s)
            any_smooth = True
        if kind in ("rotate", "smooth_rotate"):
            wi = apply_hadamard(wi, axis=-2)
            rot[idx] = True
        wf = wf.at[ia].set(wi)

    had_dim = c_in if rot.any() else 0
    q = functools.partial(quantize_weight, bits=policy.weight_bits,
                          pack=policy.pack_weights, had_dim=had_dim)
    if any_smooth:
        qw = jax.vmap(lambda ww, ss: q(ww, smooth=ss))(wf, smooth)
    else:
        qw = jax.vmap(lambda ww: q(ww))(wf)
    if had_dim and not rot.all():
        qw = dataclasses.replace(qw, had_mask=jnp.asarray(rot, jnp.float32))
    out: Params = {"qw": qw}
    if bias is not None:
        out["b"] = bias
    return out


def _fold_linear_leaf(leaf: Params, plan: PlanLike, module: str, stat, *,
                      policy) -> Params:
    spec = _resolve(plan, module, leaf["w"])
    if isinstance(spec[0], str):           # uniform (kind, alpha)
        kind, alpha = spec
        kind = _effective(kind, stat)
        return _fold_stacked(leaf["w"], kind, stat, alpha=alpha,
                             policy=policy, bias=leaf.get("b"))
    return _fold_stacked_layerwise(leaf["w"], spec, stat, policy=policy,
                                   bias=leaf.get("b"))


def _fold_experts_rotation(w: jax.Array, rot: np.ndarray, *,
                           policy: QuantPolicy) -> Params:
    """Per-layer rotate/none on an (L, E, c_in, c_out) expert stack.

    Experts never smooth (per-expert division is not in the dispatch
    path; DESIGN.md §5), so a layerwise plan reduces to a per-layer
    rotation choice — realized with the same had_mask gate the dense
    layerwise fold uses (moe dispatch rotates the block input once and
    selects per layer)."""
    wf = w.astype(jnp.float32)
    if rot.any():
        ia = jnp.asarray(np.nonzero(rot)[0])
        wf = wf.at[ia].set(apply_hadamard(wf[ia], axis=-2))
    had_dim = w.shape[-2] if rot.any() else 0
    q = functools.partial(quantize_weight, bits=policy.weight_bits,
                          pack=policy.pack_weights, had_dim=had_dim)
    qw = jax.vmap(jax.vmap(q))(wf)
    if had_dim and not rot.all():
        qw = dataclasses.replace(qw, had_mask=jnp.asarray(rot, jnp.float32))
    return {"qw": qw}


# ---------------------------------------------------------------------------
# per-family folds
# ---------------------------------------------------------------------------


def _fold_attn(attn: Params, stats, plan: PlanLike, policy: QuantPolicy) -> Params:
    f = functools.partial(_fold_linear_leaf, policy=policy)
    return {
        "wq": f(attn["wq"], plan, "k_proj", _stat(stats, "k_proj")),
        "wk": f(attn["wk"], plan, "k_proj", _stat(stats, "k_proj")),
        "wv": f(attn["wv"], plan, "k_proj", _stat(stats, "k_proj")),
        "wo": f(attn["wo"], plan, "o_proj", _stat(stats, "o_proj")),
        "ln": attn["ln"],
    }


def _fold_mla(attn: Params, stats, plan: PlanLike, policy: QuantPolicy) -> Params:
    f = functools.partial(_fold_linear_leaf, policy=policy)
    return {
        "wq": f(attn["wq"], plan, "k_proj", _stat(stats, "k_proj")),
        "wdkv": f(attn["wdkv"], plan, "k_proj", _stat(stats, "k_proj")),
        "wukv": f(attn["wukv"], plan, "kv_up", _stat(stats, "kv_up")),
        "wo": f(attn["wo"], plan, "o_proj", _stat(stats, "o_proj")),
        "ln": attn["ln"], "kv_ln": attn["kv_ln"],
    }


def _fold_mlp(mlp: Params, stats, plan: PlanLike, policy: QuantPolicy,
              *, tap_prefix: str = "") -> Params:
    f = functools.partial(_fold_linear_leaf, policy=policy)
    out = {
        "wg": f(mlp["wg"], plan, "gate_proj",
                _stat(stats, tap_prefix + "gate_proj")),
        "wu": f(mlp["wu"], plan, "gate_proj",
                _stat(stats, tap_prefix + "gate_proj")),
        "wd": f(mlp["wd"], plan, "down_proj",
                _stat(stats, tap_prefix + "down_proj")),
    }
    if "ln" in mlp:
        out["ln"] = mlp["ln"]
    return out


def _fold_moe_ffn(moe: Params, stats, plan: PlanLike, policy: QuantPolicy,
                  cfg: ModelConfig) -> Params:
    """Experts: per-expert quantization; gate/up get the block input stats
    (routed subsets share the block input → absmax is an upper bound);
    expert down_proj has no per-expert calibration stream → rotation
    (DESIGN.md §5).  Router stays f32 (it is tiny and precision-critical)."""
    gplan = _base_plan(plan)
    f = functools.partial(_fold_stacked, alpha=gplan.alpha, policy=policy)
    # experts never get runtime smoothing (per-expert division is not in
    # the dispatch path; DESIGN.md §5) — per-layer rotation only:
    spec = _resolve(plan, "gate_proj", moe["wg"])
    if isinstance(spec[0], str):           # uniform
        rot = np.full(moe["wg"].shape[0], "rotate" in spec[0])
    else:                                  # layerwise gate_proj choices
        rot = np.asarray(["rotate" in c.kind for c in spec])
    out = {
        "router": moe["router"],
        "wg": _fold_experts_rotation(moe["wg"], rot, policy=policy),
        "wu": _fold_experts_rotation(moe["wu"], rot, policy=policy),
        "wd": {"qw": f(moe["wd"], "rotate", None)["qw"]},
        "ln": moe["ln"],
    }
    if "shared" in moe:
        # shared experts share the block-input tap for gate/up, but their
        # internal width (n_shared·f) has no calibrated stream → the down
        # projection degrades to rotation (stats=None)
        out["shared"] = _fold_mlp(moe["shared"], None, plan, policy)
    if "dense" in moe:  # Arctic parallel-dense FFN: width == d_ff, taps ok
        out["dense"] = _fold_mlp(moe["dense"], stats, plan, policy)
    return out


def _fold_mamba(layer: Params, stats, plan: PlanLike, policy: QuantPolicy) -> Params:
    f = functools.partial(_fold_linear_leaf, policy=policy)
    out = dict(layer)
    out["in_proj"] = f(layer["in_proj"], plan, "in_proj",
                       _stat(stats, "in_proj"))
    out["out_proj"] = f(layer["out_proj"], plan, "out_proj",
                        _stat(stats, "out_proj"))
    return out


# ---------------------------------------------------------------------------
# public entry
# ---------------------------------------------------------------------------


def fold_quantize(params: Params, cfg: ModelConfig, *,
                  policy: QuantPolicy = QuantPolicy(),
                  plan: PlanLike = TransformPlan(),
                  stats: dict[str, CalibStats] | None = None) -> Params:
    """bf16 params → serving params (quantized linears, rest untouched).

    ``plan`` is either the legacy global :class:`TransformPlan` or a
    per-layer :class:`repro.autoplan.plan.LayerwisePlan`; a uniform
    layerwise plan folds identically to its global equivalent.
    """
    out: Params = {"embed": params["embed"], "final_ln": params["final_ln"]}
    if policy.quantize_lm_head:
        out["lm_head"] = _fold_stacked(
            params["lm_head"]["w"], "rotate", None, alpha=_base_plan(plan).alpha,
            policy=policy, bias=params["lm_head"].get("b"))
    else:
        out["lm_head"] = params["lm_head"]

    if cfg.family in ("dense", "audio", "vlm"):
        out["layers"] = {
            "attn": _fold_attn(params["layers"]["attn"], stats, plan, policy),
            "mlp": _fold_mlp(params["layers"]["mlp"], stats, plan, policy),
        }
    elif cfg.family == "moe":
        attn_fold = _fold_mla if cfg.kv_lora_rank else _fold_attn
        out["moe_layers"] = {
            "attn": attn_fold(params["moe_layers"]["attn"], stats, plan, policy),
            "moe": _fold_moe_ffn(params["moe_layers"]["moe"], stats, plan,
                                 policy, cfg),
        }
        if "dense_layers" in params:
            # leading dense layers calibrated by the moe-layer taps (same
            # module classes); reuse those stats conservatively
            out["dense_layers"] = {
                "attn": attn_fold(params["dense_layers"]["attn"], _first_layer(stats),
                                  plan, policy),
                "mlp": _fold_mlp(params["dense_layers"]["mlp"], _first_layer(stats),
                                 plan, policy),
            }
    elif cfg.family == "ssm":
        out["layers"] = _fold_mamba(params["layers"], stats, plan, policy)
    elif cfg.family == "hybrid":
        out["layers"] = _fold_mamba(params["layers"], stats, plan, policy)
        out["shared"] = {
            "attn": _fold_attn(params["shared"]["attn"], None, plan, policy),
            "mlp": _fold_mlp(params["shared"]["mlp"], None, plan, policy),
        }
    else:
        raise ValueError(cfg.family)
    return out


def _first_layer(stats):
    """Slice layer-stacked stats down to a single (broadcastable) layer."""
    if stats is None:
        return None
    return {k: dataclasses.replace(
                v, act_absmax=v.act_absmax[:1],
                act_samples=None if v.act_samples is None
                else v.act_samples[:1])
            for k, v in stats.items()}
