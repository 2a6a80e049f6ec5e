"""Mixture-of-Experts transformer (Arctic, DeepSeek-V2-lite) with
expert-parallel execution and optional MLA attention.

Expert parallelism (DESIGN.md §4): expert weights are sharded over the
``model`` mesh axis.  Activations entering the MoE FFN are replicated
over ``model`` (batch is sharded over data axes), so dispatch needs NO
all-to-all: a ``shard_map`` over ``model`` lets each shard compute only
its local experts on the tokens routed to them (capacity-bounded,
sort-based, fully differentiable), and one ``psum`` over ``model``
combines expert outputs — the same collective a tensor-parallel dense
FFN would issue.  Routing: top-k token choice with normalized gates and
a load-balancing auxiliary loss.

DeepSeek-V2 MLA: queries are full-rank; K/V derive from a compressed
kv_lora_rank latent that is ALSO what the cache stores (the paper's
technique gets an extra rotation site on this latent — DESIGN.md §5).
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.core.qlinear import QuantPolicy
from repro.models import common as cm

Params = dict[str, Any]


# ---------------------------------------------------------------------------
# expert-parallel MoE FFN
# ---------------------------------------------------------------------------


def init_moe_ffn(key, cfg: ModelConfig, dtype=jnp.bfloat16) -> Params:
    ks = jax.random.split(key, 5)
    E, d, f = cfg.num_experts, cfg.d_model, cfg.moe_d_ff
    sc_in, sc_f = d ** -0.5, f ** -0.5
    p = {
        "router": {"w": (jax.random.normal(ks[0], (d, E), jnp.float32) * sc_in
                         ).astype(jnp.float32)},  # router stays f32
        "wg": (jax.random.normal(ks[1], (E, d, f), jnp.float32) * sc_in).astype(dtype),
        "wu": (jax.random.normal(ks[2], (E, d, f), jnp.float32) * sc_in).astype(dtype),
        "wd": (jax.random.normal(ks[3], (E, f, d), jnp.float32) * sc_f).astype(dtype),
        "ln": cm.init_rms(d, dtype),
    }
    if cfg.num_shared_experts:
        p["shared"] = cm.init_mlp(ks[4], d, cfg.num_shared_experts * f, dtype)
        p["shared"].pop("ln")  # shares the block norm
    return p


def _expert_weight(mats: dict, had_dim: int = 0) -> jax.Array:
    """Materialize one expert weight stack from {'w'} or {'codes','scale'}
    (int4/int8 per-expert storage → bf16 for the grouped einsum)."""
    if "w" in mats:
        return mats["w"]
    codes = mats["codes"]
    if mats.get("packed"):
        from repro.core.quantizer import unpack_int4

        codes = jnp.swapaxes(unpack_int4(jnp.swapaxes(codes, -1, -2)), -1, -2)
    return (codes.astype(jnp.float32) * mats["scale"]).astype(jnp.bfloat16)


def _local_expert_compute(x_flat, topi, topv, wg, wu, wd, *, n_experts: int,
                          k: int, capacity_factor: float, axis: str | None,
                          wd_had: int = 0, token_valid=None):
    """Per-shard expert compute: select→pad→batched GEMM→combine.

    x_flat (T_local, d): this shard's tokens (sharded over data axes,
    replicated over ``axis``); wg/wu/wd local (E_loc, ...) either bf16
    arrays or quantized {'codes','scale'} dicts.  Capacity is derived
    from the LOCAL token count (buffers scale with per-device work, not
    global batch).  Fully differentiable (indices come from argsort,
    grads flow through gather/scatter); capacity overflow tokens are
    dropped (standard).
    """
    wg, wu, wd = (_expert_weight(m) if isinstance(m, dict) else m
                  for m in (wg, wu, wd))
    T, d = x_flat.shape
    e_loc = wg.shape[0]
    capacity = max(1, int(capacity_factor * T * k / n_experts))
    my_lo = (jax.lax.axis_index(axis) if axis else 0) * e_loc
    expert = topi.reshape(-1)            # (T*k,)
    gate = topv.reshape(-1)
    token = jnp.repeat(jnp.arange(T), k)
    local_e = expert - my_lo
    is_local = (local_e >= 0) & (local_e < e_loc)
    if token_valid is not None:
        # right-padding tokens (batched prefill) must not compete for
        # expert capacity — route them to the sentinel overflow group
        is_local &= jnp.repeat(token_valid.reshape(-1), k)
    sort_key = jnp.where(is_local, local_e, e_loc)  # sentinel group e_loc
    order = jnp.argsort(sort_key)        # group by local expert, locals first
    se = sort_key[order]
    rank = jnp.arange(se.shape[0]) - jnp.searchsorted(se, se, side="left")
    keep = (rank < capacity) & (se < e_loc)
    dest = jnp.where(keep, se * capacity + rank, e_loc * capacity)  # overflow slot
    tok_sorted = token[order]
    gate_sorted = jnp.where(keep, gate[order], 0.0)
    # scatter tokens into (E_loc*C [+1 overflow], d) buffer
    buf = jnp.zeros((e_loc * capacity + 1, d), x_flat.dtype)
    buf = buf.at[dest].set(x_flat[tok_sorted] * keep[:, None].astype(x_flat.dtype))
    xe = buf[: e_loc * capacity].reshape(e_loc, capacity, d)
    g = jnp.einsum("ecd,edf->ecf", xe, wg.astype(xe.dtype))
    u = jnp.einsum("ecd,edf->ecf", xe, wu.astype(xe.dtype))
    a = jax.nn.silu(g.astype(jnp.float32)).astype(xe.dtype) * u
    if wd_had:  # wd was folded with Rᵀ: rotate the expert activation
        from repro.core.hadamard import apply_hadamard

        a = apply_hadamard(a, wd_had)
    y = jnp.einsum("ecf,efd->ecd", a, wd.astype(xe.dtype))
    y_flat = y.reshape(e_loc * capacity, d)
    contrib = jnp.where(dest[:, None] < e_loc * capacity,
                        y_flat[jnp.minimum(dest, e_loc * capacity - 1)], 0.0)
    contrib = contrib * gate_sorted[:, None].astype(y_flat.dtype)
    out = jax.ops.segment_sum(contrib, tok_sorted, num_segments=T)
    if axis:
        out = jax.lax.psum(out, axis)
    return out


def moe_ffn(p: Params, x: jax.Array, cfg: ModelConfig,
            policy: QuantPolicy | None = None, *, taps: dict | None = None,
            valid: jax.Array | None = None):
    """x: (b, s, d) → (b, s, d) MoE output + aux load-balance loss.

    ``valid``: optional (b, s) bool mask of REAL tokens (batched prefill
    right-pads mixed prompt lengths) — invalid tokens get zero gates and
    are excluded from expert-capacity competition.
    """
    b, s, d = x.shape
    h = cm.rms_norm(x, p.get("ln"), cfg.norm_eps)
    if taps is not None:  # routed+shared expert gate/up input
        taps["gate_proj"] = h
    hf = h.reshape(-1, d)
    logits = (hf.astype(jnp.float32) @ p["router"]["w"]).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    k = cfg.experts_per_tok
    topv, topi = jax.lax.top_k(probs, k)
    topv = topv / jnp.maximum(topv.sum(-1, keepdims=True), 1e-9)  # renorm gates
    vmask = None if valid is None else valid.reshape(-1, 1)
    if vmask is not None:
        topv = topv * vmask.astype(topv.dtype)
    # load-balance aux (Switch-style): E * Σ_e f_e·P_e
    E = cfg.num_experts
    density = jnp.mean(jax.nn.one_hot(topi, E, dtype=jnp.float32), axis=(0, 1))
    p_mean = jnp.mean(probs, axis=0)
    aux = E * jnp.sum(density * p_mean)

    mesh = jax.sharding.get_abstract_mesh()
    tp = "model" if ("model" in mesh.axis_names
                     and E % mesh.shape["model"] == 0) else None

    def expert_mats(name):
        # NOTE: routed experts keep the ragged dequant-to-bf16 path below
        # (per-expert codes under shard_map; DESIGN.md §5).  Every DENSE
        # leaf in this file (attention, shared/parallel FFN, lm_head)
        # goes through cm.dense → qlinear and rides the one-pass fused
        # Pallas kernel on TPU (docs/kernels.md).
        leaf = p[name]
        if isinstance(leaf, dict) and "qw" in leaf:
            qw = leaf["qw"]
            return ({"codes": qw.w_q, "scale": qw.scale, "packed": qw.packed},
                    qw.had_dim, qw.had_mask)
        if isinstance(leaf, dict):
            return {"w": leaf.get("w", leaf)}, 0, None
        return {"w": leaf}, 0, None

    (mg, g_had, g_mask), (mu, _, _), (md, d_had, _) = (
        expert_mats(n) for n in ("wg", "wu", "wd"))
    hq = hf
    if g_had:  # gate/up folded with Rᵀ on d_model: rotate tokens once
        from repro.core.hadamard import apply_hadamard

        hr = apply_hadamard(hf, g_had)
        # g_mask gates per-layer rotation under a mixed LayerwisePlan
        # (scalar per layer once the scan slices the stack)
        hq = hr if g_mask is None else jnp.where(g_mask > 0, hr, hf)
    static = {k_: v for m in (mg, mu, md) for k_, v in m.items()
              if isinstance(v, bool)}
    mg, mu, md = ({k_: v for k_, v in m.items() if not isinstance(v, bool)}
                  for m in (mg, mu, md))
    packed = static.get("packed", False)

    vm = (jnp.ones((hf.shape[0], 1), jnp.bool_) if vmask is None
          else vmask)

    def fn(hq_, topi_, topv_, vm_, mg_, mu_, md_):
        if "codes" in mg_:
            mg_ = dict(mg_, packed=packed)
            mu_ = dict(mu_, packed=packed)
            md_ = dict(md_, packed=packed)
        return _local_expert_compute(
            hq_, topi_, topv_, mg_, mu_, md_, n_experts=E, k=k,
            capacity_factor=cfg.capacity_factor, axis=tp, wd_had=d_had,
            token_valid=vm_[:, 0])

    dp = tuple(a for a in mesh.axis_names if a != "model") if tp else ()
    dp_sz = 1
    for a in dp:
        dp_sz *= mesh.shape[a]
    if tp is None:
        out = fn(hq, topi, topv, vm, mg, mu, md)
    else:
        # batch=1 decode: tokens don't divide dp → replicate tokens and
        # keep only expert parallelism (every shard sees all tokens)
        xspec = P(dp, None) if hf.shape[0] % dp_sz == 0 else P(None, None)
        espec = jax.tree.map(lambda _: P("model", None, None), mg)
        out = jax.shard_map(
            fn,
            in_specs=(xspec, xspec, xspec, xspec, espec, espec,
                      jax.tree.map(lambda _: P("model", None, None), md)),
            out_specs=xspec, check_vma=False
        )(hq, topi, topv, vm, mg, mu, md)
    y = out.reshape(b, s, d)
    if "shared" in p:
        y = y + cm.mlp_apply(p["shared"] | {"ln": None}, h, cfg, policy,
                             residual=False)
    if "dense" in p:  # Arctic parallel dense residual FFN
        y = y + cm.mlp_apply(p["dense"] | {"ln": None}, h, cfg, policy,
                             residual=False)
    return x + y, aux


# ---------------------------------------------------------------------------
# MLA attention (DeepSeek-V2)
# ---------------------------------------------------------------------------


def init_mla(key, cfg: ModelConfig, dtype=jnp.bfloat16) -> Params:
    ks = jax.random.split(key, 5)
    H = cfg.num_heads
    qd = cfg.qk_nope_dim + cfg.qk_rope_dim
    return {
        "wq": cm.init_linear(ks[0], cfg.d_model, H * qd, dtype=dtype),
        "wdkv": cm.init_linear(ks[1], cfg.d_model,
                               cfg.kv_lora_rank + cfg.qk_rope_dim, dtype=dtype),
        "wukv": cm.init_linear(ks[2], cfg.kv_lora_rank,
                               H * (cfg.qk_nope_dim + cfg.v_head_dim), dtype=dtype),
        "wo": cm.init_linear(ks[3], H * cfg.v_head_dim, cfg.d_model, dtype=dtype),
        "kv_ln": cm.init_rms(cfg.kv_lora_rank, dtype),
        "ln": cm.init_rms(cfg.d_model, dtype),
    }


def mla_apply(p: Params, x: jax.Array, cfg: ModelConfig, *,
              layer_kv: dict | None = None, length=0,
              policy: QuantPolicy | None = None, taps: dict | None = None,
              page_table: jax.Array | None = None,
              valid_new: jax.Array | None = None,
              prefill_local: bool = False):
    """MLA block. Cache stores the compressed latent (c_kv, k_rope) only.

    ``length`` may be a (b,) vector of per-row cache depths (slot-major
    batched decode), mirroring :func:`repro.models.common.attn_apply` —
    as do ``page_table`` / ``valid_new`` / ``prefill_local``, which
    switch the latent cache to the paged pool layout.

    Paged-attention dispatch: MLA resolves to the "xla" gather in
    :func:`repro.models.common.paged_attn_backend` by construction —
    the cached latent must be up-projected through ``wukv`` into
    per-head K/V *before* attention, so the contiguous latent view is
    load-bearing (it feeds a matmul), not an attention-internal
    materialization the in-VMEM kernel could elide.  An absorbed-MLA
    kernel (folding W_uk/W_uv into q/out — changes matmul order, hence
    greedy numerics) is the documented follow-up
    (docs/paged_attention.md).
    """
    b, s, _ = x.shape
    H = cfg.num_heads
    nd, rd, vd = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    h = cm.rms_norm(x, p.get("ln"), cfg.norm_eps)
    if taps is not None:  # q and down-kv projections share this input
        taps["k_proj"] = h
    q = cm.dense(h, p["wq"], policy).reshape(b, s, H, nd + rd)
    q_nope, q_rope = q[..., :nd], q[..., nd:]
    dkv = cm.dense(h, p["wdkv"], policy)
    c_kv, k_rope = dkv[..., : cfg.kv_lora_rank], dkv[..., cfg.kv_lora_rank:]
    larr = jnp.asarray(length)
    pos = (larr[:, None] + jnp.arange(s)[None]) if larr.ndim \
        else (jnp.arange(s) + larr)
    cos, sin = cm.rope_angles(pos, rd, cfg.rope_theta)
    q_rope = cm.apply_rope(q_rope, cos, sin)
    k_rope = cm.apply_rope(k_rope[:, :, None, :], cos, sin)  # (b,s,1,rd)

    if layer_kv is not None:
        # cache latent: k slot stores c_kv (b,S,1,lora), v slot stores k_rope
        k_store = c_kv[:, :, None, :]
        v_store = jnp.pad(k_rope, ((0, 0), (0, 0), (0, 0),
                                   (0, cfg.kv_lora_rank - rd)))
        if page_table is not None:                   # paged latent pool
            layer_kv = cm.paged_update(layer_kv, k_store, v_store, length,
                                       page_table, valid_new=valid_new)
            if prefill_local:
                if layer_kv.get("k_scale") is not None:
                    c_all = cm.quant_roundtrip_kv(k_store)[:, :, 0, :]
                    k_rope_all = cm.quant_roundtrip_kv(v_store)[:, :, 0, :rd]
                else:
                    c_all = c_kv
                    k_rope_all = k_rope[:, :, 0, :]
                valid = None
            else:
                # latent decode stays on the gather view — see the
                # paged-attention dispatch note in the docstring
                ck, kr = cm.paged_view(layer_kv, page_table)
                c_all = ck[:, :, 0, :]
                k_rope_all = kr[:, :, 0, :rd]
                valid = jnp.minimum(jnp.asarray(length) + s, ck.shape[1])
        else:
            layer_kv = cm.cache_update(layer_kv, k_store, v_store, length,
                                       window=cfg.attn_window)
            ck, kr = cm.cache_read(layer_kv)
            c_all = ck[:, :, 0, :]                   # (b, S, lora)
            k_rope_all = kr[:, :, 0, :rd]            # (b, S, rd)
            valid = jnp.minimum(jnp.asarray(length) + s, c_all.shape[1])
    else:
        c_all, k_rope_all = c_kv, k_rope[:, :, 0, :]
        valid = None
    c_all = cm.rms_norm(c_all, p.get("kv_ln"), cfg.norm_eps)
    if taps is not None:  # the compressed-latent rotation site (DESIGN §5)
        taps["kv_up"] = c_all
    ukv = cm.dense(c_all, p["wukv"], policy).reshape(b, -1, H, nd + vd)
    k_nope, v = ukv[..., :nd], ukv[..., nd:]
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope_all[:, :, None, :],
                                  (*k_nope.shape[:3], rd))], axis=-1)
    qfull = jnp.concatenate([q_nope, q_rope], axis=-1)
    if layer_kv is not None:
        out = cm.attention_scores(qfull, k, v, causal=(s > 1),
                                  q_offset=length, length=valid)
    else:
        out = cm.attention_scores(qfull, k, v, causal=True,
                                  window=cfg.attn_window)
    o_in = out.reshape(b, s, H * vd)
    if taps is not None:
        taps["o_proj"] = o_in
    y = cm.dense(o_in, p["wo"], policy)
    return x + y, layer_kv


# ---------------------------------------------------------------------------
# full model
# ---------------------------------------------------------------------------


def _is_dense_layer(cfg: ModelConfig, idx: int) -> bool:
    return idx < cfg.first_dense_layers


def init(key, cfg: ModelConfig, dtype=jnp.bfloat16):
    k_emb, k_dense, k_moe, k_out = jax.random.split(key, 4)
    n_dense = cfg.first_dense_layers
    n_moe = cfg.num_layers - n_dense
    attn_init = init_mla if cfg.kv_lora_rank else cm.init_attn

    def init_moe_layer(k):
        ka, km, kd = jax.random.split(k, 3)
        p = {"attn": attn_init(ka, cfg, dtype), "moe": init_moe_ffn(km, cfg, dtype)}
        if cfg.dense_residual:
            d_p = cm.init_mlp(kd, cfg.d_model, cfg.d_ff, dtype)
            d_p.pop("ln")
            p["moe"]["dense"] = d_p
        return p

    def init_dense_layer(k):
        ka, km = jax.random.split(k)
        return {"attn": attn_init(ka, cfg, dtype),
                "mlp": cm.init_mlp(km, cfg.d_model, cfg.d_ff, dtype)}

    params = {
        "embed": cm.init_embedding(k_emb, cfg.vocab_size, cfg.d_model, dtype),
        "moe_layers": cm.stack_layer_params(
            jax.random.split(k_moe, n_moe), init_moe_layer),
        "final_ln": cm.init_rms(cfg.d_model, dtype),
        "lm_head": cm.init_linear(k_out, cfg.d_model, cfg.vocab_size, dtype=dtype),
    }
    if n_dense:
        params["dense_layers"] = cm.stack_layer_params(
            jax.random.split(k_dense, n_dense), init_dense_layer)
    return params


def _attn(cfg):
    return mla_apply if cfg.kv_lora_rank else cm.attn_apply


def _backbone(params, cfg: ModelConfig, h, *, cache=None, length=0,
              policy=None, collect_taps=False, page_table=None,
              valid_new=None, prefill_local=False, token_valid=None):
    attn = _attn(cfg)
    aux_total = jnp.zeros((), jnp.float32)
    paged = isinstance(cache, cm.PagedKVCache)
    if paged and page_table is None:
        page_table = cache.page_table

    def moe_block(lp, x, extra):
        layer_kv = extra
        taps = {} if collect_taps else None
        x, layer_kv = attn(lp["attn"], x, cfg, layer_kv=layer_kv,
                           length=length, policy=policy,
                           page_table=page_table, valid_new=valid_new,
                           prefill_local=prefill_local)
        x, aux = moe_ffn(lp["moe"], x, cfg, policy, taps=taps,
                         valid=token_valid)
        y = taps if collect_taps else layer_kv
        return x, (y, aux)

    def dense_block(lp, x, extra):
        layer_kv = extra
        x, layer_kv = attn(lp["attn"], x, cfg, layer_kv=layer_kv,
                           length=length, policy=policy,
                           page_table=page_table, valid_new=valid_new,
                           prefill_local=prefill_local)
        x = cm.mlp_apply(lp["mlp"], x, cfg, policy)
        return x, (layer_kv, jnp.zeros((), jnp.float32))

    n_dense = cfg.first_dense_layers
    caches_out = []
    for name, block, n in (("dense_layers", dense_block, n_dense),
                           ("moe_layers", moe_block,
                            cfg.num_layers - n_dense)):
        if n == 0:
            continue
        if cache is None:
            extras = None
            fn = lambda lp, x, _ , _b=block: _b(lp, x, None)
        else:
            lo = 0 if name == "dense_layers" else n_dense
            kv = {"k": cache.k[lo:lo + n], "v": cache.v[lo:lo + n]}
            if cache.quantized:
                kv.update(k_scale=cache.k_scale[lo:lo + n],
                          v_scale=cache.v_scale[lo:lo + n])
            extras = kv
            fn = block
        h, (ys, aux) = cm.scan_layers(fn, params[name], h,
                                      remat=cfg.remat and cache is None,
                                      extras=extras,
                                      sp=cfg.seq_parallel and cache is None,
                                      remat_policy=cfg.remat_policy)
        aux_total = aux_total + jnp.sum(aux)
        if cache is not None:
            caches_out.append(ys)
    if cache is not None:
        merged = jax.tree.map(lambda *xs: jnp.concatenate(xs, 0), *caches_out) \
            if len(caches_out) > 1 else caches_out[0]
        # replace() serves both cache classes (page_table rides along
        # untouched on the paged one)
        new_cache = dataclasses.replace(
            cache, k=merged["k"], v=merged["v"],
            k_scale=merged.get("k_scale"), v_scale=merged.get("v_scale"),
            length=cache.length + h.shape[1])
    else:
        new_cache = None
    h = cm.rms_norm(h, params.get("final_ln"), cfg.norm_eps)
    return h, new_cache, aux_total


def forward(params, cfg: ModelConfig, tokens=None, *, embeds=None,
            policy: QuantPolicy | None = None):
    h = cm.embed(params["embed"], tokens) if embeds is None else embeds
    x, _, aux = _backbone(params, cfg, h, policy=policy)
    return cm.dense(x, params["lm_head"], policy), aux


def train_loss(params, cfg: ModelConfig, batch, aux_weight: float = 0.01):
    logits, aux = forward(params, cfg, batch.get("tokens"),
                          embeds=batch.get("embeds"))
    ce = cm.cross_entropy(logits[:, :-1], batch["labels"][:, 1:],
                          batch.get("mask"))
    return ce + aux_weight * aux


def make_cache(cfg: ModelConfig, batch: int, max_len: int, *,
               bits: int | None = None) -> cm.KVCache:
    if cfg.kv_lora_rank:
        # latent cache: one "head" of width kv_lora_rank (stores c_kv; the
        # v slot stores k_rope padded to the same width)
        return cm.init_kv_cache(cfg, cfg.num_layers, batch, max_len, bits=bits,
                                head_dim=cfg.kv_lora_rank, kv_heads=1)
    return cm.init_kv_cache(cfg, cfg.num_layers, batch, max_len, bits=bits)


def make_paged_cache(cfg: ModelConfig, slots: int, max_len: int, *,
                     page_size: int = 64, n_pages: int | None = None,
                     bits: int | None = None) -> cm.PagedKVCache:
    if cfg.kv_lora_rank:
        return cm.init_paged_kv_cache(
            cfg, cfg.num_layers, slots, max_len, page_size=page_size,
            n_pages=n_pages, bits=bits, head_dim=cfg.kv_lora_rank, kv_heads=1)
    return cm.init_paged_kv_cache(cfg, cfg.num_layers, slots, max_len,
                                  page_size=page_size, n_pages=n_pages,
                                  bits=bits)


def prefill(params, cfg: ModelConfig, tokens, cache, policy=None):
    h = cm.embed(params["embed"], tokens)
    x, cache, _ = _backbone(params, cfg, h, cache=cache, length=0, policy=policy)
    return cm.dense(x[:, -1:], params["lm_head"], policy), cache


def prefill_paged(params, cfg: ModelConfig, tokens, lengths,
                  cache: cm.PagedKVCache, slots, policy=None):
    """In-engine batched prefill into assigned pages (right-padded rows;
    see :func:`repro.models.transformer.prefill_paged`).  Padding tokens
    are masked out of expert-capacity competition via ``moe_ffn``'s
    ``valid`` mask."""
    s = tokens.shape[1]
    h = cm.embed(params["embed"], tokens)
    ptab = cm.gather_page_rows(cache.page_table, slots)
    token_valid = jnp.arange(s)[None] < jnp.asarray(lengths)[:, None]
    x, new_cache, _ = _backbone(params, cfg, h, cache=cache, length=0,
                                policy=policy, page_table=ptab,
                                valid_new=lengths, prefill_local=True,
                                token_valid=token_valid)
    logits = cm.dense(cm.take_last_valid(x, lengths), params["lm_head"], policy)
    new_cache = dataclasses.replace(
        new_cache, length=cache.length.at[jnp.asarray(slots)].set(
            jnp.asarray(lengths, jnp.int32), mode="drop"))
    return logits, new_cache


def decode_step(params, cfg: ModelConfig, tokens, cache, policy=None):
    """One token per sequence.  ``cache.length`` may be a scalar or a
    per-slot (b,) vector (slot-major batched serving) — GQA and MLA
    attention both thread it as per-row positions."""
    h = cm.embed(params["embed"], tokens)
    x, cache, _ = _backbone(params, cfg, h, cache=cache, length=cache.length,
                            policy=policy)
    return cm.dense(x, params["lm_head"], policy), cache


def forward_with_taps(params, cfg: ModelConfig, tokens=None, *, embeds=None,
                      policy=None):
    h = cm.embed(params["embed"], tokens) if embeds is None else embeds
    attn = _attn(cfg)
    # taps only from moe layers (the paper's sites); dense layers skipped
    def block(lp, x, _):
        taps = {}
        x, _kv = attn(lp["attn"], x, cfg, policy=policy, taps=taps)
        x, aux = moe_ffn(lp["moe"], x, cfg, policy, taps=taps)
        return x, taps
    if cfg.first_dense_layers:
        def dense_fn(lp, x, _):
            x, _kv = attn(lp["attn"], x, cfg)
            return cm.mlp_apply(lp["mlp"], x, cfg), ()
        h, _ = cm.scan_layers(dense_fn, params["dense_layers"], h, remat=False)
    h, taps = cm.scan_layers(block, params["moe_layers"], h, remat=False)
    h = cm.rms_norm(h, params.get("final_ln"), cfg.norm_eps)
    return cm.dense(h, params["lm_head"]), taps
