"""Roofline-term derivation from compiled dry-run artifacts (TPU v5e).

Terms per (arch × shape × mesh), all in seconds-per-step-per-device:

    compute_s    = Σ_dtype FLOPs_dtype / peak_dtype          (int8 = 2× bf16)
    memory_s     = HBM_bytes / 819 GB/s
    collective_s = ici_wire_bytes / 50 GB/s  (+ DCN term for pod-crossing)

FLOPs/bytes come from repro.launch.hlo_analysis (per-device, while-trip
corrected).  MODEL_FLOPS = 6·N·D (train) or 2·N·tokens (decode/prefill),
with N = active params for MoE — the useful-compute ratio flags remat /
redundant work.
"""

from __future__ import annotations

import dataclasses

from repro.configs.base import ModelConfig, ShapeCell
from repro.launch.hlo_analysis import HloMetrics

__all__ = ["HW", "RooflineReport", "roofline", "model_params", "model_flops",
           "serving_decode_cell", "serving_tick_flops",
           "serving_prefill_cell", "serving_prefill_flops",
           "serving_kv_token_elems", "serving_prefill_hbm_bytes"]


@dataclasses.dataclass(frozen=True)
class HW:
    """TPU v5e per-chip constants (task spec)."""

    peak_bf16: float = 197e12
    peak_int8: float = 394e12
    hbm_bw: float = 819e9
    ici_bw: float = 50e9          # per link; collective term per task formula
    dcn_bw: float = 25e9          # cross-pod (conservative)


def model_params(cfg: ModelConfig) -> tuple[int, int]:
    """(total, active) parameter counts from the config (analytic)."""
    d, V, L = cfg.d_model, cfg.vocab_size, cfg.num_layers
    emb = V * d
    head = d * V
    total = emb + head + d  # + final norm
    active = total
    for i in range(L):
        if cfg.family in ("dense", "audio", "vlm") or (
                cfg.family == "moe" and i < cfg.first_dense_layers):
            if cfg.kv_lora_rank:
                attn = (d * cfg.num_heads * (cfg.qk_nope_dim + cfg.qk_rope_dim)
                        + d * (cfg.kv_lora_rank + cfg.qk_rope_dim)
                        + cfg.kv_lora_rank * cfg.num_heads
                        * (cfg.qk_nope_dim + cfg.v_head_dim)
                        + cfg.num_heads * cfg.v_head_dim * d)
            else:
                hd = cfg.head_dim
                attn = d * cfg.num_heads * hd + 2 * d * cfg.num_kv_heads * hd \
                    + cfg.num_heads * hd * d
            ffn = 3 * d * cfg.d_ff
            total += attn + ffn
            active += attn + ffn
        elif cfg.family == "moe":
            if cfg.kv_lora_rank:
                attn = (d * cfg.num_heads * (cfg.qk_nope_dim + cfg.qk_rope_dim)
                        + d * (cfg.kv_lora_rank + cfg.qk_rope_dim)
                        + cfg.kv_lora_rank * cfg.num_heads
                        * (cfg.qk_nope_dim + cfg.v_head_dim)
                        + cfg.num_heads * cfg.v_head_dim * d)
            else:
                hd = cfg.head_dim
                attn = d * cfg.num_heads * hd + 2 * d * cfg.num_kv_heads * hd \
                    + cfg.num_heads * hd * d
            e_ffn = 3 * d * cfg.moe_d_ff
            total += attn + cfg.num_experts * e_ffn + d * cfg.num_experts
            active += attn + cfg.experts_per_tok * e_ffn + d * cfg.num_experts
            shared = cfg.num_shared_experts * 3 * d * cfg.moe_d_ff
            dense_res = 3 * d * cfg.d_ff if cfg.dense_residual else 0
            total += shared + dense_res
            active += shared + dense_res
        elif cfg.family in ("ssm", "hybrid"):
            di = cfg.d_inner
            gn = cfg.ssm_ngroups * cfg.ssm_state
            inp = d * (2 * di + 2 * gn + cfg.ssm_nheads)
            outp = di * d
            total += inp + outp
            active += inp + outp
    if cfg.family == "hybrid":
        hd = cfg.head_dim
        shared_blk = (d * cfg.num_heads * hd + 2 * d * cfg.num_kv_heads * hd
                      + cfg.num_heads * hd * d + 3 * d * cfg.d_ff)
        total += shared_blk
        active += shared_blk
    return int(total), int(active)


def model_flops(cfg: ModelConfig, cell: ShapeCell) -> float:
    """Useful model FLOPs per step (global): 6·N·D train, 2·N·tokens serve."""
    _, active = model_params(cfg)
    if cell.kind == "train":
        tokens = cell.global_batch * cell.seq_len
        return 6.0 * active * tokens
    if cell.kind == "prefill":
        tokens = cell.global_batch * cell.seq_len
        return 2.0 * active * tokens
    tokens = cell.global_batch  # one new token per sequence
    return 2.0 * active * tokens


def serving_decode_cell(max_slots: int, max_len: int = 256) -> ShapeCell:
    """The serving engine's batched decode tick as a roofline shape cell.

    ``ServingEngine.step`` issues ONE ``(max_slots, 1)`` decode program
    per tick — exactly a ``decode``-kind cell with ``global_batch ==
    max_slots``, i.e. the shape the ``decode_*`` roofline cells already
    model.  The per-slot baseline instead issues ``n_active`` batch-1
    programs for the SAME useful FLOPs, paying the dispatch + weight-
    stream overhead once per slot; benchmarks/serving_throughput.py uses
    this cell to cross-check measured tokens/tick against the model.
    """
    return ShapeCell(f"serve_decode_b{max_slots}", max_len, max_slots,
                     "decode")


def serving_tick_flops(cfg: ModelConfig, max_slots: int) -> float:
    """Useful model FLOPs of one batched engine tick (2·N_active·slots)."""
    return model_flops(cfg, serving_decode_cell(max_slots))


def serving_prefill_cell(n_admit: int, padded_len: int) -> ShapeCell:
    """One in-engine batched prefill dispatch as a roofline shape cell.

    ``PagedServingEngine`` admits a whole batch with ONE
    ``(n_admit, padded_prompt_len)`` ``prefill_paged`` program — a
    ``prefill``-kind cell with ``global_batch == n_admit``.  The seed
    admission path instead ran ``n_admit`` batch-1 prefills plus a full
    slot-extent ``write_slot`` copy each; the padded cell's FLOPs bound
    the batching overhead (padding rows) the dispatch saving buys.
    """
    return ShapeCell(f"serve_prefill_b{n_admit}x{padded_len}", padded_len,
                     n_admit, "prefill")


def serving_prefill_flops(cfg: ModelConfig, n_admit: int,
                          padded_len: int) -> float:
    """Useful model FLOPs of one batched admission dispatch
    (2·N_active·n_admit·padded_len)."""
    return model_flops(cfg, serving_prefill_cell(n_admit, padded_len))


def serving_kv_token_elems(cfg: ModelConfig) -> int:
    """KV-cache elements appended per token, summed over every
    attention invocation (MLA stores the latent + rope stripe; hybrids
    hit the shared block every ``attn_every`` SSM layers; pure SSM has
    O(1) state — nothing per token)."""
    if cfg.family in ("dense", "audio", "vlm"):
        return cfg.num_layers * 2 * cfg.num_kv_heads * cfg.head_dim
    if cfg.family == "moe":
        per = ((cfg.kv_lora_rank + cfg.qk_rope_dim) if cfg.kv_lora_rank
               else 2 * cfg.num_kv_heads * cfg.head_dim)
        return cfg.num_layers * per
    if cfg.family == "hybrid" and cfg.attn_every:
        invocations = -(-cfg.num_layers // cfg.attn_every)
        return invocations * 2 * cfg.num_kv_heads * cfg.head_dim
    return 0


def serving_prefill_hbm_bytes(cfg: ModelConfig, n_rows: int,
                              padded_len: int, *,
                              weight_bits: int | None = None,
                              kv_bits: int | None = None) -> float:
    """Modeled HBM bytes of ONE batched admission dispatch: the weight
    stream plus the KV written for every (row, position) — prefill
    attends from VMEM/registers over its own tile, so no context read
    term.  Analytic (weight stream and KV writes only), not a
    measurement: the prefix-cache counters price saved admissions in
    it."""
    _, active = model_params(cfg)
    w_bytes = active * (weight_bits / 8 if weight_bits else 2)
    kv_elem_bytes = 1 if kv_bits == 8 else 2
    write = n_rows * padded_len * serving_kv_token_elems(cfg) * kv_elem_bytes
    return float(w_bytes + write)


@dataclasses.dataclass
class RooflineReport:
    arch: str
    shape: str
    mesh: str
    chips: int
    compute_s: float
    memory_s: float
    memory_s_kernelized: float  # minus (s×s) attention traffic a fused
    #                             Pallas flash kernel keeps in VMEM
    collective_s: float
    dcn_s: float
    dominant: str
    model_flops: float
    hlo_flops_per_device: float
    useful_ratio: float
    flops_by_dtype: dict
    hbm_gb_per_device: float
    wire_gb_per_device: float
    notes: str = ""

    @property
    def bound_time(self) -> float:
        return max(self.compute_s, self.memory_s, self.collective_s, self.dcn_s)

    @property
    def bound_time_kernelized(self) -> float:
        """Bound with the Pallas-flash memory term (s² traffic in VMEM)."""
        return max(self.compute_s, self.memory_s_kernelized,
                   self.collective_s, self.dcn_s)

    @property
    def roofline_fraction(self) -> float:
        """Fraction of the per-device compute roofline this step achieves
        if every term overlapped perfectly: useful_compute_time / bound."""
        if self.bound_time <= 0:
            return 0.0
        useful_s = (self.model_flops / self.chips) / HW().peak_bf16
        return useful_s / self.bound_time

    @property
    def roofline_fraction_kernelized(self) -> float:
        if self.bound_time_kernelized <= 0:
            return 0.0
        useful_s = (self.model_flops / self.chips) / HW().peak_bf16
        return useful_s / self.bound_time_kernelized

    def row(self) -> dict:
        return {k: (round(v, 6) if isinstance(v, float) else v)
                for k, v in dataclasses.asdict(self).items()
                if k not in ("flops_by_dtype",)} | {
                    "bound_s": round(self.bound_time, 6),
                    "roofline_frac": round(self.roofline_fraction, 4),
                    "bound_s_kern": round(self.bound_time_kernelized, 6),
                    "roofline_frac_kern": round(
                        self.roofline_fraction_kernelized, 4)}


def roofline(metrics: HloMetrics, cfg: ModelConfig, cell: ShapeCell, *,
             mesh_name: str, chips: int, pod_size: int = 1,
             hw: HW = HW(), notes: str = "") -> RooflineReport:
    flops_int = sum(v for d, v in metrics.flops_by_dtype.items()
                    if d.startswith(("s8", "u8", "s4", "u4", "s16", "s32")))
    flops_fp = metrics.flops - flops_int
    compute_s = flops_fp / hw.peak_bf16 + flops_int / hw.peak_int8
    memory_s = metrics.hbm_bytes / hw.hbm_bw
    memory_s_kern = max(metrics.hbm_bytes - metrics.s2_bytes, 0.0) / hw.hbm_bw
    # pod-crossing collectives: groups spanning more devices than one pod's
    # mesh rows — heuristic: group size that equals the pod axis (2) or a
    # multiple that includes it (DESIGN.md §7)
    dcn_bytes = 0.0
    ici_bytes = 0.0
    per_pod_chips = chips // pod_size
    for group, b in metrics.wire_bytes_by_group.items():
        if pod_size > 1 and (group == pod_size or group > per_pod_chips
                             or group == chips):
            dcn_bytes += b
        else:
            ici_bytes += b
    collective_s = ici_bytes / hw.ici_bw
    dcn_s = dcn_bytes / hw.dcn_bw
    mf = model_flops(cfg, cell)
    hlo_flops = metrics.flops
    useful = (mf / chips) / hlo_flops if hlo_flops else 0.0
    terms = {"compute": compute_s, "memory": memory_s,
             "collective": collective_s, "dcn": dcn_s}
    dominant = max(terms, key=terms.get)
    return RooflineReport(
        arch=cfg.name, shape=cell.name, mesh=mesh_name, chips=chips,
        compute_s=compute_s, memory_s=memory_s,
        memory_s_kernelized=memory_s_kern, collective_s=collective_s,
        dcn_s=dcn_s, dominant=dominant, model_flops=mf,
        hlo_flops_per_device=hlo_flops, useful_ratio=useful,
        flops_by_dtype=dict(metrics.flops_by_dtype),
        hbm_gb_per_device=metrics.hbm_bytes / 1e9,
        wire_gb_per_device=metrics.wire_bytes / 1e9, notes=notes)
