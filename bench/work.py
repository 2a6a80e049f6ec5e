"""The operations and bytes the algorithm needs, counted from shapes.

These count the model's work, not what an implementation does: a
quantized linear is M x K x N multiply-adds (2 ops each) with 4-bit
weights, per-output-channel float32 scales, and bf16 activations in and
out; paged decode attention reads the K and V of each slot's live
positions only (int8 with a float32 scale per position and head).  A
later kernel that does the same work otherwise is read on the same
yardstick.  Each ``*_seconds`` is the least time the chip could take:
the larger of operations over the peak rate and bytes over HBM
bandwidth.
"""

from __future__ import annotations

from bench.weights import shapes


def linears(m: dict) -> list[tuple[int, int]]:
    """(K, N) of each quantized linear of one layer."""
    s = shapes(m)
    d = m["hidden_size"]
    if m["family"] == "ssm":
        return [(d, s["d_in_proj"]), (s["d_inner"], d)]
    hq, hkv, hd, f = (m["num_attention_heads"], m["num_key_value_heads"],
                      s["head_dim"], m["intermediate_size"])
    return [(d, hq * hd), (d, hkv * hd), (d, hkv * hd), (hq * hd, d),
            (d, f), (d, f), (f, d)]


def linear(M: int, K: int, N: int, w_bits: int) -> tuple[float, float]:
    ops = 2.0 * M * K * N
    nbytes = K * N * w_bits / 8 + 4 * N + 2 * M * K + 2 * M * N
    return ops, nbytes


def linear_seconds(M: int, m: dict, w_bits: int, peaks: dict) -> float:
    """Least time of every quantized linear of the model for M rows."""
    t = 0.0
    for K, N in linears(m):
        ops, nbytes = linear(M, K, N, w_bits)
        t += max(ops / peaks["int8_ops"], nbytes / peaks["hbm_bytes"])
    return t * m["num_hidden_layers"]


def paged_attention(contexts: list[int], m: dict, kv_bits: int
                    ) -> tuple[float, float]:
    """One layer's decode attention over the live positions of each row
    (``contexts`` counts the new token too)."""
    s = shapes(m)
    hq, hkv, hd = m["num_attention_heads"], m["num_key_value_heads"], s["head_dim"]
    n = sum(contexts)
    ops = 4.0 * n * hq * hd
    per_pos = hkv * (2 * hd * kv_bits / 8 + (2 * 4 if kv_bits < 16 else 0))
    nbytes = n * per_pos + len(contexts) * hq * hd * 2 * 2
    return ops, nbytes


def paged_attention_seconds(contexts: list[int], m: dict, kv_bits: int,
                            peaks: dict) -> float:
    ops, nbytes = paged_attention(contexts, m, kv_bits)
    return m["num_hidden_layers"] * max(ops / peaks["bf16_flops"],
                                        nbytes / peaks["hbm_bytes"])


def token_ops(m: dict, context: int) -> tuple[float, float]:
    """(int8 ops, bf16 ops) one token needs through the whole model at
    position ``context``: the quantized linears in int8; attention (or
    the SSM scan and conv) and the output head in bf16."""
    s = shapes(m)
    L, d, V = m["num_hidden_layers"], m["hidden_size"], m["vocab_size"]
    int8 = 2.0 * L * sum(K * N for K, N in linears(m))
    if m["family"] == "ssm":
        per_layer = (2 * m["conv_kernel"] * s["conv_ch"]
                     + 6 * s["ssm_heads"] * m["head_dim"] * m["state_size"])
    else:
        per_layer = 4 * (context + 1) * m["num_attention_heads"] * s["head_dim"]
    return int8, L * per_layer + 2.0 * d * V


def model_seconds(m: dict, dispatches, peaks: dict) -> float:
    """Least time of the model's work for every token the dispatches
    served, each precision over its own peak."""
    t = 0.0
    for dsp in dispatches:
        for n, start in zip(dsp.tokens, dsp.starts):
            for pos in range(start, start + n):
                i8, bf = token_ops(m, pos)
                t += i8 / peaks["int8_ops"] + bf / peaks["bf16_flops"]
    return t
