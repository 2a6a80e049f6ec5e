"""The operations and bytes the algorithm needs, counted from shapes.

These count the model's work, not what an implementation does: a
quantized linear is M x K x N multiply-adds (2 ops each) with 4-bit
weights, per-output-channel float32 scales, and bf16 activations in and
out.  Which linears a dispatch runs, what its attention reads and what a
token costs through the whole model are the family's
(``bench/families/<family>.py``: ``linear_work``, ``attention_work``,
``token_ops``), so that a routed family can count only the experts its
tokens reach.  A later kernel that does the same work otherwise is read
on the same yardstick.  Each ``*_seconds`` is the least time the chip
could take: the larger of operations over the peak rate and bytes over
HBM bandwidth, call by call.
"""

from __future__ import annotations

from bench.spec import family


def linear(M: int, K: int, N: int, w_bits: int) -> tuple[float, float]:
    ops = 2.0 * M * K * N
    nbytes = K * N * w_bits / 8 + 4 * N + 2 * M * K + 2 * M * N
    return ops, nbytes


def _least(groups, rate: float, peaks: dict) -> float:
    """Least time of ``(layers, [(ops, bytes) per call])`` groups: each
    call bound by ops at ``rate`` or bytes at HBM bandwidth, once per
    layer of its group."""
    t = 0.0
    for layers, calls in groups:
        t += layers * sum(max(ops / rate, nbytes / peaks["hbm_bytes"])
                          for ops, nbytes in calls)
    return t


def linear_seconds(dispatch, m: dict, w_bits: int, peaks: dict) -> float:
    """Least time of every quantized linear the dispatch runs."""
    return _least(family(m["family"]).linear_work(m, dispatch, w_bits),
                  peaks["int8_ops"], peaks)


def paged_attention_seconds(dispatch, m: dict, kv_bits: int,
                            peaks: dict) -> float:
    """Least time of a decode dispatch's paged attention over each row's
    live positions."""
    return _least(family(m["family"]).attention_work(m, dispatch, kv_bits),
                  peaks["bf16_flops"], peaks)


def model_seconds(m: dict, dispatches, peaks: dict) -> float:
    """Least time of the model's work for every token the dispatches
    served, each precision over its own peak."""
    token_ops = family(m["family"]).token_ops
    t = 0.0
    for dsp in dispatches:
        for n, start in zip(dsp.tokens, dsp.starts):
            for pos in range(start, start + n):
                i8, bf = token_ops(m, pos)
                t += i8 / peaks["int8_ops"] + bf / peaks["bf16_flops"]
    return t
