"""Model FLOPs of a step, kept with the benchmark.

A copy of the program's analytic accounting (``launch/flops.py`` and
``ArchConfig.param_count``), so that no change to the program moves
the yardstick: training costs ``6 * N * tokens`` for the forward and
backward passes (N = parameters a token touches; MoE counts its top-k
experts only), plus the attention score and value products, which grow
with the square of the sequence.  Recomputed operations do not count.

``arch`` is any object with the attributes of the program's
``ArchConfig`` that these formulas read.
"""
from __future__ import annotations

import math


def _kinds(a) -> list[str]:
    n_cycles = a.n_layers // len(a.pattern)
    return list(a.pattern) * n_cycles + list(
        a.pattern[: a.n_layers % len(a.pattern)])


def _layer_params(a, kind: str) -> int:
    d = a.d_model
    q_dim, kv_dim = a.n_heads * a.head_dim, a.n_kv * a.head_dim
    n = 0
    if kind in ("global", "local", "moe"):
        n += d * (q_dim + 2 * kv_dim) + q_dim * d
        n += 2 * d
        if a.post_norm:
            n += 2 * d
        if a.qk_norm:
            n += 2 * a.head_dim
        if kind == "moe":
            n += d * a.n_experts
            n += a.n_experts * 3 * d * a.d_expert
        else:
            n += 3 * d * a.d_ff
    elif kind == "rglru":
        dr = a.d_rnn or d
        n += 2 * d + 2 * d * dr + a.conv_width * dr + 3 * dr + 2 * dr * d
        n += 3 * d * a.d_ff
    elif kind == "mlstm":
        di = 2 * d
        n += d + 2 * d * di + a.conv_width * di
        n += 3 * di * di // max(a.rnn_heads, 1) * max(a.rnn_heads, 1)
        n += 3 * di + di * d
    elif kind == "slstm":
        h = a.rnn_heads or 4
        dh = d // h
        n += d + 4 * d * d + 4 * h * dh * dh + 4 * d
        n += 2 * d * math.ceil(4 * d / 3) // 1
    else:
        raise ValueError(kind)
    return int(n)


def param_count(a) -> int:
    total = 0
    if a.has_embedding:
        total += a.vocab * a.d_model
        if not a.tie_embeddings:
            total += a.vocab * a.d_model
    else:
        total += a.d_model * a.d_model + a.d_model * a.vocab
    total += a.d_model
    return total + sum(_layer_params(a, k) for k in _kinds(a))


def active_param_count(a) -> int:
    total = param_count(a)
    if a.n_experts == 0:
        return total
    n_moe = sum(1 for k in _kinds(a) if k == "moe")
    return int(total - n_moe * (a.n_experts - a.top_k)
               * 3 * a.d_model * a.d_expert)


def _attn_flops_per_seq(a, t: int) -> float:
    total = 0.0
    for k in _kinds(a):
        if k in ("global", "moe"):
            pairs = t * t / 2 if a.causal else t * t
        elif k == "local":
            pairs = min(a.window or t, t) * t
        else:
            continue
        total += 4.0 * pairs * a.n_heads * a.head_dim
    return total


def train_step_flops(a, batch: int, seq: int) -> float:
    """Model FLOPs of one training step over ``batch`` x ``seq``
    tokens."""
    return (6.0 * active_param_count(a) * batch * seq
            + 3.0 * batch * _attn_flops_per_seq(a, seq))
