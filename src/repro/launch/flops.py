"""Analytic MODEL_FLOPS per cell (§Roofline's 'useful compute' term).

Prompt-standard accounting: MODEL_FLOPS = 6*N*D for training (fwd+bwd),
2*N*D for forward-only (prefill), 2*N*B per decoded token — with
N = active parameter count (MoE: top-k experts only; of a share of the
experts held, the top-k at that share).  Attention score/value FLOPs
are added explicitly since at 32k context they are a material fraction
(12*L*T^2*d_head*H per token-batch for full causal attention, halved
for the causal triangle, windowed for local layers; latent attention
scores at its q/k width and values at ``v_head_dim``).
"""
from __future__ import annotations

from repro.configs.base import ShapeSpec
from repro.models import ArchConfig


def _qk_v_width(cfg: ArchConfig) -> int:
    """Score plus value widths of one head."""
    return cfg.head_dim + (cfg.v_head_dim if cfg.mla else cfg.head_dim)


def _attn_flops_per_seq(cfg: ArchConfig, t: int) -> float:
    """Score+value matmul FLOPs for ONE sequence of length t (fwd)."""
    total = 0.0
    for k in cfg.layer_kinds:
        if k in ("global", "moe"):
            pairs = t * t / 2 if cfg.causal else t * t
        elif k == "local":
            w = cfg.window or t
            pairs = min(w, t) * t        # banded
        else:
            continue                     # recurrent: counted via params
        total += 2.0 * pairs * cfg.n_heads * _qk_v_width(cfg)
    return total


def model_flops(cfg: ArchConfig, shape: ShapeSpec) -> float:
    n_active = cfg.active_param_count()
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        return (6.0 * n_active * tokens
                + 3.0 * shape.global_batch * _attn_flops_per_seq(
                    cfg, shape.seq_len))
    if shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return (2.0 * n_active * tokens
                + shape.global_batch * _attn_flops_per_seq(
                    cfg, shape.seq_len))
    # decode: one token against a seq_len cache
    attn = 0.0
    for k in cfg.layer_kinds:
        if k in ("global", "moe"):
            span = shape.seq_len
        elif k == "local":
            span = min(cfg.window or shape.seq_len, shape.seq_len)
        else:
            continue
        attn += 2.0 * span * cfg.n_heads * _qk_v_width(cfg)
    return shape.global_batch * (2.0 * n_active + attn)
