"""Architecture configuration shared by all 10 assigned archs.

One ``ArchConfig`` fully determines parameter shapes, layer pattern and
runtime behaviour.  Layer *kinds* (the ``pattern`` cycle):

* ``"global"``  — full causal (or bidirectional) attention + dense FFN
* ``"local"``   — sliding-window attention + dense FFN
* ``"moe"``     — full attention + top-k MoE FFN
* ``"rglru"``   — Griffin recurrent block (conv + RG-LRU), GeGLU FFN
* ``"mlstm"``   — xLSTM matrix-LSTM block (self-contained, no FFN)
* ``"slstm"``   — xLSTM scalar-LSTM block (post-up FFN inside block)

``n_layers = first_dense + n_cycles * len(pattern) + tail``: the
``first_dense`` leading layers are "global" kinds run before the cycles
(DeepSeek-V3's ``first_k_dense_replace``), and the tail reuses the
first ``tail`` kinds of the pattern (e.g. gemma3's 34 = 5*6 + 4).

Attention is latent (MLA, DeepSeek-V2/V3) when ``kv_lora_rank > 0``:
``head_dim`` is then ``qk_nope_dim + qk_rope_dim`` and values are
``v_head_dim`` wide.  An MoE layer routes over all ``n_experts`` and
computes the part of the output that its ``n_held`` experts, ids
``expert_first`` on, give; ``d_shared > 0`` adds shared experts (one
SwiGLU of that width) that every token passes through.
"""
from __future__ import annotations

import dataclasses
import math
from dataclasses import dataclass


@dataclass(frozen=True)
class ArchConfig:
    name: str
    family: str                    # dense | moe | ssm | hybrid | audio | vlm
    n_layers: int
    d_model: int
    n_heads: int
    n_kv: int
    head_dim: int
    d_ff: int
    vocab: int
    pattern: tuple[str, ...] = ("global",)
    window: int | None = None          # sliding-window width ("local")
    attn_softcap: float | None = None  # gemma2 attention logit softcap
    final_softcap: float | None = None  # gemma2 final logit softcap
    qk_norm: bool = False
    causal: bool = True                   # False => encoder-only (hubert)
    has_embedding: bool = True            # False => frame-embedding input
    post_norm: bool = False               # gemma2-style post-layer norms
    tie_embeddings: bool = True
    act: str = "silu"                     # "silu" (SwiGLU) | "gelu" (GeGLU)
    norm_eps: float = 1e-6
    rope_theta: float = 10_000.0
    embed_scale: bool = True              # embeddings times sqrt(d_model)
    first_dense: int = 0                  # leading dense ("global") layers
    # Latent attention (MLA); off when kv_lora_rank == 0
    kv_lora_rank: int = 0
    qk_nope_dim: int = 0
    qk_rope_dim: int = 0
    v_head_dim: int = 0
    # MoE
    n_experts: int = 0                    # the router's outputs
    top_k: int = 0
    d_expert: int = 0
    d_shared: int = 0                     # shared experts' total width
    router: str = "softmax"               # "softmax" | "sigmoid" (+ bias)
    routed_scale: float = 1.0             # routed weights' scale
    expert_first: int = 0                 # first expert id held here
    experts_held: int = 0                 # 0 = all n_experts
    # Recurrent (rglru / xlstm)
    d_rnn: int = 0
    conv_width: int = 4
    rnn_heads: int = 0                    # xLSTM heads
    # Runtime knobs (overridden by shapes / perf iterations)
    dtype: str = "bfloat16"
    cache_dtype: str = ""        # "" = dtype; "float8_e4m3fn" halves KV
    attn_impl: str = "auto"   # "auto" | "xla" | "pallas" | "interpret"
    rnn_impl: str = "xla"
    remat: bool = True
    scan_layers: bool = True
    block_q: int = 512
    block_k: int = 512

    # ------------------------------------------------------------------
    @property
    def n_cycles(self) -> int:
        return (self.n_layers - self.first_dense) // len(self.pattern)

    @property
    def tail_kinds(self) -> tuple[str, ...]:
        return self.pattern[: (self.n_layers - self.first_dense)
                            % len(self.pattern)]

    @property
    def lead_kinds(self) -> tuple[str, ...]:
        return ("global",) * self.first_dense

    @property
    def layer_kinds(self) -> list[str]:
        """Every layer's kind, in order."""
        return (list(self.lead_kinds) + list(self.pattern) * self.n_cycles
                + list(self.tail_kinds))

    @property
    def mla(self) -> bool:
        return self.kv_lora_rank > 0

    @property
    def n_held(self) -> int:
        return self.experts_held or self.n_experts

    @property
    def q_dim(self) -> int:
        return self.n_heads * self.head_dim

    @property
    def kv_dim(self) -> int:
        return self.n_kv * self.head_dim

    @property
    def o_dim(self) -> int:
        """Width of the attention output before ``wo``."""
        return self.n_heads * (self.v_head_dim if self.mla
                               else self.head_dim)

    def replace(self, **kw) -> "ArchConfig":
        return dataclasses.replace(self, **kw)

    # ------------------------------------------------------------------
    def param_count(self) -> int:
        """Analytic parameter count (all layers + embeddings)."""
        total = 0
        if self.has_embedding:
            total += self.vocab * self.d_model
            if not self.tie_embeddings:
                total += self.vocab * self.d_model
        else:
            total += self.d_model * self.d_model      # frontend adapter
            total += self.d_model * self.vocab        # classifier head
        total += self.d_model                          # final norm
        for kind in self.layer_kinds:
            total += self._layer_params(kind)
        return total

    def attn_params(self) -> int:
        """Parameters of one attention mix (projections and norms)."""
        d = self.d_model
        if self.mla:
            r = self.qk_rope_dim
            return (d * self.q_dim + d * (self.kv_lora_rank + r)
                    + self.kv_lora_rank
                    + self.kv_lora_rank * self.n_heads
                    * (self.qk_nope_dim + self.v_head_dim)
                    + self.o_dim * d)
        n = d * (self.q_dim + 2 * self.kv_dim) + self.q_dim * d
        if self.qk_norm:
            n += 2 * self.head_dim
        return n

    def expert_params(self) -> int:
        """Parameters of one routed expert."""
        return 3 * self.d_model * self.d_expert

    def _layer_params(self, kind: str) -> int:
        d = self.d_model
        n = 0
        if kind in ("global", "local", "moe"):
            n += self.attn_params()
            n += 2 * d                                 # pre norms
            if self.post_norm:
                n += 2 * d
            if kind == "moe":
                n += d * self.n_experts                # router
                n += self.n_held * self.expert_params()
                n += 3 * d * self.d_shared             # shared experts
            else:
                n += 3 * d * self.d_ff                 # SwiGLU/GeGLU
        elif kind == "rglru":
            dr = self.d_rnn or d
            n += 2 * d                                 # norms
            n += 2 * d * dr                            # rec + gate branch in
            n += self.conv_width * dr                  # temporal conv
            n += 3 * dr                                # Lambda, a-gate, i-gate
            n += 2 * dr * d                            # (a,i gates use W) out
            n += 3 * d * self.d_ff                     # GeGLU FFN
        elif kind == "mlstm":
            di = 2 * d                                 # up factor 2
            n += d + 2 * d * di                        # norm + two up projs
            n += self.conv_width * di
            n += 3 * di * di // max(self.rnn_heads, 1) * max(self.rnn_heads, 1)
            n += 3 * di                                # i, f, o gate projs
            n += di * d                                # down proj
        elif kind == "slstm":
            h = self.rnn_heads or 4
            dh = d // h
            n += d                                     # norm
            n += 4 * d * d                             # W gates
            n += 4 * h * dh * dh                       # block-diag R gates
            n += 4 * d                                 # biases
            n += 2 * d * math.ceil(4 * d / 3) // 1     # post-up FFN approx
        else:
            raise ValueError(kind)
        return int(n)

    def active_param_count(self) -> int:
        """Params touched per token (MoE: the top_k of n_experts a token
        picks; with a share of the experts held, the top_k at the held
        share ``n_held / n_experts``)."""
        total = self.param_count()
        if self.n_experts == 0:
            return total
        n_moe = self.layer_kinds.count("moe")
        held = n_moe * self.n_held * self.expert_params()
        act = n_moe * self.top_k * self.expert_params()
        return int(round(total - held + act * self.n_held / self.n_experts))
