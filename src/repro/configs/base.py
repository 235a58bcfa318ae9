"""Architecture config system.

Every assigned architecture is an :class:`ArchConfig`; ``reduced()`` gives
the CPU-smoke-test variant (<=2 layers, d_model<=512, <=4 experts) of the
same family. The FULL configs are only ever lowered via the dry-run
(ShapeDtypeStruct, no allocation).
"""
from __future__ import annotations

import dataclasses
import importlib
from dataclasses import dataclass, field
from typing import Dict, Optional, Tuple


@dataclass(frozen=True)
class ArchConfig:
    name: str
    family: str                     # dense | moe | ssm | hybrid | vlm | audio
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab: int
    head_dim: int = 0               # 0 -> d_model // n_heads

    # --- MoE ----------------------------------------------------------
    moe_experts: int = 0
    moe_top_k: int = 0
    moe_every: int = 1              # MoE block every k-th layer (1 = all)
    moe_d_ff: int = 0               # expert hidden (0 -> d_ff)
    moe_shared_expert: bool = False
    moe_pad_to: int = 0             # pad experts to this count (EP axis)
    moe_capacity_factor: float = 1.25
    moe_dispatch: str = "einsum"    # einsum | scatter (§Perf C2)

    # --- SSM / hybrid / xLSTM ------------------------------------------
    ssm_state: int = 0              # Mamba2 N
    ssm_heads: int = 0              # Mamba2 H (0 -> d_inner // 64)
    ssm_conv: int = 4
    ssm_expand: int = 2
    attn_every: int = 0             # zamba2: shared attn block every k layers
    slstm_every: int = 0            # xlstm: sLSTM block every k layers
    # hybrid with per-layer mixers (granite-4.0-h): the published layer
    # types of one period, "mamba" or "attention", each mixer followed
    # by its own MLP; empty -> zamba's shared block every ``attn_every``
    layer_pattern: Tuple[str, ...] = ()

    # --- positions / attention variants ---------------------------------
    rope: bool = True
    rope_theta: float = 10000.0
    mrope_sections: Tuple[int, ...] = ()   # qwen2-vl M-RoPE (t, h, w)
    sliding_window: int = 0         # 0 = full causal attention
    qkv_bias: bool = False

    # --- encoder-decoder (whisper) --------------------------------------
    encoder_layers: int = 0
    encoder_seq: int = 0            # frames after the (stubbed) conv frontend
    is_encoder_decoder: bool = False

    # --- VLM stub --------------------------------------------------------
    vision_tokens: int = 0          # prefix length of stubbed patch embeds

    # --- scalar multipliers (granite); the defaults are the identity ------
    embedding_multiplier: float = 1.0   # x0 = embed(tokens) * m
    residual_multiplier: float = 1.0    # x = x + m * block(norm(x))
    attention_multiplier: float = 0.0   # softmax scale; 0 -> head_dim**-0.5
    logits_scaling: float = 1.0         # logits = head(x) / s

    norm_eps: float = 1e-5
    tie_embeddings: bool = False
    dtype: str = "bfloat16"
    source: str = ""                # citation

    # ------------------------------------------------------------------ #
    def __post_init__(self):
        if self.head_dim == 0:
            object.__setattr__(self, "head_dim",
                               self.d_model // max(1, self.n_heads))

    @property
    def uses_attention(self) -> bool:
        return self.family != "ssm" or self.slstm_every > 0 or False

    @property
    def sub_quadratic(self) -> bool:
        """Can this arch run 0.5M-token decode? SSM/hybrid natively; dense
        and VLM via the sliding-window variant we implement; whisper no."""
        return self.family in ("ssm", "hybrid") or self.sliding_window > 0

    @property
    def q_scale(self) -> float:
        """Query multiplier that turns the attention's head_dim**-0.5 into
        the model's own softmax scale (1.0 where it has none)."""
        if not self.attention_multiplier:
            return 1.0
        return self.attention_multiplier * self.head_dim ** 0.5

    @property
    def d_inner(self) -> int:
        return self.ssm_expand * self.d_model

    @property
    def n_ssm_heads(self) -> int:
        return self.ssm_heads or max(1, self.d_inner // 64)

    # ------------------------------------------------------------------ #
    def pattern_unit(self) -> int:
        """Layers per scanned 'superlayer' (heterogeneous layer patterns
        are grouped into repeating units)."""
        if self.family == "moe" and self.moe_every > 1:
            return self.moe_every
        if self.family == "hybrid" and self.layer_pattern:
            return len(self.layer_pattern)
        if self.family == "hybrid" and self.attn_every > 0:
            return self.attn_every
        if self.slstm_every > 0:
            return self.slstm_every
        return 1

    @property
    def n_units(self) -> int:
        u = self.pattern_unit()
        assert self.n_layers % u == 0, (self.name, self.n_layers, u)
        return self.n_layers // u

    # ------------------------------------------------------------------ #
    def param_count(self) -> int:
        """Analytic parameter count (embeddings included once if tied)."""
        d = self.d_model
        emb = self.vocab * d * (1 if self.tie_embeddings else 2)
        per_layer = 0
        n_attn = 0
        n_dense_mlp = 0
        n_moe = 0
        n_ssm = 0
        n_slstm = 0
        total = emb
        hd = self.head_dim
        attn_p = d * hd * self.n_heads + 2 * d * hd * self.n_kv_heads \
            + hd * self.n_heads * d
        for i in range(self.n_layers):
            is_moe = (self.moe_experts > 0
                      and (i % max(1, self.moe_every)
                           == max(1, self.moe_every) - 1))
            if self.family in ("dense", "vlm", "audio"):
                total += attn_p + 3 * d * self.d_ff + 2 * d
            elif self.family == "moe":
                total += attn_p + 2 * d
                if is_moe:
                    ff = self.moe_d_ff or self.d_ff
                    total += self.moe_experts * 3 * d * ff + d * self.moe_experts
                    if self.moe_shared_expert:
                        total += 3 * d * ff
                else:
                    total += 3 * d * self.d_ff
            elif self.family == "ssm":
                if self.slstm_every and (i % self.slstm_every
                                         == self.slstm_every - 1):
                    total += 4 * d * d + 2 * d      # sLSTM-ish
                else:
                    total += self._mamba_params() + 2 * d
            elif self.family == "hybrid" and self.layer_pattern:
                kind = self.layer_pattern[i % len(self.layer_pattern)]
                total += (attn_p if kind == "attention"
                          else self._mamba_params()) \
                    + 3 * d * self.d_ff + 2 * d
            elif self.family == "hybrid":
                total += self._mamba_params() + 2 * d
        if self.family == "hybrid" and self.attn_every:
            total += attn_p + 3 * d * self.d_ff + 2 * d  # one shared block
        if self.is_encoder_decoder:
            # encoder layers + decoder cross-attn already excluded above;
            # add encoder stack and cross attention
            total += self.encoder_layers * (attn_p + 3 * d * self.d_ff + 2 * d)
            total += self.n_layers * attn_p       # cross-attn per dec layer
        return int(total)

    def _mamba_params(self) -> int:
        d, di, n = self.d_model, self.d_inner, self.ssm_state
        h = self.n_ssm_heads
        # in_proj (x, z, B, C, dt) + conv + out_proj
        return (d * (2 * di + 2 * n + h) + self.ssm_conv * (di + 2 * n)
                + di * d + 2 * h)

    def active_param_count(self) -> int:
        """Params touched per token (MoE: top-k experts only)."""
        if self.moe_experts == 0:
            return self.param_count()
        d = self.d_model
        ff = self.moe_d_ff or self.d_ff
        dead = 0
        n_moe_layers = sum(
            1 for i in range(self.n_layers)
            if i % max(1, self.moe_every) == max(1, self.moe_every) - 1)
        inactive = self.moe_experts - self.moe_top_k
        dead = n_moe_layers * inactive * 3 * d * ff
        return int(self.param_count() - dead)

    # ------------------------------------------------------------------ #
    def reduced(self) -> "ArchConfig":
        """Smoke-test variant: same family/pattern, tiny dims."""
        u = self.pattern_unit()
        d = min(self.d_model, 256)
        heads = min(self.n_heads, 4)
        kv = min(self.n_kv_heads, heads)
        hd = max(16, d // heads)
        if self.mrope_sections:
            # keep the 1:1.5:1.5 t/h/w split, resized to hd//2 channels
            t = hd // 8
            h = (hd // 2 - t) // 2
            sections = (hd // 2 - 2 * h, h, h)
        else:
            sections = ()
        return dataclasses.replace(
            self,
            name=self.name + "-reduced",
            n_layers=max(u, 2 if u == 1 else u),
            d_model=d,
            n_heads=heads,
            n_kv_heads=kv,
            head_dim=hd,
            d_ff=min(self.d_ff, 512) or self.d_ff,
            moe_d_ff=min(self.moe_d_ff, 256) if self.moe_d_ff else 0,
            vocab=min(self.vocab, 1024),
            mrope_sections=sections,
            moe_experts=min(self.moe_experts, 4),
            moe_top_k=min(self.moe_top_k, 2) if self.moe_top_k else 0,
            ssm_state=min(self.ssm_state, 16) if self.ssm_state else 0,
            ssm_heads=min(self.n_ssm_heads, 4) if self.ssm_state else 0,
            encoder_layers=min(self.encoder_layers, 2),
            encoder_seq=min(self.encoder_seq, 64) if self.encoder_seq else 0,
            vision_tokens=min(self.vision_tokens, 16) if self.vision_tokens else 0,
            sliding_window=min(self.sliding_window, 64) if self.sliding_window else 0,
        )


# ---------------------------------------------------------------------- #
_REGISTRY: Dict[str, str] = {
    "zamba2-7b": "repro.configs.zamba2_7b",
    "granite-4.0-h-micro": "repro.configs.granite_4_0_h_micro",
    "minicpm-2b": "repro.configs.minicpm_2b",
    "glm4-9b": "repro.configs.glm4_9b",
    "llama4-maverick-400b-a17b": "repro.configs.llama4_maverick",
    "xlstm-1.3b": "repro.configs.xlstm_1_3b",
    "granite-moe-3b-a800m": "repro.configs.granite_moe",
    "stablelm-12b": "repro.configs.stablelm_12b",
    "qwen2-vl-2b": "repro.configs.qwen2_vl_2b",
    "llama3-405b": "repro.configs.llama3_405b",
    "whisper-tiny": "repro.configs.whisper_tiny",
}

ARCH_NAMES = tuple(sorted(_REGISTRY))


def get_config(name: str) -> ArchConfig:
    if name not in _REGISTRY:
        raise KeyError(f"unknown arch {name!r}; known: {ARCH_NAMES}")
    mod = importlib.import_module(_REGISTRY[name])
    return mod.CONFIG


def all_configs() -> Dict[str, ArchConfig]:
    return {name: get_config(name) for name in ARCH_NAMES}
