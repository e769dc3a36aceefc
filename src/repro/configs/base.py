"""Config system: architecture + shape + run configs, and the registry
behind ``--arch <id>`` / ``--shape <id>``."""
from __future__ import annotations

import dataclasses
import importlib
from typing import Optional

import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    num_experts: int
    top_k: int
    d_ff: int                         # expert hidden size
    capacity_factor: float = 1.25
    dense_residual: bool = False      # arctic: dense FFN in parallel w/ MoE
    every_n_layers: int = 1           # jamba: MoE on every other layer
    # routing: "softmax" over the top-k logits, or "sigmoid" scores whose
    # top-k are normalised to sum to 1 (divided by their sum + 1e-6)
    router: str = "softmax"
    expert_bias: bool = False         # a per-expert bias picks the top-k,
    #                                   but does not weight them


@dataclasses.dataclass(frozen=True)
class MambaConfig:
    d_state: int = 16
    d_conv: int = 4
    expand: int = 2
    dt_rank: Optional[int] = None     # default ceil(d_model/16)

    def rank(self, d_model: int) -> int:
        return self.dt_rank or -(-d_model // 16)


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                       # dense|moe|ssm|hybrid|encdec|vlm|audio
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab: int
    qkv_bias: bool = False
    tied_embeddings: bool = False
    rope_theta: float = 1e4
    norm_eps: float = 1e-5
    moe: Optional[MoEConfig] = None
    mamba: Optional[MambaConfig] = None
    # hybrid (jamba): attention layer each `attn_period` layers at offset
    attn_period: int = 0
    attn_offset: int = 0
    # encdec
    n_encoder_layers: int = 0
    # frontends (vlm/audio): the modality embedder is a stub; inputs arrive
    # as precomputed frame/patch embeddings of this many positions
    frontend_positions: int = 0
    # per-layer operator pattern (LFM2): "conv", a gated short convolution
    # of ``conv_kernel`` taps, or "full_attention"; the first n_layers
    # entries are run. Empty: every layer is attention.
    layer_types: tuple = ()
    conv_kernel: int = 0
    n_dense_layers: int = 0           # leading layers with a dense FFN
    #                                   before the MoE ones (pattern stacks)
    qk_norm: bool = False             # RMSNorm of each q / k head before RoPE
    act: str = "silu"
    param_dtype: str = "float32"
    compute_dtype: str = "bfloat16"

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_heads

    def layer_kinds(self) -> tuple:
        """The operator of each run layer: "conv" or "full_attention"."""
        if not self.layer_types:
            return ("full_attention",) * self.n_layers
        if len(self.layer_types) < self.n_layers:
            raise ValueError(f"{self.name}: {len(self.layer_types)} "
                             f"layer_types for {self.n_layers} layers")
        return tuple(self.layer_types[:self.n_layers])

    @property
    def n_attn_layers(self) -> int:
        """Layers that hold KV (every layer but the short convolutions)."""
        return self.layer_kinds().count("full_attention")

    @property
    def n_conv_layers(self) -> int:
        return self.layer_kinds().count("conv")

    @property
    def n_moe_layers(self) -> int:
        """MoE layers of a pattern stack (the layers past n_dense_layers)."""
        if self.moe is None:
            return 0
        return max(self.n_layers - self.n_dense_layers, 0)

    def sub_quadratic(self) -> bool:
        return self.family in ("ssm", "hybrid")

    def param_count(self) -> int:
        """Analytic total parameter count (sanity vs the advertised size)."""
        d, v = self.d_model, self.vocab
        emb = v * d * (1 if self.tied_embeddings else 2)
        per_attn = d * (self.n_heads + 2 * self.n_kv_heads) * self.head_dim + \
            self.n_heads * self.head_dim * d
        if self.qkv_bias:
            per_attn += (self.n_heads + 2 * self.n_kv_heads) * self.head_dim
        def ffn_params(ff):
            n_mat = 3 if self.act == "silu" else 2
            return n_mat * d * ff
        total = emb
        if self.layer_types:
            conv = 4 * d * d + self.conv_kernel * d
            norms = 2 * self.head_dim if self.qk_norm else 0
            moe = self.moe
            per_moe = 0 if moe is None else (
                moe.num_experts * ffn_params(moe.d_ff)
                + d * moe.num_experts + moe.num_experts * moe.expert_bias)
            kinds = self.layer_kinds()
            for i, kind in enumerate(kinds):
                total += (per_attn + norms if kind == "full_attention"
                          else conv) + 2 * d
                total += (ffn_params(self.d_ff)
                          if moe is None or i < self.n_dense_layers
                          else per_moe)
            return total + d
        if self.family in ("dense", "vlm", "audio"):
            total += self.n_layers * (per_attn + ffn_params(self.d_ff) + 2 * d)
        elif self.family == "moe":
            moe = self.moe
            per_moe = moe.num_experts * ffn_params(moe.d_ff) + d * moe.num_experts
            dense_part = ffn_params(self.d_ff) if moe.dense_residual else 0
            total += self.n_layers * (per_attn + per_moe + dense_part + 2 * d)
        elif self.family == "ssm":
            m = self.mamba
            di = m.expand * d
            per = (d * 2 * di            # in_proj
                   + m.d_conv * di + di  # conv + bias
                   + di * (m.rank(d) + 2 * m.d_state)   # x_proj
                   + m.rank(d) * di + di # dt_proj
                   + di * m.d_state + di # A_log, D
                   + di * d              # out_proj
                   + d)                  # norm
            total += self.n_layers * per
        elif self.family == "hybrid":
            m = self.mamba
            di = m.expand * d
            per_mamba = (d * 2 * di + m.d_conv * di + di
                         + di * (m.rank(d) + 2 * m.d_state)
                         + m.rank(d) * di + di + di * m.d_state + di + di * d)
            n_attn = self.n_layers // self.attn_period
            n_mamba = self.n_layers - n_attn
            moe = self.moe
            n_moe = self.n_layers // moe.every_n_layers
            n_dense = self.n_layers - n_moe
            total += (n_attn * per_attn + n_mamba * per_mamba
                      + n_moe * (moe.num_experts * ffn_params(moe.d_ff) + d * moe.num_experts)
                      + n_dense * ffn_params(self.d_ff)
                      + self.n_layers * 2 * d)
        elif self.family == "encdec":
            enc = self.n_encoder_layers * (per_attn + ffn_params(self.d_ff) + 2 * d)
            dec = self.n_layers * (2 * per_attn + ffn_params(self.d_ff) + 3 * d)
            total += enc + dec
        return total


@dataclasses.dataclass(frozen=True)
class ShapeConfig:
    name: str
    seq_len: int
    global_batch: int
    kind: str                          # train | prefill | decode


SHAPES = {
    "train_4k": ShapeConfig("train_4k", 4096, 256, "train"),
    "prefill_32k": ShapeConfig("prefill_32k", 32768, 32, "prefill"),
    "decode_32k": ShapeConfig("decode_32k", 32768, 128, "decode"),
    "long_500k": ShapeConfig("long_500k", 524288, 1, "decode"),
}


ARCH_IDS = [
    "internvl2-1b", "arctic-480b", "granite-moe-1b-a400m", "granite-34b",
    "qwen1.5-32b", "granite-3-2b", "qwen2-0.5b", "seamless-m4t-large-v2",
    "jamba-v0.1-52b", "falcon-mamba-7b",
]

_REGISTRY: dict = {}


def register(cfg: ModelConfig) -> ModelConfig:
    _REGISTRY[cfg.name] = cfg
    return cfg


def get_config(arch: str) -> ModelConfig:
    if arch not in _REGISTRY:
        mod = arch.replace("-", "_").replace(".", "_")
        importlib.import_module(f"repro.configs.{mod}")
    return _REGISTRY[arch]


def cell_supported(cfg: ModelConfig, shape: ShapeConfig) -> tuple[bool, str]:
    """Assignment skip rules (documented in DESIGN.md)."""
    if shape.name == "long_500k" and not cfg.sub_quadratic():
        return False, "long_500k needs sub-quadratic attention; " \
                      f"{cfg.name} is pure full-attention (skip per DESIGN.md)"
    return True, ""
