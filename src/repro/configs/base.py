"""Architecture config schema + registry.

Every assigned architecture is a ``ModelConfig`` in ``configs/<id>.py``; each
also exposes a ``smoke()`` reduction (same family, tiny dims) used by CPU
tests. ``--arch <id>`` everywhere resolves through :func:`get_config`.
"""
from __future__ import annotations

import dataclasses
import importlib
from typing import Dict, Tuple

__all__ = ["ModelConfig", "ShapeConfig", "register", "get_config", "list_archs", "SHAPES"]


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                      # dense | moe | ssm | hybrid | encdec | vlm | image
    num_layers: int = 0
    d_model: int = 0
    num_heads: int = 0
    num_kv_heads: int = 0
    head_dim: int = 0
    d_ff: int = 0
    vocab_size: int = 0

    # --- attention ---
    attn_type: str = "gqa"           # gqa | mla | none
    rope_theta: float = 10_000.0
    use_rope: bool = True
    qk_norm: bool = False
    attn_logit_softcap: float = 0.0
    # MLA (DeepSeek/MiniCPM3-style latent attention)
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_rope_head_dim: int = 0
    qk_nope_head_dim: int = 0
    v_head_dim: int = 0

    # --- norm / mlp ---
    norm_type: str = "rmsnorm"       # rmsnorm | layernorm | layernorm_np
    mlp_type: str = "swiglu"         # swiglu | gelu
    norm_eps: float = 1e-5

    # --- MoE ---
    num_experts: int = 0
    num_experts_per_tok: int = 0
    moe_capacity_factor: float = 1.25
    moe_group_size: int = 4096       # routing group (tokens); GShard-style
    router_aux_coef: float = 0.01
    router_z_coef: float = 1e-3

    # --- SSM ---
    ssm_type: str = "none"           # none | mamba1 | mamba2
    ssm_state: int = 0
    ssm_conv: int = 4
    ssm_expand: int = 2
    ssm_head_dim: int = 64           # mamba2
    ssm_dt_rank: int = 0             # mamba1 (0 -> ceil(d_model/16))
    ssm_chunk: int = 128             # scan/SSD chunk length
    ssm_scan_dtype: str = "float32"  # assoc-scan element dtype (bf16 halves HBM traffic)

    # --- hybrid (zamba-style shared attention) ---
    attn_every: int = 0              # 0 = no shared block

    # --- encoder-decoder ---
    is_encoder_decoder: bool = False
    encoder_layers: int = 0
    encoder_len: int = 1500          # stub frontend frames at serve time

    # --- modality frontend stubs (assignment: precomputed embeddings) ---
    frontend: str = "none"           # none | audio_stub | vision_stub
    num_patches: int = 0             # vision_stub: patches prepended to text

    # --- image pipeline (sobel-hd: the paper's own workload) ---
    image_h: int = 0
    image_w: int = 0
    sobel_operator: str = "sobel5"   # repro.core.filters registry name ("" = from sobel_size)
    sobel_size: int = 5              # legacy selector; sobel_operator wins when set
    sobel_directions: int = 4
    sobel_variant: str = "v2"
    sobel_backend: str = "auto"      # dispatch backend: auto | pallas-tpu | pallas-interpret | xla
    sobel_block_h: int = 0           # Pallas tile rows; 0 = tuning cache / default
    sobel_block_w: int = 0           # Pallas tile cols; 0 = tuning cache / default
    sobel_shard: str = ""            # image-mesh shard spec "DxRxC" | "auto"; "" = single device
    frame_bits: int = 0              # bits a pixel the sensor delivers (8 -> u8, 9..16 -> u16 frames); 0 = f32 frames

    @property
    def frame_dtype(self) -> str:
        """The dtype of the frames this deployment's source delivers: the
        narrowest unsigned container of ``frame_bits``, else float32."""
        if not self.frame_bits:
            return "float32"
        if not 1 <= self.frame_bits <= 16:
            raise ValueError(f"frame_bits {self.frame_bits} is not 1..16")
        return "uint8" if self.frame_bits <= 8 else "uint16"

    def edge_config(self, **overrides):
        """This config's image pipeline as a ``repro.api.EdgeConfig``."""
        from repro.api import EdgeConfig, ShardConfig
        from repro.core.filters import operator_for_size

        operator = self.sobel_operator or operator_for_size(self.sobel_size)
        cfg = EdgeConfig(
            operator=operator,
            directions=self.sobel_directions,
            variant=self.sobel_variant,
            backend=self.sobel_backend,
            block_h=self.sobel_block_h or None,
            block_w=self.sobel_block_w or None,
            shard=ShardConfig.parse(self.sobel_shard) if self.sobel_shard else None,
        )
        return cfg.replace(**overrides) if overrides else cfg

    # --- training/runtime ---
    tie_embeddings: bool = False
    dtype: str = "bfloat16"
    remat: bool = True
    remat_policy: str = "minimal"   # minimal (save carry only) | dots | none
    scan_layers: bool = True
    sub_quadratic: bool = False      # True for SSM/hybrid: long_500k runnable

    def __post_init__(self):
        if self.head_dim == 0 and self.num_heads:
            object.__setattr__(self, "head_dim", self.d_model // self.num_heads)
        if self.ssm_type == "mamba1" and self.ssm_dt_rank == 0:
            object.__setattr__(self, "ssm_dt_rank", -(-self.d_model // 16))

    @property
    def d_inner(self) -> int:
        return self.ssm_expand * self.d_model

    @property
    def ssm_heads(self) -> int:
        return self.d_inner // self.ssm_head_dim

    def replace(self, **kw) -> "ModelConfig":
        return dataclasses.replace(self, **kw)


@dataclasses.dataclass(frozen=True)
class ShapeConfig:
    name: str
    seq_len: int
    global_batch: int
    kind: str                        # train | prefill | decode

    @property
    def is_decode(self) -> bool:
        return self.kind == "decode"


# The assigned LM shape set (identical for all 10 archs).
SHAPES: Dict[str, ShapeConfig] = {
    "train_4k": ShapeConfig("train_4k", 4_096, 256, "train"),
    "prefill_32k": ShapeConfig("prefill_32k", 32_768, 32, "prefill"),
    "decode_32k": ShapeConfig("decode_32k", 32_768, 128, "decode"),
    "long_500k": ShapeConfig("long_500k", 524_288, 1, "decode"),
}

_REGISTRY: Dict[str, "tuple"] = {}

ARCH_IDS = (
    "glm4-9b",
    "olmo-1b",
    "llama3.2-1b",
    "minicpm3-4b",
    "whisper-large-v3",
    "pixtral-12b",
    "falcon-mamba-7b",
    "qwen3-moe-30b-a3b",
    "phi3.5-moe-42b-a6.6b",
    "zamba2-2.7b",
    "sobel-hd",                      # the paper's own workload, as an arch
)

_MODULES = {
    "glm4-9b": "glm4_9b",
    "olmo-1b": "olmo_1b",
    "llama3.2-1b": "llama3_2_1b",
    "minicpm3-4b": "minicpm3_4b",
    "whisper-large-v3": "whisper_large_v3",
    "pixtral-12b": "pixtral_12b",
    "falcon-mamba-7b": "falcon_mamba_7b",
    "qwen3-moe-30b-a3b": "qwen3_moe_30b_a3b",
    "phi3.5-moe-42b-a6.6b": "phi3_5_moe_42b_a6_6b",
    "zamba2-2.7b": "zamba2_2_7b",
    "sobel-hd": "sobel_hd",
    "inspect-12mp": "inspect_12mp",  # image deployment, not in ARCH_IDS
}


def register(arch_id: str, full: ModelConfig, smoke: ModelConfig) -> None:
    _REGISTRY[arch_id] = (full, smoke)


def get_config(arch_id: str, smoke: bool = False) -> ModelConfig:
    if arch_id not in _REGISTRY:
        mod = _MODULES.get(arch_id)
        if mod is None:
            raise KeyError(f"unknown arch {arch_id!r}; known: {sorted(_MODULES)}")
        importlib.import_module(f"repro.configs.{mod}")
    full, smoke_cfg = _REGISTRY[arch_id]
    return smoke_cfg if smoke else full


def list_archs() -> Tuple[str, ...]:
    return ARCH_IDS
