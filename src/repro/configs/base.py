"""Config dataclasses + registry.

``ModelConfig`` is intentionally one flat dataclass covering every family —
configs are data, the family field selects the forward implementation, and
unknown-to-a-family fields are simply unused.  This is what lets the
launcher/dry-run treat all 10 assigned architectures uniformly
(``--arch <id>``).
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple


DECODE_KERNELS = ("jnp", "auto", "interpret", "reference")


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str = "transformer"  # transformer | griffin | xlstm | vit
    n_layers: int = 2
    d_model: int = 128
    n_heads: int = 2
    n_kv_heads: int = 2
    head_dim: int = 0  # 0 -> d_model // n_heads
    d_ff: int = 256
    vocab_size: int = 256

    act: str = "swiglu"  # swiglu | geglu | gelu
    norm: str = "rms"  # rms | ln
    qkv_bias: bool = False
    attn_out_bias: bool = False
    mlp_bias: bool = False
    qk_norm: bool = False
    causal: bool = True
    scale_embeddings: bool = False

    rope: str = "standard"  # none | standard | mrope
    rope_theta: float = 10000.0
    rope_fraction: float = 1.0
    mrope_sections: Tuple[int, ...] = (16, 24, 24)
    learned_pos: int = 0  # >0: learned absolute positions (max len)
    tie_embeddings: bool = False
    continuous_inputs: int = 0  # >0: stub frontend input dim (audio/vision)
    head: str = "lm"  # lm | none

    # --- MoE ---
    moe: bool = False
    n_experts: int = 0
    top_k: int = 0
    expert_d_ff: int = 0
    n_shared_experts: int = 0
    router_score: str = "softmax"  # softmax | sigmoid
    capacity_factor: float = 1.25
    moe_dispatch_dtype: str = "float32"  # bf16: halves dispatch bytes
    moe_layer_start: int = 0
    aux_loss_weight: float = 0.01
    # expert parallelism: this chip holds experts [experts_offset,
    # experts_offset + experts_held) of the n_experts the router chooses
    # among (0: all of them)
    experts_held: int = 0
    experts_offset: int = 0
    # group-limited routing (DeepSeek-V3 noaux_tc): experts form n_group
    # groups, a token keeps its topk_group best groups
    n_group: int = 1
    topk_group: int = 1
    router_bias: bool = False  # correction bias added for selection only
    routed_scale: float = 1.0  # routed weights' scale after renormalizing

    # --- YaRN rope scaling (factor > 1 turns it on) ---
    yarn_factor: float = 1.0
    yarn_original_max_pos: int = 4096
    yarn_beta_fast: float = 32.0
    yarn_beta_slow: float = 1.0
    yarn_mscale: float = 1.0
    yarn_mscale_all_dim: float = 0.0

    # --- MLA (DeepSeek) ---
    mla: bool = False
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_dim: int = 0
    qk_rope_dim: int = 0
    v_head_dim: int = 0
    mtp: bool = False
    mtp_weight: float = 0.3

    # --- local attention ---
    window: Optional[int] = None

    # --- griffin / recurrent ---
    block_pattern: Tuple[str, ...] = ()  # e.g. ("rec","rec","attn",...)
    lru_width: int = 0
    conv_width: int = 4

    # --- xlstm ---
    proj_factor: float = 2.0
    slstm_every: int = 0  # 1 sLSTM block every N (0: pure mLSTM)

    # --- vit ---
    image_size: int = 224
    patch_size: int = 16
    n_classes: int = 1000

    # --- runtime policy ---
    max_seq_len: int = 8192
    param_dtype: str = "float32"
    compute_dtype: str = "float32"
    remat: str = "block"  # none | block
    attn_chunk: int = 512
    attn_logits_dtype: str = "float32"  # bf16: models VMEM-resident flash
    attn_prefix_chunks: bool = False  # static-prefix causal chunks (§Perf)
    unroll_scans: bool = False  # unroll inner chunk scans (cost calibration)
    # serving slot-decode attention backend: "jnp" (pure-jnp model path),
    # "auto" (compiled Pallas kernels; a TPU backend is required — off a
    # TPU it raises kernels.ops.NoTPUError), "interpret" (the Pallas
    # interpreter, any backend), "reference" (kernels/ref.py oracles).
    # Non-jnp modes route decode_step_slots / verify_step_slots through
    # kernels/ops.py; MLA latent caches always use the jnp path.
    decode_kernel: str = "jnp"

    def __post_init__(self):
        if self.head_dim == 0:
            object.__setattr__(self, "head_dim", self.d_model // self.n_heads)
        if self.decode_kernel not in DECODE_KERNELS:
            raise ValueError(
                f"decode_kernel must be one of {DECODE_KERNELS} "
                f"(got {self.decode_kernel!r})")

    @property
    def n_dense_layers(self):
        return self.moe_layer_start if self.moe else self.n_layers

    @property
    def n_experts_held(self):
        return self.experts_held or self.n_experts

    def replace(self, **kw):
        return dataclasses.replace(self, **kw)


@dataclasses.dataclass(frozen=True)
class ShapeConfig:
    name: str
    seq_len: int
    global_batch: int
    kind: str  # train | prefill | decode


SHAPES = {
    "train_4k": ShapeConfig("train_4k", 4096, 256, "train"),
    "prefill_32k": ShapeConfig("prefill_32k", 32768, 32, "prefill"),
    "decode_32k": ShapeConfig("decode_32k", 32768, 128, "decode"),
    "long_500k": ShapeConfig("long_500k", 524288, 1, "decode"),
}

_REGISTRY: dict = {}


def register_named(name):
    """Decorator registering a zero-arg config factory under ``name``."""
    def deco(fn):
        _REGISTRY[name] = fn
        return fn
    return deco


def get_config(name: str) -> ModelConfig:
    import repro.configs.archs  # noqa: F401  (populates registry)
    import repro.configs.paper_models  # noqa: F401
    if name not in _REGISTRY:
        raise KeyError(
            f"unknown config '{name}'; known: {sorted(_REGISTRY)}")
    return _REGISTRY[name]()


def list_configs():
    import repro.configs.archs  # noqa: F401
    import repro.configs.paper_models  # noqa: F401
    return sorted(_REGISTRY)
