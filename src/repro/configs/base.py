"""Config system: model configs, shape cells, and the registry."""
from __future__ import annotations

import dataclasses
from typing import Optional


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str  # dense | moe | mla_moe | ssm | hybrid | audio | vlm
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab: int
    head_dim: Optional[int] = None  # default d_model // n_heads
    qkv_bias: bool = False
    rope_theta: float = 1e4
    norm_eps: float = 1e-6
    tie_embeddings: bool = False
    # --- MoE ---
    n_experts: int = 0
    top_k: int = 0
    dense_residual: bool = False  # arctic: dense MLP branch in parallel with MoE
    # --- SSM (mamba1) ---
    ssm_state: int = 0
    ssm_conv: int = 4
    ssm_expand: int = 2
    # --- hybrid (recurrentgemma) ---
    window: int = 0  # local-attention window; 0 = full attention
    block_pattern: tuple = ()  # e.g. ("rec", "rec", "attn")
    lru_width: int = 0
    # --- modality stubs ---
    n_patches: int = 0  # vlm: SigLIP patch embeddings provided by input_specs
    enc_layers: int = 0  # audio: encoder depth
    enc_frames: int = 0  # audio: frames after the (stubbed) conv frontend
    max_decode_ctx: int = 0  # hard cap on decoder context (whisper: 448)
    # --- numerics / perf knobs (hillclimb levers) ---
    param_dtype: str = "float32"
    compute_dtype: str = "bfloat16"
    remat: str = "none"  # none | dots | full
    scan_layers: bool = True
    kernel_impl: str = "reference"  # reference | pallas | pallas_interpret
    zero1: bool = False  # shard optimizer state over the data axis
    logits_chunk: int = 0  # chunked-vocab loss; 0 = dense logits
    microbatches: int = 1  # gradient-accumulation splits per step
    fused_attention: bool = False  # force online-softmax (flash) attention at
    #   every length — models the Pallas kernel's O(S) memory on TPU (§Perf)
    cache_dtype: str = ""  # KV cache storage dtype ("" = compute_dtype);
    #   "float8_e4m3fn" halves decode cache traffic (§Perf, accuracy-checked)
    analysis_unroll: bool = False  # roofline-analysis lowering: no lax.scan /
    #   lax.map anywhere (XLA cost_analysis counts loop bodies ONCE, so the
    #   production scan modules undercount flops/bytes by ~trip count; the
    #   dry-run compiles shallow unrolled variants and extrapolates in depth)
    decode_block: int = 0  # decode-attention KV tile size (0 = kernel default
    #   of 128).  Paged serving sets it to the pool's block_len so the
    #   contiguous one-shot reference tiles its cache identically — equal
    #   tile partitions are what extend the bit-identity contract to the
    #   Pallas path under physical-block indirection (DESIGN.md §10).
    seq_shard_cache: bool = False  # decode: KV cache seq-sharded over model
    #   axis + shard_map flash-decode combine (§Perf hillclimb)
    ep_shard_map: bool = False  # MoE: explicit expert-parallel shard_map
    #   dispatch instead of GSPMD-inferred scatter collectives (§Perf)

    @property
    def hd(self) -> int:
        return self.head_dim if self.head_dim else self.d_model // self.n_heads

    @property
    def sub_quadratic(self) -> bool:
        """Can this arch serve 500k context (O(L) memory per token)?"""
        if self.family == "ssm":
            return True
        if self.family == "hybrid" and self.window > 0:
            return True
        return False


@dataclasses.dataclass(frozen=True)
class MlaMoeConfig(ModelConfig):
    """DeepSeek-V3's block (Kimi-K2 reuses it): latent attention (MLA) over
    sparse experts, after one leading dense layer.

    Routing is fixed to the published ``noaux_tc`` form: sigmoid scores over
    all ``n_experts``, the top ``top_k`` of score + a learned bias, weights
    the chosen scores normalised to sum 1 and scaled by ``route_scale``
    (one expert group).  ``d_ff`` is the dense layers' width; ``moe_d_ff``
    the routed and the shared experts'.  A chip of an expert-parallel
    deployment holds ``experts_held`` experts from ``expert_offset`` on (0:
    all); the router keeps its ``n_experts`` outputs."""

    q_lora_rank: int = 0
    kv_lora_rank: int = 0  # the cached latent's width
    qk_nope_dim: int = 0
    qk_rope_dim: int = 0  # rope dims of q/k; the k part is shared by heads
    v_head_dim: int = 0
    moe_d_ff: int = 0
    n_shared_experts: int = 0
    route_scale: float = 1.0
    experts_held: int = 0
    expert_offset: int = 0
    # YaRN rope scaling (factor 0 = plain rope at rope_theta).
    yarn_factor: float = 0.0
    yarn_orig_max_pos: int = 0
    yarn_beta_fast: float = 32.0
    yarn_beta_slow: float = 1.0
    yarn_mscale: float = 1.0
    yarn_mscale_all_dim: float = 0.0

    @property
    def n_held(self) -> int:
        return self.experts_held or self.n_experts


@dataclasses.dataclass(frozen=True)
class ShapeCell:
    name: str
    seq_len: int
    global_batch: int
    kind: str  # train | prefill | decode


SHAPES = {
    "train_4k": ShapeCell("train_4k", 4_096, 256, "train"),
    "prefill_32k": ShapeCell("prefill_32k", 32_768, 32, "prefill"),
    "decode_32k": ShapeCell("decode_32k", 32_768, 128, "decode"),
    "long_500k": ShapeCell("long_500k", 524_288, 1, "decode"),
}


def cell_applicable(cfg: ModelConfig, shape: ShapeCell) -> tuple[bool, str]:
    """Whether an (arch, shape) cell runs, and why not if it doesn't."""
    if shape.name == "long_500k" and not cfg.sub_quadratic:
        return False, "full-attention arch: O(L^2) attention / 500k KV cache not servable (DESIGN.md §4)"
    return True, ""


_REGISTRY: dict = {}


def register(cfg: ModelConfig) -> ModelConfig:
    _REGISTRY[cfg.name] = cfg
    return cfg


def get_config(name: str) -> ModelConfig:
    # Import for side effect: populate the registry.
    from repro import configs as _c  # noqa: F401

    if name not in _REGISTRY:
        raise KeyError(f"unknown arch {name!r}; known: {sorted(_REGISTRY)}")
    return _REGISTRY[name]


def all_archs() -> list[str]:
    from repro import configs as _c  # noqa: F401

    return sorted(_REGISTRY)
