"""Architecture + run configuration system.

`ArchConfig` describes *what* the model is (one file per assigned
architecture, exact public-literature configs).  `RunConfig` describes
*how* it runs (attention impl, chunk sizes, remat, MoE dispatch, CE
chunking — the §Perf hillclimbing levers).  `ShapeSpec` describes the
assigned input-shape cells.
"""
from __future__ import annotations

import importlib
from dataclasses import dataclass, field, replace


@dataclass(frozen=True)
class ArchConfig:
    name: str
    family: str  # dense | moe | ssm | hybrid | encdec
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: int | None = None
    qkv_bias: bool = False
    act: str = "swiglu"  # swiglu | gelu
    rope_theta: float = 1e4
    norm_eps: float = 1e-5
    # moe: the router chooses among n_experts; a layer may hold a share of
    # them (experts_held from expert_first; 0 = all), as expert parallelism
    # divides them between chips
    n_experts: int = 0
    experts_per_token: int = 0
    capacity_factor: float = 1.25
    router: str = "topk"  # topk (softmax, renormalised) | sparsemixer (Phi-3.5-MoE)
    router_jitter: float = 0.01  # sparsemixer's threshold eps (router_jitter_noise)
    experts_held: int = 0
    expert_first: int = 0
    # norms and biases beyond the q/k/v bias
    norm: str = "rms"  # rms | layer (LayerNorm with weight and bias)
    o_bias: bool = False
    head_bias: bool = False
    # ssm / hybrid
    ssm_state: int = 0
    attn_every: int = 0  # hybrid: shared attention block after every k SSM layers
    # encdec
    enc_layers: int = 0
    dec_layers: int = 0
    # modality
    frontend: str | None = None  # 'audio' | 'vlm' | None (stub per assignment)
    tie_embeddings: bool = False
    vocab_pad_multiple: int = 256
    # long-context capability: pure full-attention archs skip long_500k
    supports_long_context: bool = False

    @property
    def head_dim_(self) -> int:
        return self.head_dim or self.d_model // self.n_heads

    @property
    def n_held(self) -> int:
        """Experts whose weights this layer holds."""
        return self.experts_held or self.n_experts

    @property
    def vocab_padded(self) -> int:
        m = self.vocab_pad_multiple
        return (self.vocab_size + m - 1) // m * m

    @property
    def is_encdec(self) -> bool:
        return self.family == "encdec"

    def param_count_estimate(self) -> float:
        """Analytic N for MODEL_FLOPS = 6·N·D (active params for MoE)."""
        d, ff, v = self.d_model, self.d_ff, self.vocab_padded
        hd = self.head_dim_
        attn = d * hd * (self.n_heads + 2 * self.n_kv_heads) + self.n_heads * hd * d
        if self.family == "moe":
            k = self.experts_per_token
            mlp_active = 3 * d * ff * k
        elif self.family in ("ssm",):
            mlp_active = 0  # rwkv layers counted in their own structure below
        else:
            mlp_active = 3 * d * ff if self.act == "swiglu" else 2 * d * ff
        if self.family == "ssm":  # rwkv6: tm ~ 5 d² + cm 2·d·ff + d·ff
            layer = 6 * d * d + 3 * d * ff
        elif self.family == "hybrid":
            d_in = 2 * d
            ssm_layer = 2 * d * d_in + d_in * d  # in/out projections dominate
            layer = ssm_layer
        elif self.is_encdec:
            # decoder layers carry an extra cross-attention block
            layer = attn * 1.5 + mlp_active
        else:
            layer = attn + mlp_active
        n = self.n_layers * layer + v * d * (1 if self.tie_embeddings else 2)
        if self.family == "hybrid" and self.attn_every:
            n += (self.n_layers // self.attn_every) * 0  # shared block counted once
            n += attn + 3 * d * ff
        return float(n)


@dataclass(frozen=True)
class RunConfig:
    """Performance/runtime knobs — the §Perf levers."""

    attn_impl: str = "chunked"  # full | chunked
    q_chunk: int = 512
    kv_chunk: int = 1024
    skip_masked_blocks: bool = False  # causal block skipping (hillclimb)
    remat: str = "layer"  # none | layer
    scan_layers: bool = True
    scan_unroll: int = 1  # full-unroll for cost lowering
    moe_impl: str = "einsum"  # einsum | sort (hillclimb)
    moe_group: int | None = None
    ce_chunk: int = 0  # 0 = dense CE; >0 = sequence-chunked CE (hillclimb)
    ce_impl: str = "gather"  # gather | onehot (vocab-sharding-friendly gold pick)
    decode_seq_shard: bool = False  # split-S decode cache sharding (hillclimb)
    constrain_activations: bool = False  # Megatron-style layout pinning (hillclimb)
    accum_steps: int = 1  # microbatch gradient accumulation (memory lever)
    bf16_params: bool = False  # bf16 weights + f32 master in opt state (hillclimb)
    lr_chunk: int = 32  # linear-recurrence chunk
    compute_dtype: str = "bfloat16"
    param_dtype: str = "float32"
    decode_cache_dtype: str = "bfloat16"

    def for_cost_lowering(self) -> "RunConfig":
        """Variant whose scans fully unroll (exact cost_analysis)."""
        return replace(self, scan_layers=False, scan_unroll=8)


@dataclass(frozen=True)
class ShapeSpec:
    name: str
    seq_len: int
    global_batch: int
    kind: str  # train | prefill | decode

    @property
    def tokens(self) -> int:
        return self.seq_len * self.global_batch


SHAPES: dict[str, ShapeSpec] = {
    "train_4k": ShapeSpec("train_4k", 4_096, 256, "train"),
    "prefill_32k": ShapeSpec("prefill_32k", 32_768, 32, "prefill"),
    "decode_32k": ShapeSpec("decode_32k", 32_768, 128, "decode"),
    "long_500k": ShapeSpec("long_500k", 524_288, 1, "decode"),
}

ARCH_IDS = [
    "whisper_base",
    "chameleon_34b",
    "phi35_moe",
    "grok1_314b",
    "qwen25_3b",
    "phi3_mini",
    "qwen15_4b",
    "granite_20b",
    "zamba2_7b",
    "rwkv6_3b",
]

#: public `--arch` aliases (assignment ids) -> module names
ALIASES = {
    "whisper-base": "whisper_base",
    "chameleon-34b": "chameleon_34b",
    "phi3.5-moe-42b-a6.6b": "phi35_moe",
    "grok-1-314b": "grok1_314b",
    "qwen2.5-3b": "qwen25_3b",
    "phi3-mini-3.8b": "phi3_mini",
    "qwen1.5-4b": "qwen15_4b",
    "granite-20b": "granite_20b",
    "zamba2-7b": "zamba2_7b",
    "rwkv6-3b": "rwkv6_3b",
}


def _module(arch: str):
    arch = ALIASES.get(arch, arch).replace("-", "_").replace(".", "")
    return importlib.import_module(f"repro.configs.{arch}")


def get_config(arch: str) -> ArchConfig:
    return _module(arch).CONFIG


def smoke_config(arch: str) -> ArchConfig:
    return _module(arch).SMOKE


def all_configs() -> dict[str, ArchConfig]:
    return {a: get_config(a) for a in ARCH_IDS}


def cells(include_skipped: bool = False) -> list[tuple[str, str]]:
    """All assigned (arch, shape) dry-run cells; long_500k only where the
    architecture family supports sub-quadratic long context (DESIGN.md §4)."""
    out = []
    for arch in ARCH_IDS:
        cfg = get_config(arch)
        for shape in SHAPES.values():
            if shape.name == "long_500k" and not cfg.supports_long_context:
                if include_skipped:
                    out.append((arch, shape.name + ":skipped"))
                continue
            out.append((arch, shape.name))
    return out
