"""Config system: model / shape / mesh / SEAL / run configuration.

Every assigned architecture instantiates a ``ModelConfig``; the four
assigned input shapes are ``ShapeConfig`` rows in ``SHAPES``. The SEAL
technique is configured orthogonally through ``SealConfig`` so any
(arch x shape x seal-mode) combination is a valid run.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Optional, Tuple

# --------------------------------------------------------------------------
# Model
# --------------------------------------------------------------------------

BLOCK_KINDS = ("attn", "local_attn", "rglru", "ssd")


@dataclass(frozen=True)
class MoEConfig:
    num_experts: int                 # routed experts the router scores
    top_k: int
    # load-balancing aux loss weight (used in training)
    aux_loss_weight: float = 0.01
    # expert-capacity factor for GShard-style dispatch (train/prefill)
    capacity_factor: float = 1.25
    # how tokens pick experts, and so which layer serves them:
    #   "softmax": softmax top-k (qwen3, dbrx): ``layers.moe_apply`` with
    #     its capacity, ``moe_apply_dense`` when decoding;
    #   "sigmoid_bias": DeepSeek-V3's top-k of sigmoid score + a correction
    #     bias, weighted by the scores: ``layers.moe_held``, dropless, told
    #     which experts it holds, with shared experts.
    router: str = "softmax"
    d_expert: int = 0                # routed expert width (0: d_ff)
    d_shared: int = 0                # shared experts as one MLP (0: none)
    route_scale: float = 1.0         # routed_scaling_factor
    # experts this chip holds (0: all), experts [first, first + held) of
    # every MoE layer: expert parallelism's share of one chip
    experts_held: int = 0

    def __post_init__(self):
        if self.router not in ("softmax", "sigmoid_bias"):
            raise ValueError(f"unknown router {self.router!r}")

    @property
    def held(self) -> int:
        return self.experts_held or self.num_experts


@dataclass(frozen=True)
class MLAConfig:
    """Multi-head latent attention (DeepSeek-V2/V3) without a query LoRA:
    the cache holds one ``kv_lora_rank + rope_dim`` latent per token, shared
    by every head."""
    kv_lora_rank: int
    nope_dim: int                    # qk_nope_head_dim
    rope_dim: int                    # qk_rope_head_dim
    v_dim: int                       # v_head_dim

    @property
    def latent(self) -> int:
        return self.kv_lora_rank + self.rope_dim


@dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                      # dense | moe | vlm | hybrid | audio | ssm
    num_layers: int
    d_model: int
    num_heads: int                   # query heads (0 for attn-free archs)
    num_kv_heads: int
    head_dim: int
    d_ff: int
    vocab_size: int
    # periodic layer pattern, cycled over num_layers
    pattern: Tuple[str, ...] = ("attn",)
    moe: Optional[MoEConfig] = None
    # leading layers with a dense MLP of width d_ff ahead of the MoE stack
    # (DeepSeek's first_k_dense_replace); a stack of their own in params
    first_dense: int = 0
    mla: Optional[MLAConfig] = None
    # gemma-style softcaps / local attention
    logit_softcap: float = 0.0
    attn_softcap: float = 0.0
    window: int = 0                  # sliding window width for local_attn
    # SSM (mamba2 SSD) geometry
    ssm_state: int = 0
    ssm_expand: int = 2
    ssm_conv: int = 4
    ssm_head_dim: int = 64
    # RG-LRU geometry (recurrentgemma)
    rglru_block_width: int = 0       # d_rnn; 0 -> d_model
    # pad query heads up to this count (zero-initialized heads) so the head
    # axis divides the TP mesh — trades +pad/H attention FLOPs for sharded
    # S^2 score tensors (deepseek 56H -> 64H on a 16-way axis). 0 = off.
    pad_heads_to: int = 0
    # modality frontend stub: inputs arrive as precomputed embeddings
    frontend: Optional[str] = None   # None | "vit_stub" | "encodec_stub"
    norm: str = "rmsnorm"
    act: str = "silu"
    tie_embeddings: bool = False
    rope_theta: float = 10_000.0
    dtype: str = "bfloat16"
    # which shape names this arch supports; long_500k only for O(1)-state archs
    supports_long_context: bool = False

    # ---- derived ----
    @property
    def heads_eff(self) -> int:
        return max(self.num_heads, self.pad_heads_to)

    @property
    def q_dim(self) -> int:
        return self.num_heads * self.head_dim

    @property
    def kv_dim(self) -> int:
        return self.num_kv_heads * self.head_dim

    @property
    def ssm_d_inner(self) -> int:
        return self.ssm_expand * self.d_model

    @property
    def ssm_heads(self) -> int:
        return self.ssm_d_inner // self.ssm_head_dim if self.ssm_head_dim else 0

    def layer_kinds(self) -> Tuple[str, ...]:
        """The concrete kind of each of the num_layers layers."""
        p = self.pattern
        return tuple(p[i % len(p)] for i in range(self.num_layers))

    def n_superblocks(self) -> int:
        assert self.num_layers % len(self.pattern) == 0, (
            f"{self.name}: num_layers {self.num_layers} not divisible by "
            f"pattern period {len(self.pattern)}")
        return self.num_layers // len(self.pattern)

    def with_(self, **kw) -> "ModelConfig":
        return dataclasses.replace(self, **kw)

    # rough parameter counts (used for roofline MODEL_FLOPS and memory budgets)
    def param_count(self, active_only: bool = False) -> int:
        d, L = self.d_model, self.num_layers
        total = self.vocab_size * d  # embedding
        if not self.tie_embeddings:
            total += self.vocab_size * d  # lm head
        kinds = self.layer_kinds()
        for i, k in enumerate(kinds):
            moe = self.moe if i >= self.first_dense else None
            if k in ("attn", "local_attn") and self.mla is not None:
                a, h = self.mla, self.num_heads
                total += (d * self.q_dim + d * a.latent + a.kv_lora_rank
                          + a.kv_lora_rank * h * (a.nope_dim + a.v_dim)
                          + h * a.v_dim * d)
            elif k in ("attn", "local_attn"):
                total += d * self.q_dim + 2 * d * self.kv_dim + self.q_dim * d
            elif k == "rglru":
                w = self.rglru_block_width or self.d_model
                # in/out proj + gates + recurrence params
                total += 2 * d * w + 3 * w * w // 1 + 2 * w
            elif k == "ssd":
                di = self.ssm_d_inner
                # in_proj (x,z,B,C,dt) + out_proj + conv + A,D
                nbc = 2 * self.ssm_state
                total += d * (2 * di + nbc + self.ssm_heads) + di * d
                total += self.ssm_conv * (di + nbc) + 2 * self.ssm_heads
            # MLP
            if k != "ssd" and self.d_ff:
                if moe is not None and moe.router == "sigmoid_bias":
                    m = moe
                    e = m.top_k if active_only else m.held
                    total += (e * 3 * d * (m.d_expert or self.d_ff)
                              + 3 * d * m.d_shared + (d + 1) * m.num_experts)
                elif moe is not None:
                    e = moe.top_k if active_only else moe.num_experts
                    total += e * (3 * d * self.d_ff) + d * moe.num_experts
                else:
                    total += 3 * d * self.d_ff
            total += 2 * d  # norms
        return total


# --------------------------------------------------------------------------
# Paper's own CNNs (VGG-16 / ResNet-18 / ResNet-34 on CIFAR-10 & ImageNet)
# --------------------------------------------------------------------------

@dataclass(frozen=True)
class ConvSpec:
    kind: str            # "conv" | "pool" | "fc"
    out_ch: int = 0
    kernel: int = 3
    stride: int = 1
    residual: bool = False   # start of a residual block (resnets)


@dataclass(frozen=True)
class CNNConfig:
    name: str
    stages: Tuple[ConvSpec, ...]
    num_classes: int = 10
    img_size: int = 32      # CIFAR-10 for security eval; 224 for traffic model
    in_ch: int = 3

    def with_(self, **kw) -> "CNNConfig":
        return dataclasses.replace(self, **kw)


# --------------------------------------------------------------------------
# Shapes (assigned input-shape set, same four for every LM arch)
# --------------------------------------------------------------------------

@dataclass(frozen=True)
class ShapeConfig:
    name: str
    kind: str          # "train" | "prefill" | "decode"
    seq_len: int
    global_batch: int


SHAPES = {
    "train_4k":    ShapeConfig("train_4k", "train", 4_096, 256),
    "prefill_32k": ShapeConfig("prefill_32k", "prefill", 32_768, 32),
    "decode_32k":  ShapeConfig("decode_32k", "decode", 32_768, 128),
    "long_500k":   ShapeConfig("long_500k", "decode", 524_288, 1),
}


def cell_supported(model: ModelConfig, shape: ShapeConfig) -> Tuple[bool, str]:
    """Whether (arch x shape) is a runnable cell; reason when skipped."""
    if shape.name == "long_500k" and not model.supports_long_context:
        return False, ("full-attention KV cache is unbounded at 500k; run only "
                       "for SSM/hybrid archs (DESIGN.md §4)")
    return True, ""


# --------------------------------------------------------------------------
# SEAL
# --------------------------------------------------------------------------

@dataclass(frozen=True)
class SealConfig:
    """Configuration of the paper's technique.

    mode:
      none    — insecure baseline (paper's Baseline)
      direct  — full direct encryption (paper's Direct)
      counter — counter-mode w/ separate counter stream (paper's Counter)
      coloe   — colocation-mode (paper's ColoE)
    smart_ratio: fraction of kernel rows encrypted (1.0 = full encryption,
      paper's SE default is 0.5). Only meaningful when mode != none.
    cipher: "chacha20" (TPU-native production) | "aes128" (reference oracle)
    fuse_decrypt: beyond-paper — decrypt inside the consumer matmul kernel.
    verify: beyond-paper — co-locate a truncated Carter–Wegman MAC with the
      counter metadata of every sealed unit and check it at every unseal
      site (GuardNN/Seculator-style integrity on top of confidentiality).
    """
    mode: str = "coloe"
    smart_ratio: float = 0.5
    cipher: str = "chacha20"
    fuse_decrypt: bool = True
    verify: bool = False
    # layers always fully encrypted regardless of ratio (paper §3.4.1: first
    # two conv layers, last conv, last FC)
    protect_boundary_layers: bool = True


# --------------------------------------------------------------------------
# Mesh / run
# --------------------------------------------------------------------------

@dataclass(frozen=True)
class MeshConfig:
    data: int = 16
    model: int = 16
    pod: int = 1

    @property
    def n_devices(self) -> int:
        return self.data * self.model * self.pod

    def axis_names(self) -> Tuple[str, ...]:
        return ("pod", "data", "model") if self.pod > 1 else ("data", "model")

    def shape(self) -> Tuple[int, ...]:
        return ((self.pod, self.data, self.model) if self.pod > 1
                else (self.data, self.model))


@dataclass(frozen=True)
class TrainConfig:
    learning_rate: float = 3e-4
    warmup_steps: int = 100
    total_steps: int = 1_000
    weight_decay: float = 0.1
    b1: float = 0.9
    b2: float = 0.95
    eps: float = 1e-8
    grad_clip: float = 1.0
    microbatches: int = 1            # gradient accumulation factor
    remat: str = "save_carries"      # none | save_carries | full
    grad_compress_pod: bool = False  # int8 EF compression on the pod axis
    seed: int = 0
    checkpoint_every: int = 200
    checkpoint_dir: str = "/tmp/repro_ckpt"
    async_checkpoint: bool = True


@dataclass(frozen=True)
class RunConfig:
    model: ModelConfig
    shape: ShapeConfig
    mesh: MeshConfig = MeshConfig()
    seal: SealConfig = SealConfig()
    train: TrainConfig = TrainConfig()


# Published per-chip peaks, keyed by ``jax.Device.device_kind``.
# Source: Google Cloud documentation, "TPU v5e" (system architecture):
# 197 TFLOP/s bf16, 16 GiB HBM at 819 GB/s, 1,600 Gbit/s of inter-chip
# interconnect (= 4 links x 50 GB/s).
HW = {
    "TPU v5 lite": {
        "peak_flops_bf16": 197e12,   # FLOP/s
        "hbm_bw": 819e9,             # B/s
        "ici_bw": 50e9,              # B/s per link
        "hbm_bytes": 16 * 2**30,
    },
}


def hw_peaks(device_kind: str) -> dict:
    """Peaks of one chip of ``device_kind``; a chip not in ``HW`` is an
    error, never a default."""
    try:
        return HW[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; known: {sorted(HW)}") from None

# Paper's modeled GPU constants (GTX480-class) for the analytic perfmodel
PAPER_GPU = {
    "gddr_bw": 177.4e9,          # 384-bit * 3696 MT/s
    "aes_bw_per_engine": 8e9,    # state-of-the-art pipelined AES engine
    "n_mem_controllers": 6,
    "line_bytes": 128,
    "counter_bytes": 8,
    "ctr_cache_hit": {1536: 0.98, 384: 0.78, 96: 0.67, 24: 0.55},  # KB -> hit
}
