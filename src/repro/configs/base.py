"""Configuration schema: model, shapes, mesh, train/serve knobs.

Every assigned architecture is expressed as a ``ModelConfig`` whose
``layer_pattern`` cycles block kinds over the depth — one composable model
framework covers dense / MoE / SSM / hybrid / VLM / enc-dec families.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Dict, Optional, Tuple

from repro.core.telemetry import GCConfig

# block kinds understood by repro.models.blocks
KINDS = ("attn", "local", "mlstm", "slstm", "rglru", "mamba2")
# kinds whose decode cache is a recurrent state, overwritten in place each
# step (not a K/V history a snapshot length can cut)
RECURRENT_KINDS = ("mlstm", "slstm", "rglru", "mamba2")


@dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                     # dense | moe | ssm | hybrid | vlm | audio
    num_layers: int
    d_model: int
    num_heads: int
    num_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: Optional[int] = None
    layer_pattern: Tuple[str, ...] = ("attn",)
    # attention
    rope: bool = True
    rope_theta: float = 10_000.0
    qkv_bias: bool = False
    attn_softcap: float = 0.0       # gemma2 attention logit softcap
    final_softcap: float = 0.0      # gemma2 final logit softcap
    local_window: int = 0           # sliding window for "local" blocks
    post_norms: bool = False        # gemma2 sandwich norms
    attn_gather_qkv: bool = False   # perf: gather hd-sharded q/k/v so the
                                    # attention core runs shard-local
    # MLP
    act: str = "silu"               # silu | gelu | geglu
    gated_mlp: bool = True          # False: classic 2-matrix FFN (starcoder2, whisper)
    # MoE
    num_experts: int = 0
    num_shared_experts: int = 0
    top_k: int = 0
    moe_capacity_factor: float = 1.25
    moe_dispatch: str = "global"    # global (baseline) | grouped (per-sequence)
    # recurrent (ssm / hybrid)
    conv_width: int = 4             # rglru temporal conv
    rnn_width: Optional[int] = None # rglru recurrent width (default d_model)
    mlstm_chunk: int = 64           # chunkwise-parallel training chunk
    proj_factor: float = 2.0        # mlstm block up-projection
    # Mamba-2 (SSD) mixer; its conv width is ``conv_width``
    mamba_heads: int = 0
    mamba_head_dim: int = 64
    mamba_d_state: int = 128
    mamba_groups: int = 1           # B/C groups shared by heads
    mamba_chunk: int = 256          # SSD chunk of the prefill scan
    # encoder-decoder (whisper)
    encoder_layers: int = 0
    encoder_tokens: int = 0         # frontend sequence length (enc input)
    # modality frontend stub (vlm / audio): precomputed embeddings arrive as
    # inputs per the brief; this is the token count they occupy
    frontend: str = "none"          # none | vit_patches | audio_frames
    frontend_tokens: int = 0
    # embeddings
    tie_embeddings: bool = True
    embed_scale: bool = False       # gemma-style sqrt(d) embedding scaling
    # granite-style multipliers; the defaults add no operation
    embed_mult: float = 1.0         # embeddings times this
    residual_mult: float = 1.0      # each sublayer's output times this
    logits_div: float = 1.0         # logits over this
    attn_scale: Optional[float] = None  # attention logit scale; 1/sqrt(hd)
    # norm
    norm_eps: float = 1e-6

    @property
    def hd(self) -> int:
        return self.head_dim or self.d_model // self.num_heads

    @property
    def mamba_inner(self) -> int:
        return self.mamba_heads * self.mamba_head_dim

    @property
    def mamba_conv_dim(self) -> int:
        """Channels of the Mamba-2 conv: x, B and C."""
        return self.mamba_inner + 2 * self.mamba_groups * self.mamba_d_state

    @property
    def pattern_repeats(self) -> int:
        return self.num_layers // len(self.layer_pattern)

    @property
    def tail_layers(self) -> int:
        return self.num_layers % len(self.layer_pattern)

    def param_count(self) -> int:
        """Analytic parameter count (for MODEL_FLOPS and sanity checks)."""
        d, hd = self.d_model, self.hd
        n_q, n_kv = self.num_heads, self.num_kv_heads
        attn = d * hd * n_q + 2 * d * hd * n_kv + n_q * hd * d
        if self.qkv_bias:
            attn += hd * (n_q + 2 * n_kv)
        mlp = (3 if self.gated_mlp else 2) * d * self.d_ff
        moe = 0
        if self.num_experts:
            moe = (self.num_experts + self.num_shared_experts) * 3 * d * self.d_ff
            moe += d * self.num_experts  # router
            mlp = 0
        rnn_w = self.rnn_width or d
        di, cd, mh = self.mamba_inner, self.mamba_conv_dim, self.mamba_heads
        # in_proj to [z, xBC, dt], conv with bias, dt_bias, A_log, D, the
        # gated norm, out_proj
        mamba = (d * (di + cd + mh) + cd * (self.conv_width + 1) + 3 * mh
                 + di + di * d)
        kind_params = {
            "attn": attn + mlp + moe,
            "local": attn + mlp + moe,
            "mlstm": int(2.5 * d * int(d * self.proj_factor)) + 4 * (int(d * self.proj_factor)) * hd,
            "slstm": 4 * d * d + 4 * d * hd + d * 2 * d + mlp * 0,
            "rglru": 2 * d * rnn_w + 2 * rnn_w + rnn_w * self.conv_width + rnn_w * d + mlp,
            "mamba2": mamba + mlp,
        }
        total = 0
        for i in range(self.num_layers):
            kind = self.layer_pattern[i % len(self.layer_pattern)]
            total += kind_params[kind]
            total += 2 * d  # norms
        total += self.vocab_size * d  # embedding
        if not self.tie_embeddings:
            total += self.vocab_size * d
        if self.encoder_layers:
            total += self.encoder_layers * (attn + mlp + 2 * d)
        return total

    def active_param_count(self) -> int:
        """Active parameters per token (MoE: top_k + shared experts only)."""
        if not self.num_experts:
            return self.param_count()
        d = self.d_model
        full_moe = (self.num_experts + self.num_shared_experts) * 3 * d * self.d_ff
        active_moe = (self.top_k + self.num_shared_experts) * 3 * d * self.d_ff
        n_moe_layers = sum(
            1 for i in range(self.num_layers)
            if self.layer_pattern[i % len(self.layer_pattern)] in ("attn", "local")
        )
        return self.param_count() - n_moe_layers * (full_moe - active_moe)


@dataclass(frozen=True)
class ShapeConfig:
    name: str                 # train_4k | prefill_32k | decode_32k | long_500k
    kind: str                 # train | prefill | decode
    seq_len: int
    global_batch: int


SHAPES: Dict[str, ShapeConfig] = {
    "train_4k": ShapeConfig("train_4k", "train", 4_096, 256),
    "prefill_32k": ShapeConfig("prefill_32k", "prefill", 32_768, 32),
    "decode_32k": ShapeConfig("decode_32k", "decode", 32_768, 128),
    "long_500k": ShapeConfig("long_500k", "decode", 524_288, 1),
}


@dataclass(frozen=True)
class RunConfig:
    """Train/serve runtime knobs."""
    model: ModelConfig
    shape: ShapeConfig
    dtype: str = "bfloat16"
    param_dtype: str = "bfloat16"
    # distribution
    fsdp: bool = False             # shard params over data axis too (ZeRO-3)
    remat: str = "block"           # none | block
    microbatches: int = 1
    # optimizer
    lr: float = 3e-4
    weight_decay: float = 0.1
    grad_clip: float = 1.0
    grad_compression: bool = False
    # serving / MVGC.  ``gc`` is the redesigned home of every GC knob
    # (repro.core.telemetry.GCConfig, DESIGN.md §13); the flat fields below
    # remain for one release as deprecated spellings.  When ``gc`` is not
    # passed, ``__post_init__`` assembles it from them, so the two views
    # never disagree — engines read ``run.gc`` only.
    gc: Optional[GCConfig] = None
    gc_policy: str = "slrt"
    versions_per_slot: int = 8
    reader_lanes: int = 16
    page_size: int = 64
    # GC sweeps / snapshot reads on the fused Pallas kernels; None = by
    # platform (core.telemetry.resolve_kernel: compiled on a TPU, lax path
    # elsewhere)
    use_kernel: Optional[bool] = None
    kernel_interpret: Optional[bool] = None
    # retire-ring capacity for the RT policies; 0 = sized from the batch.
    # Undersizing it drops retire records (surfaced as ``dropped_retires``
    # in the engine step stats) — DL-RT can never reclaim a dropped version.
    ring_capacity: int = 0

    def __post_init__(self):
        if self.gc is None:
            gc = GCConfig(
                policy=self.gc_policy,
                versions_per_slot=self.versions_per_slot,
                reader_lanes=self.reader_lanes,
                ring_capacity=self.ring_capacity,
                use_kernel=self.use_kernel,
                kernel_interpret=self.kernel_interpret,
            )
            object.__setattr__(self, "gc", gc)
        else:
            # keep the deprecated flat fields readable either way
            object.__setattr__(self, "gc_policy", self.gc.policy)
            object.__setattr__(self, "versions_per_slot",
                               self.gc.versions_per_slot)
            object.__setattr__(self, "reader_lanes", self.gc.reader_lanes)
            object.__setattr__(self, "ring_capacity", self.gc.ring_capacity)
            object.__setattr__(self, "use_kernel", self.gc.use_kernel)
            object.__setattr__(self, "kernel_interpret",
                               self.gc.kernel_interpret)


def reduced(cfg: ModelConfig, **overrides) -> ModelConfig:
    """Tiny same-family config for CPU smoke tests."""
    base = dict(
        num_layers=max(2, 2 * len(cfg.layer_pattern)) if cfg.layer_pattern else 2,
        d_model=64,
        num_heads=4,
        num_kv_heads=max(1, 4 * cfg.num_kv_heads // max(1, cfg.num_heads)),
        d_ff=128 if cfg.d_ff else 0,
        vocab_size=256,
        head_dim=16,
        num_experts=min(cfg.num_experts, 4),
        num_shared_experts=min(cfg.num_shared_experts, 1),
        top_k=min(cfg.top_k, 2),
        local_window=min(cfg.local_window, 16) if cfg.local_window else 0,
        encoder_layers=min(cfg.encoder_layers, 2),
        encoder_tokens=min(cfg.encoder_tokens, 16) if cfg.encoder_tokens else 0,
        frontend_tokens=min(cfg.frontend_tokens, 8) if cfg.frontend_tokens else 0,
        rnn_width=64 if cfg.rnn_width else None,
        mlstm_chunk=8,
    )
    if cfg.mamba_heads:
        base.update(mamba_heads=4, mamba_head_dim=16, mamba_d_state=16,
                    mamba_chunk=8)
    # keep the layer pattern but shrink repeats
    base["num_layers"] = max(len(cfg.layer_pattern), 2)
    if len(cfg.layer_pattern) == 1:
        base["num_layers"] = 2
    base.update(overrides)
    return dataclasses.replace(cfg, **base)
