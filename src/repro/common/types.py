"""Config system: model / shape / mesh / pool / train / serve configs.

Everything is a frozen dataclass so configs hash and can be closed over by
``jax.jit`` as static arguments.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Optional, Tuple


# ---------------------------------------------------------------------------
# Model architecture.
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class MoEConfig:
    num_experts: int = 0
    top_k: int = 0
    expert_d_ff: int = 0
    dense_residual: bool = False       # arctic: dense MLP in parallel with experts
    dense_d_ff: int = 0                # width of the parallel dense residual MLP
    router_jitter: float = 0.0
    load_balance_coef: float = 0.01
    # DeepSeek-V2: shared experts every token passes through, as one SwiGLU
    # of width num_shared_experts * expert_d_ff beside the routed ones
    num_shared_experts: int = 0
    norm_topk_prob: bool = True        # renormalise the top-k weights to 1
    routed_scaling_factor: float = 1.0


@dataclass(frozen=True)
class YaRNConfig:
    """YaRN RoPE scaling (DeepSeek-V2 ``rope_scaling`` of type "yarn"):
    frequencies blended between extrapolation and interpolation by a ramp
    over the rotary dims, and the attention scale times ``mscale**2``."""
    factor: float = 40.0
    original_max_position_embeddings: int = 4096
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 0.707
    mscale_all_dim: float = 0.707


@dataclass(frozen=True)
class MLAConfig:
    """Multi-head latent attention (MiniCPM3 / DeepSeek-V2 style).
    ``q_lora_rank`` 0: the query is one full projection (no q-LoRA)."""
    q_lora_rank: int = 768
    kv_lora_rank: int = 256
    qk_nope_head_dim: int = 64
    qk_rope_head_dim: int = 32
    v_head_dim: int = 64
    yarn: Optional[YaRNConfig] = None  # RoPE of the rope dims (None: plain)


@dataclass(frozen=True)
class SSMConfig:
    """Mamba1 (falcon-mamba) / Mamba2 (zamba2) configuration."""
    kind: str = "mamba1"               # "mamba1" | "mamba2"
    d_state: int = 16
    d_conv: int = 4
    expand: int = 2
    headdim: int = 64                  # mamba2 head dim
    ngroups: int = 1                   # mamba2 B/C groups
    chunk: int = 128                   # scan chunk for chunked (SSD) form


@dataclass(frozen=True)
class ModelConfig:
    name: str = "model"
    family: str = "dense"              # dense | moe | ssm | hybrid | vlm | audio
    num_layers: int = 2
    d_model: int = 256
    num_heads: int = 4
    num_kv_heads: int = 4
    head_dim: int = 0                  # 0 -> d_model // num_heads
    d_ff: int = 1024
    vocab_size: int = 1024
    max_seq_len: int = 8192
    rope_theta: float = 10000.0
    norm_eps: float = 1e-5
    tie_embeddings: bool = False
    attn_kind: str = "gqa"             # gqa | mla | none
    moe: Optional[MoEConfig] = None
    # moe family: the first first_k_dense layers carry a dense MLP of width
    # d_ff in place of the experts (DeepSeek-V2 first_k_dense_replace)
    first_k_dense: int = 0
    mla: Optional[MLAConfig] = None
    ssm: Optional[SSMConfig] = None
    # hybrid layout: every `attn_period` layers, an attention block is applied,
    # sharing weights among `attn_shared_blocks` alternating shared blocks (zamba2).
    attn_period: int = 0
    attn_shared_blocks: int = 2
    # modality frontend stub: "none" | "vq_image" | "encodec_audio"
    frontend: str = "none"
    dtype: str = "bfloat16"
    # training-time knobs
    remat: bool = True
    scan_layers: bool = True

    @property
    def resolved_head_dim(self) -> int:
        return self.head_dim or self.d_model // self.num_heads

    @property
    def is_attention_free(self) -> bool:
        return self.family == "ssm"

    @property
    def sub_quadratic(self) -> bool:
        return self.family in ("ssm", "hybrid")

    def param_count(self) -> int:
        """Approximate parameter count N (for MODEL_FLOPS = 6*N*D)."""
        d, v, L = self.d_model, self.vocab_size, self.num_layers
        hd = self.resolved_head_dim
        n = v * d  # embeddings
        if not self.tie_embeddings:
            n += v * d
        per_layer = 0
        if self.family == "ssm":
            ssm = self.ssm or SSMConfig()
            d_in = ssm.expand * d
            # in_proj (x,z), conv, x_proj (dt,B,C), dt_proj, out_proj, A,D
            per_layer = d * (2 * d_in) + d_in * ssm.d_conv + \
                d_in * (ssm.d_state * 2 + d_in // 16) + (d_in // 16) * d_in + \
                d_in * d + d_in * ssm.d_state + d_in
            n += L * per_layer
            return n
        # attention
        if self.attn_kind == "mla" and self.mla is not None:
            m = self.mla
            qk_dim = m.qk_nope_head_dim + m.qk_rope_head_dim
            q = (d * m.q_lora_rank + m.q_lora_rank * self.num_heads * qk_dim
                 if m.q_lora_rank else d * self.num_heads * qk_dim)
            attn = (q + d * (m.kv_lora_rank + m.qk_rope_head_dim)
                    + m.kv_lora_rank * self.num_heads * (m.qk_nope_head_dim + m.v_head_dim)
                    + self.num_heads * m.v_head_dim * d)
        else:
            attn = d * self.num_heads * hd + 2 * d * self.num_kv_heads * hd \
                + self.num_heads * hd * d
        # mlp
        if self.family in ("moe",) and self.moe is not None:
            mo = self.moe
            mlp = (mo.num_experts + mo.num_shared_experts) * 3 * d * \
                mo.expert_d_ff + d * mo.num_experts
            if mo.dense_residual:
                mlp += 3 * d * mo.dense_d_ff
        else:
            mlp = 3 * d * self.d_ff
        if self.family == "hybrid":
            # mamba layers carry no MLP; only shared attention blocks do
            ssm = self.ssm or SSMConfig(kind="mamba2")
            d_in = ssm.expand * d
            nheads = d_in // ssm.headdim
            ssm_layer = d * (2 * d_in + 2 * ssm.ngroups * ssm.d_state + nheads) \
                + d_in * ssm.d_conv + d_in * d + nheads
            n_attn_uses = L // max(self.attn_period, 1) if self.attn_period else 0
            n += L * ssm_layer + min(self.attn_shared_blocks, max(n_attn_uses, 1)) * (attn + mlp)
            return n
        k = self.first_k_dense
        n += L * attn + (L - k) * mlp + k * 3 * d * self.d_ff
        return n

    def active_param_count(self) -> int:
        """Active params per token (MoE: only top-k experts count)."""
        if self.family != "moe" or self.moe is None:
            return self.param_count()
        mo = self.moe
        d, L = self.d_model, self.num_layers - self.first_k_dense
        total = self.param_count()
        all_experts = L * mo.num_experts * 3 * d * mo.expert_d_ff
        active_experts = L * mo.top_k * 3 * d * mo.expert_d_ff
        return total - all_experts + active_experts


# ---------------------------------------------------------------------------
# Input shapes (the assigned 4 shapes).
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class ShapeConfig:
    name: str
    seq_len: int
    global_batch: int
    kind: str                          # "train" | "prefill" | "decode"


TRAIN_4K = ShapeConfig("train_4k", 4096, 256, "train")
PREFILL_32K = ShapeConfig("prefill_32k", 32768, 32, "prefill")
DECODE_32K = ShapeConfig("decode_32k", 32768, 128, "decode")
LONG_500K = ShapeConfig("long_500k", 524288, 1, "decode")
ALL_SHAPES = (TRAIN_4K, PREFILL_32K, DECODE_32K, LONG_500K)
SHAPES_BY_NAME = {s.name: s for s in ALL_SHAPES}


# ---------------------------------------------------------------------------
# Mesh / distribution.
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class MeshConfig:
    shape: Tuple[int, ...] = (16, 16)
    axes: Tuple[str, ...] = ("data", "model")
    # logical -> physical axis rules; see common/sharding.py
    pipeline_stages: int = 0           # >0: map "pod" axis to pipeline stages

    @property
    def num_devices(self) -> int:
        n = 1
        for s in self.shape:
            n *= s
        return n


# ---------------------------------------------------------------------------
# IBEX pool configuration (Layer A / B).
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class PoolConfig:
    """Configuration of the IBEX compressed-memory pool.

    Paper constants (§4): 4KB page, 1KB block (co-location: 4/page), 512B
    C-chunk, 4KB P-chunk, 128B size quanta, 32B metadata entries, wr_cntr
    threshold 16, demotion watermark 256 free P-chunks.
    """
    n_pages: int = 1024                # logical (OSPA) pages tracked
    n_cchunks: int = 4096              # 512B chunks in compressed region
    n_pchunks: int = 256               # 4KB chunks in promoted region
    page_bytes: int = 4096
    block_bytes: int = 1024
    chunk_bytes: int = 512
    quantum_bytes: int = 128
    mcache_sets: int = 128             # 16-way 96KB-equivalent model: sets*ways entries
    mcache_ways: int = 16
    wr_thresh: int = 16
    demote_watermark: int = 8
    # scheme toggles (paper ablation S/C/M):
    shadow: bool = True                # shadowed promotion (§4.5)
    coloc: bool = True                 # block co-location (§4.6)
    compact: bool = True               # metadata compaction (§4.7)
    zero_elision: bool = True
    store_payload: bool = True         # Layer A carries real bytes; simx does not
    # background-demotion cadence of the batched front-end (engine/batch.py):
    # "window" (default) tops up the free-P-chunk list once per window to a
    # raised target; "access" reproduces the serial engine's per-access
    # cadence (top up to the bare watermark each window AND re-check before
    # every slow access) so small-pool configs — where the watermark is a
    # large fraction of the promoted region and cadence visibly shifts
    # traffic — can be compared serial-vs-batched tightly
    # (tests/test_simx_schemes.py::test_small_pool_cadence_knob_bounds_divergence)
    demote_cadence: str = "window"     # "window" | "access"
    # quantization tolerances for the rate-adaptive compressor (relative to
    # block amax; int8 of bf16 data carries ~0.4% inherent rounding)
    tol4: float = 0.10
    tol8: float = 0.01
    lossless: bool = False             # exact roundtrip required for 4/8-bit rates
    # compression engine implementation: "auto" runs the fused Pallas kernels
    # on TPU and the bit-identical jnp oracle elsewhere; "kernel"/"jnp" force
    # a path (core/compressor.py::resolve_impl)
    compress_impl: str = "auto"
    # batched multi-victim demotion ("auto" follows compress_impl resolution;
    # "on"/"off" force) — core/engine/ops.py::demote_batch
    fused_demote: str = "auto"

    @property
    def blocks_per_page(self) -> int:
        return self.page_bytes // self.block_bytes

    @property
    def chunks_per_page(self) -> int:
        return self.page_bytes // self.chunk_bytes

    @property
    def quanta_per_block(self) -> int:
        return self.block_bytes // self.quantum_bytes

    @property
    def vals_per_block(self) -> int:
        return self.block_bytes // 2   # bf16 values

    @property
    def vals_per_page(self) -> int:
        return self.page_bytes // 2


# ---------------------------------------------------------------------------
# Train / serve configs.
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class OptimizerConfig:
    name: str = "adamw"
    lr: float = 3e-4
    b1: float = 0.9
    b2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.1
    grad_clip: float = 1.0
    warmup_steps: int = 100
    # IBEX-compressed optimizer state (block-quantized moments)
    compress_state: bool = False
    state_block: int = 512
    # moment dtype for the uncompressed path ("float32" | "bfloat16");
    # bfloat16 halves optimizer HBM at scale while staying shard-aligned
    moment_dtype: str = "float32"
    # error-feedback int8 gradient compression for the DP all-reduce
    compress_grads: bool = False


@dataclass(frozen=True)
class TrainConfig:
    steps: int = 100
    seq_len: int = 512
    global_batch: int = 8
    microbatches: int = 1
    optimizer: OptimizerConfig = field(default_factory=OptimizerConfig)
    checkpoint_every: int = 50
    checkpoint_dir: str = "/tmp/repro_ckpt"
    keep_checkpoints: int = 3
    seed: int = 0
    log_every: int = 10


@dataclass(frozen=True)
class ServeConfig:
    max_running: int = 8               # concurrently decoding requests
    max_resident: int = 32             # requests resident in the KV pool
    page_tokens: int = 64              # tokens per KV page
    max_pages_per_seq: int = 64
    kv_rate_bits: int = 4              # compressed-pool KV rate (4 or 8)
    hot_window: int = 256              # uncompressed recent-token window (the
                                       # promoted region of the KV pool)
    attn_chunk: int = 2048             # kv chunk for the decode attention scan
    # most prompt tokens (rows x bucket) in one prefill call; 0: a bucket's
    # whole admission group is one call
    prefill_budget: int = 0
    fused_dequant_attention: bool = True  # False = paper-faithful promote-then-read
    # fabric-aware serving: lanes are striped across this many expanders;
    # preempted payloads park per-expander and victim selection balances
    # parked load across expanders (serve/engine.py, fabric/)
    n_expanders: int = 1
    # KV lane quantization implementation ("auto"/"kernel"/"jnp"), resolved
    # by core/compressor.py::quantize_blocks_fast at trace time
    quantize_impl: str = "auto"
    pool: PoolConfig = field(default_factory=PoolConfig)


def replace(cfg, **kw):
    return dataclasses.replace(cfg, **kw)
