"""Config dataclasses for models, FNO, shapes, and training runs.

Every assigned architecture is expressed as a single frozen ``ModelConfig``;
the unified transformer in ``repro.models.transformer`` interprets it. FNO
models (the paper's own architecture) use ``FNOConfig`` and are built by
``repro.core.fno``.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Optional, Tuple


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    """Unified LM-family architecture description."""

    name: str
    family: str  # dense | moe | ssm | hybrid | encoder | vlm
    num_layers: int
    d_model: int
    num_heads: int
    num_kv_heads: int
    head_dim: int
    d_ff: int
    vocab_size: int

    # --- attention ---
    attention: str = "full"  # full | swa | local_global | bidirectional | none
    window_size: int = 0  # for swa / local layers of local_global
    local_per_global: int = 0  # local_global: N local layers per global layer
    qkv_bias: bool = False
    logit_softcap: float = 0.0

    # --- positional encoding ---
    rope_style: str = "full"  # full | partial | none
    rope_fraction: float = 1.0  # fraction of head_dim rotated (partial/2d RoPE)
    rope_base: float = 10000.0

    # --- mlp / norm ---
    mlp: str = "swiglu"  # swiglu | geglu | gelu | relu2
    norm: str = "rmsnorm"  # rmsnorm | layernorm
    tie_embeddings: bool = False

    # --- MoE ---
    num_experts: int = 0
    top_k: int = 0
    dense_residual: bool = False  # Arctic: dense FFN in parallel with MoE
    capacity_factor: float = 1.25

    # --- SSM (Mamba2/SSD) ---
    ssm_state: int = 0
    ssm_expand: int = 2
    ssm_head_dim: int = 64
    ssm_chunk: int = 128
    ssm_conv_width: int = 4

    # --- hybrid (Hymba) ---
    hybrid_parallel: bool = False  # attention and SSM heads in parallel per layer
    global_layers: Tuple[int, ...] = ()  # layer indices using full attention

    # --- modality frontend (stub: input_specs provides embeddings) ---
    frontend: str = "none"  # none | audio | vision
    num_prefix_embeds: int = 0  # VLM: patch embeddings prepended to tokens

    dtype: str = "bfloat16"

    # ------------------------------------------------------------------
    @property
    def d_attn(self) -> int:
        return self.num_heads * self.head_dim

    @property
    def d_kv(self) -> int:
        return self.num_kv_heads * self.head_dim

    @property
    def d_inner(self) -> int:
        """SSM inner width."""
        return self.ssm_expand * self.d_model

    @property
    def ssm_heads(self) -> int:
        return self.d_inner // self.ssm_head_dim if self.ssm_state else 0

    @property
    def is_decoder(self) -> bool:
        return self.attention != "bidirectional"

    @property
    def has_attention(self) -> bool:
        return self.attention != "none"

    @property
    def has_ssm(self) -> bool:
        return self.ssm_state > 0

    @property
    def sub_quadratic(self) -> bool:
        """True when seq-len scaling is sub-quadratic (SSM / windowed attn)."""
        if not self.has_attention:
            return True
        return self.attention in ("swa", "local_global") or self.hybrid_parallel

    # -- parameter counting (used for MODEL_FLOPS = 6*N*D) -------------
    def param_count(self, active_only: bool = False) -> int:
        d, f = self.d_model, self.d_ff
        emb = self.vocab_size * d
        per_layer = 0
        if self.has_attention:
            per_layer += d * self.d_attn + 2 * d * self.d_kv  # QKV
            per_layer += self.d_attn * d  # O
            if self.qkv_bias:
                per_layer += self.d_attn + 2 * self.d_kv
        if self.has_ssm:
            di = self.d_inner
            per_layer += d * 2 * di  # in_proj (x, z)
            per_layer += d * 2 * self.ssm_state  # B, C proj (ngroups=1, MQA-like)
            per_layer += d * self.ssm_heads  # dt proj
            per_layer += di * self.ssm_conv_width  # depthwise conv
            per_layer += di * d  # out proj
            per_layer += 2 * self.ssm_heads  # A_log, D
        # MLP
        gated = self.mlp in ("swiglu", "geglu")
        mlp_p = d * f * (3 if gated else 2)
        if self.num_experts:
            experts = self.top_k if active_only else self.num_experts
            per_layer += experts * mlp_p + d * self.num_experts  # + router
            if self.dense_residual:
                per_layer += mlp_p
        elif f > 0:
            per_layer += mlp_p
        per_layer += 2 * d  # norms
        total = emb + self.num_layers * per_layer + d
        if not self.tie_embeddings:
            total += self.vocab_size * d  # lm head
        return total

    def validate(self) -> None:
        assert self.d_model > 0 and self.num_layers > 0
        if self.has_attention:
            assert self.num_heads % max(self.num_kv_heads, 1) == 0, self.name
        if self.num_experts:
            assert 0 < self.top_k <= self.num_experts
        if self.has_ssm:
            assert self.d_inner % self.ssm_head_dim == 0, (
                f"{self.name}: d_inner={self.d_inner} not divisible by "
                f"ssm_head_dim={self.ssm_head_dim}")
        if self.attention == "local_global":
            assert self.local_per_global > 0 and self.window_size > 0
        if self.attention == "swa":
            assert self.window_size > 0


@dataclasses.dataclass(frozen=True)
class PrecisionPolicy:
    """One precision policy for the whole spectral stack.

    Every boundary that used to assume ``jnp.float32`` consumes this object
    instead — configs own the presets, ``core/fno.py`` applies the
    param/compute casts, ``kernels/ops.py``/``kernels/engine.py`` honor the
    spectral-operand and accumulator dtypes, and ``train/train_step.py``
    takes the grad-accumulation dtype. Cast ownership (ROADMAP.md
    §Precision policy):

      * ``param_dtype``    — master-parameter storage (init + AdamW update).
      * ``compute_dtype``  — activation / kernel I/O dtype; ``apply_fno``
        casts once at the top, the fused layers cast their operands inside
        the custom_vjp so cotangents leave at the *primal* dtypes.
      * ``spectral_dtype`` — the DFT operand matrices (the bundles cached
        in ``core/spectral.py``, keyed on this dtype).
      * ``accum_dtype``    — MXU/VMEM accumulators in the Pallas engine
        (stays f32 under the bf16 preset: casts happen only at ref-write
        boundaries).
      * ``grad_acc_dtype`` — microbatch gradient-accumulation buffer.
    """

    param_dtype: str = "float32"
    compute_dtype: str = "float32"
    spectral_dtype: str = "float32"
    accum_dtype: str = "float32"
    grad_acc_dtype: str = "float32"

    _ALIASES = {"f32": "float32", "float32": "float32",
                "bf16": "bfloat16", "bfloat16": "bfloat16"}

    @classmethod
    def from_name(cls, name: str) -> "PrecisionPolicy":
        """Presets: "f32"/"float32" → pure f32; "bf16"/"bfloat16" → bf16
        compute + spectral operands, f32 master params / accumulators /
        grad accumulation (standard mixed-precision training).

        Any other dtype name falls back to a uniform policy at that dtype
        (params, compute, and spectral operands all at `name`; f32
        accumulation) — preserving the historical ``FNOConfig.dtype``
        contract for e.g. "float64"; the name is validated when the dtype
        is first used."""
        canon = cls._ALIASES.get(name)
        if canon is None:
            return cls(param_dtype=name, compute_dtype=name,
                       spectral_dtype=name)
        if canon == "float32":
            return cls()
        return cls(param_dtype="float32", compute_dtype="bfloat16",
                   spectral_dtype="bfloat16", accum_dtype="float32",
                   grad_acc_dtype="float32")

    @property
    def is_mixed(self) -> bool:
        return self.compute_dtype != self.param_dtype


def kept_modes(modes, truncation: str = "low") -> Tuple[int, ...]:
    """Kept spectral bins per axis: ``modes`` itself under "low" (the
    first k of every axis, TurboFNO); under "corners" 2k on every complex
    axis (the k lowest and k highest bins, Li et al.) and k on the last,
    the real-input axis. The spectral weight and every DFT operand are
    sized by these counts."""
    modes = tuple(int(m) for m in modes)
    if truncation == "low":
        return modes
    assert truncation == "corners", truncation
    return tuple(2 * m for m in modes[:-1]) + modes[-1:]


@dataclasses.dataclass(frozen=True)
class FNOConfig:
    """Fourier Neural Operator configuration (the paper's architecture)."""

    name: str
    ndim: int  # 1, 2, or 3
    hidden: int  # HiddenDim (channels)
    num_layers: int
    in_channels: int
    out_channels: int
    spatial: Tuple[int, ...]  # (N,), (X, Y), or (X, Y, Z)
    modes: Tuple[int, ...]  # modes per spatial axis (per corner: truncation)
    weight_mode: str = "shared"  # shared (paper CGEMM) | per_mode (classic FNO)
    lifting_dim: int = 0  # 0 => 2*hidden
    # Which spectral bins a block keeps (docs/DESIGN.md §3.4): "low", the
    # first k of every axis (TurboFNO), or "corners", the k lowest and k
    # highest of every complex axis and the first k of the last (Li et
    # al.'s FNO). ``kept_modes`` gives the counts.
    truncation: str = "low"
    # The blocks' activation, also the one inside the lifting and
    # projection MLPs: "gelu" (tanh form) or "relu" (Li et al.).
    activation: str = "gelu"
    # False leaves the last block unactivated (Li et al.'s FNO-3D).
    act_last: bool = True
    # 2: lift = lift2(act(lift1(x))), through lifting_dim; 1: one linear
    # layer in_channels -> hidden (Li et al.'s fc0), and lifting_dim is
    # the projection's width alone.
    lift_layers: int = 2
    path: str = "xla"  # ref | xla | pallas
    dtype: str = "float32"  # precision preset name (PrecisionPolicy.from_name)
    policy: Optional[PrecisionPolicy] = None  # explicit override of `dtype`
    # Whole-block fusion on the pallas path: spectral + 1x1 bypass + bias +
    # GELU in ONE pallas_call per layer (kernels/ops.fno_block_nd). The
    # ref/xla paths ignore it and stay the staged parity oracle.
    fuse_block: bool = False
    # Explicit (bb, bo, bh) launch-plan override for the pallas kernels.
    # None (the default) lets ``repro.tuning.resolve_block_plan`` pick the
    # tuned-cache winner (fallback: plans._BLOCK_DEFAULTS). A component of 0
    # keeps the resolved value for that axis. See configs.fno.with_block_plan.
    block_plan: Optional[Tuple[int, int, int]] = None
    # TP inter-layer collective layout (docs/DESIGN.md §6). "scatter" (the
    # production default) completes each interior layer's sharded k-loop
    # with a psum_scatter that emits the NEXT layer's hidden shard directly
    # — half the collective bytes of "psum", which all-reduces every layer
    # to a replicated pre-activation (the PR-5 layout, kept as the parity/
    # fallback layout). Ignored when TP is off.
    tp_layout: str = "scatter"  # scatter | psum
    # Opt-in comm/compute overlap for the scattered layout: the interior
    # reduce-scatter runs as a ppermute ring (tp-1 chunk hops), whose
    # async collective-permute steps XLA can hide under the neighboring
    # layers' k-loop compute. Same math, same sharding — smoke-checked by
    # scripts/overlap_smoke.py against the one-shot psum_scatter.
    tp_overlap: bool = False
    # Fold the lifting MLP into the FIRST fused block kernel and the
    # projection MLP into the LAST one (engine prologue/epilogue operands)
    # so the non-spectral ends stop round-tripping HBM. Pallas path with
    # fuse_block only; under TP the ends stay staged (the final psum +
    # nonlinearity sit between the last k-loop and the projection — see
    # DESIGN.md §6) and this flag is ignored.
    fuse_ends: bool = False

    @property
    def precision(self) -> PrecisionPolicy:
        """The resolved precision policy (explicit `policy` wins, else the
        `dtype` preset)."""
        return self.policy or PrecisionPolicy.from_name(self.dtype)

    @property
    def kept_modes(self) -> Tuple[int, ...]:
        return kept_modes(self.modes, self.truncation)

    @property
    def truncation_ratio(self) -> Tuple[float, ...]:
        full = tuple(s // 2 + 1 for s in self.spatial)
        return tuple(m / f for m, f in zip(self.modes, full))

    def param_count(self) -> int:
        """Every weight and bias: lifting MLP (or one layer), per block the
        complex spectral weight (re+im; one per kept mode when per-mode)
        and the 1x1 bypass with its bias, projection MLP."""
        h, cin, cout = self.hidden, self.in_channels, self.out_channels
        lift = self.lifting_dim or 2 * h
        if self.lift_layers == 1:
            p = cin * h + h
        else:
            p = cin * lift + lift + lift * h + h
        per_layer = 2 * h * h
        if self.weight_mode == "per_mode":
            per_layer *= math.prod(self.kept_modes)
        per_layer += h * h + h
        p += self.num_layers * per_layer
        p += h * lift + lift + lift * cout + cout
        return p

    def validate(self) -> None:
        assert self.ndim in (1, 2, 3) and len(self.spatial) == self.ndim
        assert len(self.modes) == self.ndim
        for m, s in zip(self.modes, self.spatial):
            assert 0 < m <= s // 2, (
                f"{self.name}: modes {m} must be <= {s // 2} (Nyquist excl.)")
        assert self.truncation in ("low", "corners"), (
            f"{self.name}: truncation must be 'low' or 'corners', got "
            f"{self.truncation!r}")
        assert self.activation in ("gelu", "relu"), (
            f"{self.name}: activation must be 'gelu' or 'relu', got "
            f"{self.activation!r}")
        assert self.lift_layers in (1, 2), (
            f"{self.name}: lift_layers must be 1 or 2, got "
            f"{self.lift_layers!r}")
        assert not self.fuse_ends or (
            self.truncation == "low" and self.activation == "gelu"
            and self.act_last and self.lift_layers == 2), (
            f"{self.name}: fuse_ends folds the two-layer GELU ends of a "
            f"low-corner model into the kernel; set it off")
        if self.block_plan is not None:
            assert len(self.block_plan) == 3 and all(
                isinstance(v, int) and v >= 0 for v in self.block_plan), (
                f"{self.name}: block_plan must be 3 non-negative ints, got "
                f"{self.block_plan!r}")
        assert self.tp_layout in ("scatter", "psum"), (
            f"{self.name}: tp_layout must be 'scatter' or 'psum', got "
            f"{self.tp_layout!r}")


@dataclasses.dataclass(frozen=True)
class ShapeSpec:
    """One input-shape cell from the assignment."""

    name: str
    seq_len: int
    global_batch: int
    kind: str  # train | prefill | decode


SHAPES = {
    "train_4k": ShapeSpec("train_4k", 4096, 256, "train"),
    "prefill_32k": ShapeSpec("prefill_32k", 32768, 32, "prefill"),
    "decode_32k": ShapeSpec("decode_32k", 32768, 128, "decode"),
    "long_500k": ShapeSpec("long_500k", 524288, 1, "decode"),
}

# Reduced shapes for CPU smoke tests.
SMOKE_SHAPES = {
    "train_4k": ShapeSpec("train_4k", 64, 2, "train"),
    "prefill_32k": ShapeSpec("prefill_32k", 128, 1, "prefill"),
    "decode_32k": ShapeSpec("decode_32k", 128, 2, "decode"),
    "long_500k": ShapeSpec("long_500k", 256, 1, "decode"),
}
