"""Three-term roofline analysis from a compiled dry-run artifact.

    compute    = HLO_FLOPs_per_chip / peak_FLOP/s
    memory     = HLO_bytes_per_chip / HBM_bw
    collective = collective_bytes_per_chip / link_bw

``cost_analysis()`` on the partitioned module reports per-chip flops/bytes.
Collective bytes are NOT in cost_analysis: we parse the post-SPMD HLO text
and sum the result-shape bytes of every all-gather / all-reduce /
reduce-scatter / all-to-all / collective-permute (shapes in the partitioned
module are already per-shard).
"""
from __future__ import annotations

import dataclasses
import re
from typing import Dict, Optional, Tuple

import numpy as np

from repro.roofline import hw

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
}

_DTYPE_ALIASES = {"float32": "f32", "bfloat16": "bf16", "float16": "f16",
                  "float64": "f64"}


def dtype_bytes(name: str) -> int:
    """Bytes per element for an HLO short name OR a numpy-style name
    ("float32"/"bfloat16"), so PrecisionPolicy fields plug in directly."""
    return _DTYPE_BYTES[_DTYPE_ALIASES.get(name, name)]

_COLL_RE = re.compile(
    r"=\s*(?:\(([^)]*)\)|(\w+\[[\d,]*\][^\s]*))\s+"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\(")
_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def _shape_bytes(shape_str: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(shape_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_bytes(hlo_text: str) -> Dict[str, int]:
    """Per-chip bytes moved by each collective type (result-shape sizes)."""
    out: Dict[str, int] = {}
    for m in _COLL_RE.finditer(hlo_text):
        shape_str = m.group(1) or m.group(2)
        op = m.group(3)
        out[op] = out.get(op, 0) + _shape_bytes(shape_str)
    return out


@dataclasses.dataclass
class Roofline:
    arch: str
    shape: str
    mesh: str
    chips: int
    hlo_flops: float  # per chip
    hlo_bytes: float  # per chip
    coll_bytes: float  # per chip
    coll_detail: Dict[str, int]
    model_flops: float  # whole-step useful FLOPs (6ND etc.), global
    temp_bytes: int = 0
    arg_bytes: int = 0

    @property
    def t_compute(self) -> float:
        return self.hlo_flops / hw.PEAK_FLOPS_BF16

    @property
    def t_memory(self) -> float:
        return self.hlo_bytes / hw.HBM_BW

    @property
    def t_collective(self) -> float:
        return self.coll_bytes / hw.ICI_LINK_BW

    @property
    def bottleneck(self) -> str:
        terms = {"compute": self.t_compute, "memory": self.t_memory,
                 "collective": self.t_collective}
        return max(terms, key=terms.get)

    @property
    def t_bound(self) -> float:
        return max(self.t_compute, self.t_memory, self.t_collective)

    @property
    def useful_flop_ratio(self) -> float:
        """MODEL_FLOPS / (chips · HLO_FLOPs): remat/masking/dispatch waste."""
        denom = self.chips * self.hlo_flops
        return self.model_flops / denom if denom else 0.0

    @property
    def mfu_bound(self) -> float:
        """Model-FLOP utilization achievable at the roofline bound."""
        t = self.t_bound
        if t <= 0:
            return 0.0
        return (self.model_flops / self.chips / t) / hw.PEAK_FLOPS_BF16

    def row(self) -> str:
        d = self.coll_detail
        det = ",".join(f"{k[:2]}:{v/2**20:.0f}M" for k, v in sorted(d.items()))
        return (f"{self.arch:16s} {self.shape:12s} {self.mesh:9s} "
                f"{self.t_compute*1e3:9.2f} {self.t_memory*1e3:9.2f} "
                f"{self.t_collective*1e3:9.2f} {self.bottleneck:10s} "
                f"{self.useful_flop_ratio:7.3f} {self.mfu_bound:6.3f}  {det}")


HEADER = (f"{'arch':16s} {'shape':12s} {'mesh':9s} {'comp_ms':>9s} "
          f"{'mem_ms':>9s} {'coll_ms':>9s} {'bottleneck':10s} "
          f"{'useful':>7s} {'MFU<=':>6s}  collectives")


def analyze(compiled, *, arch: str, shape: str, mesh_name: str, chips: int,
            model_flops: float) -> Roofline:
    """Roofline terms from the compiled per-device module.

    Uses the multiplicity-aware HLO counter (roofline/hlo_counter.py):
    XLA's cost_analysis counts while-loop bodies once, understating both
    FLOPs and in-loop collective bytes for scan-over-layers models.
    """
    from repro.roofline import hlo_counter as hc
    ma = compiled.memory_analysis()
    txt = compiled.as_text()
    counts = hc.count(txt)
    coll = {k: int(v) for k, v in counts.coll_bytes.items()}
    return Roofline(
        arch=arch, shape=shape, mesh=mesh_name, chips=chips,
        hlo_flops=float(counts.flops),
        hlo_bytes=float(counts.bytes),
        coll_bytes=float(sum(coll.values())),
        coll_detail=coll,
        model_flops=model_flops,
        temp_bytes=getattr(ma, "temp_size_in_bytes", 0),
        arg_bytes=getattr(ma, "argument_size_in_bytes", 0),
    )


# ---------------------------------------------------------------------------
# MODEL_FLOPS (useful-work) estimates
# ---------------------------------------------------------------------------
def lm_model_flops(cfg, shape_kind: str, seq_len: int, global_batch: int
                   ) -> float:
    """6·N_active·tokens for train, 2·N_active·tokens for inference."""
    n_active = cfg.param_count(active_only=True)
    tokens = global_batch * (seq_len if shape_kind != "decode" else 1)
    mult = 6.0 if shape_kind == "train" else 2.0
    return mult * n_active * tokens


def fno_model_flops(cfg, batch: int, *, training: bool = True) -> float:
    """Exact useful FLOPs of the truncated-DFT FNO layer algebra
    (docs/DESIGN.md §3.3), per batch element; training=True multiplies by
    3 for fwd+bwd (train step), training=False is the serving forward.

    Rank-generic (matches the engine's stage order): each forward DFT
    stage transforms one spatial axis n_j→k_j over the pencils formed by
    the other (partially transformed) axes — 4 real-matmul FLOP factors for
    the real first stage, 8 for complex stages; CGEMM is 8·Πk·H·O; the
    inverse chain mirrors the forward with O channels.

    1D (x [H,N], modes K):   rDFT 4·H·N·K | CGEMM 8·K·H·O | irDFT 4·O·N·K
    2D (x [H,X,Y], KX,KY):   rDFT_Y 4·H·X·Y·KY | cDFT_X 8·H·KY·X·KX |
                             CGEMM 8·KX·KY·H·O | icDFT_X 8·O·KY·KX·X |
                             irDFT_Y 4·O·X·KY·Y
    """
    import math
    h = o = cfg.hidden
    sp = math.prod(cfg.spatial)
    lift = cfg.lifting_dim or 2 * h
    r = cfg.ndim
    spatial, modes = list(cfg.spatial), list(cfg.kept_modes)
    cur = list(spatial)

    def stage(ch, ax, real):
        pencils = math.prod(cur) // cur[ax]
        return (4 if real else 8) * ch * pencils * spatial[ax] * modes[ax]

    spectral = stage(h, r - 1, True)  # rDFT along s_R (real input)
    cur[r - 1] = modes[r - 1]
    for ax in range(r - 2, -1, -1):  # cDFT along s_{R-1}…s_1
        spectral += stage(h, ax, False)
        cur[ax] = modes[ax]
    spectral += 8 * math.prod(modes) * h * o  # CGEMM over hidden
    for ax in range(r - 1):  # icDFT along s_1…s_{R-1}
        spectral += stage(o, ax, False)
        cur[ax] = spatial[ax]
    spectral += stage(o, r - 1, True)  # irDFT along s_R (real output)
    if cfg.weight_mode == "per_mode":
        pass  # CGEMM term identical per mode (already counted per-mode)
    # Whole FNO block = spectral + bypass 1x1 GEMM + pointwise epilogue
    # (bias add + residual add + tanh-GELU ≈ 10 flops/elt). Fusion
    # (cfg.fuse_block) moves these into the kernel's k-loop/epilogue but
    # does not change the FLOP count — only the byte model below does.
    per_layer = spectral + 2 * sp * h * o + 12 * sp * o
    lifting = 2 * sp * (cfg.in_channels * lift + lift * h)
    proj = 2 * sp * (h * lift + lift * cfg.out_channels)
    fwd = batch * (cfg.num_layers * per_layer + lifting + proj)
    return (3.0 if training else 1.0) * fwd


def fno_model_bytes(cfg, batch: int, *, variant: str = "full",
                    training: bool = True,
                    fuse_block: bool = None) -> float:
    """Dtype-aware HBM-traffic model of one FNO step (the memory side of
    the roofline — TurboFNO's whole argument is that this term binds).

    Reads cfg.precision (PrecisionPolicy): activations and kernel I/O move
    at the compute dtype, DFT operand bundles at the spectral dtype, dW
    emissions and the AdamW master update at the param dtype — so the
    model predicts the bf16 traffic reduction directly (compute/spectral
    terms halve, master-param terms don't).

    Fused-path accounting per spectral layer: the full-fusion kernel
    touches HBM exactly once per operand (read x, read W, read operands,
    write y — the paper's fusion claim); partial fusion adds the
    inter-launch complex pairs (written once, read once, both directions
    batched into one outer launch per side at rank ≥ 3). Training adds the
    adjoint pipeline (same traffic as forward, dx at the compute dtype)
    and the fused wgrad (re-reads x and gy, writes dW at the param dtype),
    plus the f32 master AdamW update (read params + 2 moments, write all
    three, read grads).

    fuse_block (default: cfg.fuse_block) models the whole-block fusion on
    the full-fusion path: spectral + bypass + bias + GELU in one kernel,
    so the spectral-y / bypass-y / sum / activation intermediates (~4 HBM
    round trips on B·H·∏s tensors per layer, forward alone) never move;
    training keeps three fused kernels (gz recompute, dx adjoint, extended
    wgrad emitting dW + dW_b + dbias in one pass).
    """
    import math
    pol = cfg.precision
    cb = dtype_bytes(pol.compute_dtype)
    pb = dtype_bytes(pol.param_dtype)
    sb = dtype_bytes(pol.spectral_dtype)
    if fuse_block is None:
        fuse_block = getattr(cfg, "fuse_block", False)
    h = o = cfg.hidden
    sp = math.prod(cfg.spatial)
    lift = cfg.lifting_dim or 2 * h
    act = batch * h * sp  # one hidden activation tensor (elements)
    wmul = math.prod(cfg.kept_modes) if cfg.weight_mode == "per_mode" else 1
    wc = 2 * h * o * wmul  # complex spectral weight (re+im)
    byp_w = h * o + o  # bypass 1x1 weight + bias
    mats = 4 * sum(n * k for n, k in zip(cfg.spatial, cfg.kept_modes))

    spectral_fwd = (act + wc + act) * cb + mats * sb
    if variant == "partial" and cfg.ndim >= 2:
        kout = math.prod(cfg.kept_modes[1:])
        inter = 2 * batch * (h + o) * cfg.spatial[0] * kout  # complex pairs
        spectral_fwd += 2 * inter * cb  # write + re-read between launches

    if fuse_block and variant == "full":
        # ONE kernel per block: read x, spectral W, W_b + bias; write the
        # activated output once. Intermediates live only in VMEM.
        per_layer = (2 * act + wc + byp_w) * cb + mats * sb
        if training:
            # gz recompute (reads x, gy, all weights; writes gz) + dx
            # adjoint (reads gz, weights; writes dx) + ONE extended wgrad
            # (reads x, gz; writes dW, dW_b, dbias at the param dtype).
            per_layer += (3 * act + wc + byp_w) * cb + mats * sb
            per_layer += (2 * act + wc + h * o) * cb + mats * sb
            per_layer += 2 * act * cb + (wc + byp_w) * pb
    else:
        # Staged block: spectral kernel + bypass GEMM (read x, W_b + bias,
        # write y_b) + sum (read s, y_b; write z) + GELU (read z, write h).
        per_layer = (spectral_fwd + (2 * act + byp_w) * cb
                     + 3 * act * cb + 2 * act * cb)
        if training:
            # adjoint spectral + spectral wgrad + GELU vjp (read gy, z;
            # write gz) + bypass dx (read gz, W_b; write) + dW_b/dbias
            # (re-read gz, x; emit at param dtype) + cotangent sum.
            per_layer += spectral_fwd + 2 * act * cb + wc * pb
            per_layer += 3 * act * cb
            per_layer += (2 * act + h * o) * cb
            per_layer += 2 * act * cb + byp_w * pb

    io = batch * sp * (cfg.in_channels + cfg.out_channels) * cb
    lift_proj = (2 * batch * sp * (2 * lift + h)
                 + cfg.in_channels * lift + lift * h
                 + h * lift + lift * cfg.out_channels) * cb
    total = cfg.num_layers * per_layer + lift_proj + io
    if training:
        n_params = cfg.param_count()
        total += 7 * n_params * pb  # AdamW: r/w params + 2 moments, read g
    return float(total)


def fno_collective_bytes(cfg, dp: int, tp: int, *, scattered: bool = True,
                         batch: int = 8) -> Dict[str, float]:
    """Per-device ICI wire bytes of the TP collectives in one sharded FNO
    forward (the collective side of the roofline for the DP×TP serve
    path — docs/DESIGN.md §6).

    Each fused block's sharded k-loop produces per-device partial sums of
    the full hidden activation T = (batch/dp)·hidden·∏spatial·compute
    bytes. Completing them costs, per device, on a tp-device ring:

      * ``psum`` (all-reduce, the PR-5 every-layer layout):
        2·(tp-1)/tp · T — reduce-scatter + all-gather under the hood;
      * ``reduce-scatter`` (the scattered layout): (tp-1)/tp · T — the
        interior layer emits the NEXT layer's hidden shard directly and
        skips the gather half, exactly 0.5× the psum wire bytes. The
        ppermute ring (cfg.tp_overlap) moves the same bytes in tp-1
        chunk hops — overlap changes the schedule, not the traffic.

    scattered=True models cfg.tp_layout="scatter": num_layers-1 interior
    reduce-scatters + the final layer's psum (the projection consumes the
    full hidden vector, so the last layer always all-reduces).
    scattered=False models tp_layout="psum": num_layers psums.

    Mirrors the runtime's degradation rules: tp<=1 or hidden % tp != 0
    folds TP away (no collectives — ``make_context``). Returns a dict
    {"interior_per_layer", "final", "total"} so callers can surface the
    per-layer ratio directly (``FNOServer.collective_plan``).
    """
    import math
    if tp <= 1 or cfg.hidden % tp != 0:
        return {"interior_per_layer": 0.0, "final": 0.0, "total": 0.0}
    cb = dtype_bytes(cfg.precision.compute_dtype)
    t = (batch / max(dp, 1)) * cfg.hidden * math.prod(cfg.spatial) * cb
    psum = 2.0 * (tp - 1) / tp * t
    interior = ((tp - 1) / tp * t) if scattered else psum
    n_interior = max(cfg.num_layers - 1, 0)
    final = psum if cfg.num_layers > 0 else 0.0
    return {"interior_per_layer": interior, "final": final,
            "total": n_interior * interior + final}
