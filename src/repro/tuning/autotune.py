"""The block-size autotuner: generate → VMEM-prune → rank → compile → persist.

TVM/Ansor-shaped search specialized to the fused FNO engine's tiny
3-parameter launch space (docs/DESIGN.md §8). For every tuning key the
config matrix can emit (all ``FNO_IDS`` × {full, reduced} × {f32, bf16}
× launch kinds):

1. **generate** the candidate grid — bb ∈ {1, 2, 4, 8}, bo/bh ∈ {1, 2,
   …, 128}, plus the rank's static default — clamped to the probe
   dims and to Mosaic's tiling rule (``plans.legal_blocks``) and deduped
   on the effective triple;
2. **prune** statically with ``analysis.vmem.launch_estimate`` against
   ``VMEM_BUDGET_BYTES`` (the limit the kernels compile under);
3. **rank** the survivors statically (least pad waste, then the largest
   bo/bh/bb);
4. **compile** the winner of every full-size probe
   (``hidden·∏spatial > COMPILE_ELEMS``) for a described v5e where
   libtpu is installed — a plan the compiler refuses for VMEM falls
   through to the next, and the winner is flagged ``source:
   "compiled"``; elsewhere ``"estimated"``. Nothing here is timed: the
   interpreter's wall time says nothing about the chip;
5. **persist** winners + evidence (VMEM estimate, probe shapes) via
   ``store.save_cache`` — the committed ``tuning/cache/blocks.json``
   that ``resolve_launch_plans`` reads.

Entry points: :func:`tune` (library), ``scripts/autotune.py`` (CLI).
"""
from __future__ import annotations

import dataclasses
import itertools
from typing import Dict, List, Optional, Tuple

from repro.tuning import plans as P
from repro.tuning import store

# Probes over this many elements (hidden·∏spatial) — the full-size 2D/3D
# grids — have their winner compiled for a described v5e; the reduced
# configs and the full-size 1D config keep their static winner.
COMPILE_ELEMS = 131_072

_BB_GRID = (1, 2, 4, 8)
# Preferences only: plans.legal_blocks clamps every one to a plan Mosaic's
# tiling rule admits (bo a multiple of 8; bh too at rank 1) and the
# candidates dedupe on the clamped triple. bh down to 1: the full-size 3D
# grid keeps bh·∏s elements of x resident per program.
_BOH_GRID = (1, 2, 4, 8, 16, 32, 64, 128)
_PROBE_BATCH = 8
_COMPILE_TRIES = 8
# Static ranking prefers the largest batch block whose program stays under
# a quarter of the VMEM budget. A larger program buys no MXU efficiency
# (a full-width 2D slab already gives its DFT matmuls 8192 rows) and
# Mosaic's compile time grows with it: fno2d block_fwd compiled for a
# described v5e in 8.4 s at bb=1, ~20 s at bb=2, ~68 s at bb=4.
_SOFT_BUDGET_FRACTION = 4


@dataclasses.dataclass(frozen=True)
class Workload:
    """One tunable workload = one (shape class, layout, dtype) cell."""

    label: str  # e.g. "fno2d/reduced"
    rank: int
    hidden: int
    spatial: Tuple[int, ...]
    modes: Tuple[int, ...]
    per_mode: bool
    dtype: str  # "f32" | "bf16"

    @property
    def klass(self) -> str:
        return P.shape_class(self.hidden, self.hidden, self.spatial,
                             self.modes)

    @property
    def layout(self) -> str:
        return "per_mode" if self.per_mode else "shared"

    @property
    def elems(self) -> int:
        n = self.hidden
        for s in self.spatial:
            n *= s
        return n

    @property
    def launches(self) -> Tuple[str, ...]:
        # Rank 1 has no distinct core launch: partial fusion degenerates
        # to full there and the resolver aliases core → block_fwd.
        if self.rank == 1:
            return tuple(k for k in P.LAUNCH_KINDS if k != "core")
        return P.LAUNCH_KINDS

    def policy(self):
        from repro.configs.base import PrecisionPolicy
        return PrecisionPolicy.from_name(self.dtype)


def tunable_workloads(smoke: bool = False) -> List[Workload]:
    """Every (shape class, layout, dtype) the config matrix can emit,
    deduped (e.g. reduced fno2d and reduced fno2d-large share a cell).
    ``smoke`` keeps only the reduced shapes — a seconds-long CI pass."""
    from repro.configs import FNO_IDS, get_config

    out: List[Workload] = []
    seen = set()
    for arch in FNO_IDS:
        variants = [(get_config(arch, reduced=True), "reduced")]
        if not smoke:
            variants.append((get_config(arch), "full"))
        for cfg, tag in variants:
            for dtype in ("f32", "bf16"):
                w = Workload(
                    label=f"{arch}/{tag}", rank=cfg.ndim, hidden=cfg.hidden,
                    spatial=tuple(cfg.spatial), modes=cfg.kept_modes,
                    per_mode=cfg.weight_mode == "per_mode", dtype=dtype)
                cell = (w.rank, w.klass, w.layout, w.dtype)
                if cell not in seen:
                    seen.add(cell)
                    out.append(w)
    return out


def _candidates(w: Workload) -> List[Tuple[int, int, int]]:
    """Candidate grid, clamped to the probe dims and deduped on the
    effective triple (two preferences that clamp to the same launch are
    the same candidate)."""
    boh = _BOH_GRID + (w.hidden,)  # the whole dim is always a legal block
    raw = list(itertools.product(_BB_GRID, boh, boh))
    raw.append(P._BLOCK_DEFAULTS[w.rank])
    seen, out = set(), []
    for t in raw:
        eff = P.legal_blocks(w.rank, _PROBE_BATCH, w.hidden, w.hidden, *t)
        if eff not in seen:
            seen.add(eff)
            out.append(eff)
    return out


def _pad_waste(w: Workload, t: Tuple[int, int, int]) -> float:
    """Fractional compute overhead from padding each gridded dim up to a
    block multiple (bb against the probe batch; bo/bh against hidden)."""
    def frac(dim, b):
        return (-dim % b) / dim

    return (frac(_PROBE_BATCH, t[0]) + frac(w.hidden, t[1])
            + frac(w.hidden, t[2]))


def _static_rank(w: Workload, feasible):
    """Least pad waste first, then the largest bo (widest MXU output
    tile), bh (longest k-loop windows); then the largest bb (fewest batch
    launches) among programs within the soft budget, the smallest
    program beyond it."""
    from repro.analysis.vmem import VMEM_BUDGET_BYTES

    soft = VMEM_BUDGET_BYTES // _SOFT_BUDGET_FRACTION
    return sorted(feasible, key=lambda e: (
        _pad_waste(w, e[0]), -e[0][1], -e[0][2], max(e[1], soft),
        -e[0][0]))


def _feasible(w: Workload) -> Dict[str, List[Tuple[Tuple[int, int, int],
                                                   int]]]:
    """Per launch kind: (triple, est_bytes) for every candidate that
    fits the budget."""
    from repro.analysis.vmem import VMEM_BUDGET_BYTES, launch_estimate

    shapes = (w.hidden, w.spatial, w.modes, w.per_mode)
    pol = w.policy()
    out: Dict[str, List] = {}
    for launch in w.launches:
        fits = []
        for t in _candidates(w):
            est = launch_estimate(shapes, launch, t, batch=_PROBE_BATCH,
                                  policy=pol)
            if est.total_bytes <= VMEM_BUDGET_BYTES:
                fits.append((t, est.total_bytes))
        out[launch] = fits
    return out


# ---------------------------------------------------------------------------
# The probe: one launch's operands at the workload's shapes.
# ---------------------------------------------------------------------------
def _probe_arrays(w: Workload):
    import jax
    import jax.numpy as jnp

    pol = w.policy()
    cp = jnp.dtype(pol.compute_dtype)
    h, r = w.hidden, w.rank
    ks = [jax.random.PRNGKey(i) for i in range(6)]
    x = jax.random.normal(ks[0], (_PROBE_BATCH, h) + w.spatial, cp)
    wshape = (h, h) + (w.modes if w.per_mode else ())
    wr = jax.random.normal(ks[1], wshape, cp) * 0.1
    wi = jax.random.normal(ks[2], wshape, cp) * 0.1
    wb = jax.random.normal(ks[3], (h, h), cp) * 0.1
    bias = jax.random.normal(ks[4], (h,), cp) * 0.1
    gy = jax.random.normal(ks[5], x.shape, cp)
    return x, wr, wi, wb, bias, gy


def _launch(w: Workload, launch: str, triple: Tuple[int, int, int],
            interpret: bool):
    """ONE launch of the given kind as fn(x, wr, wi, wb, bias, gy) — the
    same internal entry points the custom_vjps call, so what is compiled
    is a real launch."""
    import jax.numpy as jnp

    from repro.kernels import ops

    bb, bo, bh = triple
    pol = w.policy()
    modes = w.modes
    if launch == "block_fwd":
        return lambda x, wr, wi, wb, bias, gy: ops._fnond_fused(
            x, wr, wi, modes, bb, bo, bh, interpret, pol, wb=wb, bias=bias,
            act="gelu")
    if launch == "core":
        return lambda x, wr, wi, wb, bias, gy: ops._fnond_partial(
            x, wr, wi, modes, bb, bo, bh, interpret, pol)
    if launch == "gz_recompute":
        return lambda x, wr, wi, wb, bias, gy: ops._fnond_fused(
            x, wr, wi, modes, bb, bo, bh, interpret, pol, wb=wb, bias=bias,
            gy=gy, act="gelu_vjp")
    if launch == "dx_adjoint":
        return lambda x, wr, wi, wb, bias, gy: ops._fnond_fused(
            gy, jnp.swapaxes(wr, 0, 1), jnp.swapaxes(wi, 0, 1), modes, bb,
            bo, bh, interpret, pol, adjoint=True, wb=jnp.swapaxes(wb, 0, 1))
    return lambda x, wr, wi, wb, bias, gy: ops._fnond_wgrad(
        x, gy, modes, bb, bo, bh, interpret, per_mode=w.per_mode, pol=pol,
        with_bypass=True)


# ---------------------------------------------------------------------------
# The compiler's verdict: a statically ranked winner is compiled for a
# described TPU v5e (no chip needed, only libtpu). A plan Mosaic refuses —
# most often for VMEM the estimate does not see, such as the in-kernel
# relayouts of the per-mode CGEMM — falls through to the next candidate.
# ---------------------------------------------------------------------------
def _compiled_choice(w: Workload, launch: str, fits, chip, key: str, log):
    """Walk the ranking until a plan compiles: (triple, est, source). A
    VMEM refusal moves on to the next candidate whose estimate is at most
    3/4 of the refused one's (the estimate misses the compiler's internal
    scratch, so its neighbours would be refused too); any other refusal
    is about the launch, not the plan, so the static winner stays
    ("estimated") and the refusal is logged."""
    cap, tries = float("inf"), 0
    for t, est in fits:
        if est > cap:
            continue
        why = _compile_refusal(w, launch, t, chip)
        if why is None:
            return t, est, "compiled"
        log(f"  {key}: {t} refused: {why}")
        tries += 1
        if "memory space vmem" not in why or tries == _COMPILE_TRIES:
            break
        cap = 0.75 * est
    return fits[0][0], fits[0][1], "estimated"


def _described_chip():
    """A one-device sharding on a described v5e, or None without libtpu."""
    try:
        from jax.experimental import topologies
        from jax.sharding import SingleDeviceSharding

        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception:  # noqa: BLE001 — no TPU compiler installed
        return None
    return SingleDeviceSharding(topo.devices[0])


def _compile_refusal(w: Workload, launch: str, triple, chip
                     ) -> Optional[str]:
    """None when the launch compiles for the described chip, else the
    first line of the compiler's refusal."""
    import jax

    shapes = tuple(jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=chip)
                   for a in jax.eval_shape(lambda: _probe_arrays(w)))
    try:
        jax.jit(_launch(w, launch, triple, interpret=False)).lower(
            *shapes).compile()
    except Exception as e:  # noqa: BLE001 — a refusal is a search result
        return str(e).strip().splitlines()[0][:200]
    return None


# ---------------------------------------------------------------------------
# Driver
# ---------------------------------------------------------------------------
def tune(smoke: bool = False, out: Optional[str] = None,
         log=print) -> Tuple[str, Dict[str, dict]]:
    """Run the full search and persist the cache. Statically ranked
    full-size winners are compiled for a described v5e where libtpu is
    installed (``smoke``: the reduced shapes only, nothing compiled — the
    CI mode). Returns (cache_path, entries)."""
    entries: Dict[str, dict] = {}
    chip = None if smoke else _described_chip()
    if not smoke and chip is None:
        log("  !! no v5e can be described here: full-size winners are "
            "not compile-checked")
    for w in tunable_workloads(smoke=smoke):
        feasible = _feasible(w)
        for launch in w.launches:
            key = P.plan_key(w.rank, w.klass, w.layout, w.dtype, launch)
            if key in entries:
                continue
            fits = _static_rank(w, feasible[launch])
            if not fits:
                log(f"  !! {key}: NO feasible candidate — key left to the "
                    f"static fallback")
                continue
            t, est = fits[0]
            source = "estimated"
            if chip is not None and w.elems > COMPILE_ELEMS:
                t, est, source = _compiled_choice(w, launch, fits, chip,
                                                  key, log)
            log(f"  {key}: {t} ({est / 2**20:.1f} MiB est, {source})")
            entries[key] = {"probe": {"batch": _PROBE_BATCH,
                                      "hidden": w.hidden,
                                      "spatial": list(w.spatial),
                                      "modes": list(w.modes)},
                            "workload": w.label,
                            "bb": t[0], "bo": t[1], "bh": t[2],
                            "est_bytes": est, "source": source}
    path = store.save_cache(entries, meta={"smoke": smoke}, path=out)
    log(f"wrote {len(entries)} entries -> {path}")
    return path, entries
