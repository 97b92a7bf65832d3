"""Persisted block-plan cache: committed JSON of autotuned winners.

One file (``tuning/cache/blocks.json`` by default, committed) maps tuning
keys (``plans.plan_key``) to winning (bb, bo, bh) triples plus the
evidence they were chosen on (VMEM estimate, measured wall time, probe
shapes). The loader is mtime-keyed-lru so repeated resolution during a
trace costs one dict lookup, while a regenerated file is picked up
without process restart.

Staleness contract (``check_tuning_cache``, wired into ``scripts/lint.py
--tuning``): the cache's ``meta.engine_signature`` must equal
``kernels.engine.BLOCK_SIGNATURE`` and ``meta.vmem_budget_bytes`` the
current budget — a cache tuned against an older launch geometry or
budget is an error, not a silent fallback. Each entry must parse as a
valid key, carry a positive triple that Mosaic's tiling rule admits
(``plans.tiling_legal``), and (re-estimated against its recorded probe
shapes with the CURRENT estimator) still fit the budget.
"""
from __future__ import annotations

import functools
import json
import os
from typing import Dict, List, Optional, Tuple

from repro.analysis import Finding

CACHE_DIR = os.path.join(os.path.dirname(__file__), "cache")
DEFAULT_CACHE_PATH = os.path.join(CACHE_DIR, "blocks.json")

_EMPTY = {"meta": {}, "entries": {}}


@functools.lru_cache(maxsize=16)
def _load(path: str, mtime_ns: int) -> dict:
    with open(path) as f:
        data = json.load(f)
    data.setdefault("meta", {})
    data.setdefault("entries", {})
    return data


def load_cache(path: Optional[str] = None) -> dict:
    """{"meta": {...}, "entries": {key: {bb,bo,bh,...}}} — empty when the
    file is absent or unparseable (resolution then falls back to the
    static defaults; the staleness lint reports the defect)."""
    path = path or DEFAULT_CACHE_PATH
    try:
        st = os.stat(path)
    except OSError:
        return _EMPTY
    try:
        return _load(path, st.st_mtime_ns)
    except (json.JSONDecodeError, OSError):
        return _EMPTY


def lookup(key: str, path: Optional[str] = None
           ) -> Optional[Tuple[int, int, int]]:
    """The cached winning triple for a key, or None on miss."""
    e = load_cache(path)["entries"].get(key)
    if not e:
        return None
    try:
        t = (int(e["bb"]), int(e["bo"]), int(e["bh"]))
    except (KeyError, TypeError, ValueError):
        return None
    return t if all(v > 0 for v in t) else None


def save_cache(entries: Dict[str, dict], meta: Optional[dict] = None,
               path: Optional[str] = None) -> str:
    """Write a cache file (sorted keys, meta stamped with the current
    engine signature + budget unless overridden) and return its path."""
    from repro.analysis.vmem import VMEM_BUDGET_BYTES
    from repro.kernels.engine import BLOCK_SIGNATURE

    path = path or DEFAULT_CACHE_PATH
    full_meta = {"engine_signature": BLOCK_SIGNATURE,
                 "vmem_budget_bytes": VMEM_BUDGET_BYTES}
    full_meta.update(meta or {})
    data = {"meta": full_meta,
            "entries": {k: entries[k] for k in sorted(entries)}}
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(data, f, indent=1, sort_keys=False)
        f.write("\n")
    return path


def check_tuning_cache(path: Optional[str] = None) -> List[Finding]:
    """Staleness + integrity lint over one cache file (see module doc)."""
    from repro.analysis.vmem import VMEM_BUDGET_BYTES, launch_estimate
    from repro.configs.base import PrecisionPolicy
    from repro.kernels.engine import BLOCK_SIGNATURE
    from repro.tuning import plans as P

    path = path or DEFAULT_CACHE_PATH
    rel = os.path.relpath(path, os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.dirname(__file__)))))
    if not os.path.exists(path):
        return [Finding("tuning-cache", rel,
                        "no tuned block cache committed — every launch "
                        "falls back to the static defaults (regenerate: "
                        "scripts/autotune.py)", severity="warn")]
    data = load_cache(path)
    if not data["entries"] and not data["meta"]:
        return [Finding("tuning-cache", rel,
                        "cache file exists but is empty/unparseable — "
                        "regenerate with scripts/autotune.py")]

    findings: List[Finding] = []
    sig = data["meta"].get("engine_signature")
    if sig != BLOCK_SIGNATURE:
        findings.append(Finding(
            "tuning-cache", rel,
            f"engine signature mismatch: cache tuned against {sig!r} but "
            f"the engine is {BLOCK_SIGNATURE!r} — the launch geometry "
            f"changed; regenerate with scripts/autotune.py"))
    budget = data["meta"].get("vmem_budget_bytes")
    if budget != VMEM_BUDGET_BYTES:
        findings.append(Finding(
            "tuning-cache", rel,
            f"budget mismatch: cache assumed {budget} bytes VMEM, current "
            f"budget is {VMEM_BUDGET_BYTES} — winners may not fit; "
            f"regenerate with scripts/autotune.py"))

    for key, e in data["entries"].items():
        try:
            parsed = P.parse_key(key)
        except ValueError as exc:
            findings.append(Finding("tuning-cache", f"{rel}::{key}",
                                    f"unparseable key: {exc}"))
            continue
        triple = lookup(key, path)
        if triple is None:
            findings.append(Finding(
                "tuning-cache", f"{rel}::{key}",
                f"entry must carry positive integer bb/bo/bh, got "
                f"{ {k: e.get(k) for k in ('bb', 'bo', 'bh')} }"))
            continue
        probe = e.get("probe")
        if not probe:
            findings.append(Finding("tuning-cache", f"{rel}::{key}",
                                    "entry lacks the probe shapes needed "
                                    "to re-check feasibility"))
            continue
        hidden = int(probe["hidden"])
        if not P.tiling_legal(parsed["rank"], hidden, hidden, triple[1],
                            triple[2]):
            findings.append(Finding(
                "tuning-cache", f"{rel}::{key}",
                f"illegal plan: (bo, bh)={triple[1:]} breaks Mosaic's "
                f"(8, 128) tiling rule for hidden={hidden} (bo, and bh at "
                f"rank 1, a multiple of 8 or the whole dim) — the kernel "
                f"cannot compile for a TPU; regenerate the cache"))
            continue
        # Refit against the CURRENT estimator: a winner that no longer
        # fits means the byte model (or kernel) moved under the cache.
        pol = PrecisionPolicy.from_name(parsed["dtype"])
        est = launch_estimate(
            (int(probe["hidden"]), tuple(probe["spatial"]),
             tuple(probe["modes"]), parsed["layout"] == "per_mode"),
            parsed["launch"], triple, batch=int(probe.get("batch", 8)),
            policy=pol)
        if est.total_bytes > VMEM_BUDGET_BYTES:
            findings.append(Finding(
                "tuning-cache", f"{rel}::{key}",
                f"stale winner: {triple} now estimates "
                f"{est.total_bytes / 2**20:.1f} MiB for its probe shapes "
                f"(> {VMEM_BUDGET_BYTES / 2**20:.0f} MiB budget) — the "
                f"estimator or engine changed; regenerate the cache"))
    return findings
