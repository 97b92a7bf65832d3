"""Block-size autotuner + persisted plan cache (docs/DESIGN.md §8).

The engine's launch geometry is fixed (grid = (B/bb, O/bo, H/bh), hidden
innermost — ``kernels/engine.py``) but the RIGHT (bb, bo, bh) per launch
depends on shapes, weight layout, dtype, and the 16 MiB/core VMEM budget.
This package owns that decision end to end:

* ``plans``   — tuning-key schema, the hashable :class:`LaunchPlans`
  bundle the custom_vjps carry, and the plan rules: the static
  ``_BLOCK_DEFAULTS`` fallback and ``legal_blocks``, the clamp every
  launch runs under;
* ``resolve`` — ``resolve_block_plan`` / ``resolve_launch_plans``:
  override → tuned cache → static ``_BLOCK_DEFAULTS`` fallback;
* ``store``   — the committed JSON cache (``tuning/cache/blocks.json``)
  and its staleness lint (``check_tuning_cache``, wired into
  ``scripts/lint.py --tuning``);
* ``autotune`` — the TVM/Ansor-shaped generate → VMEM-prune → rank →
  compile search that regenerates the cache (``scripts/autotune.py``).
"""
from repro.tuning.plans import (BlockPlan, LAUNCH_KINDS, LaunchPlans,
                                plan_key, shape_class)
from repro.tuning.resolve import (resolve_block_plan, resolve_launch_plans,
                                  serve_quantum)
from repro.tuning.store import (DEFAULT_CACHE_PATH, check_tuning_cache,
                                load_cache, save_cache)

__all__ = [
    "BlockPlan", "LAUNCH_KINDS", "LaunchPlans", "plan_key", "shape_class",
    "resolve_block_plan", "resolve_launch_plans", "serve_quantum",
    "DEFAULT_CACHE_PATH",
    "check_tuning_cache", "load_cache", "save_cache",
]
