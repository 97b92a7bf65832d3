#!/usr/bin/env python
"""Block-size autotuner CLI (docs/DESIGN.md §8).

Regenerates the committed tuned-plan cache
(``src/repro/tuning/cache/blocks.json``): for every tuning key the config
matrix can emit, generate the (bb, bo, bh) candidate grid, prune it
statically against the VMEM budget (``analysis.vmem.launch_estimate``),
rank the survivors, compile the full-size winners for a described v5e,
and persist the winners with their evidence. The committed cache is what
``repro.tuning.resolve_launch_plans`` serves; without it every launch
falls back to the static ``tuning.plans._BLOCK_DEFAULTS``.

Usage:
  PYTHONPATH=src python scripts/autotune.py                 # full regen
  PYTHONPATH=src python scripts/autotune.py --smoke         # CI smoke

--smoke tunes only the reduced shapes into a throwaway file, then lints
it with ``store.check_tuning_cache`` — a seconds-long pipeline check
that never touches the committed cache.
"""
import argparse
import os
import sys
import tempfile

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--smoke", action="store_true",
                    help="reduced shapes, throwaway output + "
                         "staleness lint (CI)")
    ap.add_argument("--out", default=None,
                    help="cache path (default: the committed "
                         "src/repro/tuning/cache/blocks.json)")
    args = ap.parse_args()

    from repro.tuning import autotune, check_tuning_cache

    if args.smoke:
        with tempfile.TemporaryDirectory() as td:
            out = os.path.join(td, "blocks.json")
            path, entries = autotune.tune(smoke=True, out=out)
            findings = [f for f in check_tuning_cache(path)
                        if f.severity == "error"]
            for f in findings:
                print(f"  error: {f.target}: {f.message}")
            print(f"autotune smoke: {len(entries)} entries, "
                  f"{len(findings)} lint error(s)")
            return 1 if findings or not entries else 0

    path, entries = autotune.tune(out=args.out)
    findings = [f for f in check_tuning_cache(path)
                if f.severity == "error"]
    for f in findings:
        print(f"  error: {f.target}: {f.message}")
    return 1 if findings or not entries else 0


if __name__ == "__main__":
    sys.exit(main())
