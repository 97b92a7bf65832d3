#!/usr/bin/env bash
# Tier-1 gate (ROADMAP.md §Tier-1 verify): the full suite must pass with
# zero collection errors. Run from anywhere; extra args forwarded to pytest
# (e.g. scripts/check.sh -x -k kernels).
set -euo pipefail
cd "$(dirname "$0")/.."
export PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH}
# Fused-block trace-count guard (PR 4): one FNO block on the full-fusion
# pallas path must stay exactly ONE pallas_call (and its grad exactly
# four). Pure tracing — runs in a couple of seconds, no kernels execute.
python scripts/fused_block_smoke.py
# FNO serving smoke (ISSUE 5): the batched serve driver on the fused
# pallas path, one bucket — asserts one pallas_call per layer through the
# sharded dispatch and that every served output is finite.
python -m repro.launch.serve --arch fno2d --reduced --requests 2 \
  --max-batch 2
# TP overlap smoke (ISSUE 8): the scattered layout's ppermute-ring
# overlap mode vs the one-shot psum_scatter on a forced dp2xtp4 mesh —
# forward/grad parity plus the exact traced collective plan ((tp-1)
# ppermutes per interior layer, one final psum).
python scripts/overlap_smoke.py
# Autotuner smoke (ISSUE 7): the generate -> VMEM-prune -> persist
# pipeline over the reduced shapes into a throwaway cache, then the
# staleness lint over it. Pure python byte-model math — seconds, no jax.
python scripts/autotune.py --smoke
# Continuous-batching replay smoke (ISSUE 10): a seeded traffic replay
# through the async coalescing queue on a virtual clock — exact shed/
# coalesce/deadline counts, the no-late-serving deadline contract, replay
# determinism, and the rollout trace contract (K-step device-resident
# rollout == num_layers pallas_calls for K in {1,4} — docs/DESIGN.md §10).
python scripts/serve_replay_smoke.py
# Chaos smoke (ISSUE 9): the deterministic fault plan (kernel fault, NaN
# injection, replica kill, corrupt checkpoint) replayed through the
# resilient serving runtime — every accepted request answered finite,
# degraded/shed counts exactly match the plan, XLA-fallback parity
# <= 2e-4, corrupt-checkpoint reload rolls back (docs/DESIGN.md §9).
python scripts/chaos_smoke.py
# Contract lint (ISSUE 6/7): AST rules, config-registry audit, static
# VMEM estimates (tuned plans, error severity), tuned-cache staleness,
# and the jaxpr trace lints (pallas counts / cast ownership / collective
# budget) over the whole config matrix. Pure tracing + AST — no kernels
# execute.
python scripts/lint.py --all
# The pytest gate below includes tests/test_tpu_compile.py: the full-width
# fused block and its gradient compiled for a described TPU v5e (needs
# libtpu, not a chip).
# Collection gate: when pytest selection args (-k/-m/paths) could deselect
# a broken module, a full collect-only pass must still fail the script on
# any collection error. A bare run needs no gate — pytest itself exits
# nonzero on collection errors.
if [ "$#" -gt 0 ]; then
  python -m pytest -q --collect-only >/dev/null
fi
exec python -m pytest -q "$@"
