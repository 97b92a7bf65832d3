"""Fast interpret-mode trace-count regression guard (CI, scripts/check.sh).

Asserts the PR-4 fusion contract at the jaxpr level — cheap (no kernel
execution, just tracing) and robust to interpret mode, where pallas_calls
lower to plain HLO and can't be counted post-compilation:

  * ONE FNO block forward on the full-fusion pallas path traces to
    exactly one pallas_call (spectral + bypass GEMM + bias + GELU all
    inside the engine's k-loop/epilogue);
  * jax.grad of the block traces to exactly four (fwd + gz recompute +
    dx adjoint + extended wgrad) — every cotangent on fused kernels;
  * a whole apply_fno forward with cfg.fuse_block traces to exactly
    num_layers + 2 pallas_calls (the blocks and the two end_mlp ends).

Since ISSUE 6 this is a thin wrapper over the contract-linter framework
(``repro.analysis.jaxpr_lint.fused_block_contract``) — the same checkers
``scripts/lint.py --trace`` sweeps over the full config matrix — so the
CI step name and its pass/fail semantics are unchanged while the logic
lives in exactly one place.
"""
import sys

from repro.analysis import format_findings
from repro.analysis.jaxpr_lint import fused_block_contract
from repro.configs import get_config


def main() -> None:
    findings = fused_block_contract()
    if findings:
        print(format_findings(findings), file=sys.stderr)
        raise AssertionError(
            f"fused-block contract violated ({len(findings)} finding(s))")
    layers = get_config("fno2d", reduced=True).num_layers
    print(f"fused-block smoke OK: block fwd=1, grad=4, "
          f"model={layers + 2} pallas_calls ({layers} layers, 2 ends)")


if __name__ == "__main__":
    main()
