"""Helpers for the benchmark's CPU tests: put the benchmark and the
program on the path, and shrink a cell to a size the CPU runs in seconds
(the Pallas kernels run in interpret mode there)."""
import copy
import os
import sys

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(os.path.dirname(HERE))
for _p in (os.path.join(ROOT, "src"), HERE):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import harness  # noqa: E402

TINY = {"name": "tiny", "ndim": 2, "hidden": 16, "num_layers": 2,
        "in_channels": 3, "out_channels": 1, "spatial": [32, 32],
        "modes": [8, 8], "lifting_dim": 32, "dtype": "float32",
        "param_dtype": "float32", "path": "pallas", "fuse_block": True,
        "variant": "full"}

# The traffic of each kind, cut to the tiny size.
SMALL = {"closed_loop": {"request_samples": 8, "max_batch": 8,
                         "pool_samples": 32, "ref_rows": 8}}

PEAK = {"flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}


def tiny_spec(cell: str, weight_mode: str = "shared", limits=None):
    """The cell's spec with a tiny configuration and traffic, held to
    ``limits`` (default: the cell's own)."""
    spec = harness.load_spec(cell)
    spec.config = dict(TINY, weight_mode=weight_mode)
    spec.mix = dict(copy.deepcopy(spec.mix), **SMALL[spec.mix["kind"]])
    if limits is not None:
        spec.limits = {k: {"limit": v} for k, v in limits.items()}
    return spec


def run_tiny(spec, seed: int = 5, seconds: float = 1.0, trace: bool = False):
    """One run of the harness on the CPU, past its look for a chip."""
    import time

    import jax
    return harness.run_cell(spec, seed, seconds, trace, jax.devices()[:1],
                            time.perf_counter(), peak=PEAK,
                            log=lambda msg: None)
