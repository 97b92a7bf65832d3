#!/usr/bin/env python3
"""Run one cell of the on-chip benchmark once, from the checkout's root:

    python3 benchmarks/onchip/run.py --workload fno2d.serve.bulk \\
        --seed 7 --seconds 10 --trace 0

The cell is a ``workloads`` entry of ``BENCHMARK.json``. ``--trace 0``
prints the cell's end-to-end metrics, ``--trace 1`` traces the window
and prints its per-layer metrics. The last line of standard output is
one JSON object: ``correct``, ``attempted``, ``failed``, ``metrics``,
``device`` (and with ``--trace 1`` a ``breakdown``), then ``checks``:
each number compared with the reference beside its limit, which the
last lines of standard error repeat.

It runs only on a TPU with as many chips as the cell asks for; anything
else exits 2 and prints no result. JAX's persistent compilation cache
lives in the checkout (``launch/compile_cache``), so only a cell's first
run there compiles.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def tpu_devices(chips: int):
    """The first ``chips`` TPU devices, or None where JAX has fewer."""
    import jax
    try:
        devices = jax.devices()
    except RuntimeError:
        return None
    if devices[0].platform != "tpu" or len(devices) < chips:
        return None
    return devices[:chips]


def main(argv=None) -> int:
    args = parse(argv)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    sys.path.insert(0, HERE)
    import harness

    spec = harness.load_spec(args.workload)
    devices = tpu_devices(spec.chips)
    if devices is None:
        print(f"run.py: {args.workload} needs {spec.chips} TPU chip(s); "
              f"JAX finds none or fewer", file=sys.stderr)
        return 2
    harness.configure(spec)
    out = harness.run_cell(spec, args.seed, args.seconds, bool(args.trace),
                           devices, T_START)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
