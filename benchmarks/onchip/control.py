#!/usr/bin/env python3
"""Readings that a cell's limits are set from, on the chip, in one process:

    python3 benchmarks/onchip/control.py --workload fno2d.serve.bulk \\
        --seeds 11,12,13 --controls high,bf16,altered --seconds 2

For each seed it drives the cell's timed path as a run does (set-up, a
short window at the cell's own load, the comparison with the reference)
and prints the program's readings. For each seed of ``--control-seeds``
(default: the first three seeds) it then prints the readings of each
control, with the reference put in the program's place: at a lower
precision (``high``, ``bf16``), or with a fault planted (``altered``
answers). The benchmark's own runs never run this. Each reading is
one JSON line on standard output; ``--out`` appends them to a file too.
"""
import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--controls", default="")
    ap.add_argument("--seconds", type=float, default=2.0)
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    sys.path.insert(0, HERE)
    import gc

    import harness
    import run as entry

    spec = harness.load_spec(args.workload)
    devices = entry.tpu_devices(spec.chips)
    if devices is None:
        print("control.py: no TPU with enough chips", file=sys.stderr)
        return 2
    harness.configure(spec)
    driver = harness.load_module(
        harness.HERE / "traffic" / f"{spec.mix['kind']}.py")
    seeds = [int(s) for s in args.seeds.split(",") if s]
    ctl_seeds = ([int(s) for s in args.control_seeds.split(",") if s]
                 or seeds[:3])
    controls = [c for c in args.controls.split(",") if c]

    def emit(row):
        line = json.dumps(row)
        print(line, flush=True)
        if args.out:
            with open(args.out, "a") as f:
                f.write(line + "\n")

    for seed in seeds:
        t0 = time.perf_counter()
        run = harness.Run(spec, seed, args.seconds, False, devices)
        state = driver.setup(run)
        t1 = time.perf_counter()
        win = driver.window(run, state)
        del state
        gc.collect()
        got = driver.check(run, win.record)
        emit({"workload": spec.name, "seed": seed, "who": "program",
              "precision": spec.config.get("matmul_precision", "default"),
              "readings": got, "metrics": win.metrics,
              "setup_s": t1 - t0, "check_s": time.perf_counter() - t1
              - args.seconds})
        for what in controls if seed in ctl_seeds else ():
            t2 = time.perf_counter()
            got = driver.control(run, win.record, what)
            emit({"workload": spec.name, "seed": seed, "who": what,
                  "readings": got, "s": time.perf_counter() - t2})
        del win
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
