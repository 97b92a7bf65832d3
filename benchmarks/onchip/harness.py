"""The benchmark's harness: finds a cell's files by the names in
``BENCHMARK.json``, runs it once and builds its result line.

A cell (``workloads`` entry) names a configuration and a traffic mix.

* ``configs/<config>.json``: the model's sizes as run (``FNOConfig``
  fields, plus ``variant``); the ``file`` of its ``configs`` entry.
* ``mixes/<traffic>.json``: the traffic's parameters; its ``kind`` names
  the driver module ``traffic/<kind>.py``.
* ``limits/<cell>.json``: the limit of each number that decides
  ``correct`` (``limits``), with the readings it was set from, and the
  control and faults that fail it (``control``, ``faults``).
* ``metrics/<metric>.py``: one reader per per-layer metric.

A driver module has three functions. ``setup(run)`` builds the system
under test from the seed and warms every shape the window uses;
``window(run, state)`` drives it for ``run.seconds`` and returns a
``Window``; ``check(run, record)`` compares what the window produced with
the plain reference, after the program's state is freed, and returns the
numbers compared.
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import importlib.util
import json
import math
import os
import shutil
import sys
import tempfile
import time
import traceback
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]


def load_json(path: Path) -> Any:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path):
    """Import a file of the benchmark by its path (names may hold dots);
    it may import the benchmark's modules and its own directory's."""
    for d in (str(HERE), str(path.parent)):
        if d not in sys.path:
            sys.path.insert(0, d)
    name = "onchip_" + path.stem.replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise ImportError(f"cannot load {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Spec:
    """Everything the files say about one cell."""

    name: str
    chips: int
    config_name: str
    config: Dict[str, Any]
    traffic: str
    mix: Dict[str, Any]
    limits: Dict[str, Any]  # number compared -> {"limit", readings...}
    control: Optional[str]  # the control that fails a limit
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]


def _applies(metric: Dict[str, Any], cell: str,
             e2e_names: Sequence[str]) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    return metric.get("moves", None) in e2e_names or "moves" not in metric


def load_spec(cell: str, root: Path = ROOT,
              bench: Optional[Dict[str, Any]] = None,
              base: Path = HERE) -> Spec:
    """Read the cell's entries and files (mixes and limits under
    ``base``); raises ``KeyError`` for a cell that ``BENCHMARK.json``
    does not name."""
    bench = bench if bench is not None else load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if cell not in cells:
        raise KeyError(f"no workload {cell!r} in BENCHMARK.json; "
                       f"known: {sorted(cells)}")
    w = cells[cell]
    configs = {c["name"]: c for c in bench["configs"]}
    config = load_json(root / configs[w["config"]]["file"])
    mix = load_json(base / "mixes" / f"{w['traffic']}.json")
    checks_path = base / "limits" / f"{cell}.json"
    checks = load_json(checks_path) if checks_path.exists() else {}
    e2e = [m for m in bench["end_to_end"] if _applies(m, cell, ())]
    names = [m["name"] for m in e2e]
    per_layer = [m for m in bench["per_layer"] if _applies(m, cell, names)]
    return Spec(name=cell, chips=int(w["chips"]), config_name=w["config"],
                config=config, traffic=w["traffic"], mix=mix,
                limits=checks.get("limits", {}),
                control=checks.get("control"), end_to_end=e2e,
                per_layer=per_layer)


def configure(spec: Spec) -> None:
    """What a run on the chip takes from its checkout and configuration:
    JAX's persistent compilation cache in the checkout, holding every
    program however fast it compiled (so a second run compiles nothing),
    and the configuration's matmul precision. No entry point of the
    program sets that precision: the configuration states it so that the
    comparison with the reference can fail a lower one."""
    import jax
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    if spec.config.get("matmul_precision"):
        jax.config.update("jax_default_matmul_precision",
                          spec.config["matmul_precision"])


def seed32(seed: int) -> int:
    """The seed folded into the 31 bits a JAX PRNG key keeps."""
    return int(seed) % 2_147_483_647


@dataclasses.dataclass
class Window:
    """What a driver's measured window returns."""

    metrics: Dict[str, float]  # end-to-end values, by name
    attempted: int
    failed: int
    counters: Dict[str, Any]  # what the per-layer readers read
    record: Any  # what check() needs, host-side; no program state
    # The compiled programs the window runs (``jit(f).lower(...).compile()``).
    programs: List[Any] = dataclasses.field(default_factory=list)


class Run:
    """One run of one cell: its spec, seed and settings, the devices it
    holds, and the host spans it records while tracing."""

    def __init__(self, spec: Spec, seed: int, seconds: float, trace: bool,
                 devices: Sequence[Any]):
        self.spec, self.seed, self.seconds = spec, int(seed), float(seconds)
        self.trace, self.devices = trace, list(devices)
        self.cfg = spec.config
        self.mix = spec.mix

    def key(self):
        import jax
        return jax.random.PRNGKey(seed32(self.seed))

    def rng(self, stream: int = 0):
        import numpy as np
        return np.random.default_rng([int(self.seed), stream])

    def fno_config(self):
        """The configuration file as the program's ``FNOConfig``."""
        from repro.configs.base import FNOConfig
        fields = {f.name for f in dataclasses.fields(FNOConfig)}
        kw = {k: (tuple(v) if isinstance(v, list) else v)
              for k, v in self.cfg.items() if k in fields}
        return FNOConfig(**kw)

    def span(self, name: str):
        """A host span in the trace (a no-op when not tracing)."""
        if not self.trace:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(name)


def compile_counter():
    """A list that grows by one for each backend compile from now on."""
    import jax
    hits: List[float] = []

    def listen(event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            hits.append(duration)

    jax.monitoring.register_event_duration_secs_listener(listen)
    return hits


def memory_peak(devices, programs: Sequence[Any] = ()) -> Optional[int]:
    """Peak device memory on the fullest chip: the runtime's peak of the
    buffers the process held, plus the largest temporary space of the
    programs the window ran (``memory_analysis()``), which the TPU
    runtime's ``peak_bytes_in_use`` leaves out."""
    peaks = [int(stats["peak_bytes_in_use"]) for stats in
             (d.memory_stats() or {} for d in devices)
             if "peak_bytes_in_use" in stats]
    if not peaks:
        return None
    temp = max([int(p.memory_analysis().temp_size_in_bytes)
                for p in programs] or [0])
    return max(peaks) + temp


# Host spans the benchmark's drivers record inside the window; idle gaps
# are attributed to the innermost of these.
SPANS = ("request generation", "server call", "output fetch")


def _traced(fn: Callable[[], Window], tmp: str) -> Window:
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(tmp, profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation("window"):
            win = fn()
    finally:
        jax.profiler.stop_trace()
    return win


def run_cell(spec: Spec, seed: int, seconds: float, trace: bool, devices,
             t_start: float, log=None,
             peak: Optional[Dict[str, float]] = None) -> Dict[str, Any]:
    """Run the cell once; returns the result object to print. ``peak``
    replaces the table's row (for tests off the chip)."""
    log = log or (lambda msg: print(msg, file=sys.stderr, flush=True))
    if str(HERE) not in sys.path:
        sys.path.insert(0, str(HERE))
    import devtrace as tr
    import peaks as pk

    peak = peak or pk.peaks(devices[0].device_kind)
    driver = load_module(HERE / "traffic" / f"{spec.mix['kind']}.py")
    run = Run(spec, seed, seconds, trace, devices)
    state = driver.setup(run)
    compiles = compile_counter()
    setup_s = time.perf_counter() - t_start
    log(f"set-up {setup_s:.3f} s")

    reduced = None
    if trace:
        tmp = tempfile.mkdtemp(prefix="onchip_trace_")
        try:
            win = _traced(lambda: driver.window(run, state), tmp)
            paths = [os.path.join(d, f) for d, _, fs in os.walk(tmp)
                     for f in fs if f.endswith(".xplane.pb")]
            if not paths:
                raise RuntimeError("the profiler wrote no trace")
            dev_events, host = tr.load(paths[0])
            window = tr.window_of(host)
            if window is None:
                raise RuntimeError("the trace holds no window span")
            spans = [s for s in host if s.name in SPANS]
            reduced = tr.reduce(dev_events, spans, window,
                                tr.hlo_kinds([p.as_text()
                                              for p in win.programs]))
        finally:
            shutil.rmtree(tmp, ignore_errors=True)
    else:
        win = driver.window(run, state)
    n_compiles = len(compiles)
    mem = memory_peak(devices, win.programs)
    del state
    gc.collect()

    checks: List[Tuple[str, float, float]] = []
    try:
        got = driver.check(run, win.record)
        log(f"readings {json.dumps(got)}")
        checks = [(name, float(got[name]), float(lim["limit"]))
                  for name, lim in sorted(spec.limits.items())]
        correct = all(math.isfinite(v) and v <= lim for _, v, lim in checks)
        correct = correct and bool(checks) and n_compiles == 0
    except Exception:  # noqa: BLE001 — a check that fails to run is false
        log(traceback.format_exc())
        correct = False

    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": mem}
    out: Dict[str, Any] = {"correct": bool(correct),
                           "attempted": int(win.attempted),
                           "failed": int(win.failed)}
    if trace:
        view = View(run=run, trace=reduced, counters=win.counters,
                    peak=peak, chips=len(devices))
        values = {}
        for m in spec.per_layer:
            reader = load_module(HERE / "metrics" / f"{m['name']}.py")
            v = reader.read(view)
            if v is not None:
                values[m["name"]] = {"value": float(v), "unit": m["unit"]}
        out["metrics"] = values
        device["busy_s"] = reduced.busy_s
        device["window_s"] = reduced.window_s
        out["device"] = device
        out["breakdown"] = {"device_ops": tr.top(reduced.op_s),
                            "idle_gaps": tr.top(reduced.idle_by_span)}
    else:
        values = {}
        for m in spec.end_to_end:
            if m["name"] == "setup_s":
                values["setup_s"] = {"value": setup_s, "unit": m["unit"]}
            elif m["name"] in win.metrics:
                values[m["name"]] = {"value": float(win.metrics[m["name"]]),
                                     "unit": m["unit"]}
        out["metrics"] = values
        out["device"] = device
    log(f"compiles in the window: {n_compiles}")
    for name, v, lim in checks:
        log(f"check {name} {v!r} limit {lim!r}")
    out["checks"] = {name: {"value": v, "limit": lim}
                     for name, v, lim in checks}
    return out


@dataclasses.dataclass
class View:
    """What a per-layer metric's reader sees."""

    run: Run
    trace: Any  # trace.Reduced
    counters: Dict[str, Any]
    peak: Dict[str, float]
    chips: int
