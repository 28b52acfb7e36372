"""Run one benchmark cell on the chips of this machine.

    python3 perfbench/run.py --workload <cell> --seed <n> --seconds <s> \\
        --trace <0|1>

The cell is looked up in ``BENCHMARK.json``; its configuration, traffic
mix, limits and per-layer metric readers are files found by name:

    perfbench/configs/<config>.json     sizes, quality, transforms
    perfbench/traffic/<traffic>.json    the mix; ``kind`` names its driver
    perfbench/kinds/<kind>.py           one driver per kind of traffic
    perfbench/limits/<cell>.json        the limit of each compared number
    perfbench/metrics/<metric>.py       ``read(ctx)``: a per-layer metric

A run sets up (imports, device check, images, warm-up of exactly the
shapes the cell uses), measures for ``--seconds`` seconds, compares a
sample of what the window produced with ``perfbench/reference.py``, and
prints one JSON line last. ``--trace 0`` reports the cell's end-to-end
metrics, ``--trace 1`` its per-layer metrics from a profiler trace of
the window. A machine without the cell's TPU chips exits with code 2
and prints no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()
MARKS = []   # (set-up stage, seconds since T_START at its end)


def mark(stage: str) -> None:
    MARKS.append((stage, time.perf_counter() - T_START))

import argparse  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parent.parent
BENCH = ROOT / "perfbench"


def load_json(path: pathlib.Path) -> dict:
    with open(path) as f:
        return json.load(f)


class Cell:
    """Everything a cell names, resolved from ``BENCHMARK.json``."""

    def __init__(self, name: str, root: pathlib.Path = ROOT):
        bench = load_json(root / "BENCHMARK.json")
        cells = {w["name"]: w for w in bench["workloads"]}
        if name not in cells:
            raise KeyError(f"no workload {name!r} in BENCHMARK.json; "
                           f"known: {sorted(cells)}")
        self.name = name
        self.spec = cells[name]
        configs = {c["name"]: c for c in bench["configs"]}
        self.config = load_json(root / configs[self.spec["config"]]["file"])
        self.config.setdefault("name", self.spec["config"])
        self.traffic = load_json(
            root / "perfbench" / "traffic" / f"{self.spec['traffic']}.json")
        self.limits = load_json(root / "perfbench" / "limits" / f"{name}.json")
        self.chips = self.spec["chips"]
        self.end_to_end = [m for m in bench["end_to_end"]
                           if name in m.get("workloads", [name])]
        self.per_layer = [m for m in bench["per_layer"]
                          if name in m.get("workloads", [name])]
        self.root = root

    def driver_class(self):
        kind = self.traffic["kind"]
        return importlib.import_module(f"perfbench.kinds.{kind}").Driver

    def reader(self, metric: str):
        path = self.root / "perfbench" / "metrics" / f"{metric}.py"
        spec = importlib.util.spec_from_file_location(
            "perfbench.metrics._" + metric.replace(".", "_"), path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod.read


class Context:
    """What a per-layer metric reader may read."""

    def __init__(self, trace, phases, driver, device_kind: str):
        self.trace = trace
        self.phases = phases
        self.driver = driver
        self.device_kind = device_kind
        self.pixels = getattr(driver, "pixels", {})
        self.counters = (driver.counters() if hasattr(driver, "counters")
                         else {})

    def peaks(self) -> dict:
        table = load_json(BENCH / "peaks.json")["devices"]
        if self.device_kind not in table:
            raise KeyError(f"no peaks for device kind {self.device_kind!r}")
        return table[self.device_kind]


def parse_args(argv):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def open_chips(chips: int):
    """The TPU devices, or None when fewer than ``chips`` are found.

    Before JAX starts, its compile cache is put at a fixed path inside
    the checkout, and every program is kept however fast it compiled, so
    that only the first run of a cell in a checkout compiles."""
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    import jax
    mark("import jax")
    devices = jax.devices()
    mark("devices")
    if devices[0].platform != "tpu" or len(devices) < chips:
        print(f"perfbench: {chips} TPU chip(s) needed; found "
              f"{len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        return None
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    from repro import compile_cache
    compile_cache.enable()
    mark("imports")
    return devices


def main(argv=None) -> int:
    args = parse_args(argv)
    cell = Cell(args.workload)
    devices = open_chips(cell.chips)
    if devices is None:
        return 2
    result = execute(cell, args, devices)
    print(json.dumps(result), flush=True)
    return 0


def report_setup(phases) -> None:
    """One stderr line: what each stage of set-up took, and how much of it
    JAX spent tracing, lowering and compiling or loading programs."""
    stages, t_prev = [], 0.0
    for stage, t in MARKS:
        stages.append(f"{stage} {t - t_prev:.3f}")
        t_prev = t
    jax_s = ", ".join(f"{k} {v:.3f}" for k, v in sorted(phases.stage_s.items()))
    print(f"perfbench: set-up {t_prev:.3f} s: {', '.join(stages)}; JAX stages "
          f"{jax_s}; programs {phases.compiles['setup']}, cache hits "
          f"{phases.cache_hits['setup']}", file=sys.stderr)
    MARKS.clear()


def execute(cell: Cell, args, devices) -> dict:
    """Set up, measure, check: the run after the device check."""
    import jax

    from perfbench import checks, harness
    phases = harness.Phases()
    harness.install_listeners(phases)
    driver = cell.driver_class()(cell.config, cell.traffic, args.seed,
                                 phases)
    mark("inputs")
    driver.warm_up()
    mark("warm-up")
    setup_s = time.perf_counter() - T_START
    report_setup(phases)

    if args.trace:
        trace_dir = tempfile.mkdtemp(prefix="perfbench-trace-")
        opts = jax.profiler.ProfileOptions()
        opts.host_tracer_level = 1
        opts.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    phases.window_open = True
    with phases.phase("window"):
        driver.run_window(args.seconds)
    phases.window_open = False
    if args.trace:
        jax.profiler.stop_trace()
    dev = devices[0]
    stats = [d.memory_stats() or {} for d in devices[:cell.chips]]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices),
              "memory_peak_bytes": max(s.get("peak_bytes_in_use", 0)
                                       for s in stats)}
    attempted, failed = driver.outcome()
    result = {"correct": False, "attempted": attempted, "failed": failed,
              "metrics": {}, "device": device}
    if args.trace:
        from perfbench.trace_reduce import Trace
        xplanes = sorted(pathlib.Path(trace_dir).rglob("*.xplane.pb"))
        trace = Trace.from_file(str(xplanes[-1]))
        shutil.rmtree(trace_dir, ignore_errors=True)
        device["busy_s"] = trace.busy_ns("window") / 1e9
        device["window_s"] = trace.wall_ns("window") / 1e9
        ctx = Context(trace, phases, driver, dev.device_kind)
        for m in cell.per_layer:
            value = cell.reader(m["name"])(ctx)
            if value is not None:
                result["metrics"][m["name"]] = {"value": value,
                                                "unit": m["unit"]}
        result["breakdown"] = {
            "device_ops": trace.top_ops("window"),
            "idle_gaps": trace.idle_gaps("window",
                                         list(driver.phases_measured))}
    else:
        values = dict(driver.end_to_end(), setup_s=setup_s)
        for m in cell.end_to_end:
            result["metrics"][m["name"]] = {"value": values[m["name"]],
                                            "unit": m["unit"]}

    print(f"perfbench: {cell.name} seed {args.seed}: setup {setup_s:.3f} s, "
          f"window compiles {dict(phases.compiles)}", file=sys.stderr)
    tally = checks.Tally(cell.limits)
    driver.check(tally)
    result["correct"] = tally.correct
    result["checks"] = tally.report()
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    return result


if __name__ == "__main__":
    sys.exit(main())
