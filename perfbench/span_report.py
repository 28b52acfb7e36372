"""Where a cell's device idle time goes, by the program's own spans.

    python3 perfbench/span_report.py --workload <cell> --seed <n> \\
        --seconds <s> [--out <file.json>]

Runs the cell as ``run.py --trace 1`` does (set-up, warm-up, a traced
window) and prints, for each phase, its wall and device-idle time, the
span readings below, and for each ``repro.*`` span name: how many spans
started in the phase, their self time (less their child spans, summed
over host threads), the device-idle time under that self time (merged
over threads; names overlap where threads run at once), and the name's
share of the idle time when each idle instant is shared equally among
the threads then in a span (these shares add up). Idle time no span
covers is the harness's own.

Readings per phase (None where the program records no spans):
``entropy_host_ms_per_mpix`` (self time of ``repro.entropy.*`` less the
``repro.xfer.*`` inside, summed over threads, per Mpx);
``entropy_host_idle_pct`` (% of the phase with the device idle while
some thread is in that self time); ``xfer_bytes_per_image`` (``nbytes``
of the ``repro.xfer.*`` spans per image); ``reassemble_ms_per_call``
(self time of ``repro.engine.reassemble`` per engine call of the phase).
Nothing is compared and nothing is checked: this reads a trace, it is
not a benchmark result.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import shutil
import sys
import tempfile

sys.path[:0] = [str(pathlib.Path(__file__).resolve().parent.parent)]

from perfbench import harness, run  # noqa: E402
from perfbench.spans import SpanTrace  # noqa: E402


def traced_window(cell: run.Cell, seed: int, seconds: float):
    """(span trace, driver) of one traced window of ``cell``."""
    import jax
    phases = harness.Phases()
    harness.install_listeners(phases)
    driver = cell.driver_class()(cell.config, cell.traffic, seed, phases)
    driver.warm_up()
    trace_dir = tempfile.mkdtemp(prefix="perfbench-spans-")
    opts = jax.profiler.ProfileOptions()
    opts.host_tracer_level = 1
    opts.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    phases.window_open = True
    with phases.phase("window"):
        driver.run_window(seconds)
    phases.window_open = False
    jax.profiler.stop_trace()
    xplanes = sorted(pathlib.Path(trace_dir).rglob("*.xplane.pb"))
    trace = SpanTrace.from_file(str(xplanes[-1]))
    shutil.rmtree(trace_dir, ignore_errors=True)
    return trace, driver


def readings(st: SpanTrace, phase: str, pixels: int, images) -> dict:
    wall = st.trace.wall_ns(phase)
    calls = st.count(phase, f"repro.engine.{phase}")
    if not st.spans or not wall:
        return dict.fromkeys(("entropy_host_ms_per_mpix",
                              "entropy_host_idle_pct",
                              "xfer_bytes_per_image",
                              "reassemble_ms_per_call"))
    return {
        "entropy_host_ms_per_mpix":
            st.self_ns(phase, "repro.entropy.") / 1e6 / (pixels / 1e6),
        "entropy_host_idle_pct": (100.0 * st.idle_under_ns(
            phase, "repro.entropy.") / wall if st.trace.devices else None),
        "xfer_bytes_per_image": (st.stat_sum(phase, "repro.xfer.", "nbytes")
                                 / images if images else None),
        "reassemble_ms_per_call": (st.self_ns(
            phase, "repro.engine.reassemble") / 1e6 / calls
            if calls else None)}


def report(st: SpanTrace, driver) -> dict:
    out = {}
    for phase in driver.phases_measured:
        wall = st.trace.wall_ns(phase)
        busy = st.trace.busy_ns(phase)
        spans = {}
        for name, (self_ns, idle_ns, share_ns) in sorted(
                st.idle_by_span(phase).items()):
            if self_ns:
                spans[name] = {"count": st.count(phase, name),
                               "self_s": self_ns / 1e9,
                               "idle_s": idle_ns / 1e9,
                               "idle_share_s": share_ns / 1e9}
        out[phase] = {
            "wall_s": wall / 1e9,
            "idle_s": (wall - busy) / 1e9 if st.trace.devices else None,
            "readings": readings(st, phase, driver.pixels[phase],
                                 getattr(driver, "n_images", None)),
            "spans": spans}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out")
    args = ap.parse_args(argv)
    cell = run.Cell(args.workload)
    if run.open_chips(cell.chips) is None:
        return 2
    st, driver = traced_window(cell, args.seed, args.seconds)
    result = {"workload": cell.name, "seed": args.seed,
              "pixels": driver.pixels,
              "images": getattr(driver, "n_images", None),
              "phases": report(st, driver)}
    text = json.dumps(result, indent=1)
    if args.out:
        pathlib.Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        pathlib.Path(args.out).write_text(text)
    print(text)
    return 0


if __name__ == "__main__":
    sys.exit(main())
