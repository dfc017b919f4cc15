#!/usr/bin/env python3
"""One cell of ``BENCHMARK.json``, once.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

A new process each time. Everything before the timed window is set-up; the
float32 reference runs after the window, once the program's state is freed.
The last line of stdout is one JSON object (``correct``, ``attempted``,
``failed``, ``metrics``, ``device``, and with ``--trace 1`` ``breakdown``).
Lines before it say what phase the run is in, each number compared beside
its limit, and how set-up went. A run that cannot measure (no TPU, fewer
chips than the cell asks for, any exception) prints why, with the phase and
the traceback, exits non-zero and prints no result line.
"""

from __future__ import annotations

import time

_T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import traceback  # noqa: E402

_HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(_HERE))   # the checkout: the program
sys.path.insert(0, _HERE)                    # benchmark/: the harness

from harness import device  # noqa: E402

TRACE_DIR = os.path.join(device.CHECKOUT, ".bench_trace")
EXIT_NO_ACCELERATOR = 3
EXIT_FAILED = 1


def say(text: str) -> None:
    print(f"[bench {time.perf_counter() - _T_PROCESS:8.2f}s] {text}",
          flush=True)


def parse_args(argv):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return p.parse_args(argv)


def main(argv=None) -> int:
    args = parse_args(argv)
    phase = {"name": "start"}

    def enter(name):
        phase["name"] = name
        say(f"phase: {name}")

    try:
        return _run(args, enter)
    except device.NoAccelerator as e:
        say(f"cannot measure: {e}")
        return EXIT_NO_ACCELERATOR
    except BaseException:
        say(f"FAILED in phase {phase['name']!r}:")
        traceback.print_exc(file=sys.stdout)
        sys.stdout.flush()
        return EXIT_FAILED


def _run(args, enter) -> int:
    from harness import manifest

    enter("start")
    cache_dir = device.place_compile_cache()
    cell = manifest.Cell(manifest.load_manifest(), args.workload)
    say(f"cell {cell.name}: config {cell.config_name}, traffic "
        f"{cell.traffic_name} ({cell.traffic['kind']}), {cell.chips} chip(s),"
        f" seed {args.seed}, {args.seconds} s, trace {args.trace}; compile "
        f"cache {cache_dir}")

    import jax

    devices = device.require_chips(cell.chips)
    events = device.CompileEvents()
    say(f"device: {devices[0].device_kind} x {len(devices)} "
        f"({devices[0].platform}); jax {jax.__version__}")

    traced = bool(args.trace)
    annotate = jax.profiler.TraceAnnotation if traced \
        else (lambda _name: contextlib.nullcontext())

    @contextlib.contextmanager
    def profiler():
        if not traced:
            yield
            return
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 2
        jax.profiler.start_trace(TRACE_DIR, profiler_options=options)
        try:
            yield
        finally:
            jax.profiler.stop_trace()

    # The runner is found by the traffic file's ``kind``: harness/<kind>.py.
    runner = importlib.import_module(f"harness.{cell.traffic['kind']}")
    result = runner.run({
        "cell": cell, "seed": args.seed, "seconds": args.seconds,
        "trace": traced, "devices": devices, "events": events,
        "say": say, "phase": enter, "annotate": annotate,
        "profiler": profiler,
    })

    setup_s = result["window_start"] - _T_PROCESS
    end_to_end = dict(result["end_to_end"], setup_s=setup_s)
    info = device.describe(devices)
    info["memory_peak_bytes"] = int(result["memory_peak_bytes"])
    line = {"correct": result["correct"], "attempted": result["attempted"],
            "failed": result["failed"]}

    if not traced:
        units = {m["name"]: m["unit"] for m in cell.end_to_end()}
        missing = sorted(set(units) - set(end_to_end))
        if missing:
            raise RuntimeError(f"the runner reported no {missing}")
        line["metrics"] = {k: {"value": end_to_end[k], "unit": units[k]}
                           for k in units}
    else:
        enter("reduction")
        from harness import xplane

        # A CPU rehearsal has no device plane to reduce: its readers of
        # counters and spans still run, its readers of the trace find nothing.
        trace = window = None
        if info["platform"] == "tpu":
            trace = xplane.Trace.from_file(
                xplane.find_xplane(TRACE_DIR), result["layer"].get(
                    "span_names", ("window", "next(batch)", "fit hook")))
            window = trace.window("window")
            info["busy_s"] = trace.busy_s(window)
            info["window_s"] = (window[1] - window[0]) / 1e9
        ctx = {"cell": cell, "trace": trace, "window": window,
               "run": result["layer"], "end_to_end": end_to_end,
               "device": info, "peaks": device.peaks_of(info["kind"])
               if info["platform"] == "tpu" else None, "say": say}
        metrics = {}
        for m in cell.per_layer():
            value = cell.reader(m["name"])(ctx)
            if value is None:
                say(f"per-layer {m['name']}: nothing to read, left out")
                continue
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
        line["metrics"] = metrics
        if trace is not None:
            line["breakdown"] = {"device_ops": trace.top_ops(10, window),
                                 "idle_gaps": trace.idle_gaps(window, 10)}
        line["end_to_end_traced"] = end_to_end
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
    line["device"] = info
    enter("done")
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
