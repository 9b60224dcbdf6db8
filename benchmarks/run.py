#!/usr/bin/env python3
"""Run one cell of ``BENCHMARK.json`` once and print its result line.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell names a configuration (``benchmarks/configs/<config>.json``), a
traffic mix (``benchmarks/traffic/<traffic>.json``) and through the mix a
window kind (``benchmarks/windows/<window>.py``). Every metric of
``BENCHMARK.json`` has a reader of its name under ``benchmarks/metrics/``.
Nothing here depends on which cell, configuration or metric it is.

Without a TPU the run exits non-zero and prints no result. ``--rehearse``
runs the same code at a tiny size wherever JAX runs; its line says
``"rehearsal": true``, names the platform and carries no device metric.
"""
import time
T_PROCESS = time.perf_counter()

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)

from benchmarks.harness import correct, device as device_mod, peaks
from benchmarks.harness.files import load_cell, load_file, read_json


def metrics_for(bench, cell, traced):
    """The metric entries this run reports: the cell's end-to-end metrics,
    or with ``--trace 1`` its per-layer metrics."""
    group = bench["per_layer"] if traced else bench["end_to_end"]
    return [m for m in group
            if "workloads" not in m or cell["name"] in m["workloads"]]


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes on whatever JAX finds; no device metric")
    args = ap.parse_args(argv)

    bench = read_json("BENCHMARK.json")
    cell, cfg, traffic, window = load_cell(bench, args.workload)
    if args.seconds is None:
        args.seconds = bench["run_seconds"]

    harness = {"t_process": T_PROCESS}
    res = window.run(cell, cfg, traffic, args, harness)

    # the reference runs only now: the window is closed, the peak is read
    # and the program's state is freed
    t0 = time.perf_counter()
    reference = load_file(cfg["reference"]["file"], "bench_reference")
    ref = correct.reference_readings(reference, cfg["reference"]["kwargs"],
                                     res.pop("reference_inputs"))
    limits = read_json("benchmarks", "limits", cell["name"] + ".json")
    numbers = correct.compare(res["program"], ref)
    ok, table = correct.judge(numbers, limits["rehearse" if args.rehearse
                                              else "limits"])
    reference_s = time.perf_counter() - t0

    dev = res["device"]
    ctx = {"run": res, "cell": cell, "cfg": cfg, "traffic": traffic,
           "bench": bench, "peaks": None, "trace": None}
    if not args.rehearse:
        ctx["peaks"] = peaks.peaks(dev.device_kind)
    if res["trace"] is not None:
        from benchmarks.harness import trace_view
        ctx["trace"] = trace_view.TraceView(res["trace"], res["chips"])
        if not args.rehearse:   # a CPU has no device ops to split
            res["failed_checks"] += ctx["trace"].faults(res["nodes"])
    metrics = {}
    for m in metrics_for(bench, cell, bool(args.trace)):
        if args.rehearse and m["source"] == "device_trace":
            continue        # a CPU has no device trace to read
        reader = load_file("benchmarks/metrics/%s.py" % m["name"],
                           "bench_metric")
        value = reader.read(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}

    failed_checks = res["failed_checks"]
    line = {
        "correct": bool(ok and not failed_checks),
        "attempted": int(res["steps"]),
        "failed": 0 if not failed_checks else int(res["steps"]),
        "metrics": metrics,
        "device": device_mod.device_entry(dev, res["chips"],
                                          res["memory_peak_bytes"]),
    }
    if ctx["trace"] is not None and not args.rehearse:
        line["device"].update(ctx["trace"].device_fields())
        line["breakdown"] = ctx["trace"].breakdown()
    if args.rehearse:
        line["rehearsal"] = True
        line["device"].pop("memory_peak_bytes")
    line["info"] = {
        "steps": res["steps"], "batch": res["batch"],
        "window_s": res["window_s"], "max_steps_ahead": res["max_ahead"],
        "setup_s": res["setup_s"], "setup_marks_s": res["setup_marks_s"],
        "reference_s": reference_s, "cache": res["cache"],
        "cache_dir": res["cache_dir"], "failed_checks": failed_checks,
        "memory_stats": res["memory_stats"],
        "losses": [res["program"]["losses"], ref["losses"]],
    }
    line["compared"] = {k: {"value": v["value"], "limit": v["limit"]}
                        for k, v in table.items()}
    for msg in failed_checks:
        print("check failed: " + msg, file=sys.stderr)
    correct.print_table(table)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
