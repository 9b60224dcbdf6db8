#!/usr/bin/env python3
"""``tools/limits.py`` for a cell whose reference plants its own faults:
read, on the chip and at the cell's own size, what its limits are set
from, and hold the committed limits against it.

    python3 benchmarks/tools/limits_faults.py --workload <cell> \\
        --seeds 1,2,3 [--control-seeds 1] [--out chiprun_out/<file>.jsonl]

Every seed gives the program's numbers (the lower reading). A control seed
also gives the upper readings (``--sides`` names a few of them): ``control``
(the reference with ``reference.CONTROL``: operands and state one step
below what the configuration states), ``half_batch`` (the reference fed
the first half of every sequence's positions, the mean taken over them),
one side for each name in ``reference.FAULTS`` (the reference run with
``fault=<name>``: one mechanism wrong), and the two that need no run:
``state_unchanged`` (a step that returns its state as it got it: all norms
nought) and ``bn_stats_unchanged`` (a fused step that never writes the
auxiliary state back; the name is ``tools/limits.py``'s). Each side is
judged by
``correct.judge`` with the limits of ``limits/<cell>.json`` as a run of
the benchmark would be; ``tests/test_faults_lm.py`` holds the committed
limits against the rows kept in that file.
"""
import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks.harness import correct
from benchmarks.harness.files import load_cell, load_file, read_json


#: the reference's readings with some of the norms nought
unchanged = load_file("benchmarks/tools/limits.py", "bench_limits").unchanged


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--sides", default="",
                    help="of the upper sides, only these (comma-separated)")
    ap.add_argument("--out", default=None)
    ap.add_argument("--rehearse", action="store_true")
    a = ap.parse_args(argv)

    cell, cfg, traffic, window = load_cell(read_json("BENCHMARK.json"),
                                           a.workload)
    reference = load_file(cfg["reference"]["file"], "bench_reference")
    kwargs = cfg["reference"]["kwargs"]
    limits = read_json("benchmarks", "limits", cell["name"] + ".json")[
        "rehearse" if a.rehearse else "limits"]
    control_seeds = {int(s) for s in a.control_seeds.split(",") if s}
    out = open(os.path.join(ROOT, a.out), "a") if a.out else None

    for seed in [int(s) for s in a.seeds.split(",") if s]:
        t0 = time.perf_counter()
        args = argparse.Namespace(seed=seed, seconds=0.0, trace=0,
                                  rehearse=a.rehearse)
        res = window.run(cell, cfg, traffic, args,
                         {"t_process": t0, "readings_only": True})
        ri = res["reference_inputs"]
        ref = correct.reference_readings(reference, kwargs, ri)
        sides = {"program": res["program"]}
        if seed in control_seeds:
            half = dict(ri, batches=[(x[:, :x.shape[1] // 2],
                                      y[:, :y.shape[1] // 2])
                                     for x, y in ri["batches"]])
            upper = {
                "control": lambda: correct.reference_readings(
                    reference, kwargs, ri, **reference.CONTROL),
                "half_batch": lambda: correct.reference_readings(
                    reference, kwargs, half),
                "state_unchanged": lambda: unchanged(
                    ref, ("grad_norms", "change_norms", "aux_change_norms")),
                "bn_stats_unchanged": lambda: unchanged(
                    ref, ("aux_change_norms",))}
            for fault in reference.FAULTS:
                upper[fault] = lambda fault=fault: \
                    correct.reference_readings(reference, kwargs, ri,
                                               fault=fault)
            wanted = [s for s in a.sides.split(",") if s] or list(upper)
            sides.update({name: upper[name]() for name in wanted})
        row = {"seed": seed, "workload": a.workload, "proved": [],
               "losses": {"reference": ref["losses"]},
               "norms": {"reference": ref}}
        for side, readings in sides.items():
            ok, table = correct.judge(correct.compare(readings, ref), limits)
            row["proved"].append({
                "seed": seed, "side": side, "correct": ok,
                "over": [k for k, r in table.items()
                         if r["limit"] is not None
                         and not r["value"] <= r["limit"]],
                "numbers": {k: r["value"] for k, r in table.items()}})
            row["losses"][side] = readings["losses"]
            if side in ("program", "control"):
                row["norms"][side] = readings
            print("seed %d %-18s correct %-5s over %s" % (
                seed, side, ok, row["proved"][-1]["over"]), file=sys.stderr)
        row["seconds"] = time.perf_counter() - t0
        line = json.dumps(row)
        print(line, flush=True)
        if out:
            out.write(line + "\n")
            out.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
