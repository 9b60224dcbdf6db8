#!/usr/bin/env python3
"""Read, on the chip and at a cell's own size, what its limits are set
from, and hold the cell's committed limits against it: the program's
numbers over many seeds (the lower reading), and over a few seeds the
control's and each planted fault's (the upper readings).

    python3 benchmarks/tools/limits.py --workload <cell> --seeds 1,2,3 \\
        [--control-seeds 1,2,3] [--out chiprun_out/limits.<cell>.jsonl]

One process: the programs compile once. Every side is judged by
``correct.judge`` with the limits of ``limits/<cell>.json`` as a run of the
benchmark would be, and the verdicts go into the rows' ``proved`` lists;
those of the control seeds are kept in the cell's limits file, where
``tests/test_faults.py`` holds the committed limits against them.

- ``control``: the reference put in the program's place with the operands
  of every convolution and of the classifier, and their gradients, rounded
  to 8-bit floats, and the float32 masters and momentum held in bf16
  (each the step below what the configuration states);
- ``half_batch``: the reference fed the first half of every batch (the
  mean taken over the rest);
- ``state_unchanged``: a step that returns its state as it got it reads 1
  in every norm by the measure itself (no gradient reaches the optimizer,
  nothing changes), so it needs no run: the reference's losses, all
  norms nought;
- ``bn_stats_unchanged``: the same for the moving statistics alone (a
  fused step that never writes them back).
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


def unchanged(ref, keys):
    """The reference's readings with the norms under ``keys`` nought."""
    out = dict(ref)
    for key in keys:
        out[key] = {k: 0.0 for k in ref[key]}
    return out


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--out", default=None)
    ap.add_argument("--rehearse", action="store_true")
    a = ap.parse_args(argv)

    cell, cfg, traffic, window = load_cell(read_json("BENCHMARK.json"),
                                           a.workload)
    reference = load_file(cfg["reference"]["file"], "bench_reference")
    kwargs = cfg["reference"]["kwargs"]
    limits = read_json("benchmarks", "limits", cell["name"] + ".json")[
        "rehearse" if a.rehearse else "limits"]
    seeds = [int(s) for s in a.seeds.split(",") if s]
    control_seeds = {int(s) for s in a.control_seeds.split(",") if s}
    out = open(os.path.join(ROOT, a.out), "a") if a.out else None

    for seed in seeds:
        t0 = time.perf_counter()
        args = argparse.Namespace(seed=seed, seconds=0.0, trace=0,
                                  rehearse=a.rehearse)
        res = window.run(cell, cfg, traffic, args,
                         {"t_process": t0, "readings_only": True})
        ri = res["reference_inputs"]
        ref = correct.reference_readings(reference, kwargs, ri)
        sides = {"program": res["program"]}
        if seed in control_seeds:
            sides["control"] = correct.reference_readings(
                reference, kwargs, ri, **reference.CONTROL)
            half = dict(ri, batches=[(x[:len(x) // 2], y[:len(y) // 2])
                                     for x, y in ri["batches"]])
            sides["half_batch"] = correct.reference_readings(
                reference, kwargs, half)
            sides["state_unchanged"] = unchanged(
                ref, ("grad_norms", "change_norms", "aux_change_norms"))
            sides["bn_stats_unchanged"] = unchanged(
                ref, ("aux_change_norms",))
        row = {"seed": seed, "workload": a.workload, "proved": [],
               "losses": {"reference": ref["losses"]},
               # every leaf's norms, so that a number thought of later can
               # be read from this run
               "norms": {"reference": ref}}
        for side, readings in sides.items():
            numbers = correct.compare(readings, ref)
            ok, table = correct.judge(numbers, limits)
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
