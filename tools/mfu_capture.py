"""Fresh-capture MFU/roofline analysis for the bench workload.

One command reproduces PERF.md's breakdown table and roofline ceiling
from a NEW xprof capture (so the analysis tracks the current code, not
round-3's trace):

    python tools/mfu_capture.py              # real chip (or CPU smoke:
    MXTPU_BENCH_SMOKE=1 python tools/mfu_capture.py)

This process imports no JAX and so never holds the chip: the one child
(``bench.py --child``) does, and keeps JAX's persistent compile cache
where ``mxnet_tpu.jax_cache.place()`` says.

Runs ``bench.py --child`` with MXTPU_BENCH_TRACE set, finds the
resulting ``.xplane.pb``, aggregates per-op self time into the same
categories PERF.md uses (convolution fusions / elementwise loop
fusions / copy-and-data-formatting / other), and re-derives the
memory-bound MFU ceiling from the step's FLOPs and bytes.

FLOPs/bytes come from the bench child's PROGRAM CARD first
(``telemetry.programs()`` — the compile-time ``cost_analysis`` /
``memory_analysis`` figures the child embeds in its JSON line as
``step_flops``/``step_bytes_accessed``), so the roofline no longer
NEEDS an xprof capture; the xplane ``hlo_stats`` aggregation remains
as the fallback byte source (older children) and still feeds the
per-category self-time table when a trace materialises.
"""
import argparse
import glob
import json
import os
import re
import subprocess
import sys
import tempfile

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
sys.path.insert(0, REPO)

# HBM bandwidth by device kind (public spec sheets), for the
# FLOP/byte break-even in the roofline re-derivation
HBM_BW = [("v6", 1.6e12), ("trillium", 1.6e12), ("v5p", 2.77e12),
          ("v5 lite", 8.19e11), ("v5e", 8.19e11), ("v5litepod", 8.19e11),
          ("v4", 1.2e12), ("v3", 9.0e11), ("v2", 7.0e11)]


def hbm_bw_for(kind):
    k = kind.lower()
    for sub, val in HBM_BW:
        if sub in k:
            return val
    raise ValueError("mfu_capture: no HBM bandwidth known for device kind "
                     "%r — add it to HBM_BW with its source" % (kind,))


def run_traced_child(trace_dir, timeout):
    env = dict(os.environ)
    env["MXTPU_BENCH_TRACE"] = trace_dir
    try:
        proc = subprocess.run(
            [sys.executable, os.path.join(REPO, "bench.py"), "--child"],
            stdout=subprocess.PIPE, text=True, timeout=timeout, env=env)
        stdout = proc.stdout
    except subprocess.TimeoutExpired as e:
        stdout = e.stdout or b""
        if isinstance(stdout, bytes):
            stdout = stdout.decode("utf-8", "replace")
    for ln in reversed(stdout.splitlines()):
        ln = ln.strip()
        if ln.startswith("{"):
            try:
                return json.loads(ln)
            except ValueError:
                pass
    return None


def find_xplane(trace_dir):
    hits = sorted(glob.glob(os.path.join(
        trace_dir, "**", "*.xplane.pb"), recursive=True))
    return hits[-1] if hits else None


def categorise(name, category_hint=""):
    text = (category_hint or "") + " " + name
    if re.search(r"convolution|%conv", text, re.I):
        return "convolution fusions"
    if re.search(r"copy|transpose|bitcast|data formatting|pad", text, re.I):
        return "copy/data-formatting"
    if re.search(r"select-and-scatter", text, re.I):
        return "select-and-scatter"
    if re.search(r"fusion|add|multiply|divide|maximum|loop", text, re.I):
        return "elementwise loop fusions"
    return "other"


_SKIP = re.compile(
    r"ThunkExecutor|wait for completion|^\$|np\.asarray|^\s*$|"
    r"^python$|profiler|RunExecutable|ExecuteComputation|BufferAlloc",
    re.I)


def hlo_op_rows(xplane_path):
    """Aggregate per-HLO-op self time (and bytes, when the plane carries
    byte stats) straight from the xplane proto — no tool-data converter
    needed. Returns [{name, dur_ps, bytes}]."""
    from tensorflow.tsl.profiler.protobuf import xplane_pb2
    xs = xplane_pb2.XSpace()
    with open(xplane_path, "rb") as f:
        xs.ParseFromString(f.read())
    # prefer accelerator planes; otherwise the host XLA-client lines
    planes = [p for p in xs.planes if "/device:" in p.name.lower()
              or "tpu" in p.name.lower()]
    host_fallback = not planes
    if host_fallback:
        planes = [p for p in xs.planes if p.name == "/host:CPU"]
    agg = {}
    for pl in planes:
        emeta = {k: v for k, v in pl.event_metadata.items()}
        smeta = {k: v.name for k, v in pl.stat_metadata.items()}
        lines = list(pl.lines)
        if host_fallback:
            lines = [ln for ln in lines if "XLA" in ln.name]
        else:
            # device planes carry module/step summary lines whose events
            # span all ops — summing them would double-count; keep the
            # op-level line(s) only
            op_lines = [ln for ln in lines if "ops" in ln.name.lower()]
            if op_lines:
                lines = op_lines
            else:
                lines = [ln for ln in lines
                         if not re.search(r"module|step", ln.name, re.I)]
        for line in lines:
            for ev in line.events:
                md = emeta.get(ev.metadata_id)
                name = (md.display_name or md.name) if md else "?"
                if _SKIP.search(name):
                    continue
                row = agg.setdefault(name, {"name": name, "dur_ps": 0,
                                            "bytes": 0.0, "category": ""})
                row["dur_ps"] += ev.duration_ps
                for st in ev.stats:
                    sname = smeta.get(st.metadata_id, "").lower()
                    # ONLY the aggregate byte counter; per-memory-space
                    # breakdowns ("bytes accessed0{}", ...) would
                    # double-count
                    if sname.replace("_", " ").strip() == "bytes accessed":
                        which = st.WhichOneof("value")
                        if which in ("int64_value", "uint64_value",
                                     "double_value"):
                            row["bytes"] += float(getattr(st, which))
                    elif "category" in sname:
                        which = st.WhichOneof("value")
                        if which == "str_value":
                            row["category"] = st.str_value
                        elif which == "ref_value":
                            row["category"] = smeta.get(st.ref_value, "")
    return list(agg.values())


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--timeout", type=float, default=900)
    ap.add_argument("--trace-dir", default="")
    args = ap.parse_args()

    trace_dir = args.trace_dir or tempfile.mkdtemp(prefix="mfu_trace_")
    print("mfu_capture: tracing into", trace_dir, file=sys.stderr)
    bench_line = run_traced_child(trace_dir, args.timeout)
    if not bench_line or "value" not in bench_line:
        print(json.dumps({"error": "traced bench child yielded no "
                          "measurement", "bench": bench_line}))
        return 1

    # the bench child's program card carries the step's compile-time
    # FLOPs and bytes — the online source that makes the xprof capture
    # optional for the roofline arithmetic
    card_flops = bench_line.get("step_flops")
    card_bytes = bench_line.get("step_bytes_accessed")

    xplane = find_xplane(trace_dir)
    if not xplane and not card_bytes:
        print(json.dumps({"error": "no xplane.pb written and the bench "
                          "child carried no program card",
                          "bench": bench_line}))
        return 1

    out = {"bench": bench_line, "xplane": xplane}
    bytes_total = 0.0
    if xplane:
        rows = hlo_op_rows(xplane)
        shares = {}
        total_ps = 0
        for row in rows:
            total_ps += row["dur_ps"]
            cat = categorise(row["name"], row.get("category", ""))
            shares[cat] = shares.get(cat, 0) + row["dur_ps"]
            bytes_total += row["bytes"]
        top = sorted(rows, key=lambda r: -r["dur_ps"])[:8]
        out.update({
            "hlo_rows": len(rows),
            "op_time_total_ms": round(total_ps / 1e9, 2),
            "self_time_share": {
                k: round(v / total_ps, 4) for k, v in sorted(
                    shares.items(), key=lambda kv: -kv[1])}
            if total_ps else {},
            "top_ops": [{"name": r["name"][:60],
                         "ms": round(r["dur_ps"] / 1e9, 2)} for r in top],
        })
    # roofline ceiling re-derivation (PERF.md arithmetic, fresh inputs):
    # FLOP/byte of the step vs the chip's break-even ratio. Byte source
    # priority: program card (exact, compile-time) > xplane hlo_stats.
    # The roofline is a device metric: the CPU harness check
    # (MXTPU_BENCH_SMOKE) derives none, and an unknown device kind raises.
    from bench import peak_flops_for, ITERS, SMOKE  # noqa: E402
    peak = None if SMOKE else peak_flops_for(bench_line["device"])
    bw = None if SMOKE else hbm_bw_for(bench_line["device"])
    if card_bytes:
        bytes_per_step = float(card_bytes)
        out["bytes_source"] = "program_card"
    elif bytes_total:
        bytes_per_step = bytes_total / ITERS
        out["bytes_source"] = "xplane_hlo_stats"
    else:
        bytes_per_step = None
    if bytes_per_step and bench_line.get("tflops_per_s") and peak and bw:
        step_s = (bench_line["batch"] / bench_line["value"])
        flops_per_step = (float(card_flops) if card_flops
                          else bench_line["tflops_per_s"] * 1e12 * step_s)
        intensity = flops_per_step / bytes_per_step
        out["bytes_accessed_per_step"] = bytes_per_step
        out["flop_per_byte"] = round(intensity, 1)
        out["mfu_roofline_ceiling"] = round(
            min(1.0, intensity / (peak / bw)), 3)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
