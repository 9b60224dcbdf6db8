"""What the two lane probes (``serve_probe.py``, ``module_fit_probe.py``)
share: the virtual CPU mesh flag, the one JSON line a lane prints, and
the choice of lane from the command line. Imports nothing that imports
JAX, so a probe can call ``force_cpu_devices`` before JAX starts."""
import json
import os
import sys


def force_cpu_devices(n):
    """Ask XLA's CPU backend for ``n`` virtual devices. Must run before
    the backend initialises; a count the environment already names
    stands."""
    flags = os.environ.get("XLA_FLAGS", "")
    if "--xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=%d" % n
        ).strip()


def emit(out, json_out):
    """Print a lane's JSON as one line, and write it to ``json_out``."""
    line = json.dumps(out)
    print(line, flush=True)
    if json_out:
        with open(json_out, "w") as f:
            f.write(line + "\n")
    return out


def main(lanes, children=()):
    """Run the one lane ``sys.argv`` names: ``lanes`` maps a flag to a
    function of ``json_out`` (the value of ``--json-out``, or None).
    Flags in ``children`` are legs a lane starts itself and stay out of
    the usage line."""
    chosen = [flag for flag in lanes if flag in sys.argv]
    if len(chosen) != 1:
        raise SystemExit("usage: %s %s [--json-out PATH]" % (
            os.path.basename(sys.argv[0]),
            "|".join(f for f in lanes if f not in children)))
    json_out = None
    if "--json-out" in sys.argv:
        i = sys.argv.index("--json-out") + 1
        if i >= len(sys.argv) or sys.argv[i].startswith("--"):
            raise SystemExit("--json-out: missing output path")
        json_out = sys.argv[i]
    return lanes[chosen[0]](json_out)
