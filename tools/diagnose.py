"""Diagnose script — OS/hardware/python/framework/accelerator report
(parity: reference tools/diagnose.py; the network-mirror checks are
dropped — this build is zero-egress by design).

Usage: python tools/diagnose.py [--accelerator 0]
The accelerator probe runs in a bounded subprocess: this process pins
itself to the CPU platform (it imports the framework), so it never holds
the chip, and a child that cannot get the chip cannot stall the report.
"""
import argparse
import os
import platform
import subprocess
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def section(title):
    print("-" * 24)
    print(title)


def diag_python():
    section("Python")
    print("version      :", sys.version.replace("\n", " "))
    print("executable   :", sys.executable)


def diag_os():
    section("OS")
    print("platform     :", platform.platform())
    print("system       :", platform.system(), platform.release())
    print("machine      :", platform.machine())


def diag_hardware():
    section("Hardware")
    print("cpu count    :", os.cpu_count())
    try:
        with open("/proc/meminfo") as f:
            for line in f:
                if line.startswith(("MemTotal", "MemAvailable")):
                    print(line.strip())
    except OSError:
        pass
    try:
        with open("/proc/cpuinfo") as f:
            for line in f:
                if line.startswith("model name"):
                    print("cpu model    :",
                          line.split(":", 1)[1].strip())
                    break
    except OSError:
        pass


def diag_framework():
    section("Framework")
    os.environ.setdefault("MXNET_TPU_FORCE_CPU", "1")
    import mxnet_tpu as mx
    print("mxnet_tpu    :", mx.__version__,
          "(", os.path.dirname(mx.__file__), ")")
    import jax
    print("jax          :", jax.__version__)
    import numpy
    print("numpy        :", numpy.__version__)
    lib = os.path.join(os.path.dirname(mx.__file__), "_lib",
                       "libmxtpu_c_api.so")
    print("native C ABI :", "built" if os.path.exists(lib) else
          "NOT BUILT (run `make`)")


def diag_accelerator(timeout):
    section("Accelerator")
    code = ("import jax; d = jax.devices()[0]; "
            "print(d.platform, d.device_kind)")
    try:
        proc = subprocess.run([sys.executable, "-c", code],
                              capture_output=True, text=True,
                              timeout=timeout)
        out = proc.stdout.strip()
        print("devices      :", out or proc.stderr.strip()[-200:])
    except subprocess.TimeoutExpired:
        print("devices      : backend init did not finish in %ds "
              "(is another process holding the chip?)" % timeout)


def main():
    ap = argparse.ArgumentParser()
    for choice in ("python", "os", "hardware", "framework",
                   "accelerator"):
        ap.add_argument("--" + choice, default=1, type=int)
    ap.add_argument("--timeout", default=60, type=int)
    args = ap.parse_args()
    if args.python:
        diag_python()
    if args.os:
        diag_os()
    if args.hardware:
        diag_hardware()
    if args.framework:
        diag_framework()
    if args.accelerator:
        diag_accelerator(args.timeout)


if __name__ == "__main__":
    main()
