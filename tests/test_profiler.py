"""Profiler + monitor + viz suite — parity with reference test_profiler.py / test_viz.py."""
import json
import os
import subprocess
import sys

import numpy as np

import mxnet_tpu as mx


def test_profiler_chrome_trace(tmp_path):
    fname = str(tmp_path / "profile.json")
    mx.profiler.set_config(profile_all=True, filename=fname)
    mx.profiler.set_state("run")
    a = mx.nd.uniform(shape=(64, 64))
    b = mx.nd.dot(a, a)
    b.wait_to_read()
    mx.profiler.set_state("stop")
    mx.profiler.dump()
    assert os.path.exists(fname)
    with open(fname) as f:
        trace = json.load(f)
    events = trace.get("traceEvents", trace)
    assert isinstance(events, list) and len(events) > 0


def test_profiler_autostart_env(tmp_path):
    """MXNET_PROFILER_AUTOSTART=1 starts tracing at import (config.py
    _autostart_profiler); a later stop dumps the configured file."""
    code = (
        "import mxnet_tpu as mx\n"
        "assert mx.profiler._state['running'] is True, 'not autostarted'\n"
        "a = mx.nd.uniform(shape=(8, 8)); (a * a).wait_to_read()\n"
        "mx.profiler.set_state('stop')\n"
        "import os, json\n"
        "assert os.path.exists('profile.json')\n"
        "json.load(open('profile.json'))\n"
        "print('AUTOSTART_OK')\n")
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, MXNET_PROFILER_AUTOSTART="1",
               JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join(
                   p for p in (root, os.environ.get("PYTHONPATH")) if p))
    proc = subprocess.run([sys.executable, "-c", code], cwd=str(tmp_path),
                          env=env, capture_output=True, text=True,
                          timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "AUTOSTART_OK" in proc.stdout


def test_profiler_scope_region_in_trace(tmp_path):
    """profiler.Scope is a telemetry span: the region is written ONCE,
    by the profiler itself (an annotation on the device dump's clock),
    not merged a second time from the ring; the ring keeps it too."""
    from mxnet_tpu import telemetry
    fname = str(tmp_path / "scope_profile.json")
    mx.profiler.set_config(filename=fname)
    before = telemetry.span_count("my_hot_region")
    mx.profiler.set_state("run")
    with mx.profiler.Scope("my_hot_region"):
        a = mx.nd.uniform(shape=(32, 32))
        mx.nd.dot(a, a).wait_to_read()
    mx.profiler.set_state("stop")
    with open(fname) as f:
        trace = json.load(f)
    events = trace.get("traceEvents", trace)
    assert isinstance(events, list) and events
    region = [e for e in events
              if e.get("ph") == "X" and e["name"] == "my_hot_region"]
    assert len(region) == 1, "Scope region must be in the dump exactly once"
    assert region[0].get("cat") != "host"     # the profiler's own slice
    assert telemetry.span_count("my_hot_region") == before + 1


def test_link_chrome_trace_fallback_no_gz(tmp_path):
    """When the backend produced NO .trace.json.gz (converter skipped),
    _link_chrome_trace must still materialise the configured filename —
    a host-span-only chrome trace, never a missing file."""
    from mxnet_tpu import telemetry
    fname = str(tmp_path / "fallback_profile.json")
    empty_dir = tmp_path / "empty_trace"
    empty_dir.mkdir()
    old = dict(mx.profiler._state)
    try:
        mx.profiler._state.update(
            {"running": False, "filename": fname, "dir": str(empty_dir)})
        telemetry.mark_trace_start()
        with telemetry.span("host_only_span"):
            pass
        mx.profiler._link_chrome_trace()
    finally:
        mx.profiler._state.update(old)
    with open(fname) as f:
        trace = json.load(f)
    names = {e["name"] for e in trace["traceEvents"]
             if e.get("cat") == "host"}
    assert "host_only_span" in names


def test_monitor_taps_outputs():
    mon = mx.monitor.Monitor(interval=1, sort=True)
    data = mx.sym.Variable("data")
    out = mx.sym.exp(data, name="expout")
    exe = out.simple_bind(ctx=mx.current_context(), data=(2, 2))
    mon.install(exe)
    exe.arg_dict["data"][:] = 1.0
    mon.tic()
    exe.forward()
    seen = [name for _, name, _ in mon.toc()]
    assert len(seen) > 0


def test_print_summary(capsys):
    data = mx.sym.Variable("data")
    fc1 = mx.sym.FullyConnected(data=data, num_hidden=16, name="fc1")
    out = mx.sym.SoftmaxOutput(data=fc1, name="softmax")
    mx.visualization.print_summary(out, shape={"data": (1, 8)})
    captured = capsys.readouterr().out
    assert "fc1" in captured
    # 8*16 weights + 16 bias = 144 params
    assert "144" in captured


def test_plot_network_graphviz_or_skip():
    try:
        import graphviz  # noqa: F401
    except ImportError:
        return  # gated: graphviz not installed
    data = mx.sym.Variable("data")
    out = mx.sym.FullyConnected(data=data, num_hidden=4)
    dot = mx.visualization.plot_network(out, shape={"data": (1, 8)})
    assert dot is not None


def test_scope_releases_span_when_annotation_fails(monkeypatch):
    """If the profiler annotation fails to arm, the host span still
    opens, closes and records (every entered span exits — mxlife), and
    the region's own code runs: tracing never costs the program."""
    from mxnet_tpu import telemetry

    def _boom(name, ids, step_num):
        raise RuntimeError("annotation failed to arm")

    telemetry.enable()
    before = telemetry.span_count("failing_region")
    ran = []
    monkeypatch.setattr(telemetry, "_annotation", _boom)
    with mx.profiler.Scope("failing_region"):
        ran.append(True)
    assert ran == [True]
    # the host span closed (one recorded sample), not leaked open
    assert telemetry.span_count("failing_region") == before + 1
    telemetry.reset()
