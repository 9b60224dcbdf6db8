"""Profiler — chrome-trace output of device execution + host spans.

Parity: reference ``src/engine/profiler.{h,cc}`` + ``python/mxnet/
profiler.py`` (SURVEY.md §5.1; chrome://tracing JSON output). TPU-native
design: wraps the JAX/XLA profiler, which records real device op spans
(the reference stamped engine-op spans). ONE CLOCK: importing this
module installs ``jax.profiler.TraceAnnotation`` as telemetry's
annotation factory, so every same-thread telemetry span (fit_batch/
feed/step_prep/step/step_install/io_next/bind/...) is written by the
profiler itself, on ``/host:CPU`` beside the device planes and on
their clock, with its causal ids (``epoch``, ``nbatch``) as stats —
whoever started the trace (``set_state('run')``, a bare
``jax.profiler.start_trace``, a benchmark). ``fit_batch`` is a
``StepTraceAnnotation``, which the profiler's step view groups by.
Only spans that cross threads (the serving request chain) and
retroactive ones are MERGED from telemetry's ring into the chrome
JSON, with their request flow arrows; their alignment to the device
clock is a guess by magnitude (``_aligned_host_events``).
TensorBoard-compatible artifacts stay in the output directory.
"""
from __future__ import annotations

import glob
import json
import os
import time

import jax

from . import telemetry
from .base import MXNetError

__all__ = ["profiler_set_config", "profiler_set_state", "set_config",
           "set_state", "dump", "pause", "resume"]

_state = {"running": False, "filename": "profile.json", "dir": None}


def _annotation(name, ids, step_num):
    """Telemetry's annotation factory: the profiler-side half of a
    same-thread span, its causal ids riding as the event's stats."""
    ids = ids or {}
    if step_num is not None:
        return jax.profiler.StepTraceAnnotation(name, step_num=step_num,
                                                **ids)
    return jax.profiler.TraceAnnotation(name, **ids)


telemetry._annotation = _annotation


def set_config(profile_all=None, profile_symbolic=None,
               profile_imperative=None, profile_memory=None, profile_api=None,
               filename="profile_output.json", **kwargs):
    """(parity: mx.profiler.set_config / MXSetProcessProfilerConfig)"""
    _state["filename"] = filename


profiler_set_config = set_config


def set_state(state="stop", profile_process="worker"):
    """(parity: mx.profiler.set_state — 'run' starts tracing, 'stop' dumps)"""
    if state == "run":
        if not _state["running"]:
            out_dir = os.path.splitext(_state["filename"])[0] + "_trace"
            os.makedirs(out_dir, exist_ok=True)
            jax.profiler.start_trace(out_dir)
            # stamp the host-span window: the merged dump keeps only
            # spans recorded while the device trace ran
            telemetry.mark_trace_start()
            _state["dir"] = out_dir
            _state["running"] = True
    elif state == "stop":
        if _state["running"]:
            jax.profiler.stop_trace()
            _state["running"] = False
            _link_chrome_trace()
    else:
        raise MXNetError("state must be 'run' or 'stop'")


profiler_set_state = set_state


# any epoch-microsecond stamp after ~1973 exceeds this; a trace-relative
# stamp would need a ~3-year-long trace to reach it
_EPOCH_TS_FLOOR_US = 1e14


def _aligned_host_events(device_events, host):
    """Ring-only span events (cross-thread request spans, retroactive
    ``record_span`` ones, their flows) on the device trace's timebase,
    by a GUESS: same-thread spans need none, the profiler stamps them.
    Telemetry stamps spans in epoch microseconds; XLA's trace converter
    may emit epoch-based OR trace-relative timestamps depending on
    version. The two cases are separated by MAGNITUDE (epoch stamps are
    ~1.7e15 us; trace-relative ones start near zero — a first-device-op
    gap, e.g. a minutes-long in-window compile, cannot cross that
    line): epoch-based device stamps need no adjustment; trace-relative
    ones get the host events shifted so the trace-start instant maps
    onto the earliest device timestamp."""
    t0_us = telemetry.trace_start_epoch_us()
    dts = [e["ts"] for e in device_events
           if e.get("ph") in ("X", "B") and "ts" in e]
    if not dts or t0_us is None:
        return host
    dmin = min(dts)
    if dmin > _EPOCH_TS_FLOOR_US:    # device stamps already epoch-based
        return host
    shift = dmin - t0_us
    for e in host:
        if "ts" in e:
            e["ts"] = round(e["ts"] + shift, 3)
    return host


def _link_chrome_trace():
    """Surface the chrome trace at the configured filename as plain JSON
    — the reference emits an uncompressed chrome://tracing file
    (profiler.cc:161) — with the telemetry spans the profiler did NOT
    write itself (those that cross threads, with their request flows)
    merged into the device event list; the same-thread spans are in the
    device dump already, as annotations. When the backend produced no
    ``.trace.json.gz`` (some platforms/versions skip the converter), a
    trace of every ring span is still written so the configured
    filename always materialises."""
    out_dir = _state["dir"]
    if not out_dir:
        return
    matches = glob.glob(os.path.join(out_dir, "**", "*.trace.json.gz"),
                        recursive=True)
    host = telemetry.chrome_events(skip_annotated=bool(matches))
    # what rides in the file's otherData (a chrome-trace field perfetto
    # preserves): the program cards (cost/memory/compile figures of
    # every program dispatched) and the flight recorder's recent
    # time-series window, so one file carries timeline, cost model AND
    # the metrics trajectory around the captured window
    from . import flight
    other = {"mxnet_tpu_programs": telemetry.programs(),
             "mxnet_tpu_series": flight.series(240)}
    other = {k: v for k, v in other.items() if v}
    if not any(e.get("ph") == "X" for e in host):
        host = []           # no slice to merge: no empty track either
    if matches and not other and not host:
        # nothing to add (telemetry disabled): stream the device dump
        # through verbatim instead of paying a full parse+re-serialize
        # of a potentially huge trace
        import gzip
        import shutil
        with gzip.open(sorted(matches)[-1], "rb") as src, \
                open(_state["filename"], "wb") as dst:
            shutil.copyfileobj(src, dst)
        return
    trace = None
    if matches:
        import gzip
        with gzip.open(sorted(matches)[-1], "rb") as src:
            raw = src.read()
        try:
            trace = json.loads(raw.decode("utf-8", "replace"))
        except ValueError:
            # unparseable device dump: keep the reference behavior
            # (surface it verbatim) rather than lose it to the merge
            with open(_state["filename"], "wb") as dst:
                dst.write(raw)
            return
    if not isinstance(trace, dict) or \
            not isinstance(trace.get("traceEvents"), list):
        events = trace if isinstance(trace, list) else []
        trace = {"traceEvents": events, "displayTimeUnit": "ms"}
    trace["traceEvents"].extend(
        _aligned_host_events(trace["traceEvents"], host))
    if other and isinstance(trace.setdefault("otherData", {}), dict):
        trace["otherData"].update(other)
    with open(_state["filename"], "w") as dst:
        json.dump(trace, dst)


def dump(finished=True, profile_process="worker"):
    """(parity: mx.profiler.dump)"""
    if _state["running"]:
        set_state("stop")


def pause(profile_process="worker"):
    pass


def resume(profile_process="worker"):
    pass


def Scope(name):
    """Annotate a region: a telemetry span, hence an annotation in the
    profiler's trace AND an entry in the ring and the snapshot
    percentiles (parity: the reference's ``profiler.Scope``)."""
    return telemetry.span(name)


def dump_profile():
    """Deprecated alias of dump() (parity: profiler.dump_profile)."""
    dump(True)
