"""Tier-1 lanes for the serving path.

``tools/serve_probe.py`` runs each lane once on one CPU device:
``--serve-smoke`` (the micro-batching ``serving.InferenceEngine``),
``--warm-smoke`` (two fresh processes over one persisted compile
cache), ``--chaos-smoke`` (overload control under injected faults) and
``--postmortem-smoke`` (the flight recorder). Each test below holds one
property of a lane's JSON: counts, equality, and the guarantees the
engine itself promises. A rate is read on the chip
(``benchmarks/run.py``).
"""
import os
import subprocess
import sys

import pytest

from helpers import ROOT, rate_keys, run_lane


def _lane(flag):
    @pytest.fixture(scope="module")
    def lane(tmp_path_factory):
        out = run_lane("serve_probe.py", flag,
                       tmp_path_factory.mktemp("serving_lane"))
        assert out["lane"] == flag.strip("-").replace("-", "_")
        return out
    return lane


serve = _lane("--serve-smoke")
warm = _lane("--warm-smoke")
chaos = _lane("--chaos-smoke")
postmortem = _lane("--postmortem-smoke")


# -- serve: the micro-batching engine ---------------------------------------

def test_serve_lane_one_program_a_bucket(serve):
    assert serve["max_batch"] >= 8
    assert len(serve["programs"]) == len(serve["buckets"]), \
        (serve["programs"], serve["buckets"])


def test_serve_lane_no_compile_in_window(serve):
    assert serve["telemetry"]["jit_compiles"] == 0, serve["telemetry"]


def test_serve_lane_one_dispatch_a_full_batch(serve):
    """The burst goes out in full batches (the probe's coalescing
    deadline is one no burst reaches): 256 one-row requests are 16
    dispatches of 16 rows, none padded. This is the count behind
    "batched serving beats one request at a time"."""
    c = serve["telemetry"]["counters"]
    n, width = serve["n_requests"], serve["max_batch"]
    assert c["serving.requests"] == c["serving.resolved"] == n, c
    assert c["dispatch.serve"] == c["serving.batches"] == n // width, c
    assert c["serving.batch_rows"] == n and c["serving.pad_rows"] == 0, c


def test_serve_lane_reports_no_rate(serve):
    assert rate_keys(serve) == []


# -- warm: the persisted compile cache --------------------------------------

def test_warm_lane_cold_leg_compiles_and_stores_every_bucket(warm):
    cold, n = warm["cold"], warm["n_buckets"]
    assert cold["jit_compile_spans"] >= n, cold
    assert cold["compile_cache"].get("compile_cache.store", 0) >= n, cold


def test_warm_lane_warm_leg_never_compiles(warm):
    assert warm["warm"]["jit_compile_spans"] == 0, warm["warm"]


def test_warm_lane_every_program_is_a_deserialize_hit(warm):
    leg, n = warm["warm"], warm["n_buckets"]
    assert leg["compile_cache"].get("compile_cache.hit", 0) >= n, leg
    assert leg["jit_deserialize_spans"] >= n, leg
    assert leg["sources"] == ["disk_cache"], leg


def test_warm_lane_deserialized_programs_compute_the_same_bits(warm):
    assert warm["warm"]["probe_sum"] == warm["cold"]["probe_sum"], warm


def test_warm_lane_reports_no_rate(warm):
    assert rate_keys(warm) == []


# -- chaos: overload control at 2x offered load under injected faults -------

@pytest.fixture(scope="module")
def hot(chaos):
    return chaos["at_twice_capacity"]


def test_chaos_lane_no_future_hangs(hot):
    assert hot["hung"] == 0, hot


def test_chaos_lane_every_admitted_request_resolves(hot):
    assert hot["ok"] + hot["shed_deadline"] + hot["failed"] \
        == hot["submitted"], hot


def test_chaos_lane_engine_sheds_under_overload(chaos, hot):
    """The engine degraded DELIBERATELY: structured sheds, not a hung
    queue."""
    assert hot["shed_admission"] + hot["shed_deadline"] > 0, hot
    assert chaos["shed_requests"] > 0, chaos


def test_chaos_lane_admitted_p99_within_promised_deadline(chaos, hot):
    """The one duration a lane compares: the deadline the engine itself
    promised every admitted request."""
    assert hot["admitted_p99_ms"] <= chaos["deadline_ms"], hot


def test_chaos_lane_queue_stays_bounded(chaos, hot):
    assert hot["queued_rows"] <= chaos["max_queue_rows"], hot


def test_chaos_lane_fault_accounting_is_exact(hot):
    """The telemetry counter equals the fault registry's fire count."""
    assert hot["faults_fired"] > 0, hot
    assert hot["faults_injected_counter"] == hot["faults_fired"], hot


def test_chaos_lane_reports_no_rate(chaos):
    assert rate_keys(chaos) == []


# -- postmortem: the flight recorder ----------------------------------------

def test_postmortem_lane_terminal_fault_leaves_a_dump_that_parses(
        postmortem):
    assert postmortem["failed_requests"] > 0
    pm = postmortem["postmortem_path"]
    assert pm and os.path.exists(pm), pm
    assert postmortem["view_rc"] == 0
    # the dump parses through the CLI end to end
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "flight_view.py"),
         pm], stdout=subprocess.PIPE, stderr=subprocess.PIPE,
        text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr[-1000:]
    assert "slowest requests" in proc.stdout


def test_postmortem_lane_dump_names_fault_site_and_dying_batch(postmortem):
    summary = postmortem["view_summary"]
    assert summary["reason"] == "serving_dispatch_failure", summary
    assert summary["exception"]["fault_site"] == "dispatch", summary
    assert sorted(summary["extra"]["req_ids"]) \
        == postmortem["failed_req_ids"], summary["extra"]


def test_postmortem_lane_corrupted_dump_is_rejected(postmortem):
    assert postmortem["corrupt_view_rc"] not in (None, 0)


def test_postmortem_lane_sampler_keeps_a_series_window(postmortem):
    win = postmortem["series_window"]
    assert win["n"] > 0 and len(win["samples"]) == win["n"]
    assert {"ts", "dt_ms", "counters", "queue_depth"} \
        <= set(win["samples"][-1])


def test_postmortem_lane_no_future_hangs(postmortem):
    assert postmortem["hung"] == 0


def test_postmortem_lane_recorder_work_is_bounded_a_request(postmortem):
    """What the recorder does in the waves' window, counted: two spans a
    request (wait, request), two a batch (batch, d2h), one
    ``serving.batch`` event a batch, and nothing else but the lane's
    own injected-fault events and a shed's. This is the count that the
    old share-of-wall-clock guard stood for."""
    rec = postmortem["recorder"]
    requests = rec["counters"]["serving.requests"]
    batches = rec["counters"]["serving.batches"]
    assert requests == rec["requests"] and 0 < batches <= requests, rec
    spans = rec["span_counts"]
    assert set(spans) <= {"serve_wait", "serve_request", "serve_batch",
                          "serve_d2h"}, spans
    assert spans["serve_wait"] <= requests, rec
    assert spans["serve_request"] <= requests, rec
    assert spans["serve_batch"] == spans["serve_d2h"] == batches, rec
    events = rec["event_counts"]
    assert events.pop("serving.batch") == batches, rec
    assert events.pop("serving.shed", 0) <= requests, rec
    assert set(events) <= {"fault.injected"}, events


def test_postmortem_lane_reports_no_rate(postmortem):
    assert rate_keys(postmortem) == []
