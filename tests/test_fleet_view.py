"""Fleet observability suite (ISSUE 18): the shared-flight-dir
artifact discipline (rank-stamped filenames, rank-local throttle — the
two-writer collision regression), the fleet_view merger (per-rank
summaries, dead-rank naming, straggler blame join), the clock-offset
solver (synthetic known-skew round-trip, bounded by one gate-poll
interval), corrupt-dump degradation (named warning; exit 2 only when
ZERO ranks parse), the merged perfetto trace, and peer-postmortem
gathering for the dead_worker cluster view."""
import json
import os
import subprocess
import sys
import time

import pytest

from mxnet_tpu import flight, telemetry

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "tools"))
import fleet_view   # noqa: E402  (stdlib-only CLI module)
import flight_view  # noqa: E402


@pytest.fixture(autouse=True)
def _clean_registry(monkeypatch):
    """Fresh telemetry and an inert flight recorder around every test.
    Both are process-global, and so are the recorder's dump sequence
    and per-reason throttle: whatever dumped before this file in the
    same xdist worker must not number or suppress a dump made here."""
    telemetry.enable()
    telemetry.reset()
    flight.configure(None)
    monkeypatch.setattr(flight, "_seq", 0)
    monkeypatch.setattr(flight, "_last_dump", {})
    yield
    flight.configure(None)
    telemetry.enable()
    telemetry.reset()


# ---------------------------------------------------------------------------
# Synthetic fleet artifacts
# ---------------------------------------------------------------------------

GATE_POLL_S = 0.05      # CollectiveGate default poll — the solver's
                        # documented error bound


def _dump(directory, rank, reason="dead_worker", ts=None, spans=(),
          events=(), counters=None, extra=None, host=None, pid=4000,
          dead_ranks=()):
    rec = {
        "schema": flight_view.SCHEMA_PREFIX + "1",
        "reason": reason,
        "ts": ts if ts is not None else time.time(),
        "pid": pid + rank,
        "process": {"rank": rank, "num_processes": 2,
                    "dead_ranks": list(dead_ranks),
                    "host": host or ("host%d" % rank), "pid": pid + rank},
        "counters": dict(counters or {}),
        "events": list(events),
        "spans": list(spans),
        "online": {"mfu": 0.1 + rank / 100.0},
    }
    if extra is not None:
        rec["extra"] = extra
    path = os.path.join(directory, "postmortem-r%d-%d-001-%s.json"
                        % (rank, pid + rank, reason))
    with open(path, "w") as f:
        json.dump(rec, f)
    return path


def _gate_span(channel, gen, ts, wait_ms=1.0, last_rank=None,
               excess_ms=0.0):
    ctx = {"channel": channel, "generation": gen,
           "wait_ms": round(wait_ms, 3)}
    if last_rank is not None:
        ctx.update({"last_rank": last_rank,
                    "excess_ms": round(excess_ms, 3)})
    return {"name": "gate_wait", "ts": ts, "dur_ms": wait_ms,
            "tid": 1, "ctx": ctx}


def _skewed_fleet(directory, skew_s, n_gens=6):
    """Two ranks recording the same gate crossings; rank 1's clock
    runs ``skew_s`` ahead. Crossing ENDS are the shared instants: both
    ranks leave within a poll of the last arrival, so each rank's
    (ts + dur) for a generation differs only by clock skew + jitter
    inside one poll interval."""
    base = 1000000.0
    spans0, spans1 = [], []
    for gen in range(1, n_gens + 1):
        end = base + gen * 0.5                      # true shared end
        w0, w1 = 40.0, 2.0                          # rank 0 waited
        # a little sub-poll jitter so the solver has to median it out
        j = (gen % 3) * 0.01
        spans0.append(_gate_span("step", gen, end - w0 / 1e3 + j,
                                 wait_ms=w0, last_rank=1,
                                 excess_ms=35.0))
        spans1.append(_gate_span("step", gen,
                                 end + skew_s - w1 / 1e3,
                                 wait_ms=w1, last_rank=1,
                                 excess_ms=35.0))
    straggler_events = [
        {"ts": base + 2.0 + skew_s, "kind": "dist.straggler", "tid": 1,
         "data": {"rank": 1, "channel": "step", "generation": 4,
                  "excess_ms": 35.0, "wait_ms": 2.0, "streak": 3}}]
    _dump(directory, 0, reason="dead_worker", ts=base + 10,
          spans=spans0,
          counters={"heartbeat.gate_wait_ms.step": 240.0,
                    "heartbeat.gate_crossings.step": n_gens},
          extra={"dead_ranks": [1]})
    _dump(directory, 1, reason="worker_abort", ts=base + 9 + skew_s,
          spans=spans1, events=straggler_events,
          counters={"heartbeat.gate_wait_ms.step": 12.0,
                    "heartbeat.gate_crossings.step": n_gens})


# ---------------------------------------------------------------------------
# Satellite 1: shared-flight-dir collision regression — two ranks, one
# directory, rank-stamped filenames, rank-local throttle
# ---------------------------------------------------------------------------

def test_two_ranks_one_flight_dir_no_collision(tmp_path):
    """Two worker processes sharing MXNET_FLIGHT_DIR dump the SAME
    reason back to back: each rank's artifacts are rank-stamped (no
    overwrite), the 1 s per-reason throttle is rank-LOCAL (rank 1's
    dump is not suppressed by rank 0's), and fleet_view reads both."""
    shared = str(tmp_path)
    prog = (
        "import os, sys\n"
        "from mxnet_tpu import flight\n"
        "flight.configure(%r)\n"
        "p1 = flight.postmortem('collide')\n"
        "p2 = flight.postmortem('collide')\n"   # in-throttle: None
        "assert p1 is not None and p2 is None, (p1, p2)\n"
        "print(os.path.basename(p1))\n" % shared)
    procs = []
    for rank in (0, 1):
        env = dict(os.environ, JAX_PLATFORMS="cpu",
                   DMLC_RANK=str(rank), DMLC_NUM_WORKER="2")
        procs.append(subprocess.Popen(
            [sys.executable, "-c", prog], stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT, text=True, env=env, cwd=ROOT))
    names = []
    for rank, p in enumerate(procs):
        out, _ = p.communicate(timeout=300)
        assert p.returncode == 0, out[-2000:]
        names.append(out.strip().splitlines()[-1])
    assert names[0].startswith("postmortem-r0-")
    assert names[1].startswith("postmortem-r1-")
    dumps = sorted(f for f in os.listdir(shared) if f.endswith(".json"))
    assert len(dumps) == 2, dumps          # one per rank, zero clobbers
    ranks, warnings = fleet_view.load_fleet(shared)
    assert warnings == []
    assert sorted(ranks) == [0, 1]
    for rank, data in ranks.items():
        rec = data["rec"]
        assert rec["reason"] == "collide"
        assert rec["process"]["rank"] == rank


def test_postmortem_filename_and_series_are_rank_stamped(tmp_path):
    flight.configure(str(tmp_path))
    path = flight.postmortem("unit")
    ident = telemetry.process_identity()
    assert os.path.basename(path) == (
        "postmortem-r%d-%d-001-unit.json"
        % (ident["rank"], os.getpid()))
    # the dump's identity block matches the filename stamp
    rec = flight_view.load_dump(path)
    assert rec["process"]["rank"] == ident["rank"]
    assert rec["process"]["host"] == ident["host"]


# ---------------------------------------------------------------------------
# Satellite 3: clock-offset solver — synthetic known-skew round-trip,
# corrupt-dump degradation
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("skew_s", [1.75, -0.6])
def test_clock_offset_round_trip_within_one_poll(tmp_path, skew_s):
    """Two synthetic rank dumps whose clocks differ by a KNOWN skew:
    the solver recovers it within one gate-poll interval (the crossing
    ends it matches are only that well aligned by construction)."""
    _skewed_fleet(str(tmp_path), skew_s)
    ranks, warnings = fleet_view.load_fleet(str(tmp_path))
    assert warnings == []
    ref, offsets, matched = fleet_view.solve_offsets(ranks)
    assert ref == 0
    assert offsets[0] == 0.0
    assert matched[1] == 6
    assert abs(offsets[1] - skew_s) <= GATE_POLL_S
    # applying the offset lands rank 1's crossings on rank 0's
    # timebase to within the same bound
    c0 = fleet_view.gate_crossings(ranks[0]["rec"])
    c1 = fleet_view.gate_crossings(ranks[1]["rec"])
    for key in c0:
        assert abs((c1[key] - offsets[1]) - c0[key]) <= GATE_POLL_S


def test_fleet_summary_names_dead_and_stragglers(tmp_path):
    _skewed_fleet(str(tmp_path), 1.75)
    ranks, warnings = fleet_view.load_fleet(str(tmp_path))
    summary = fleet_view.summarize(ranks, warnings)
    assert summary["schema"] == fleet_view.FLEET_SCHEMA
    assert summary["n_ranks"] == 2
    # dead: union of the worker_abort reason and the survivor's extra
    assert summary["dead_ranks"] == [1]
    # blame join: rank 0's spans attribute their waits to rank 1;
    # rank 1's dist.straggler verdict corroborates
    top = summary["stragglers"][0]
    assert top["rank"] == 1
    assert top["blamed_crossings"] == 6
    assert top["blamed_wait_ms"] == pytest.approx(240.0)
    assert top["straggler_events"] == 1
    rs = summary["ranks"]["0"]
    assert rs["host"] == "host0"
    assert rs["gate_wait_ms"] == {"step": 240.0}
    assert rs["crossings"] == {"step": 6}
    assert rs["mfu"] == pytest.approx(0.10)


def test_corrupt_dump_degrades_to_named_warning(tmp_path, capsys):
    """A malformed per-rank dump must not take the fleet view down:
    the rank is skipped with a warning NAMING the file, the remaining
    ranks still merge, and the exit code stays 0. Only a fleet with
    zero parseable ranks exits 2."""
    _skewed_fleet(str(tmp_path), 0.5)
    bad = os.path.join(str(tmp_path), "postmortem-r2-9999-001-x.json")
    with open(bad, "w") as f:
        f.write("{\"schema\": \"mxnet_tpu.flight/1\", \"reason\":")
    rc = fleet_view.main(["fleet_view.py", str(tmp_path), "--json"])
    captured = capsys.readouterr()
    assert rc == 0
    summary = json.loads(captured.out)
    assert summary["n_ranks"] == 2          # rank 2 skipped
    assert any("postmortem-r2" in w for w in summary["warnings"])
    assert "postmortem-r2" in captured.err  # named on stderr too


def test_zero_parseable_ranks_exits_2(tmp_path, capsys):
    bad = os.path.join(str(tmp_path), "postmortem-r0-1-001-x.json")
    with open(bad, "w") as f:
        f.write("not json")
    assert fleet_view.main(["fleet_view.py", str(tmp_path)]) == 2
    assert "no parseable rank dumps" in capsys.readouterr().err
    # empty dir: same verdict
    empty = os.path.join(str(tmp_path), "empty")
    os.makedirs(empty)
    assert fleet_view.main(["fleet_view.py", empty]) == 2
    # bad usage
    assert fleet_view.main(["fleet_view.py"]) == 2
    assert fleet_view.main(["fleet_view.py", str(tmp_path),
                            "--bogus"]) == 2


# ---------------------------------------------------------------------------
# Merged trace
# ---------------------------------------------------------------------------

def test_merged_trace_tracks_offsets_and_gate_flows(tmp_path):
    _skewed_fleet(str(tmp_path), 1.75)
    ranks, _ = fleet_view.load_fleet(str(tmp_path))
    trace = fleet_view.merged_trace(ranks)
    evs = trace["traceEvents"]
    # one labelled process track per rank; the dead one is marked
    pnames = {e["pid"]: e["args"]["name"] for e in evs
              if e.get("ph") == "M" and e["name"] == "process_name"}
    assert pnames[0] == "rank 0 (host0)"
    assert pnames[1] == "rank 1 (host1) [dead]"
    # offset correction: matching crossings land within one poll on
    # the merged (reference) timebase
    ends = {}
    for e in evs:
        if e.get("ph") == "X" and e["name"] == "gate_wait":
            gen = e["args"]["generation"]
            ends.setdefault(gen, {})[e["pid"]] = e["ts"] + e["dur"]
    for gen, per_rank in ends.items():
        assert abs(per_rank[0] - per_rank[1]) <= GATE_POLL_S * 1e6
    # cross-rank flow arrows tie each generation's crossings together
    flows = [e for e in evs if e.get("cat") == "gate"]
    assert {e["ph"] for e in flows} == {"s", "f"}
    assert len([e for e in flows if e["ph"] == "s"]) == 6
    # instant markers for the straggler verdict ride on rank 1's track
    marks = [e for e in evs if e.get("ph") == "i"
             and e["name"] == "dist.straggler"]
    assert marks and marks[0]["pid"] == 1


def test_fleet_view_cli_json_and_trace(tmp_path):
    _skewed_fleet(str(tmp_path), 0.8)
    view = os.path.join(ROOT, "tools", "fleet_view.py")
    trace_out = str(tmp_path / "merged.json")
    proc = subprocess.run(
        [sys.executable, view, str(tmp_path), "--json",
         "--trace", trace_out],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        timeout=120)
    assert proc.returncode == 0, proc.stderr
    summary = json.loads(proc.stdout)
    assert summary["dead_ranks"] == [1]
    assert abs(summary["clock"]["offsets_s"]["1"] - 0.8) <= GATE_POLL_S
    with open(trace_out) as f:
        trace = json.load(f)
    assert trace["metadata"]["reference_rank"] == 0
    # the human render mode works on the same dir
    proc = subprocess.run([sys.executable, view, str(tmp_path)],
                          stdout=subprocess.PIPE, stderr=subprocess.PIPE,
                          text=True, timeout=120)
    assert proc.returncode == 0
    assert "dead ranks: [1]" in proc.stdout
    assert "straggler ranking" in proc.stdout


# ---------------------------------------------------------------------------
# Tentpole (c): peer-postmortem gathering — the survivor's dead_worker
# dump carries the victim's last seconds
# ---------------------------------------------------------------------------

def test_gather_peer_postmortems_picks_newest_per_peer(tmp_path):
    shared = str(tmp_path)
    _dump(shared, 1, reason="worker_abort", ts=123.0,
          events=[{"ts": 122.9, "kind": "fault.injected", "tid": 1,
                   "data": {"site": "kv_collective"}}])
    # an older dump from the same peer must lose to the newer one
    older = os.path.join(shared, "postmortem-r1-4001-000-early.json")
    with open(older, "w") as f:
        json.dump({"schema": "mxnet_tpu.flight/1", "reason": "early",
                   "ts": 1.0, "counters": {}, "events": [],
                   "spans": []}, f)
    t = time.time()
    os.utime(older, (t - 100, t - 100))
    _dump(shared, 0, reason="dead_worker")     # self: excluded
    peers = flight.gather_peer_postmortems(directory=shared,
                                           exclude_rank=0)
    assert len(peers) == 1
    p = peers[0]
    assert p["rank"] == 1
    assert p["reason"] == "worker_abort"
    assert p["events_tail"][-1]["kind"] == "fault.injected"
    # unreadable dir: degrade to empty, never raise
    assert flight.gather_peer_postmortems(
        directory=os.path.join(shared, "absent")) == []


def test_snapshot_and_series_carry_process_identity():
    snap = telemetry.snapshot()
    ident = telemetry.process_identity()
    assert snap["process"] == ident
    assert set(ident) == {"rank", "num_processes", "dead_ranks",
                          "host", "pid"}
    win = flight.series_window(1)
    assert win["process"] == ident
