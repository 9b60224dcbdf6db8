"""Tier-1 dist lane (ISSUE 12): real 2-process ``dist_sync`` on one box.

``tools/module_fit_probe.py --dist-smoke`` runs once: two workers wired
through ``jax.distributed`` over localhost (gloo CPU collectives) run
the SAME fused donated-buffer train step over a process-spanning dp
mesh (leg A); a single process runs the same global batch (leg B); and
in the chaos leg (C) rank 1, first a straggler, is killed
deterministically by an injected ``kv_collective`` fault mid-epoch, so
that rank 0 must detect it, re-mesh over the survivors, resume from the
last atomic checkpoint and finish. Every leg runs under a hard timeout
in the probe (a hung worker is a failure, never a hung lane). Each test
below holds one property of the lane's JSON.
"""
import pytest

from helpers import rate_keys, run_lane


@pytest.fixture(scope="module")
def lane(tmp_path_factory):
    # the probe's own per-leg deadlines fire well inside this cap, so a
    # hang still reports as the probe's "worker hung" SystemExit
    out = run_lane("module_fit_probe.py", "--dist-smoke",
                   tmp_path_factory.mktemp("dist_lane"), timeout=780)
    assert out["lane"] == "module_fit_dist_smoke"
    return out


def test_dist_lane_both_workers_finish(lane):
    assert lane["fused"]["rcs"] == [0, 0], lane["fused"]
    assert lane["fused"]["completed"] == [True, True], lane["fused"]


def test_dist_lane_fused_step_never_falls_back(lane):
    assert lane["fused"]["fallback_codes"] == [None, None], lane["fused"]
    assert lane["fused"]["kvstore_dist_fallbacks"] == [0, 0], lane["fused"]


def test_dist_lane_one_fused_collective_step_a_batch(lane):
    assert lane["fused"]["dist_counters"]["kvstore.dist.fused_steps"] \
        == lane["fused"]["steps_expected"], lane["fused"]


def test_dist_lane_replicas_bit_equal_across_ranks(lane):
    assert lane["ranks_bit_equal"] is True


def test_dist_lane_params_match_single_process_oracle(lane):
    """rtol=1e-5: the cross-host psum reassociates the batch
    reduction, so close and not bit-equal."""
    assert lane["single"]["rcs"] == [0] and lane["single"]["completed"]
    assert lane["oracle_allclose"] is True, lane["oracle_max_abs_diff"]


def test_dist_lane_victim_dies_by_the_injected_fault(lane):
    assert lane["chaos"]["rcs"][1] == lane["chaos"]["fault_rc"], \
        lane["chaos"]


def test_dist_lane_survivor_finishes_with_finite_params(lane):
    assert lane["chaos"]["rcs"][0] == 0, lane["chaos"]
    survivor = lane["chaos"]["survivor"]
    assert survivor["completed"] and survivor["finite"], survivor


def test_dist_lane_survivor_remeshes_and_resumes_once(lane):
    elastic = lane["chaos"]["survivor"]["elastic"]
    assert elastic.get("elastic.dead_workers") == 1, elastic
    assert elastic.get("elastic.remesh") == 1, elastic
    assert elastic.get("elastic.resumed") == 1, elastic


def test_dist_lane_postmortem_names_dead_rank_and_step(lane):
    """The survivor's dead_worker dump parses through
    tools/flight_view.py and names rank 1 and the step it died on."""
    assert lane["chaos"]["postmortems"], "no dead_worker postmortem"
    extra = lane["chaos"]["postmortem_extra"]
    assert extra is not None, "flight_view failed to parse"
    assert extra["dead_ranks"] == [1], extra
    assert extra["epoch"] == 1 and extra["nbatch"] == 2, extra


def test_dist_lane_survivor_dump_carries_victims_postmortem(lane):
    """Gathered from the shared flight dir at recovery time."""
    peers = lane["chaos"]["postmortem_extra"]["peer_postmortems"]
    assert any(p["rank"] == 1 and p["reason"] == "worker_abort"
               for p in peers), peers


def test_dist_lane_fleet_view_names_the_dead_rank(lane):
    """ONE merged cluster view (ISSUE 18) over both ranks' dumps."""
    assert lane["chaos"]["fleet_rc"] == 0, lane["chaos"]["fleet_stderr"]
    fleet = lane["chaos"]["fleet"]
    assert fleet["n_ranks"] >= 2, fleet
    assert fleet["dead_ranks"] == [1], fleet


def test_dist_lane_fleet_view_blames_the_straggler(lane):
    """Rank 0's dispatch ran undelayed, so every excess gate wait and
    every dist.straggler verdict lands on rank 1."""
    stragglers = lane["chaos"]["fleet"]["stragglers"]
    assert stragglers and stragglers[0]["rank"] == 1, stragglers
    assert stragglers[0]["straggler_events"] > 0, stragglers


def test_dist_lane_fleet_clocks_solved_from_matched_crossings(lane):
    """The size of the solved offset is held on synthetic skews in
    test_fleet_view.py; here: an offset for every rank, from crossings
    that really matched."""
    clock = lane["chaos"]["fleet"]["clock"]
    assert clock["reference_rank"] == 0
    assert set(clock["offsets_s"]) >= {"0", "1"}, clock
    assert any(int(m) > 0 for r, m in clock["matched_crossings"].items()
               if int(r) != 0), clock


def test_dist_lane_merged_trace_has_a_track_a_rank(lane):
    assert set(lane["chaos"]["trace_tracks"]) >= {0, 1}, lane["chaos"]


def test_dist_lane_reports_no_rate(lane):
    assert rate_keys(lane) == []
