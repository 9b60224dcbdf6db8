"""Tier-1 lane for the continuous-batching decode engine.

``tools/serve_probe.py --decode-smoke`` runs once on the 8-device
virtual CPU mesh (its mp leg needs it). Each test below holds one
property of the lane's JSON: equality, counts and bytes. A rate is read
on the chip (``benchmarks/run.py``).
"""
import pytest

from helpers import (CROSS_SHAPE_ULPS, float32_ulps_at_scale, rate_keys,
                     run_lane)


@pytest.fixture(scope="module")
def lane(tmp_path_factory):
    out = run_lane("serve_probe.py", "--decode-smoke",
                   tmp_path_factory.mktemp("decode_lane"), mesh_devices=8)
    assert out["lane"] == "decode_smoke"
    return out


def test_decode_lane_tokens_equal_one_at_a_time(lane):
    """Slot-batched decode generates the SAME tokens, and the same
    arg-max at every step, as one-at-a-time decode through the same
    engine."""
    assert lane["equality"]["tokens_equal"] is True
    assert lane["equality"]["argmax_equal"] is True


def test_decode_lane_logits_equal_across_slot_widths(lane):
    """Slot bucket 4 against slot bucket 1 is two programs: float32
    logits within the one tolerance ``helpers.py`` states."""
    eq = lane["equality"]
    gap = float32_ulps_at_scale(eq["logits_max_abs_diff"],
                                eq["logits_max_abs"])
    assert gap <= CROSS_SHAPE_ULPS, (gap, eq)


def test_decode_lane_no_compile_in_window(lane):
    """Warmup built every prompt-length and slot-count bucket program
    up front."""
    assert lane["jit_compiles_in_window"] == 0, lane


def test_decode_lane_every_token_and_sequence_accounted(lane):
    c = lane["counters"]
    n_seq = lane["slots"] * lane["waves"]
    assert c["decode.tokens"] == lane["total_tokens"], c
    assert c["decode.requests"] == c["decode.resolved"] == n_seq, c
    assert c["decode.slot_admit"] == c["decode.slot_retire"] == n_seq, c


def test_decode_lane_steps_bounded_by_schedule(lane):
    """Every decode dispatch advanced a full-or-draining pool: the step
    count lands at ~tokens/slots plus the last long tail."""
    c = lane["counters"]
    assert c["decode.steps"] <= lane["total_tokens"] // lane["slots"] \
        + lane["gen_long"], c


def test_decode_lane_continuous_takes_under_half_the_static_steps(lane):
    """Arithmetic on counters: a static whole-batch decoder pays the
    longest member's steps for every wave."""
    assert lane["static_schedule_steps"] \
        == lane["waves"] * lane["gen_long"]
    assert 2 * lane["counters"]["decode.steps"] \
        < lane["static_schedule_steps"], lane["counters"]


def test_decode_lane_kv_ledger_is_one_over_mp(lane):
    """Under DECODE_PARTITION_RULES on the 1x8 mesh the KV-cache pool's
    committed ledger bytes are exactly 1/8 of the same pool replicated
    onto that mesh; the sharded engine still decodes."""
    assert lane["devices"] >= 8
    mp = lane["mp"]
    assert mp["mesh"] == {"dp": 1, "mp": 8}
    assert mp["replicated_kv_bytes"] == 8 * mp["sharded_kv_bytes"], mp
    assert mp["decoded_tokens"] == 8, mp


def test_decode_lane_reports_no_rate(lane):
    assert rate_keys(lane) == []
