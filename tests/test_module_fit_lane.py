"""Tier-1 lanes for the user-facing Module.fit path.

``tools/module_fit_probe.py`` runs each lane once (CPU backend, tiny
MLP): ``--fit-smoke`` on one device, ``--dp-smoke`` on the 8-device
virtual mesh, ``--mp-smoke`` on the same mesh laid out 2x4 dp x mp with
every parameter rule-sharded over mp. Each lane trains the fused
whole-step program and the phase-split oracle from one seed on the same
batches. Every test below holds one property of a lane's JSON: counts,
bytes and equality. A rate is read on the chip (``benchmarks/run.py``).
"""
import pytest

from helpers import rate_keys, run_lane


def _lane(flag):
    @pytest.fixture(scope="module")
    def lane(tmp_path_factory):
        # the probe sets its own virtual-mesh flag for dp and mp
        return run_lane("module_fit_probe.py", flag,
                        tmp_path_factory.mktemp("fit_lane"), timeout=420)
    return lane


fit = _lane("--fit-smoke")
dp = _lane("--dp-smoke")
mp = _lane("--mp-smoke")

# the lane's fixture -> its name in its JSON and the mesh that JSON names
LANES = {"fit": ("module_fit_smoke", {}),
         "dp": ("module_fit_dp_smoke", {"n_devices": 8}),
         "mp": ("module_fit_mp_smoke", {"n_devices": 8,
                                        "mesh_axes": {"dp": 2, "mp": 4}})}


@pytest.fixture(params=sorted(LANES))
def lane(request):
    """Each of the three lanes in turn; its probe has run once a
    module."""
    return request.getfixturevalue(request.param)


def test_fit_lane_fused_is_one_dispatch_a_batch(lane):
    """The window saw ONLY the fused program, once a batch: a batch
    that fell back would add fwd_bwd / opt_update dispatches."""
    assert lane["fused"]["dispatch_counts"] == {
        "train_step": lane["nbatch"]}, lane["fused"]


def test_fit_lane_split_is_three_dispatches_a_batch(lane):
    n = lane["nbatch"]
    assert lane["phase_split"]["dispatch_counts"] == {
        "fwd_bwd": n, "opt_update": n, "metric": n}, lane["phase_split"]


def test_fit_lane_only_the_pinned_leg_fell_back(lane):
    assert lane["fused"]["fallback_code"] is None, lane["fused"]
    assert lane["phase_split"]["fallback_code"] == "env_pin"


def test_fit_lane_fused_params_equal_the_oracle(lane):
    """Two epochs from one seed on the same batches: the fused step's
    parameters are the phase-split oracle's, bit for bit."""
    assert lane["params_bit_equal"] is True


def test_fit_lane_ran_on_the_mesh_asked_for(request, lane):
    name, mesh = LANES[request.node.callspec.params["lane"]]
    assert lane["lane"] == name
    assert {k: lane[k] for k in ("n_devices", "mesh_axes")
            if k in lane} == mesh


def test_fit_lane_reports_no_rate(lane):
    assert rate_keys(lane) == []


def test_mp_lane_param_ledger_is_one_over_mp(mp):
    """Committed param bytes a device under the mp rules against the
    replicated layout (biases and the tiny fc2 rows may leave a little
    slack above the exact 1/mp)."""
    led = mp["ledger"]
    assert led["mp"] == 4
    assert 0 < led["param_bytes_per_device_mp"] * led["mp"] \
        <= 1.5 * led["param_bytes_per_device_replicated"], led
