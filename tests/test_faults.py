"""Fault-injection registry (mxnet_tpu/faults.py): spec grammar,
deterministic schedules, exact fire counts, and the wired sites
(dispatch / io_next / compile_cache.load / kv_push)."""
import os
import time

import numpy as np
import pytest

import jax

jax.config.update("jax_platforms", "cpu")

import mxnet_tpu as mx
from mxnet_tpu import faults, telemetry
from mxnet_tpu.base import MXNetError


@pytest.fixture(autouse=True)
def _clean_registry():
    faults.clear()
    yield
    faults.clear()


# ---------------------------------------------------------------------------
# Spec grammar
# ---------------------------------------------------------------------------

def test_parse_basic_rules():
    rules = faults.parse_spec(
        "dispatch:raise:n=3;d2h:nan:every=2;io_next:delay=50:first=4")
    assert [r.site for r in rules] == ["dispatch", "d2h", "io_next"]
    assert rules[0].action == "raise" and rules[0].n == 3
    assert rules[1].action == "nan" and rules[1].every == 2
    assert rules[2].action == "delay" and rules[2].delay_ms == 50.0 \
        and rules[2].first == 4


def test_parse_probability_with_seed():
    (r,) = faults.parse_spec("kv_push:raise:p=0.25,seed=9")
    assert r.p == 0.25 and r.seed == 9


@pytest.mark.parametrize("bad", [
    "nosuchsite:raise",                 # unknown site
    "dispatch:explode",                 # unknown action
    "dispatch:raise:n=3:extra",         # too many fields
    "dispatch:raise:n=0",               # n < 1
    "dispatch:raise:p=1.5",             # p out of range
    "dispatch:raise:n=2,every=3",       # exclusive schedules
    "dispatch:delay=abc",               # bad delay
])
def test_parse_rejects_bad_specs(bad):
    with pytest.raises(MXNetError):
        faults.parse_spec(bad)


def test_invalid_env_spec_is_ignored_not_fatal(monkeypatch):
    # a typo'd MXNET_FAULTS must not brick the process at an arbitrary
    # dispatch site — it warns and runs fault-free
    monkeypatch.setenv(faults.ENV, "dispatch:bogus")
    faults._loaded = False
    assert faults.active() is False
    assert faults.fire("dispatch") is None


def test_env_spec_loads_lazily(monkeypatch):
    monkeypatch.setenv(faults.ENV, "io_next:raise:n=1")
    faults._loaded = False
    assert faults.active() is True
    assert faults.spec() == "io_next:raise:n=1"


# ---------------------------------------------------------------------------
# Schedules + exact counts
# ---------------------------------------------------------------------------

def test_nth_call_schedule_exact():
    faults.configure("dispatch:raise:n=3")
    fired = []
    for i in range(1, 6):
        try:
            faults.fire("dispatch")
        except faults.InjectedFault:
            fired.append(i)
    assert fired == [3]
    assert faults.counts() == {"dispatch": {"calls": 5, "fired": 1}}


def test_every_schedule_exact():
    faults.configure("dispatch:raise:every=2")
    fired = []
    for i in range(1, 7):
        try:
            faults.fire("dispatch")
        except faults.InjectedFault:
            fired.append(i)
    assert fired == [2, 4, 6]
    assert faults.counts()["dispatch"] == {"calls": 6, "fired": 3}


def test_first_schedule_exact():
    faults.configure("d2h:nan:first=2")
    got = [faults.fire("d2h") for _ in range(5)]
    assert got == ["nan", "nan", None, None, None]


def test_probability_schedule_is_deterministic():
    faults.configure("dispatch:raise:p=0.5,seed=42")
    seq1 = []
    for _ in range(20):
        try:
            faults.fire("dispatch")
            seq1.append(0)
        except faults.InjectedFault:
            seq1.append(1)
    # same seed -> same schedule, exactly
    faults.reset_counts()
    seq2 = []
    for _ in range(20):
        try:
            faults.fire("dispatch")
            seq2.append(0)
        except faults.InjectedFault:
            seq2.append(1)
    assert seq1 == seq2
    assert 0 < sum(seq1) < 20      # p=0.5 over 20 draws: some of each
    assert faults.counts()["dispatch"]["fired"] == sum(seq2)


def test_delay_action_sleeps():
    faults.configure("io_next:delay=30")
    t0 = time.perf_counter()
    assert faults.fire("io_next") is None
    assert time.perf_counter() - t0 >= 0.025


def test_injections_counted_in_telemetry():
    telemetry.enable()
    base = telemetry.counters().get("faults.injected.dispatch", 0)
    faults.configure("dispatch:raise:first=2")
    for _ in range(4):
        try:
            faults.fire("dispatch")
        except faults.InjectedFault:
            pass
    assert telemetry.counters().get("faults.injected.dispatch", 0) \
        - base == 2


def test_raise_rule_does_not_short_circuit_sibling_counts():
    # a raise sharing the call with another firing rule must not eat
    # its telemetry count: registry and telemetry stay EXACTLY equal
    telemetry.enable()
    base = telemetry.counters().get("faults.injected.dispatch", 0)
    faults.configure("dispatch:raise:n=1;dispatch:delay=1")
    with pytest.raises(faults.InjectedFault):
        faults.fire("dispatch")
    assert faults.counts()["dispatch"]["fired"] == 2
    assert telemetry.counters().get("faults.injected.dispatch", 0) \
        - base == 2
    # call 2: only the always-on delay rule fires
    assert faults.fire("dispatch") is None
    assert faults.counts()["dispatch"]["fired"] == 3
    assert telemetry.counters().get("faults.injected.dispatch", 0) \
        - base == 3


def test_injected_fault_is_transient_mxnet_error():
    err = faults.InjectedFault("dispatch")
    assert isinstance(err, MXNetError)
    assert err.transient is True and err.site == "dispatch"


def test_poison_sets_nan_and_skips_non_float():
    f = np.ones((2, 3), np.float32)
    i = np.ones((2,), np.int32)
    ro = np.ones((2,), np.float32)
    ro.setflags(write=False)
    out = faults.poison([f, i, ro])
    assert np.isnan(out[0].reshape(-1)[0])
    assert (out[1] == 1).all()
    assert np.isnan(out[2].reshape(-1)[0])    # copied, then poisoned
    assert not np.isnan(ro.reshape(-1)[0])    # original untouched


# ---------------------------------------------------------------------------
# Wired sites
# ---------------------------------------------------------------------------

def _mlp():
    data = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(data, num_hidden=8, name="fc1")
    return mx.sym.SoftmaxOutput(net, name="softmax")


def test_dispatch_site_fires_in_executor():
    sym = _mlp()
    ex = sym.simple_bind(ctx=mx.cpu(), data=(2, 4))
    ex.forward(is_train=False)        # compile + first dispatch, clean
    faults.configure("dispatch:raise:n=1")
    with pytest.raises(faults.InjectedFault):
        ex.forward(is_train=False)
    faults.clear()
    ex.forward(is_train=False)        # executor still healthy after


def test_io_next_site_raises_and_poisons():
    X = np.random.RandomState(0).normal(size=(8, 4)).astype(np.float32)
    it = mx.io.NDArrayIter(X, None, batch_size=4)
    faults.configure("io_next:raise:n=1")
    it.reset()
    with pytest.raises(faults.InjectedFault):
        next(iter(it))
    # nan action corrupts the DATA arrays
    faults.configure("io_next:nan:n=1")
    it.reset()
    batch = next(iter(it))
    arr = batch.data[0]
    host = arr.asnumpy() if hasattr(arr, "asnumpy") else np.asarray(arr)
    assert np.isnan(host.reshape(-1)[0])


def test_compile_cache_load_site_degrades_to_reject(tmp_path, monkeypatch):
    from mxnet_tpu import compile_cache
    monkeypatch.setenv("MXNET_COMPILE_CACHE", str(tmp_path))
    monkeypatch.setattr(compile_cache, "_DIR_TRUST", {})
    telemetry.enable()
    telemetry.reset()
    sym = _mlp()
    ex = sym.simple_bind(ctx=mx.cpu(), data=(2, 4))
    ex.forward(is_train=False)        # compiles + stores
    assert telemetry.counters().get("compile_cache.store", 0) >= 1
    # an injected load failure must fall back to a fresh compile, not
    # break dispatch
    faults.configure("compile_cache.load:raise")
    telemetry.reset()
    ex2 = sym.simple_bind(ctx=mx.cpu(), data=(2, 4))
    out = ex2.forward(is_train=False)[0].asnumpy()
    assert np.isfinite(out).all()
    c = telemetry.counters()
    assert c.get("compile_cache.reject.injected", 0) >= 1
    assert c.get("compile_cache.hit", 0) == 0


def test_kv_push_site():
    kv = mx.kv.create("local")
    a = mx.nd.ones((4,))
    kv.init(0, a)
    faults.configure("kv_push:raise:n=1")
    with pytest.raises(faults.InjectedFault):
        kv.push(0, mx.nd.ones((4,)))
    # engine healthy after
    kv.push(0, mx.nd.ones((4,)))
    out = mx.nd.zeros((4,))
    kv.pull(0, out=out)
    assert np.isfinite(out.asnumpy()).all()
