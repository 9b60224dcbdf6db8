"""The persisted AOT executable cache (mxnet_tpu/compile_cache.py).

The zero-cold-start contract (ISSUE 6): a program compiled once is
serialized into a content-addressed on-disk store and a later
``_InstrumentedProgram`` (a fresh process in production; a fresh
wrapper here) DESERIALIZES it instead of invoking XLA — and every way
the store can lie (corrupt blob, stale jax/jaxlib version tag, wrong
backend or mesh topology, mangled container) degrades to a fresh
compile with ONE structured warning and a ``compile_cache.reject``
counter bump, never to a wrong answer or an error.
"""
import glob
import json
import logging
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx  # noqa: F401  (backend pin via conftest)
from mxnet_tpu import compile_cache, telemetry
from mxnet_tpu import executor as _ex


@pytest.fixture
def cache_dir(tmp_path, monkeypatch):
    d = str(tmp_path / "cc")
    monkeypatch.setenv("MXNET_COMPILE_CACHE", d)
    monkeypatch.delenv("MXNET_CARD_CORPUS", raising=False)
    # per-test single-warning window and a clean counter registry
    compile_cache._WARNED.clear()
    telemetry.enable()
    telemetry.reset()
    yield d
    telemetry.reset()


def _fresh_program(graph_key=None):
    """A new instrumented wrapper over the same tiny fn — each instance
    has an empty in-memory signature cache, so a second instance
    models a fresh process against the shared disk store."""
    def fn(x, y):
        return (x @ y) * 2.0 + jnp.sin(x).sum()
    return _ex._InstrumentedProgram("forward", fn, argnames=("x", "y"),
                                   graph_key=graph_key)


def _args():
    return (jnp.arange(12.0).reshape(3, 4), jnp.ones((4, 2)))


def _cc_counters():
    return {k: v for k, v in telemetry.counters().items()
            if k.startswith("compile_cache.")}


def _entry_files(cache_dir):
    return sorted(glob.glob(os.path.join(cache_dir, "*", "*.mxcc")))


def _span_count(name):
    return telemetry.snapshot()["spans"].get(name, {}).get("count", 0)


# ---------------------------------------------------------------------------
# Enablement
# ---------------------------------------------------------------------------

def test_disabled_by_default(monkeypatch):
    monkeypatch.delenv("MXNET_COMPILE_CACHE", raising=False)
    assert compile_cache.cache_dir() is None
    assert not compile_cache.enabled()
    monkeypatch.setenv("MXNET_COMPILE_CACHE", "0")
    assert not compile_cache.enabled()
    # disabled store/load are clean no-ops
    assert compile_cache.store("0" * 64, object()) == 0
    assert compile_cache.load("0" * 64) is None


def test_disabled_cache_leaves_programs_unaffected(monkeypatch):
    monkeypatch.delenv("MXNET_COMPILE_CACHE", raising=False)
    telemetry.enable()
    telemetry.reset()
    prog = _fresh_program()
    out = prog(*_args())
    assert not _cc_counters()
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(_args()[0] @ _args()[1]) * 2.0
        + np.sin(np.asarray(_args()[0])).sum(), rtol=1e-6)


# ---------------------------------------------------------------------------
# Store / load round trip
# ---------------------------------------------------------------------------

def test_cold_store_then_warm_deserialize(cache_dir):
    cold = _fresh_program()
    expect = np.asarray(cold(*_args()))
    cc = _cc_counters()
    assert cc.get("compile_cache.miss") == 1
    assert cc.get("compile_cache.store") == 1
    assert cc.get("compile_cache.bytes_written", 0) > 0
    assert len(_entry_files(cache_dir)) == 1
    compiles_before = _span_count("jit_compile")
    assert compiles_before >= 1

    warm = _fresh_program()
    got = np.asarray(warm(*_args()))
    cc = _cc_counters()
    assert cc.get("compile_cache.hit") == 1
    # the warm build added NO jit_compile span — XLA never ran
    assert _span_count("jit_compile") == compiles_before
    assert _span_count("jit_deserialize") == 1
    np.testing.assert_array_equal(got, expect)
    # the card distinguishes the disk hit from a compile
    cards = [c for c in telemetry.programs().values()
             if c.get("source") == "disk_cache"]
    assert len(cards) == 1
    assert cards[0]["compile_ms"] == 0.0
    assert cards[0]["deserialize_ms"] >= 0.0


def test_quick_key_tier_skips_tracing(cache_dir):
    gk = ["testgraph", "fwd", True]
    cold = _fresh_program(graph_key=gk)
    cold(*_args())
    traces = _span_count("jit_trace")
    lowers = _span_count("jit_lower")
    assert traces >= 1 and lowers == traces
    warm = _fresh_program(graph_key=gk)
    warm(*_args())
    # the quick-key index resolved before tracing: no new trace or lower
    assert _span_count("jit_trace") == traces
    assert _span_count("jit_lower") == lowers
    assert _span_count("jit_deserialize") == 1
    assert _cc_counters().get("compile_cache.hit") == 1


def test_signature_change_misses(cache_dir):
    cold = _fresh_program()
    cold(*_args())
    other = _fresh_program()
    other(jnp.ones((5, 4)), jnp.ones((4, 2)))   # different shape
    cc = _cc_counters()
    assert cc.get("compile_cache.miss") == 2
    assert cc.get("compile_cache.store") == 2
    assert cc.get("compile_cache.hit") is None


# ---------------------------------------------------------------------------
# Poisoning: every bad entry falls back to a fresh compile with one
# structured warning and a reject counter bump
# ---------------------------------------------------------------------------

def _poison(cache_dir, mutate):
    """Run a cold build, then corrupt its stored entry via
    ``mutate(meta, blob) -> (meta, blob)``."""
    cold = _fresh_program()
    expect = np.asarray(cold(*_args()))
    [path] = _entry_files(cache_dir)
    meta, blob = compile_cache._read_entry(path)
    meta, blob = mutate(meta, blob)
    compile_cache._write_entry(path, meta, blob)
    return expect


def _warm_after_poison(caplog, expect):
    before = _span_count("jit_compile")
    with caplog.at_level(logging.WARNING,
                         logger="mxnet_tpu.compile_cache"):
        warm = _fresh_program()
        got = np.asarray(warm(*_args()))
    # fell back to a FRESH compile, and the answer stayed right
    assert _span_count("jit_compile") == before + 1
    np.testing.assert_array_equal(got, expect)
    return [r for r in caplog.records
            if "compile_cache: rejected" in r.message]


@pytest.mark.parametrize("case", ["corrupt", "version", "mesh"])
def test_poisoned_entry_rejects_once_and_recompiles(
        cache_dir, caplog, case):
    def mutate(meta, blob):
        if case == "corrupt":
            bad = bytearray(blob)
            bad[len(bad) // 2] ^= 0xFF           # flip a payload byte
            return meta, bytes(bad)
        if case == "version":
            meta["jaxlib"] = "0.0.0-stale"       # stale version tag
            return meta, blob
        meta["devices"] = [["tpu", 0], ["tpu", 1],
                           ["tpu", 2], ["tpu", 3]]   # foreign mesh
        return meta, blob

    expect = _poison(cache_dir, mutate)
    warnings = _warm_after_poison(caplog, expect)
    # EXACTLY one structured warning for the poisoned entry
    assert len(warnings) == 1, [r.message for r in warnings]
    cause = {"corrupt": "corrupt", "version": "version",
             "mesh": "mesh"}[case]
    assert "cause=%s" % cause in warnings[0].message
    cc = _cc_counters()
    assert cc.get("compile_cache.reject") == 1
    assert cc.get("compile_cache.reject.%s" % cause) == 1
    assert cc.get("compile_cache.hit") is None


def test_truncated_container_rejects(cache_dir, caplog):
    cold = _fresh_program()
    expect = np.asarray(cold(*_args()))
    [path] = _entry_files(cache_dir)
    with open(path, "wb") as f:
        f.write(b"garbage, not an entry")
    warnings = _warm_after_poison(caplog, expect)
    assert len(warnings) == 1
    assert _cc_counters().get("compile_cache.reject.corrupt") == 1


def test_reject_warns_only_once_across_retries(cache_dir, caplog):
    expect = _poison(cache_dir, lambda m, b: (dict(m, jaxlib="stale"), b))
    with caplog.at_level(logging.WARNING,
                         logger="mxnet_tpu.compile_cache"):
        for _ in range(3):      # three fresh wrappers trip the same entry
            prog = _fresh_program()
            np.testing.assert_array_equal(np.asarray(prog(*_args())),
                                          expect)
    warnings = [r for r in caplog.records
                if "compile_cache: rejected" in r.message]
    assert len(warnings) == 1, [r.message for r in warnings]
    # ...but every attempt still counted
    assert _cc_counters().get("compile_cache.reject") >= 1


def test_mangled_index_reads_as_miss(cache_dir):
    gk = ["g", 1]
    cold = _fresh_program(graph_key=gk)
    cold(*_args())
    [idx] = glob.glob(os.path.join(cache_dir, "index", "*", "*.json"))
    with open(idx, "w") as f:
        f.write("{not json")
    warm = _fresh_program(graph_key=gk)
    warm(*_args())
    # the content-key tier still resolves the program from disk
    assert _cc_counters().get("compile_cache.hit") == 1


# ---------------------------------------------------------------------------
# Donated programs: excluded by default, opt-in round trip
# ---------------------------------------------------------------------------

def _donating_program():
    def step(w, g):
        return w - 0.1 * g
    return _ex._InstrumentedProgram(
        "train_step", step,
        jit_kwargs={"donate_argnums": (0,)}, argnames=("w", "g"))


def test_donated_programs_not_persisted_by_default(cache_dir,
                                                   monkeypatch):
    """Executing a deserialized input-donating executable intermittently
    corrupts the heap on this jaxlib (see compile_cache.persistable) —
    donated programs must stay OFF the persisted tier unless opted in."""
    monkeypatch.delenv("MXNET_COMPILE_CACHE_DONATED", raising=False)
    assert compile_cache.persistable(()) is True
    assert compile_cache.persistable((0,)) is False
    cold = _donating_program()
    cold(jnp.ones((8, 8)), jnp.ones((8, 8)))
    assert not _cc_counters()                    # no store, no miss
    assert _entry_files(cache_dir) == []
    warm = _donating_program()
    out = warm(jnp.ones((8, 8)), jnp.ones((8, 8)))
    np.testing.assert_allclose(np.asarray(out), 0.9)
    assert not _cc_counters()


def test_donated_program_roundtrip_when_opted_in(cache_dir, monkeypatch):
    monkeypatch.setenv("MXNET_COMPILE_CACHE_DONATED", "1")
    assert compile_cache.persistable((0,)) is True
    cold = _donating_program()
    cold(jnp.ones((8, 8)), jnp.ones((8, 8)))
    assert _cc_counters().get("compile_cache.store") == 1
    warm = _donating_program()
    w = jnp.ones((8, 8))
    out = warm(w, jnp.ones((8, 8)))
    assert _cc_counters().get("compile_cache.hit") == 1
    np.testing.assert_allclose(np.asarray(out), 0.9)
    with pytest.raises(RuntimeError):
        _ = np.asarray(w)       # donated buffer really was consumed


# ---------------------------------------------------------------------------
# Corpus store (append-only JSONL)
# ---------------------------------------------------------------------------

def test_corpus_roundtrip(cache_dir):
    path = compile_cache.corpus_path()
    assert path == os.path.join(cache_dir, "card_corpus.jsonl")
    rec = {"kind": "serving", "max_batch": 16, "rows_hist": {"3": 5}}
    assert compile_cache.corpus_append(rec)
    assert compile_cache.corpus_append({"kind": "other", "x": 1})
    got = compile_cache.corpus_records(kind="serving")
    assert got == [rec]
    assert len(compile_cache.corpus_records()) == 2


def test_corpus_env_override_and_disable(tmp_path, monkeypatch):
    monkeypatch.delenv("MXNET_COMPILE_CACHE", raising=False)
    monkeypatch.setenv("MXNET_CARD_CORPUS", str(tmp_path / "c.jsonl"))
    assert compile_cache.corpus_append({"kind": "serving"})
    assert len(compile_cache.corpus_records()) == 1
    monkeypatch.setenv("MXNET_CARD_CORPUS", "0")
    assert compile_cache.corpus_path() is None
    assert not compile_cache.corpus_append({"kind": "serving"})


def test_corpus_skips_mangled_lines(cache_dir):
    path = compile_cache.corpus_path()
    compile_cache.corpus_append({"kind": "serving", "n": 1})
    with open(path, "a") as f:
        f.write("{truncated mid-append\n")   # a killed run's tail
    compile_cache.corpus_append({"kind": "serving", "n": 2})
    recs = compile_cache.corpus_records(kind="serving")
    assert [r["n"] for r in recs] == [1, 2]


def test_corpus_rejects_unserializable(cache_dir):
    assert not compile_cache.corpus_append({"kind": "x",
                                            "bad": object()})


# ---------------------------------------------------------------------------
# Autotune plan round-trips through the corpus (ISSUE 6 acceptance)
# ---------------------------------------------------------------------------

def test_plan_roundtrips_through_corpus(cache_dir):
    from mxnet_tpu.tuner import plan_serving
    rec = {"kind": "serving", "max_batch": 16,
           "rows_hist": {"3": 50, "10": 30, "16": 5},
           "bucket_ms": {"4": {"total_ms": 40.0, "count": 10},
                         "16": {"total_ms": 160.0, "count": 10}},
           "spans": {"serve_d2h": {"total_ms": 100.0, "count": 10},
                     "serve_batch": {"total_ms": 50.0, "count": 10}}}
    compile_cache.corpus_append(rec)
    plan = plan_serving(compile_cache.corpus_records(kind="serving"))
    assert plan is not None
    compile_cache.corpus_append(plan)
    [stored] = compile_cache.corpus_records(kind="autotune_plan")
    assert stored == plan
    # and the plan recomputed from the re-read corpus is the same plan
    again = plan_serving(compile_cache.corpus_records(kind="serving"))
    assert again == plan


def test_untrusted_cache_dir_disables_tier(tmp_path, monkeypatch):
    """Cache entries are pickles: a group/world-writable cache dir must
    disable the persisted tier (another local user could plant
    deserialization payloads at the predictable path)."""
    d = tmp_path / "shared_cc"
    d.mkdir()
    os.chmod(d, 0o777)
    monkeypatch.setenv("MXNET_COMPILE_CACHE", str(d))
    compile_cache._DIR_TRUST.clear()
    try:
        assert not compile_cache.enabled()
        assert compile_cache.load("0" * 64) is None
        telemetry.enable()
        telemetry.reset()
        prog = _fresh_program()
        prog(*_args())                # still compiles and runs fine
        assert not _cc_counters()
        assert _entry_files(str(d)) == []
    finally:
        compile_cache._DIR_TRUST.clear()


def test_owned_private_dir_stays_trusted(tmp_path, monkeypatch):
    d = tmp_path / "own_cc"
    d.mkdir()
    os.chmod(d, 0o700)
    monkeypatch.setenv("MXNET_COMPILE_CACHE", str(d))
    compile_cache._DIR_TRUST.clear()
    try:
        assert compile_cache.enabled()
    finally:
        compile_cache._DIR_TRUST.clear()
