"""Continuous-batching decode engine: slot pool + donated KV cache.

Equivalence methodology: the one thing continuous batching must never
do is change the math. The reference for "slot-batched" is the SAME
engine driven one sequence at a time (decode dispatches at slot bucket
1); the batched leg drives all slots concurrently (bucket S). Token ids
compare exactly; logits, being the results of two programs of different
batch shape, compare within the tolerance that ``helpers.py`` states
once with its reason, in fp32 and bf16. An eager (un-jitted) incremental reference rides along for
token-id equality, catching any batching bug the cross-bucket
comparison could mask.
"""
import threading
import time

import numpy as np
import pytest

import jax.numpy as jnp

import mxnet_tpu as mx
from helpers import assert_equal_across_shapes
from mxnet_tpu import telemetry
from mxnet_tpu.decode import (DecodeEngine, AttentionDecodeCell,
                              LSTMDecodeCell, DeadlineExceeded,
                              QueueOverflow, CircuitOpen, EngineClosed)

PROMPTS = ([3, 1, 4, 1, 5], [9, 2, 6], [5, 3, 5, 8, 9, 7, 9, 3], [2, 7])


def _prompts():
    return [np.array(p, np.int32) for p in PROMPTS]


def _attn_cell(dtype=np.float32, heads=4):
    return AttentionDecodeCell(vocab=29, embed=16, heads=heads,
                               head_dim=8, max_len=48, dtype=dtype)


def _engine(cell, **kw):
    kw.setdefault("slots", 4)
    kw.setdefault("max_prompt_len", 16)
    kw.setdefault("max_new_tokens", 10)
    kw.setdefault("keep_logits", True)
    return DecodeEngine(cell, cell.init_params(1), **kw)


def _serial_then_batched(eng, prompts, **kw):
    """The equivalence harness: one-at-a-time (slot bucket 1) then all
    concurrent (slot bucket N) through the SAME engine and cache pool."""
    serial = [eng.generate(p, **kw) for p in prompts]
    futs = [eng.submit(p, **kw) for p in prompts]
    batched = [f.result(timeout=120) for f in futs]
    return serial, batched


# -- bit-exact equivalence ---------------------------------------------------

@pytest.mark.parametrize("dtype", [np.float32, jnp.bfloat16],
                         ids=["fp32", "bf16"])
def test_slot_batched_bit_exact_attention(dtype):
    """Slot-batched decode against one-at-a-time decode for the
    KV-cached attention cell, in fp32 and bf16: the SAME tokens; bf16
    logits bit for bit, fp32 logits as far as two programs of different
    slot-bucket width can be held to
    (``helpers.assert_equal_across_shapes``)."""
    with _engine(_attn_cell(dtype)) as eng:
        serial, batched = _serial_then_batched(eng, _prompts())
    for a, b in zip(serial, batched):
        assert a.tokens == b.tokens
        assert_equal_across_shapes(a.logits, b.logits)


def test_slot_batched_lstm_tokens_exact():
    """The RNN-shaped cell (hidden/cell state pool): token ids are
    EXACT across slot-bucket widths; the logits are ULP-tight only —
    the (B, E) x (E, 4H) gate matmul specializes per batch width
    (measured: 1-ULP drift at width 4 vs 1), the same
    kernel-specialization reality test_serving.py documents for
    cross-bucket comparisons."""
    cell = LSTMDecodeCell(vocab=23, embed=8, hidden=16, max_len=32)
    with _engine(cell) as eng:
        serial, batched = _serial_then_batched(eng, _prompts())
    for a, b in zip(serial, batched):
        assert a.tokens == b.tokens
        np.testing.assert_allclose(a.logits, b.logits, atol=1e-6)


def test_engine_matches_eager_incremental_reference():
    """The engine's tokens match an UN-JITTED incremental decode using
    the cell's own step math — the cross-implementation check the
    bucket-vs-bucket comparison cannot provide."""
    import jax
    cell = _attn_cell()
    params_np = cell.init_params(1)
    with _engine(cell, slots=2, max_new_tokens=8) as eng:
        got = [eng.generate(p) for p in _prompts()[:2]]
    params = {k: jnp.asarray(v) for k, v in params_np.items()}
    for prompt, res in zip(_prompts()[:2], got):
        state = {n: jnp.zeros(s[1:], d)
                 for n, (s, d) in cell.cache_spec(1).items()}
        # eager prefill: teacher-force the prompt one token at a time
        toks = []
        for i, t in enumerate(prompt):
            state, logits = cell.step(params, state, jnp.int32(t),
                                      jnp.int32(i))
        tok = int(jnp.argmax(logits))
        toks.append(tok)
        pos = len(prompt)
        while len(toks) < 8:
            state, logits = cell.step(params, state, jnp.int32(tok),
                                      jnp.int32(pos))
            tok = int(jnp.argmax(logits))
            toks.append(tok)
            pos += 1
        assert toks == res.tokens
        np.testing.assert_allclose(np.asarray(logits, np.float32),
                                   np.asarray(res.logits[-1], np.float32),
                                   rtol=2e-5, atol=2e-6)


def test_retire_readmit_no_state_bleed():
    """A slot's cache is fully overwritten on re-admission: the same
    prompt decodes bit-identically before and after the slot hosted a
    DIFFERENT longer sequence (stale cache positions past the new
    prompt's length are never attended)."""
    cell = _attn_cell()
    with _engine(cell, slots=1, max_new_tokens=12) as eng:
        probe = np.array([2, 7], np.int32)
        first = eng.generate(probe)
        # occupy the single slot with a longer, different sequence
        eng.generate(np.array([5, 3, 5, 8, 9, 7, 9, 3], np.int32),
                     max_new_tokens=16)
        again = eng.generate(probe)
    assert first.tokens == again.tokens
    assert np.array_equal(first.logits, again.logits)


# -- steady-state compile discipline ----------------------------------------

def test_zero_steady_state_compiles():
    """After warmup every (prompt bucket, slot bucket) program exists:
    live traffic across varying prompt lengths and slot occupancies
    records ZERO jit_compile spans."""
    cell = _attn_cell()
    eng = _engine(cell, max_new_tokens=6)
    try:
        telemetry.reset()      # drop the warmup compiles from the books
        futs = [eng.submit(p) for p in _prompts()]
        [f.result(timeout=120) for f in futs]
        for p in _prompts()[:2]:       # different occupancy mix
            eng.generate(p)
        spans = telemetry.span_stats()
        assert spans.get("jit_compile", {}).get("count", 0) == 0
        assert spans["serve_decode_step"]["count"] == eng.stats()["steps"]
    finally:
        eng.close()


def test_warmup_builds_every_bucket_card():
    cell = _attn_cell()
    with _engine(cell) as eng:
        cards = eng.program_cards()
        prefill = [k for k in cards if k.startswith("decode_prefill")]
        step = [k for k in cards if k.startswith("decode_step")]
        assert len(prefill) == len(eng.prompt_buckets)
        assert len(step) == len(eng.slot_buckets)


# -- ledger interplay --------------------------------------------------------

def test_kv_cache_charged_to_ledger_by_kind():
    """The cache pool is a NAMED by-kind ledger charge: stats() reports
    it, ledger_top() names it (the OOM-postmortem requirement), and the
    per-slot figure divides evenly."""
    cell = _attn_cell()
    with _engine(cell, slots=4) as eng:
        st = eng.stats()
        expect = sum(int(np.prod(s)) * np.dtype(d).itemsize
                     for s, d in cell.cache_spec(4).values())
        assert st["kv_cache_bytes"] == expect
        assert st["kv_cache_bytes_per_slot"] == expect // 4
        # the global per-context ledger carries the charge by kind
        # (>=: every decode engine sharing the context adds to it)
        led = telemetry.ledger().get("mesh(1dev)", {})
        assert led.get("by_kind", {}).get("kv_cache", 0) >= expect
        kinds = {r["kind"] for r in telemetry.ledger_top(64)}
        assert "kv_cache" in kinds


def test_mp_sharded_cache_reads_fraction_of_replicated():
    """The mp leg: under DECODE_PARTITION_RULES on a 1x8 mesh the
    head-sharded cache's committed (per-shard x devices) bytes read
    exactly 1/mp of the same cache replicated onto that mesh."""
    from mxnet_tpu.parallel.ring_attention import DECODE_PARTITION_RULES
    import jax
    if len(jax.devices()) < 8:
        pytest.skip("needs the 8-device CPU mesh")
    cell = _attn_cell(heads=8)
    axes = {"dp": 1, "mp": 8}
    ctxs = [mx.context.cpu(i) for i in range(8)]
    with _engine(cell, partition_rules=DECODE_PARTITION_RULES,
                 mesh_axes=axes, contexts=ctxs) as sharded:
        sharded_bytes = sharded.stats()["kv_cache_bytes"]
        assert sharded.generate(np.array([1, 2, 3], np.int32),
                                max_new_tokens=4).tokens
    with _engine(cell, partition_rules=[], mesh_axes=axes,
                 contexts=ctxs) as repl:
        repl_bytes = repl.stats()["kv_cache_bytes"]
    assert repl_bytes == 8 * sharded_bytes


def test_serving_stats_device_bytes_by_kind():
    """InferenceEngine.stats() now carries the ledger's by-kind view of
    its context (model params vs kv_cache on a shared mesh)."""
    from tests.test_serving import _engine as _serving_engine
    _, _, eng = _serving_engine()
    with eng:
        db = eng.stats()["device_bytes"]
    assert set(db) == {"context", "total", "by_kind"}
    assert isinstance(db["by_kind"], dict)


# -- overload control --------------------------------------------------------

def test_deadline_shed_at_slot_saturation():
    """A saturated slot pool sheds queued prompts past their deadline
    (DeadlineExceeded, cause slot_wait) instead of decoding answers
    nobody is waiting for; the survivor completes."""
    cell = _attn_cell()
    with _engine(cell, slots=1, max_new_tokens=48 - 16) as eng:
        long_fut = eng.submit(_prompts()[2], max_new_tokens=30)
        doomed = eng.submit(_prompts()[0], max_new_tokens=2,
                            deadline_ms=1.0)
        with pytest.raises(DeadlineExceeded):
            doomed.result(timeout=60)
        assert long_fut.result(timeout=120).tokens
        st = eng.stats()
        assert st["shed_by_cause"].get("slot_wait") == 1
        assert st["shed_requests"] == 1
        assert st["resolved"] == 1


def test_queue_overflow_sheds_at_admission():
    cell = _attn_cell()
    with _engine(cell, slots=1, max_queue=1) as eng:
        running = eng.submit(_prompts()[2], max_new_tokens=30)
        # wait for admission so the next submit deterministically QUEUES
        deadline = time.monotonic() + 30
        while eng.overload_state()["active_slots"] < 1:
            assert time.monotonic() < deadline, "admission stalled"
            time.sleep(0.001)
        # the queue bound counts sequences WAITING for a slot; fill it
        queued = eng.submit(_prompts()[0], max_new_tokens=2)
        with pytest.raises(QueueOverflow):
            eng.submit(_prompts()[1], max_new_tokens=2)
        assert running.result(timeout=120).tokens
        assert queued.result(timeout=120).tokens
        assert eng.stats()["shed_by_cause"].get("admission", 0) >= 1


def test_mid_decode_deadline_shed():
    """A slotted sequence past its deadline sheds at the step boundary
    and frees the slot. A delaying step proxy makes the timing
    deterministic (CPU steps are too fast to outlast any real
    deadline)."""
    cell = _attn_cell()
    with _engine(cell, slots=2) as eng:
        real = eng._decode_prog

        class _Slow:
            entry = real.entry

            def __call__(self, *a):
                time.sleep(0.01)
                return real(*a)

        eng._decode_prog = _Slow()
        fut = eng.submit(_prompts()[2], max_new_tokens=30,
                         deadline_ms=50.0)
        with pytest.raises(DeadlineExceeded):
            fut.result(timeout=60)
        eng._decode_prog = real
        st = eng.stats()
        assert st["shed_by_cause"].get("decode") == 1
        assert st["active_slots"] == 0
        # the pool keeps serving after the shed
        assert eng.generate(_prompts()[0], max_new_tokens=2).tokens


def test_dispatch_failure_poisons_pool_and_recovers():
    """A terminal decode-dispatch failure fails every in-flight
    sequence (the donated pool is unrecoverable), rebuilds a zeroed
    pool, and the engine keeps serving — with bit-identical results."""
    cell = _attn_cell()
    with _engine(cell, slots=2, retry_budget=0,
                 breaker_threshold=0) as eng:
        before = eng.generate(_prompts()[0], max_new_tokens=4)
        real = eng._decode_prog

        class _Boom:
            entry = real.entry

            def __call__(self, *a):
                raise ValueError("injected: decode backend fell over")

            def build(self, *a):
                return real.build(*a)

        eng._decode_prog = _Boom()
        fut = eng.submit(_prompts()[1], max_new_tokens=4)
        with pytest.raises(mx.MXNetError, match="poisoned"):
            fut.result(timeout=60)
        eng._decode_prog = real
        after = eng.generate(_prompts()[0], max_new_tokens=4)
        st = eng.stats()
    assert before.tokens == after.tokens
    assert np.array_equal(before.logits, after.logits)
    assert st["failed_requests"] == 1
    assert st["dispatch_failures"] == 1


def test_breaker_trips_and_resets():
    cell = _attn_cell()
    with _engine(cell, slots=1, retry_budget=0, breaker_threshold=1,
                 breaker_reset_s=3600.0) as eng:
        real = eng._decode_prog

        class _Boom:
            entry = real.entry

            def __call__(self, *a):
                raise ValueError("injected")

        eng._decode_prog = _Boom()
        with pytest.raises(mx.MXNetError):
            eng.generate(_prompts()[0], max_new_tokens=4)
        eng._decode_prog = real
        with pytest.raises(CircuitOpen):
            eng.submit(_prompts()[0])
        assert eng.stats()["breaker"]["open"]
        eng.reset_breaker()
        assert eng.generate(_prompts()[0], max_new_tokens=2).tokens


# -- lifecycle ---------------------------------------------------------------

def test_close_drains_admitted_sequences():
    """close() resolves every already-submitted sequence (generation
    completes) before returning; later submits raise EngineClosed."""
    cell = _attn_cell()
    eng = _engine(cell)
    futs = [eng.submit(p, max_new_tokens=6) for p in _prompts()]
    eng.close()
    for f in futs:
        assert len(f.result(timeout=1).tokens) == 6
    with pytest.raises(EngineClosed):
        eng.submit(_prompts()[0])
    eng.close()      # idempotent


def test_submit_validation():
    cell = _attn_cell()
    with _engine(cell) as eng:
        with pytest.raises(mx.MXNetError, match="max_prompt_len"):
            eng.submit(np.arange(17, dtype=np.int32))
        with pytest.raises(mx.MXNetError, match="max_len"):
            eng.submit(_prompts()[0], max_new_tokens=48)
        with pytest.raises(mx.MXNetError, match="non-empty"):
            eng.submit(np.zeros((0,), np.int32))
    with pytest.raises(mx.MXNetError, match="overload"):
        _engine(cell, overload="panic")


def test_eos_stops_generation():
    """Generation stops at the default or per-request EOS id."""
    cell = _attn_cell()
    with _engine(cell, max_new_tokens=12) as eng:
        free = eng.generate(_prompts()[1])
        assert len(free.tokens) == 12
        eos = free.tokens[3]
        stopped = eng.generate(_prompts()[1], eos_id=eos)
        assert stopped.tokens == free.tokens[:4]
        assert stopped.tokens[-1] == eos


# -- telemetry ---------------------------------------------------------------

def test_decode_counters_and_flow_spans():
    """The decode.* counters land and the per-token flow spans
    (serve_prefill -> serve_decode_step x N -> serve_detokenize) are
    recorded with causal req ctx."""
    telemetry.reset()
    cell = _attn_cell()
    with _engine(cell) as eng:
        futs = [eng.submit(p, max_new_tokens=5) for p in _prompts()]
        [f.result(timeout=120) for f in futs]
    c = telemetry.counters()
    assert c["decode.requests"] == 4
    assert c["decode.slot_admit"] == 4
    assert c["decode.slot_retire"] == 4
    assert c["decode.resolved"] == 4
    assert c["decode.tokens"] == 20
    assert c["decode.steps"] >= 4
    spans = telemetry.span_stats()
    for name in telemetry.DECODE_SPANS:
        assert spans[name]["count"] >= 4, name
    assert spans["serve_prefill"]["count"] == 4
    assert spans["serve_detokenize"]["count"] == 4


def test_log_decode_line(caplog):
    from mxnet_tpu.callback import TelemetryLogger
    telemetry.reset()
    logger = TelemetryLogger(frequent=1)
    cell = _attn_cell()
    with caplog.at_level("INFO", logger="mxnet_tpu.telemetry"):
        with _engine(cell, telemetry_logger=logger,
                     max_new_tokens=6) as eng:
            [f.result(timeout=120)
             for f in [eng.submit(p) for p in _prompts()]]
    lines = [r.message for r in caplog.records
             if r.message.startswith("decode:")]
    assert lines
    assert "tok/s=" in lines[-1]
    assert "active_slots=" in lines[-1]


def test_overload_state_for_flight_sampler():
    cell = _attn_cell()
    with _engine(cell) as eng:
        ov = eng.overload_state()
    assert {"queued_rows", "active_slots", "slots", "breaker_open",
            "closed"} <= set(ov)
