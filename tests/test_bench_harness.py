"""The bench harness must be un-losable: a child that already printed its
measurement and THEN hangs (a stall in the optional module phase, or a
hang the parent can only kill from outside) must still yield a parsed
result in the supervisor.

Mirrors the reference's benchmark_score.py contract of always emitting a
number; what is ours is the rule that a machine with no chip, an unknown
device kind or a requested phase that yields nothing is a non-zero exit
and never a quiet fallback."""
import json
import os
import subprocess
import sys
import textwrap

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import bench  # noqa: E402


def test_last_json_line_picks_last_parseable():
    text = "\n".join([
        "noise",
        json.dumps({"value": 1}),
        "bench: warming up",
        json.dumps({"value": 2, "unit": "img/s"}),
        "{truncated",  # a partial line from a killed child
    ])
    assert bench._last_json_line(text) == {"value": 2, "unit": "img/s"}


def test_last_json_line_accepts_bytes():
    # TimeoutExpired.stdout can be bytes even under text=True
    raw = (json.dumps({"value": 3.5}) + "\n").encode()
    assert bench._last_json_line(raw) == {"value": 3.5}
    assert bench._last_json_line(None) is None
    assert bench._last_json_line("") is None


def test_run_phase_salvages_stdout_of_hung_child(tmp_path, monkeypatch):
    """A child that prints its JSON then hangs forever: _run_phase must
    kill it at the timeout and return the salvaged measurement."""
    stub = tmp_path / "hang_after_print.py"
    stub.write_text(textwrap.dedent("""
        import json, sys, time
        print(json.dumps({"value": 42.0, "unit": "img/s"}), flush=True)
        time.sleep(3600)
    """))
    orig = subprocess.run

    def fake_run(cmd, **kw):
        # route the harness's child invocation to the hanging stub
        return orig([sys.executable, str(stub)], **kw)

    monkeypatch.setattr(subprocess, "run", fake_run)
    # the timeout must comfortably cover interpreter startup on a
    # loaded host, so that the print lands first
    parsed, timed_out = bench._run_phase("--child", timeout=20)
    assert timed_out
    assert parsed == {"value": 42.0, "unit": "img/s"}


def test_run_phase_handles_crash_without_output(tmp_path, monkeypatch):
    stub = tmp_path / "crash.py"
    stub.write_text("import sys; sys.exit(7)\n")
    orig = subprocess.run

    def fake_run(cmd, **kw):
        return orig([sys.executable, str(stub)], **kw)

    monkeypatch.setattr(subprocess, "run", fake_run)
    parsed, timed_out = bench._run_phase("--child", timeout=10)
    assert parsed is None and not timed_out


@pytest.mark.slow
def test_smoke_end_to_end():
    """Full harness in smoke mode: one JSON line on stdout, rc 0."""
    env = dict(os.environ, MXTPU_BENCH_SMOKE="1")
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "bench.py")],
        stdout=subprocess.PIPE, text=True, timeout=900, env=env)
    assert proc.returncode == 0
    out = bench._last_json_line(proc.stdout)
    assert out is not None and "value" in out and out["unit"] == "img/s"


def _patched_supervise(monkeypatch, phases, deadline=30.0, smoke=False,
                       ab=False):
    """Run supervise() with _run_phase replaced by a scripted stub.
    `phases` maps mode -> callable returning (parsed, timed_out); the
    stub records the call sequence. Returns (rc, calls, stdout_json)."""
    calls = []

    def fake_phase(mode, timeout, env_extra=None):
        calls.append(mode)
        n = calls.count(mode)
        fn = phases[mode]
        if fn.__code__.co_argcount >= 2:
            return fn(n, env_extra)
        return fn(n)

    monkeypatch.setenv("MXTPU_BENCH_AB", "1" if ab else "0")
    # optional phases default OFF here; dedicated tests opt back in
    monkeypatch.setenv("MXTPU_BENCH_DP", "0")
    monkeypatch.setenv("MXTPU_BENCH_SERVE", "0")
    monkeypatch.setenv("MXTPU_BENCH_DECODE", "0")
    monkeypatch.setattr(bench, "_run_phase", fake_phase)
    monkeypatch.setattr(bench, "TOTAL_DEADLINE", deadline)
    monkeypatch.setattr(bench, "SMOKE", smoke)
    monkeypatch.setattr(bench, "PROBE_TIMEOUT", 1.0)
    monkeypatch.setattr(bench, "PROBE_GAP", 0.0)
    monkeypatch.setattr(bench, "RAW_MIN", 0.5)
    import io
    from contextlib import redirect_stdout
    buf = io.StringIO()
    with redirect_stdout(buf):
        rc = bench.supervise()
    return rc, calls, bench._last_json_line(buf.getvalue())


def test_supervise_emits_error_json_when_backend_never_up(monkeypatch):
    """Probes that never succeed: no raw child is ever launched, and a
    diagnostic JSON line is still printed (a killed harness with nothing
    parseable on stdout must be impossible)."""
    import time as _time

    def failing_probe(n):
        _time.sleep(0.2)  # a real probe child costs wall-clock
        return None, True

    rc, calls, out = _patched_supervise(
        monkeypatch,
        {"--probe": failing_probe},
        deadline=2.0)
    assert rc == 1
    assert "--child" not in calls          # raw child is probe-gated
    assert calls.count("--probe") >= 2     # it LOOPS, not one-shot
    assert out is not None and "error" in out and out["probe_ok"] is False


def test_supervise_probe_gates_then_measures(monkeypatch):
    """First probe fails, second succeeds, raw child then measures; the
    module phase result is merged in."""
    meas = {"value": 123.0, "unit": "img/s"}
    rc, calls, out = _patched_supervise(
        monkeypatch,
        {"--probe": lambda n: ((None, True) if n == 1
                               else ({"device": "x"}, False)),
         "--child": lambda n: (dict(meas), False),
         "--module-child": lambda n: ({"module_fit_img_s": 99.0}, False)},
        deadline=600.0)
    assert rc == 0
    assert calls.index("--child") > calls.index("--probe")
    assert out["value"] == 123.0 and out["module_fit_img_s"] == 99.0


def test_supervise_raw_failure_returns_to_probing(monkeypatch):
    """A raw child that dies after a good probe sends the loop back to
    probing; a later raw attempt can still win."""
    state = {"raw": 0}

    def raw(n):
        state["raw"] = n
        if n < 2:
            return None, False
        return {"value": 7.0, "unit": "img/s"}, False

    monkeypatch.setenv("MXTPU_BENCH_MODULE", "0")
    rc, calls, out = _patched_supervise(
        monkeypatch,
        {"--probe": lambda n: ({"device": "x"}, False), "--child": raw},
        deadline=600.0)
    assert rc == 0 and out["value"] == 7.0 and state["raw"] == 2


def test_supervise_fused_bn_ab_phase(monkeypatch):
    """With budget left after the raw number, a second raw child runs
    with the fused-BN knob pinned on; the baseline pins it off."""
    envs = []

    def raw(n, env_extra=None):
        envs.append(env_extra)
        return {"value": 100.0 + n, "unit": "img/s"}, False

    monkeypatch.setenv("MXTPU_BENCH_MODULE", "0")
    rc, calls, out = _patched_supervise(
        monkeypatch,
        {"--probe": lambda n: ({"device": "x"}, False), "--child": raw},
        deadline=600.0, ab=True)
    assert rc == 0
    assert envs[0] == {"MXNET_FUSED_BN_ADD_RELU": "0"}
    assert envs[1] == {"MXNET_FUSED_BN_ADD_RELU": "1"}
    assert out["value"] == 101.0 and out["img_s_fused_bn_tail"] == 102.0


def test_budget_args_bare_number(monkeypatch):
    """--budget-s 1200 rescales the total deadline and strips the flag
    (whoever runs the harness under an outer limit hands its window in)."""
    monkeypatch.setattr(bench, "TOTAL_DEADLINE", 1500.0)
    rest = bench._apply_budget_args(["--budget-s", "1200", "--child"])
    assert rest == ["--child"]
    assert bench.TOTAL_DEADLINE == 1200.0


def test_budget_args_per_phase(monkeypatch):
    for name in ("TOTAL_DEADLINE", "PROBE_TIMEOUT", "RAW_TIMEOUT",
                 "MODULE_TIMEOUT"):
        monkeypatch.setattr(bench, name, getattr(bench, name))
    rest = bench._apply_budget_args(
        ["--budget-s=probe=60,raw=600", "--budget-s", "module=300"])
    assert rest == []
    assert bench.PROBE_TIMEOUT == 60.0
    assert bench.RAW_TIMEOUT == 600.0
    assert bench.MODULE_TIMEOUT == 300.0


def test_budget_args_unknown_phase_fails_loudly(monkeypatch):
    monkeypatch.setattr(bench, "TOTAL_DEADLINE", 1500.0)
    with pytest.raises(SystemExit):
        bench._apply_budget_args(["--budget-s", "warmup=10"])


def test_budget_args_malformed_fails_loudly(monkeypatch):
    """A trailing --budget-s with no value, or a non-numeric seconds
    value, must exit with a usage error — not an IndexError/ValueError
    traceback that skips the harness's final-JSON-line contract."""
    monkeypatch.setattr(bench, "TOTAL_DEADLINE", 1500.0)
    with pytest.raises(SystemExit):
        bench._apply_budget_args(["--child", "--budget-s"])
    with pytest.raises(SystemExit):
        bench._apply_budget_args(["--budget-s", "1.5x"])
    with pytest.raises(SystemExit):
        bench._apply_budget_args(["--budget-s", "raw=fast"])


def test_no_backend_round_marked_skipped(monkeypatch):
    """A round where no probe finds a chip must read as unmeasurable
    (skipped: true), not as a zero — and exits non-zero."""
    import time as _time

    def failing_probe(n):
        _time.sleep(0.2)
        return None, True

    rc, calls, out = _patched_supervise(
        monkeypatch, {"--probe": failing_probe}, deadline=2.0)
    assert rc == 1
    assert out["skipped"] is True


def test_backend_up_but_raw_failed_not_skipped(monkeypatch):
    """Probe succeeded but every raw child died: that IS a measurement
    failure (skipped: false) — the backend was reachable."""
    rc, calls, out = _patched_supervise(
        monkeypatch,
        {"--probe": lambda n: ({"device": "x"}, False),
         "--child": lambda n: (None, False)},
        deadline=8.0)
    assert rc == 1
    assert "error" in out and out["skipped"] is False


def test_module_phase_ab_merge_and_partial_emission(monkeypatch):
    """The module child's fused + phase-split numbers both merge into
    the final line, and the raw number is banked as a partial line
    BEFORE the module phase runs (an outer kill mid-module-phase
    salvages it)."""
    import io
    from contextlib import redirect_stdout

    calls = []

    def fake_phase(mode, timeout, env_extra=None):
        calls.append(mode)
        if mode == "--probe":
            return {"device": "x"}, False
        if mode == "--child":
            return {"value": 500.0, "unit": "img/s"}, False
        return {"module_fit_img_s": 90.0,
                "module_fit_phase_split_img_s": 30.0}, False

    monkeypatch.setenv("MXTPU_BENCH_AB", "0")
    monkeypatch.setenv("MXTPU_BENCH_MODULE", "1")
    monkeypatch.setenv("MXTPU_BENCH_DP", "0")
    monkeypatch.setenv("MXTPU_BENCH_SERVE", "0")
    monkeypatch.setenv("MXTPU_BENCH_DECODE", "0")
    monkeypatch.setattr(bench, "_run_phase", fake_phase)
    monkeypatch.setattr(bench, "TOTAL_DEADLINE", 600.0)
    monkeypatch.setattr(bench, "SMOKE", False)
    monkeypatch.setattr(bench, "PROBE_TIMEOUT", 1.0)
    monkeypatch.setattr(bench, "PROBE_GAP", 0.0)
    monkeypatch.setattr(bench, "RAW_MIN", 0.5)
    buf = io.StringIO()
    with redirect_stdout(buf):
        rc = bench.supervise()
    assert rc == 0
    lines = [json.loads(l) for l in buf.getvalue().splitlines()
             if l.strip().startswith("{")]
    # a partial line with the raw number lands before the module phase
    partials = [l for l in lines if l.get("partial")]
    assert partials and partials[0]["value"] == 500.0
    assert "module_fit_img_s" not in partials[0]
    final = lines[-1]
    assert not final.get("partial")
    assert final["module_fit_img_s"] == 90.0
    assert final["module_fit_phase_split_img_s"] == 30.0


def test_supervise_dp_phase_merges(monkeypatch):
    """With budget left, the dp A/B child runs and its per-axis-size
    table merges into the final line."""
    dp_table = {"1": {"fused_img_s": 150.0, "kvstore_img_s": 150.0},
                "8": {"fused_img_s": 1000.0, "kvstore_img_s": 400.0}}

    def fake_phase(mode, timeout, env_extra=None):
        if mode == "--probe":
            return {"device": "x"}, False
        if mode == "--child":
            return {"value": 500.0, "unit": "img/s"}, False
        assert mode == "--dp-child", mode
        return {"lane": "dp_ab", "dp": dict(dp_table),
                "per_chip_batch": 128}, False

    import io
    from contextlib import redirect_stdout
    monkeypatch.setenv("MXTPU_BENCH_AB", "0")
    monkeypatch.setenv("MXTPU_BENCH_MODULE", "0")
    monkeypatch.setenv("MXTPU_BENCH_DP", "1")
    monkeypatch.setenv("MXTPU_BENCH_SERVE", "0")
    monkeypatch.setenv("MXTPU_BENCH_DECODE", "0")
    monkeypatch.setattr(bench, "_run_phase", fake_phase)
    monkeypatch.setattr(bench, "TOTAL_DEADLINE", 600.0)
    monkeypatch.setattr(bench, "SMOKE", False)
    monkeypatch.setattr(bench, "PROBE_TIMEOUT", 1.0)
    monkeypatch.setattr(bench, "PROBE_GAP", 0.0)
    monkeypatch.setattr(bench, "RAW_MIN", 0.5)
    buf = io.StringIO()
    with redirect_stdout(buf):
        rc = bench.supervise()
    assert rc == 0
    out = bench._last_json_line(buf.getvalue())
    assert out["dp"] == dp_table
    assert out["dp_per_chip_batch"] == 128
    assert out["value"] == 500.0


def test_dp_child_per_axis_partials_and_artifact(tmp_path, monkeypatch):
    """dp_child emits a partial line per axis size (a hang at a larger
    mesh salvages the smaller sizes), marks a silently-fallen-back fused
    leg by its stable reason CODE, and banks the MULTICHIP-schema
    artifact."""
    import io
    from contextlib import redirect_stdout
    from mxnet_tpu.module import FusedFallback

    class _Dev:
        platform = "cpu"
        device_kind = "cpu"

    calls = []

    def fake_throughput(dev, contexts=None, kvstore=None):
        calls.append((len(contexts), kvstore,
                      os.environ["MXNET_MODULE_FUSED_STEP"]))
        if len(contexts) == 2 and os.environ[
                "MXNET_MODULE_FUSED_STEP"] == "1":
            return 100.0, FusedFallback("monitor", "monitor installed")
        return 100.0 * len(contexts), None

    monkeypatch.setattr(bench, "_init_device", lambda jax: _Dev())
    monkeypatch.setattr(bench, "_module_fit_throughput", fake_throughput)
    # the oversized 999 must be SKIPPED, not abort the later valid sizes
    monkeypatch.setenv("MXTPU_BENCH_DP_AXES", "1,999,2")
    monkeypatch.setenv("MXTPU_ARTIFACT_DIR", str(tmp_path))
    # dp_child mutates the fused-step pin; monkeypatch restores it
    monkeypatch.setenv("MXNET_MODULE_FUSED_STEP", "1")
    buf = io.StringIO()
    with redirect_stdout(buf):
        bench.dp_child()
    lines = [json.loads(l) for l in buf.getvalue().splitlines()
             if l.strip().startswith("{")]
    partials = [l for l in lines if l.get("partial")]
    assert len(partials) == 2          # one banked line per axis size
    assert set(partials[0]["dp"]) == {"1"}
    final = lines[-1]
    assert set(final["dp"]) == {"1", "2"}
    assert final["dp"]["2"]["fused_fallback"] == "monitor"
    assert final["dp"]["1"]["fused_img_s"] == 100.0
    # at k=1 the 'device' kvstore resolves to None — the split leg must
    # be marked as the plain phase-split baseline, not a kvstore number
    assert final["dp"]["1"]["split_kvstore_active"] is False
    assert final["dp"]["2"]["split_kvstore_active"] is True
    # the A/B drove both legs through the same in-process kvstore
    assert all(kv == "device" for _, kv, _ in calls)
    with open(tmp_path / "multichip_dp_ab.json") as f:
        art = json.load(f)
    # the completed sweep reads as a clean round (per-size interim
    # writes carry ok=False/truncated=True so a killed run reads as
    # partial — that state must be gone after the final bank)
    assert art["ok"] is True and art["skipped"] is False
    assert "truncated" not in art
    assert art["dp"] == final["dp"]


def test_budget_args_dp_phase(monkeypatch):
    monkeypatch.setattr(bench, "DP_TIMEOUT", bench.DP_TIMEOUT)
    rest = bench._apply_budget_args(["--budget-s", "dp=120"])
    assert rest == [] and bench.DP_TIMEOUT == 120.0


def test_budget_args_serve_phase(monkeypatch):
    monkeypatch.setattr(bench, "SERVE_TIMEOUT", bench.SERVE_TIMEOUT)
    rest = bench._apply_budget_args(["--budget-s", "serve=90"])
    assert rest == [] and bench.SERVE_TIMEOUT == 90.0


def test_supervise_serve_phase_merges(monkeypatch):
    """With budget left, the serving sweep child runs and its
    throughput/latency table merges into the final line under
    "serving"."""
    sv = {"lane": "serving", "unbatched_req_s": 100.0,
          "burst_req_s": 900.0, "serve_speedup": 9.0,
          "burst_latency_ms": {"p50_ms": 4.0, "p95_ms": 9.0,
                               "p99_ms": 11.0},
          "offered_loads": {"0.80": {"achieved_req_s": 700.0}},
          "compiles_per_bucket": 1.0}

    def fake_phase(mode, timeout, env_extra=None):
        if mode == "--probe":
            return {"device": "x"}, False
        if mode == "--child":
            return {"value": 500.0, "unit": "img/s"}, False
        assert mode == "--serve-child", mode
        return dict(sv), False

    import io
    from contextlib import redirect_stdout
    monkeypatch.setenv("MXTPU_BENCH_AB", "0")
    monkeypatch.setenv("MXTPU_BENCH_MODULE", "0")
    monkeypatch.setenv("MXTPU_BENCH_DP", "0")
    monkeypatch.setenv("MXTPU_BENCH_SERVE", "1")
    monkeypatch.setenv("MXTPU_BENCH_DECODE", "0")
    monkeypatch.setattr(bench, "_run_phase", fake_phase)
    monkeypatch.setattr(bench, "TOTAL_DEADLINE", 600.0)
    monkeypatch.setattr(bench, "SMOKE", False)
    monkeypatch.setattr(bench, "PROBE_TIMEOUT", 1.0)
    monkeypatch.setattr(bench, "PROBE_GAP", 0.0)
    monkeypatch.setattr(bench, "RAW_MIN", 0.5)
    buf = io.StringIO()
    with redirect_stdout(buf):
        rc = bench.supervise()
    assert rc == 0
    out = bench._last_json_line(buf.getvalue())
    assert out["value"] == 500.0
    assert out["serving"]["serve_speedup"] == 9.0
    assert out["serving"]["burst_latency_ms"]["p95_ms"] == 9.0
    assert "lane" not in out["serving"]


def test_supervise_decode_phase_merges(monkeypatch):
    """With budget left, the continuous-batching decode child runs and
    its throughput/per-token-latency table merges into the final line
    under "decode"."""
    dc = {"lane": "decode", "static_tok_s": 7000.0,
          "continuous_tok_s": 20000.0, "decode_speedup": 2.86,
          "token_latency_ms": {"p50_ms": 0.21, "p95_ms": 0.26,
                               "p99_ms": 0.32},
          "jit_compiles_timed": 0, "kv_cache_bytes": 524288}

    def fake_phase(mode, timeout, env_extra=None):
        if mode == "--probe":
            return {"device": "x"}, False
        if mode == "--child":
            return {"value": 500.0, "unit": "img/s"}, False
        assert mode == "--decode-child", mode
        return dict(dc), False

    import io
    from contextlib import redirect_stdout
    monkeypatch.setenv("MXTPU_BENCH_AB", "0")
    monkeypatch.setenv("MXTPU_BENCH_MODULE", "0")
    monkeypatch.setenv("MXTPU_BENCH_DP", "0")
    monkeypatch.setenv("MXTPU_BENCH_SERVE", "0")
    monkeypatch.setenv("MXTPU_BENCH_DECODE", "1")
    monkeypatch.setattr(bench, "_run_phase", fake_phase)
    monkeypatch.setattr(bench, "TOTAL_DEADLINE", 600.0)
    monkeypatch.setattr(bench, "SMOKE", False)
    monkeypatch.setattr(bench, "PROBE_TIMEOUT", 1.0)
    monkeypatch.setattr(bench, "PROBE_GAP", 0.0)
    monkeypatch.setattr(bench, "RAW_MIN", 0.5)
    buf = io.StringIO()
    with redirect_stdout(buf):
        rc = bench.supervise()
    assert rc == 0
    out = bench._last_json_line(buf.getvalue())
    assert out["value"] == 500.0
    assert out["decode"]["decode_speedup"] == 2.86
    assert out["decode"]["token_latency_ms"]["p99_ms"] == 0.32
    assert out["decode"]["jit_compiles_timed"] == 0
    assert "lane" not in out["decode"]


def test_serve_child_smoke_sweep(monkeypatch):
    """serve_child end to end in smoke mode (tiny MLP on CPU): partial
    emission per phase, one compile per bucket, p95 in the artifact and
    the offered-load ladder populated."""
    import io
    from contextlib import redirect_stdout
    monkeypatch.setattr(bench, "SMOKE", True)

    class _Dev:
        device_kind = "cpu"
        platform = "cpu"

    def init(jax):
        jax.config.update("jax_platforms", "cpu")
        return jax.devices()[0]

    monkeypatch.setattr(bench, "_init_device", init)
    buf = io.StringIO()
    with redirect_stdout(buf):
        bench.serve_child()
    lines = [json.loads(l) for l in buf.getvalue().splitlines()
             if l.strip().startswith("{")]
    partials = [l for l in lines if l.get("partial")]
    # one partial per phase: unbatched, burst, 3 load points
    assert len(partials) >= 5
    out = lines[-1]
    assert out["lane"] == "serving"
    assert out["compiles_per_bucket"] == 1.0
    assert out["unbatched_req_s"] > 0 and out["burst_req_s"] > 0
    assert out["burst_latency_ms"]["p95_ms"] is not None
    assert set(out["offered_loads"]) == {"0.50", "0.80", "0.95"}
    for pt in out["offered_loads"].values():
        assert pt["achieved_req_s"] > 0
        assert pt["latency_ms"]["p95"] >= pt["latency_ms"]["p50"] >= 0
    # the serving telemetry rode into the artifact summary
    assert "serve_request" in out["telemetry"]["spans"]


def test_module_child_marks_silent_fallback(monkeypatch):
    """module_child must not record two phase-split numbers as a fused
    A/B: when the fused leg silently falls back, the emitted JSON
    carries the fallback reason."""
    import io
    from contextlib import redirect_stdout
    class _Dev:
        platform = "cpu"
        device_kind = "cpu"

    monkeypatch.setattr(bench, "_init_device", lambda jax: _Dev())
    monkeypatch.setattr(bench, "_module_fit_throughput",
                        lambda dev: (42.0, "kvstore-mediated update"))
    buf = io.StringIO()
    with redirect_stdout(buf):
        bench.module_child()
    lines = [json.loads(l) for l in buf.getvalue().splitlines()]
    assert lines[-1]["module_fit_img_s"] == 42.0
    assert lines[-1]["module_fit_fused_fallback"] == \
        "kvstore-mediated update"
    # every result line names where it ran
    assert lines[-1]["platform"] == "cpu" and lines[-1]["device"] == "cpu"
    assert lines[-1]["device_count"] >= 1
    # a clean fused leg carries no fallback marker
    monkeypatch.setattr(bench, "_module_fit_throughput",
                        lambda dev: (42.0, None))
    buf = io.StringIO()
    with redirect_stdout(buf):
        bench.module_child()
    lines = [json.loads(l) for l in buf.getvalue().splitlines()]
    assert "module_fit_fused_fallback" not in lines[-1]


def test_supervise_aborts_after_consecutive_dead_probes(monkeypatch):
    """A machine with no chip answers every probe the same way. After
    PROBE_FAIL_LIMIT consecutive failures the supervisor must stop
    probing IMMEDIATELY (despite budget remaining) and emit the
    diagnostic, with the cold-start seconds of every attempt
    recorded."""
    import time as _time

    def failing_probe(n):
        _time.sleep(0.05)
        return None, True

    monkeypatch.setattr(bench, "PROBE_FAIL_LIMIT", 3)
    rc, calls, out = _patched_supervise(
        monkeypatch, {"--probe": failing_probe}, deadline=600.0)
    assert rc == 1
    # the loop stopped at the limit, not at the (10-minute) deadline
    assert calls.count("--probe") == 3
    assert out["probe_aborted"] is True
    assert out["skipped"] is True
    assert len(out["probe_seconds"]) == 3
    assert all(s >= 0 for s in out["probe_seconds"])


def test_supervise_probe_fail_counter_resets_on_success(monkeypatch):
    """Two dead probes, a good one, then the raw child measures: the
    consecutive-failure counter resets on success so a slow (but
    present) chip is NOT declared absent, and the probe cold-start
    seconds ride in the successful JSON too."""
    meas = {"value": 55.0, "unit": "img/s"}
    monkeypatch.setenv("MXTPU_BENCH_MODULE", "0")
    monkeypatch.setattr(bench, "PROBE_FAIL_LIMIT", 3)
    rc, calls, out = _patched_supervise(
        monkeypatch,
        {"--probe": lambda n: ((None, True) if n <= 2
                               else ({"device": "x"}, False)),
         "--child": lambda n: (dict(meas), False)},
        deadline=600.0)
    assert rc == 0
    assert calls.count("--probe") == 3
    assert out["value"] == 55.0
    assert len(out["probe_seconds"]) == 3


# -- no quiet fallback: a missing chip, an unknown device kind or a ---------
# -- requested phase that yields nothing is a non-zero exit -----------------

_OPTIONAL = {"module": "--module-child", "dp": "--dp-child",
             "serve": "--serve-child", "decode": "--decode-child",
             "ab": "--child"}


@pytest.mark.parametrize("phase", sorted(_OPTIONAL))
def test_requested_phase_yielding_nothing_fails_the_run(monkeypatch, phase):
    """The raw number is measured and still printed, but a requested
    optional phase whose child yields nothing makes the exit code
    non-zero and is named in ``failed_phases``."""
    import io
    from contextlib import redirect_stdout

    def fake_phase(mode, timeout, env_extra=None):
        if mode == "--probe":
            return {"device": "x"}, False
        if mode == "--child" and (env_extra or {}).get(
                "MXNET_FUSED_BN_ADD_RELU") != "1":
            return {"value": 500.0, "unit": "img/s"}, False
        assert mode == _OPTIONAL[phase], mode
        return None, False

    for name in _OPTIONAL:
        monkeypatch.setenv("MXTPU_BENCH_" + name.upper(),
                           "1" if name == phase else "0")
    monkeypatch.setattr(bench, "_run_phase", fake_phase)
    monkeypatch.setattr(bench, "TOTAL_DEADLINE", 3000.0)
    monkeypatch.setattr(bench, "SMOKE", False)
    monkeypatch.setattr(bench, "PROBE_TIMEOUT", 1.0)
    monkeypatch.setattr(bench, "PROBE_GAP", 0.0)
    monkeypatch.setattr(bench, "RAW_MIN", 0.5)
    buf = io.StringIO()
    with redirect_stdout(buf):
        rc = bench.supervise()
    assert rc == 1
    out = bench._last_json_line(buf.getvalue())
    assert out["value"] == 500.0
    assert out["failed_phases"] == [phase]


@pytest.mark.parametrize("entry", ["probe", "child", "module_child",
                                   "dp_child", "mp_child", "serve_child",
                                   "decode_child"])
def test_children_refuse_a_machine_with_no_chip(monkeypatch, capsys, entry):
    """Outside MXTPU_BENCH_SMOKE every child's first act is
    ``_init_device``, which raises where JAX finds only the CPU — no
    child goes on to measure the host and print it as a result."""
    monkeypatch.setattr(bench, "SMOKE", False)
    with pytest.raises(RuntimeError, match="no accelerator"):
        getattr(bench, entry)()
    assert "{" not in capsys.readouterr().out


@pytest.mark.parametrize("kind,peak", [("TPU v5 lite", 197e12),
                                       ("TPU v5e", 197e12),
                                       ("TPU v4", 275e12),
                                       ("cpu", None),
                                       ("NVIDIA H100", None)])
def test_peak_flops_unknown_kind_raises(kind, peak):
    """A device that is not in the peak table is an error, not a default
    (an MFU against a guessed ceiling, or ``mfu: null`` next to an img/s
    from the wrong device, is how a CPU run passes for a chip run)."""
    if peak is None:
        with pytest.raises(ValueError, match="no peak"):
            bench.peak_flops_for(kind)
    else:
        assert bench.peak_flops_for(kind) == peak
