"""The seven ``setup.*`` readers of the program's own set-up logging: no
reading on a state without its spans or ``xla_build`` events (a program
older than them) or whose ring has lost them, the right numbers on a
hand-built telemetry state, and the reference's builds after the window's
start left out."""
import time

import pytest

from benchmarks.harness.files import load_file, read_json
from mxnet_tpu import jax_cache, telemetry

NAMES = ("setup.trace_s", "setup.lower_s", "setup.card_s", "setup.self_s",
         "setup.other_programs", "setup.other_programs_s",
         "setup.cache_misses")
BUILD = "/jax/core/compile/backend_compile_duration"


@pytest.fixture(autouse=True)
def _clean():
    telemetry.enable()
    telemetry.reset()
    yield
    telemetry.enable()
    telemetry.reset()


def _read(name, t0=None):
    reader = load_file("benchmarks/metrics/%s.py" % name, "reader")
    t0 = time.perf_counter() if t0 is None else t0
    return reader.read({"run": {"trace": {"t0": t0}}, "trace": None})


def _spans(base_ns):
    """fit_setup 0-10 s holding bind 1-3 and init_params 3-4; a step from
    19 s holding jit_build 20-30, which holds trace 21-24, lower 24-26,
    compile 26-28 and card 28-28.5."""
    for name, a, b in (("bind", 1, 3), ("init_params", 3, 4),
                       ("fit_setup", 0, 10), ("jit_trace", 21, 24),
                       ("jit_lower", 24, 26), ("jit_compile", 26, 28),
                       ("jit_card", 28, 28.5), ("jit_build", 20, 30),
                       ("fit_batch", 19, 40)):
        telemetry._record_span(name, base_ns + int(a * 1e9),
                               base_ns + int(b * 1e9))


def _build(fn, s, hit=False, instrumented=False):
    """One backend build as JAX reports it to the listener."""
    if hit:
        jax_cache._on_event("/jax/compilation_cache/cache_hits")
    if instrumented:
        with jax_cache.instrumented_build():
            jax_cache._on_duration(BUILD, s, fun_name=fn)
    else:
        jax_cache._on_duration(BUILD, s, fun_name=fn)


@pytest.mark.parametrize("name", NAMES)
def test_nothing_logged_gives_none(name):
    telemetry._record_span("bind", 0, 10 ** 9)     # an older program's
    telemetry._record_span("jit_trace", 0, 10 ** 9)
    assert _read(name) is None


@pytest.mark.parametrize("name", NAMES[4:])
def test_untraced_run_gives_none(name):
    _build("jit_f", 0.5)
    reader = load_file("benchmarks/metrics/%s.py" % name, "reader")
    assert reader.read({"run": {"trace": None}, "trace": None}) is None


def test_span_readers_on_a_known_state():
    _spans(time.perf_counter_ns())
    assert _read("setup.trace_s") == pytest.approx(3.0)
    assert _read("setup.lower_s") == pytest.approx(2.0)
    assert _read("setup.card_s") == pytest.approx(0.5)
    # fit_setup 10 less bind and init 3; jit_build 10 less its phases 7.5;
    # fit_batch holds jit_build and is no part of it
    assert _read("setup.self_s") == pytest.approx(9.5, abs=1e-5)


def test_event_readers_leave_out_the_reference(capsys):
    _build("jit_make_weights", 2.0)
    _build("jit_train_step", 7.0, hit=True, instrumented=True)
    _build("jit_broadcast_in_dim", 0.25, hit=True)
    _build("jit_broadcast_in_dim", 0.25)
    t0 = time.perf_counter()
    _build("jit_reference_step", 50.0)             # after the window opened
    assert _read("setup.other_programs", t0) == 3
    assert _read("setup.other_programs_s", t0) == pytest.approx(2.5)
    assert _read("setup.cache_misses", t0) == 2
    err = capsys.readouterr().err
    assert "jit_make_weights x1 2.000000" in err
    assert "jit_broadcast_in_dim x2 0.500000" in err
    assert "jit_reference_step" not in err and "jit_train_step" not in err


@pytest.mark.parametrize("name", NAMES[4:])
def test_builds_that_left_the_ring_give_none(name):
    _build("jit_make_weights", 2.0)
    for i in range(telemetry.EVENT_RING_SIZE):
        telemetry.record_event("tick", i=i)
    assert _read(name) is None


def test_spans_that_left_the_ring_give_none():
    _spans(time.perf_counter_ns())
    for _ in range(telemetry.SPAN_RING_SIZE - 3):
        with telemetry.span("feed"):
            pass
    # the three oldest spans (bind, init_params, fit_setup) are gone
    assert _read("setup.self_s") is None
    # cumulative seconds do not live in the ring
    assert _read("setup.trace_s") == pytest.approx(3.0)


def test_spans_dropped_before_the_set_up_leave_it_whole():
    for _ in range(telemetry.SPAN_RING_SIZE):
        with telemetry.span("feed"):
            pass
    _spans(time.perf_counter_ns() + 10 ** 9)
    assert _read("setup.self_s") == pytest.approx(9.5, abs=1e-5)


def test_entries_are_there_with_their_keys():
    entries = {e["name"]: e for e in read_json("BENCHMARK.json")["per_layer"]}
    for name in NAMES:
        e = entries[name]
        assert e == {"name": name, "unit": e["unit"], "better": "lower",
                     "source": e["source"],
                     "layer": "step builder and caches", "moves": "setup_s"}
        assert e["unit"] == ("count" if name in (
            "setup.other_programs", "setup.cache_misses") else "s")
        assert e["source"] == ("program_span" if name in NAMES[:4]
                               else "program_counter")
