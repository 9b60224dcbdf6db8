"""``metrics/moe.chunks_per_pass.py`` and ``metrics/moe.overflow_share.py``:
how often the routed layer's chunked passes ran more than one chunk, from
the program's counters; no reading where the cell is no language model's,
the window saw no step, or the program has no such counter (the parent of
the PR that added them)."""
import pytest

from benchmarks.harness.files import load_file, read_json

NAMES = ("moe.chunks_per_pass", "moe.overflow_share")


def _read(name, run):
    reader = load_file("benchmarks/metrics/%s.py" % name, "reader")
    return reader.read({"run": run, "trace": None, "peaks": None})


def _lm(moe):
    return {"lm": {"moe": moe, "model": {"experts_held": [0, 16]}}}


@pytest.mark.parametrize("name", NAMES)
@pytest.mark.parametrize("run", [
    {"trace": None, "chips": 1},                            # resnet50.fit
    _lm({}),                                                # no step seen
    _lm({"moe.steps": 12, "moe.rows_held": 150000,          # the parent
         "moe.rows_max": 30000}),
], ids=["another_cell", "no_step", "no_counter"])
def test_nothing_to_read_gives_none(name, run):
    assert _read(name, run) is None


def test_ratios_of_the_counters():
    moe = {"moe.steps": 12, "moe.rows_held": 150000, "moe.rows_max": 30000,
           "moe.chunks_run": 21, "moe.rows_overflow": 51696}
    assert _read("moe.chunks_per_pass", _lm(moe)) == 21 / 12
    assert _read("moe.overflow_share", _lm(moe)) == 51696 / 150000
    # every layer-step inside its first chunk: the floor
    even = dict(moe, **{"moe.chunks_run": 12, "moe.rows_overflow": 0})
    assert _read("moe.chunks_per_pass", _lm(even)) == 1.0
    assert _read("moe.overflow_share", _lm(even)) == 0.0


def test_entries_are_appended_and_list_the_cell():
    entries = read_json("BENCHMARK.json")["per_layer"][-2:]
    assert [e["name"] for e in entries] == list(NAMES)
    for e, unit in zip(entries, ("count", "ratio")):
        assert e == {"name": e["name"], "unit": unit, "better": "lower",
                     "source": "program_counter", "layer": "model step",
                     "moves": "step_ms_p95",
                     "workloads": ["trinity_mini.fit"]}
