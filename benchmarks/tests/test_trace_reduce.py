"""The reduction from trace events to per-layer numbers, on a hand-built
list: overlapping ops, a gap, an op with no category; and the guard that
fails a run whose categories cannot be trusted."""
import os

import pytest

from benchmarks.harness import trace_reduce as tr

CONV, LOOP, COPY = "convolution fusion", "loop fusion", "data formatting"

# device 0: conv 0.0-1.0; loop 0.5-1.5 (overlaps the conv); gap 1.5-2.0;
#           copy 2.0-3.0 of which 2.0-2.4 lies under a conv 1.9-2.4;
# device 1: conv 0.0-2.0; copy 2.0-2.5
OPS = [
    (0, "fusion.1", CONV, 0.0, 1.0),
    (0, "fusion.2", LOOP, 0.5, 1.0),
    (0, "fusion.3", CONV, 1.9, 0.5),
    (0, "copy.1", COPY, 2.0, 1.0),
    (1, "fusion.1", CONV, 0.0, 2.0),
    (1, "copy.1", COPY, 2.0, 0.5),
]
LO, HI = 0.0, 4.0


def test_interval_arithmetic():
    assert tr.union([(0, 1), (0.5, 1.5), (2, 3)]) == [(0, 1.5), (2, 3)]
    assert tr.length(tr.union([(0, 1), (0.5, 1.5), (2, 3)])) == 2.5
    assert tr.subtract([(0, 4)], [(1, 2), (3, 5)]) == [(0, 1), (2, 3)]
    assert tr.subtract([(2, 3)], [(1.9, 2.4)]) == [(2.4, 3)]
    assert tr.clip([(0, 2), (3, 5)], 1, 4) == [(1, 2), (3, 4)]


def test_busy_and_idle_take_the_union_not_the_sum():
    busy = tr.busy_by_device(OPS, LO, HI)
    assert busy[0] == pytest.approx(1.5 + 1.1)   # 0-1.5 and 1.9-3.0
    assert busy[1] == pytest.approx(2.5)
    # the worst device is the one that was busy least
    assert tr.idle_share(OPS, LO, HI) == pytest.approx(1 - 2.5 / 4.0)


def test_groups_are_by_category_and_mean_over_devices():
    g = tr.seconds_by_group(OPS, LO, HI)
    assert g["conv"] == pytest.approx((1.0 + 0.5 + 2.0) / 2)
    assert g["other"] == pytest.approx((1.0 + 1.0 + 0.5) / 2)
    assert g["categorised_share"] == 1.0


def test_an_op_without_a_category_is_never_a_convolution():
    ops = OPS + [(0, "convolution.7", "uncategorised", 3.5, 0.25)]
    g = tr.seconds_by_group(ops, LO, HI)
    assert g["conv"] == pytest.approx((1.0 + 0.5 + 2.0) / 2)
    assert g["categorised_share"] < 1.0


def test_untrusted_categories_fail_the_run():
    g = tr.seconds_by_group(OPS, LO, HI)
    assert tr.category_faults(g, has_convolutions=True) == []
    # the metadata read for no op: nothing categorised, no convolution
    bare = [(d, n, "uncategorised", s, t) for d, n, _, s, t in OPS]
    faults = tr.category_faults(tr.seconds_by_group(bare, LO, HI), True)
    assert len(faults) == 2 and "hlo_category" in faults[0]
    # read for some: a convolution's 0.5 s of 6.0 s goes uncategorised
    some = [o if o[1] != "fusion.3" else o[:2] + ("uncategorised",) + o[3:]
            for o in OPS]
    g = tr.seconds_by_group(some, LO, HI)
    assert g["categorised_share"] == pytest.approx(5.5 / 6.0)
    assert len(tr.category_faults(g, True)) == 1
    # a model with no convolution needs no convolution op
    loops = [o for o in OPS if o[2] != CONV]
    assert tr.category_faults(tr.seconds_by_group(loops, LO, HI),
                              has_convolutions=False) == []
    assert tr.category_faults(tr.seconds_by_group([], LO, HI), False)


def test_step_module_and_gaps():
    modules = [(0, "jit_step", 0.0, 1.5), (0, "jit_step", 1.9, 1.1),
               (0, "jit_small", 3.2, 0.1), (1, "jit_step", 0.0, 2.5)]
    name, runs = tr.step_module(modules, LO, HI)
    assert name == "jit_step" and runs == [1.5, 1.1]
    spans = [("feed.next", 1.45, 0.5), ("drain", 3.0, 1.0)]
    gaps = dict(tr.idle_gaps(OPS, spans, LO, HI))
    assert gaps["feed.next"] == pytest.approx(0.4)     # 1.5-1.9
    assert gaps["drain"] == pytest.approx(1.0)         # 3.0-4.0
    top = tr.top_ops(OPS, LO, HI, n=2)
    assert top[0][0].startswith(CONV)


RECORDED = os.path.join(os.path.dirname(__file__), "data",
                        "resnet50.fit.xplane.pb")


@pytest.mark.skipif(not os.path.exists(RECORDED),
                    reason="no recorded trace kept (too large to commit)")
def test_on_a_recorded_trace():
    ops, modules, spans = tr.load(RECORDED, ("bench.window",))
    assert ops and modules and spans
    lo, hi = spans[0][1], spans[0][1] + spans[0][2]
    g = tr.seconds_by_group(ops, lo, hi)
    assert tr.category_faults(g, has_convolutions=True) == []
    assert 0.0 <= tr.idle_share(ops, lo, hi) < 1.0
