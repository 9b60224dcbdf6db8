"""``harness/program_spans.py`` on hand-built tuples: the split of the step
module's ops by scope path, the host spans of a fit step, the attribution of
idle time; then ``run.py --rehearse --trace 1``, whose line has to carry
every ``program_span`` metric."""
import json
import os
import subprocess
import sys

import pytest

from benchmarks.harness import program_spans as ps

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

J = "jit(train_step)/"


@pytest.mark.parametrize("path,phase", [
    (J + "forward/jvp(stage1_unit1_conv1)/conv_general_dilated:", "forward"),
    (J + "forward/fc1/dot_general:", "forward"),
    (J + "jit(main)/forward/jvp(bn0)/jit(_var)/div:", "forward"),
    (J + "backward/transpose(jvp(stage1_unit1_conv1))/"
         "conv_general_dilated:", "backward"),
    # an op under transpose(jvp(forward/x)) is backward wherever it stands
    (J + "transpose(jvp(forward/x))/mul:", "backward"),
    (J + "backward/transpose(forward)/jvp(softmax)/sub:", "backward"),
    (J + "backward/convert_element_type:", "backward"),
    (J + "optimizer/add:", "optimizer"),
    (J + "metric/reduce_sum:", "other"),
    # a path that starts in no phase, or none at all, is other
    (J + "convert_element_type:", "other"),
    ("opt_states[138][1]:", "other"),
    ("", "other"),
    (None, "other"),
])
def test_phase_of(path, phase):
    assert ps.phase_of(path) == phase


@pytest.mark.parametrize("path,node", [
    (J + "forward/jvp(stage1_unit1_conv1)/conv_general_dilated:",
     "stage1_unit1_conv1"),
    (J + "backward/transpose(jvp(stage3_unit2_bn1))/jit(_var)/reduce_sum:",
     "stage3_unit2_bn1"),
    (J + "backward/transpose(forward)/jvp(softmax)/sub:", "softmax"),
    (J + "forward/fc1/dot_general:", "fc1"),
    (None, None),
])
def test_node_of(path, node):
    assert ps.node_of(path) == node


def _step_trace():
    """Two runs of the step module on device 0 (and one of another module),
    with ops ``(device, name, category, start, duration)``."""
    modules = [(0, "jit_train_step(77)", 1.0, 1.0),
               (0, "jit_convert_element_type(5)", 2.5, 0.1),
               (0, "jit_train_step(77)", 3.0, 1.0)]
    names = {"%conv.f": J + "forward/jvp(c1)/conv_general_dilated:",
             "%conv.b": J + "backward/transpose(jvp(c1))/"
                            "conv_general_dilated:",
             "%bn.f": J + "forward/jvp(bn1)/mul:",
             "%bn.b": J + "transpose(jvp(forward/bn1))/mul:",
             "%sgd": J + "optimizer/add:",
             "%acc": J + "metric/add:",
             "%cast": J + "convert_element_type:"}
    ops = []
    for base in (1.0, 3.0):
        ops += [(0, "%conv.f", "convolution fusion", base + 0.00, 0.20),
                (0, "%bn.f", "loop fusion", base + 0.20, 0.10),
                (0, "%conv.b", "convolution fusion", base + 0.30, 0.40),
                (0, "%bn.b", "loop fusion", base + 0.70, 0.05),
                (0, "%sgd", "loop fusion", base + 0.75, 0.02),
                (0, "%acc", "loop fusion", base + 0.77, 0.01),
                (0, "%cast", "data formatting", base + 0.78, 0.03),
                (0, "%copy-done", "copy-done", base + 0.81, 0.04)]
    # an op of the other module, and one outside the window
    ops += [(0, "%other.module", "loop fusion", 2.5, 0.1),
            (0, "%conv.f", "convolution fusion", 9.0, 0.2)]
    return ops, modules, {0: names}


def test_the_four_phases_sum_to_the_step_modules_op_time():
    ops, modules, paths = _step_trace()
    mine = ps.step_ops(ops, modules, 0.5, 5.0)
    assert len(mine) == 16          # neither the other module's nor 9.0's
    total, conv, scoped = ps.seconds_by_phase(mine, paths)
    assert total == pytest.approx({"forward": 0.6, "backward": 0.9,
                                   "optimizer": 0.04, "other": 0.16})
    assert sum(total.values()) == pytest.approx(sum(o[4] for o in mine))
    assert conv == pytest.approx({"forward": 0.4, "backward": 0.8,
                                  "optimizer": 0.0, "other": 0.0})
    # the copy has no path at all; the cast has one, in no phase
    assert scoped == pytest.approx(1 - 0.08 / 1.7)
    by_node = ps.seconds_by_node(mine, paths)
    assert by_node[("c1", "forward")] == pytest.approx(0.4)
    assert by_node[("c1", "backward")] == pytest.approx(0.8)


def test_a_program_without_the_name_or_the_scopes_reads_nothing():
    ops, modules, paths = _step_trace()
    old = [(d, n.replace("jit_train_step", "jit_step"), s, t)
           for d, n, s, t in modules]
    assert ps.step_ops(ops, old, 0.5, 5.0) == []
    total, conv, scoped = ps.seconds_by_phase(
        ps.step_ops(ops, modules, 0.5, 5.0), {})
    assert total["other"] == pytest.approx(1.7) and scoped == 0.0


def _host_spans():
    """Three fit steps of 10 ms, 1 ms apart, ``callbacks`` between them."""
    spans = []
    for i in range(3):
        t = 0.100 + 0.011 * i
        ids = {"epoch": 0, "nbatch": i}
        spans += [("fit_batch", t, 0.010, dict(ids, step_num=i, _r=1)),
                  ("feed", t + 0.001, 0.002, ids),
                  ("step_prep", t + 0.003, 0.001, ids),
                  ("step", t + 0.004, 0.003 + 0.001 * i, ids),
                  ("step_install", t + 0.008, 0.0005, ids),
                  ("io_next", t + 0.009, 0.0002, ids),
                  ("callbacks", t + 0.0101, 0.0004, ids)]
    return sorted(spans, key=lambda s: s[1])


def test_steps_and_their_inner_spans():
    steps = ps.steps_of(_host_spans(), 0.0, 1.0)
    assert [st[3]["step_num"] for st, _ in steps] == [0, 1, 2]
    for st, inner in steps:
        assert [s[0] for s in inner] == ["feed", "step_prep", "step",
                                         "step_install", "io_next"]
        assert all(s[3]["nbatch"] == st[3]["nbatch"] for s in inner)
    assert ps.median_ms(ps.per_step_seconds(steps, ("feed",))) \
        == pytest.approx(2.0)
    assert ps.median_ms(ps.per_step_seconds(steps, ("step",))) \
        == pytest.approx(4.0)
    assert ps.median_ms(ps.per_step_seconds(
        steps, ("step_prep", "step_install"))) == pytest.approx(1.5)
    assert ps.per_step_seconds(steps, ("shard_put",)) == []
    assert ps.median_ms([]) is None
    # a window that cuts the last step off leaves it out
    assert len(ps.steps_of(_host_spans(), 0.0, 0.125)) == 2


def test_unspanned_counts_nested_spans_once():
    spans = _host_spans()
    # from one step's start to the next: 11 ms, of which feed 2, prep 1,
    # step 3 (4 in the second), install 0.5, io_next 0.2, callbacks 0.4
    assert ps.unspanned_seconds(spans, 0.0, 1.0) \
        == pytest.approx([0.0039, 0.0029])
    # a span nested in another is counted once
    nested = spans + [("shard_put", 0.1015, 0.001, {})]
    assert ps.unspanned_seconds(nested, 0.0, 1.0) \
        == pytest.approx([0.0039, 0.0029])
    assert ps.unspanned_seconds([], 0.0, 1.0) == []


def test_idle_time_goes_to_the_innermost_span():
    spans = _host_spans()
    # the device is busy from 0.106 on: idle are 0.090-0.106 of the window
    ops = [(0, "%op", "loop fusion", 0.106, 0.030)]
    idle, under, split = ps.idle_under_spans(ops, spans, 0.090, 0.136)
    assert idle == pytest.approx(0.016)
    assert under == pytest.approx(0.006)      # 0.100-0.106, in the step
    assert split == pytest.approx({"feed": 0.002, "step_prep": 0.001,
                                   "step": 0.002, "fit_batch alone": 0.001})
    assert ps.idle_under_spans([], spans, 0.0, 1.0) is None
    # no span of the program: idle, but none of it attributed
    idle, under, split = ps.idle_under_spans(ops, [], 0.090, 0.136)
    assert (under, split) == (0.0, {})


def test_rehearsal_line_carries_every_program_span_metric():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=1")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
         "--workload", bench["workloads"][0]["name"], "--seed",
         str(2 ** 31 + 25), "--seconds", "1", "--trace", "1", "--rehearse"],
        env=env, cwd=ROOT, capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["correct"] is True, out.stderr[-3000:]
    wanted = {m["name"] for m in bench["per_layer"]
              if m["source"] == "program_span"}
    assert wanted >= {"fit.feed_ms", "fit.step_call_ms",
                      "fit.bookkeeping_ms", "fit.unspanned_ms",
                      "setup.bind_s", "setup.init_s"}
    assert wanted <= set(line["metrics"]), sorted(line["metrics"])
    for name in wanted:
        assert line["metrics"][name]["value"] >= 0.0
    # the scopes are a device's: a CPU rehearsal reports none of them
    assert not {"forward.ms", "backward.ms", "conv_fwd.ms",
                "device.idle_attributed"} & set(line["metrics"])
