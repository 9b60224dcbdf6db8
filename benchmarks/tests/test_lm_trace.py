"""``harness/lm_trace.py`` on hand-built rows: node from a scope path,
and the grouped products found by name."""
from benchmarks.harness import lm_trace


def test_parts_unwrap_the_wrappers():
    path = "jit(train_step)/backward/transpose(jvp(checkpoint))/" \
           "transpose(jvp(l2_moe))/grouped/ragged_dot:"
    assert list(lm_trace._parts(path)) == [
        "train_step", "backward", "checkpoint", "l2_moe", "grouped",
        "ragged_dot"]
    assert list(lm_trace._parts("")) == []


def _view(rows):
    v = lm_trace.NodeTimes.__new__(lm_trace.NodeTimes)
    v.rows = rows
    return v


def test_times_by_node_and_the_grouped_products():
    v = _view([
        ("l1_moe", "forward/jvp(l1_moe)/grouped/ragged_dot", "%ragged-dot.1",
         2e-3),
        ("l1_moe", "forward/jvp(l1_moe)/dispatch/gather", "%fusion.7", 5e-3),
        ("", "", "%ragged-dot-none.9", 1e-3),
        ("l1_shared_w1", "forward/jvp(l1_shared_w1)/dot", "%fusion.8", 4e-3),
        ("l1_attn_core", "forward/jvp(l1_attn_core)/flash_attention_fwd",
         "%flash_attention_fwd.2", 6e-3),
        ("l1_attn_core", "forward/jvp(l1_attn_core)/transpose", "%copy.3",
         1e-3)])
    assert abs(v.ms(("_moe",)) - 7.0) < 1e-9
    assert abs(v.ms(("_moe", "_shared_")) - 11.0) < 1e-9
    assert abs(v.ms(("_attn_core",)) - 7.0) < 1e-9
    assert abs(v.ms(("_attn_core",), "flash_attention") - 6.0) < 1e-9
    every, under = v.grouped_ms()
    assert abs(every - 3.0) < 1e-9 and abs(under - 2.0) < 1e-9
    # a gate between the products is the layer's time, not a product's
    v.rows.append(("l1_moe", "forward/jvp(l1_moe)/grouped/mul", "%fusion.9", 1e-3))
    assert abs(v.grouped_ms()[0] - 3.0) < 1e-9
    assert v.ms(("_nothing",)) is None
    assert _view([]).grouped_ms() == (None, None)
    assert "(no node in the scope path)" in v.by_node()
