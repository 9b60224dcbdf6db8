"""``harness/hybrid_trace.py`` and the hybrid cell's trace readers on a
made trace: a loop is not counted beside its body."""
from benchmarks.harness import hybrid_trace, lm_trace
from benchmarks.harness.files import load_file


def test_leaf_ops_drop_the_op_that_holds_others():
    ops = [(0, "%while.1", "while", 1.0, 4.0),
           (0, "%fusion.2", "loop fusion", 1.5, 1.0),    # the loop's body
           (0, "%fusion.3", "loop fusion", 3.0, 1.5),
           (0, "%fusion.4", "loop fusion", 5.0, 1.0),    # starts at its end
           (0, "%copy.5", "data formatting", 7.0, 0.5),
           (1, "%fusion.6", "loop fusion", 2.0, 0.5)]    # another device
    kept = hybrid_trace.leaf_ops(list(reversed(ops)))
    assert [o[1] for o in kept] == ["%fusion.2", "%fusion.3", "%fusion.4",
                                    "%copy.5", "%fusion.6"]
    # a loop inside a loop: only the innermost ops stay
    nested = [(0, "%while.1", "while", 0.0, 10.0),
              (0, "%while.2", "while", 1.0, 3.0),
              (0, "%fusion.3", "loop fusion", 1.5, 1.0)]
    assert [o[1] for o in hybrid_trace.leaf_ops(nested)] == ["%fusion.3"]
    assert hybrid_trace.leaf_ops([]) == []


def _ctx(rows):
    v = lm_trace.NodeTimes.__new__(lm_trace.NodeTimes)
    v.rows = rows
    trace = type("T", (), {"leaf_node_times": v, "node_times": v})()
    lm = {"model": {"layer_types": ["mamba", "moe", "attention", "mamba"],
                    "num_hidden_layers": 4, "experts_held": [0, 8],
                    "num_experts": 128, "num_experts_per_tok": 6,
                    "hidden_size": 2688, "head_dim": 128,
                    "num_attention_heads": 32, "num_key_value_heads": 2,
                    "moe_intermediate_size": 1856, "num_shared_experts": 1,
                    "vocab_size": 16384},
          "tokens": 8192, "seq_len": 8192, "moe": {}}
    cfg = {"symbol": {"kwargs": {
        "mamba_num_heads": 64, "mamba_head_dim": 64, "ssm_state_size": 128,
        "n_groups": 8, "chunk_size": 128,
        "moe_shared_expert_intermediate_size": 3712}}}
    return {"trace": trace, "run": {"lm": lm, "nodes": [], "chips": 1},
            "cfg": cfg,
            "peaks": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}}


def _read(name, ctx):
    return load_file("benchmarks/metrics/%s.py" % name, "reader").read(ctx)


def test_readers_on_made_rows():
    ctx = _ctx([
        ("l0_mixer_ssd", "forward/jvp(l0_mixer_ssd)/ssd/diag/dot", "%f.1",
         3e-3),
        ("l0_mixer_ssd", "backward/transpose(jvp(l0_mixer_ssd))/ssd/pass/dot",
         "%f.2", 2e-3),
        ("l0_mixer_conv", "forward/jvp(l0_mixer_conv)/mul", "%f.3", 1e-3),
        ("l0_mixer_norm", "forward/jvp(l0_mixer_norm)/mul", "%f.4", 5e-4),
        ("l0_mixer_in", "forward/jvp(l0_mixer_in)/dot", "%f.5", 7e-3),
        ("l2_attn_core", "forward/jvp(l2_attn_core)/flash_attention_fwd",
         "%flash_attention_fwd.6", 6e-3),
        ("l1_moe", "forward/jvp(l1_moe)/grouped/ragged_dot",
         "%ragged-dot.7", 2e-3),
        ("", "", "%ragged-dot-none.8", 1e-3),
        ("l1_shared_w1", "forward/jvp(l1_shared_w1)/dot", "%f.9", 4e-3)])
    assert abs(_read("ssm.scan_ms", ctx) - 5.0) < 1e-9
    assert abs(_read("ssm.ms", ctx) - 6.5) < 1e-9
    # the accepted readers of the layers this cell shares with
    # ``trinity_mini.fit`` go by the nodes' names and read them here too
    assert abs(_read("attn.ms", ctx) - 6.0) < 1e-9
    assert abs(_read("attn.fwd_ms", ctx) - 6.0) < 1e-9
    # the routed node, the shared expert and the product with no path
    assert abs(_read("moe.ms", ctx) - 7.0) < 1e-9
    assert abs(_read("moe.dispatch_ms", ctx) - 0.0) < 1e-9
    # two mixers, three passes each, bound by bytes: 2 x 3 x 2 x 8,192 x
    # 10,304 bytes at 819 GB/s over the 5 ms read
    least_ms = 1e3 * 6 * 2 * 8192 * 10304 / 819e9
    assert abs(_read("ssm_roofline", ctx) - 100 * least_ms / 5.0) < 1e-9
    assert 0 < _read("ssm_roofline", ctx) < 100


def test_readers_read_nothing_without_a_trace():
    ctx = {"run": {"trace": None, "chips": 1}, "trace": None, "peaks": None,
           "cfg": {}}
    for name in ("nemotron_step.mfu", "ssm.ms", "ssm.scan_ms",
                 "ssm_roofline", "ssm.chunks_per_step", "attn.ms",
                 "attn.fwd_ms", "moe.ms", "moe.dispatch_ms",
                 "moe.load_max_over_mean", "moe.chunks_per_pass",
                 "moe.overflow_share"):
        assert _read(name, ctx) is None, name


def test_mfu_from_the_traced_part():
    ctx = _ctx([])
    ctx["run"]["trace"] = {"t0": 0.0, "t1": 4.0, "steps": 10}
    ctx["run"]["lm"]["model"]["layer_types"] = [
        "mamba", "moe", "mamba", "moe", "mamba", "attention", "moe", "mamba",
        "moe"]
    ctx["run"]["lm"]["model"]["num_hidden_layers"] = 9
    # 17.6 TFLOP a step, 10 steps in 4 s, of 197 TFLOP/s
    assert abs(_read("nemotron_step.mfu", ctx) - 22.38) < 0.05
