"""MXNET_BACKWARD_DO_MIRROR — segmented rematerialisation.

The mirror knob evaluates the op graph in ~sqrt(N) jax.checkpoint
segments (executor.py eval_graph_mirrored ≙ reference
graph_executor.cc:282-305 mirror policy). These tests pin:
  * gradients and BN aux updates identical to the plain path,
  * recompute genuinely emitted (more matmuls in the lowered program),
  * dropout (an RNG op) reproducing the same mask under recompute.
"""
import os

import numpy as np
import pytest

import mxnet_tpu as mx


def _build(with_bn=True, with_dropout=False):
    data = mx.sym.Variable("data")
    h = data
    for i in range(6):
        h = mx.sym.FullyConnected(h, num_hidden=32, name="fc%d" % i)
        if with_bn:
            h = mx.sym.BatchNorm(h, name="bn%d" % i)
        h = mx.sym.Activation(h, act_type="relu")
        if with_dropout:
            h = mx.sym.Dropout(h, p=0.5, name="do%d" % i)
    h = mx.sym.FullyConnected(h, num_hidden=4, name="head")
    return mx.sym.SoftmaxOutput(h, name="softmax")


def _run(sym, mirror, seed=0):
    os.environ["MXNET_BACKWARD_DO_MIRROR"] = "1" if mirror else "0"
    try:
        rs = np.random.RandomState(seed)
        exe = sym.simple_bind(ctx=mx.cpu(), grad_req="write",
                              data=(8, 16), softmax_label=(8,))
        for name, arr in exe.arg_dict.items():
            if name not in ("data", "softmax_label"):
                arr[:] = rs.normal(0, 0.1, arr.shape).astype(np.float32)
        exe.arg_dict["data"][:] = rs.normal(size=(8, 16)).astype(np.float32)
        exe.arg_dict["softmax_label"][:] = rs.randint(0, 4, 8).astype(
            np.float32)
        exe.forward_backward()
        grads = {n: g.asnumpy() for n, g in exe.grad_dict.items()
                 if g is not None}
        aux = {n: a.asnumpy() for n, a in exe.aux_dict.items()}
        outs = [o.asnumpy() for o in exe.outputs]
        return outs, grads, aux
    finally:
        os.environ["MXNET_BACKWARD_DO_MIRROR"] = "0"


def test_mirror_matches_plain():
    sym = _build(with_bn=True)
    outs_p, grads_p, aux_p = _run(sym, mirror=False)
    outs_m, grads_m, aux_m = _run(sym, mirror=True)
    for a, b in zip(outs_p, outs_m):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)
    assert set(grads_p) == set(grads_m)
    for n in grads_p:
        np.testing.assert_allclose(grads_p[n], grads_m[n], rtol=1e-5,
                                   atol=1e-6, err_msg=n)
    # BN moving stats updated identically through the checkpoint
    assert aux_p and set(aux_p) == set(aux_m)
    for n in aux_p:
        np.testing.assert_allclose(aux_p[n], aux_m[n], rtol=1e-5,
                                   atol=1e-6, err_msg=n)


def test_mirror_emits_recompute():
    from mxnet_tpu import random as _random

    def lowered_dots(mirror):
        os.environ["MXNET_BACKWARD_DO_MIRROR"] = "1" if mirror else "0"
        try:
            sym = _build(with_bn=False)
            exe = sym.simple_bind(ctx=mx.cpu(), grad_req="write",
                                  data=(8, 16), softmax_label=(8,))
            gn = tuple(n for n in exe._arg_names
                       if exe._grad_req[n] != "null")
            fn = exe._prog.fwd_bwd_fn(True, gn)
            args = {n: a._data for n, a in
                    zip(exe._arg_names, exe.arg_arrays)}
            aux = {n: a._data for n, a in
                    zip(exe._aux_names, exe.aux_arrays)}
            hg = tuple([None] * exe.output_entries_len())
            low = fn.lower(args, aux, _random.take_key(), hg)
            return low.as_text().count("dot_general")
        finally:
            os.environ["MXNET_BACKWARD_DO_MIRROR"] = "0"

    assert lowered_dots(True) > lowered_dots(False)


def test_mirror_dropout_mask_consistent():
    """The recomputed forward must replay the SAME dropout mask the
    original forward drew, or gradients are silently wrong."""
    sym = _build(with_bn=False, with_dropout=True)
    # grads of a dropout net are only self-consistent if the mask is
    # identical between the saved and recomputed forward: verify the
    # mirrored grads match the plain path run with the SAME rng state
    from mxnet_tpu import random as _random
    _random.seed(42)
    _, grads_p, _ = _run(sym, mirror=False)
    _random.seed(42)
    _, grads_m, _ = _run(sym, mirror=True)
    for n in grads_p:
        np.testing.assert_allclose(grads_p[n], grads_m[n], rtol=1e-5,
                                   atol=1e-6, err_msg=n)


# ---- a segment keeps the values an op has named -------------------------
# (executor.MIRROR_KEEPS: the attention kernel's output and log-sum-exp)

_T, _HEADS, _KV, _DIM = 128, 4, 2, 16


def _fc(x, name, width, **kw):
    return mx.sym.FullyConnected(x, name=name, num_hidden=width,
                                 no_bias=True, flatten=False, **kw)


def _attention(x, p, window, **kw):
    a = _fc(x, p + "in", _HEADS * _DIM, **kw)
    o = mx.sym._contrib_CausalAttention(
        _fc(a, p + "wq", _HEADS * _DIM), _fc(a, p + "wk", _KV * _DIM),
        _fc(a, p + "wv", _KV * _DIM), name=p + "attn_core",
        num_heads=_HEADS, num_kv_heads=_KV, window=window)
    return o * mx.sym.Activation(_fc(a, p + "wg", _HEADS * _DIM),
                                 act_type="sigmoid")


def _attn_layers(window, stages):
    """Two layers of projection + attention + gate; with ``stages`` each
    starts a checkpoint segment, as the language model's layers do."""
    h = mx.sym.Variable("data")
    for i in range(2):
        kw = {"attr": {"__mirror_stage__": "1"}} if stages else {}
        h = h + _attention(h, "l%d_" % i, window, **kw)
    return mx.sym.MakeLoss(mx.sym.sum(h * h), name="loss")


def _attn_node(window):
    """Three op nodes: a graph ``can_segment`` refuses, so mirroring wraps
    it in the one whole-graph checkpoint of ``_vjp_over_graph``."""
    x = mx.sym.Variable("data")
    return mx.sym.MakeLoss(mx.sym.sum(mx.sym._contrib_CausalAttention(
        x, x, x, name="attn_core", num_heads=_HEADS, num_kv_heads=_HEADS,
        window=window)), name="loss")


def _grad_program(sym, mirror=False, seed=0, **shapes):
    """``(jaxpr text of the gradient, lowered text, outputs, gradients)``
    of ``sym`` bound on ``shapes`` (default: (1, T, heads * dim) data)."""
    from mxnet_tpu import random as _random
    os.environ["MXNET_BACKWARD_DO_MIRROR"] = "1" if mirror else "0"
    try:
        rs = np.random.RandomState(seed)
        exe = sym.simple_bind(ctx=mx.cpu(), grad_req="write", **(
            shapes or {"data": (1, _T, _HEADS * _DIM)}))
        for arr in exe.arg_dict.values():
            arr[:] = rs.normal(0, 0.3, arr.shape).astype(np.float32)
        gn = tuple(n for n in exe._arg_names if exe._grad_req[n] != "null")
        fn = exe._prog.fwd_bwd_fn(True, gn)
        args = {n: a._data for n, a in zip(exe._arg_names, exe.arg_arrays)}
        aux = {n: a._data for n, a in zip(exe._aux_names, exe.aux_arrays)}
        hg = tuple([None] * exe.output_entries_len())
        traced = fn._jitted.trace(args, aux, _random.take_key(), hg)
        exe.forward_backward()
        return (str(traced.jaxpr), traced.lower().as_text(),
                [o.asnumpy() for o in exe.outputs],
                {n: g.asnumpy() for n, g in exe.grad_dict.items()})
    finally:
        os.environ["MXNET_BACKWARD_DO_MIRROR"] = "0"


def _bare_checkpoint(monkeypatch):
    """The parent's form: a checkpoint that keeps nothing, names or not."""
    from mxnet_tpu import executor
    monkeypatch.setattr(executor, "_MIRROR_POLICY", None)


def _kernels(jaxpr):
    return [jaxpr.count("name=flash_attention_" + k)
            for k in ("fwd", "dq", "dkv")]


@pytest.mark.parametrize("window", [0, 64])
def test_segment_keeps_attention_residuals(window, monkeypatch):
    """One forward kernel a layer in the gradient (a bare checkpoint runs
    it twice); the kept arrays are the ones the recomputation makes, so
    nothing differs from the bare checkpoint by a bit."""
    jaxpr, _, outs, grads = _grad_program(_attn_layers(window, True))
    assert _kernels(jaxpr) == [2, 2, 2]
    with monkeypatch.context() as m:
        _bare_checkpoint(m)
        jaxpr_b, _, outs_b, grads_b = _grad_program(
            _attn_layers(window, True))
    assert _kernels(jaxpr_b) == [4, 2, 2]
    np.testing.assert_array_equal(outs[0], outs_b[0])
    assert set(grads) == set(grads_b) and len(grads) == 11
    for n in grads:
        np.testing.assert_array_equal(grads[n], grads_b[n], err_msg=n)
    # and the graph bound without segments agrees
    jaxpr_p, _, outs_p, grads_p = _grad_program(_attn_layers(window, False))
    assert _kernels(jaxpr_p) == [2, 2, 2] and "remat" not in jaxpr_p
    np.testing.assert_allclose(outs[0], outs_p[0], rtol=1e-5, atol=1e-6)
    for n in grads:
        np.testing.assert_allclose(grads[n], grads_p[n], rtol=1e-5,
                                   atol=1e-6, err_msg=n)


@pytest.mark.parametrize("window", [0, 64])
def test_whole_graph_checkpoint_takes_the_same_policy(window, monkeypatch):
    sym = _attn_node(window)
    from mxnet_tpu.executor import _GraphProgram
    assert not _GraphProgram(sym).can_segment()
    jaxpr, _, outs, grads = _grad_program(sym, mirror=True)
    assert "remat" in jaxpr and _kernels(jaxpr) == [1, 1, 1]
    with monkeypatch.context() as m:
        _bare_checkpoint(m)
        jaxpr_b, _, outs_b, grads_b = _grad_program(sym, mirror=True)
    assert _kernels(jaxpr_b) == [2, 1, 1]
    np.testing.assert_array_equal(outs[0], outs_b[0])
    np.testing.assert_array_equal(grads["data"], grads_b["data"])


def _named_in_source():
    """Every ``checkpoint_name(value, "<name>")`` in the package, as
    ``(name, file, enclosing function)``."""
    import ast
    import mxnet_tpu
    root = os.path.dirname(mxnet_tpu.__file__)
    found = []

    def visit(node, path, fn):
        if isinstance(node, ast.FunctionDef):
            fn = node.name
        if isinstance(node, ast.Call) and "checkpoint_name" in (
                getattr(node.func, "id", None),
                getattr(node.func, "attr", None)):
            found.append((node.args[1].value, path, fn))
        for child in ast.iter_child_nodes(node):
            visit(child, path, fn)

    for where, _, files in os.walk(root):
        for f in files:
            if f.endswith(".py"):
                path = os.path.join(where, f)
                with open(path) as src:
                    visit(ast.parse(src.read()),
                          os.path.relpath(path, root), None)
    return found


def test_each_kept_name_is_made_by_one_op():
    """Every name a segment keeps is given in exactly one place of the
    package (one op's body), and no value is named that is not kept."""
    from mxnet_tpu import executor
    found = _named_in_source()
    names = [n for n, _, _ in found]
    assert sorted(names) == sorted(executor.MIRROR_KEEPS), found
    assert len(set(executor.MIRROR_KEEPS)) == len(executor.MIRROR_KEEPS)
    where = {n: (f, fn) for n, f, fn in found}
    assert where["flash_attention_out"] == ("pallas/flash_attention.py",
                                            "_fwd")
    for n in ("kda_intra_aqk", "kda_intra_w", "kda_intra_u"):
        assert where[n] == ("ops/lm.py", "_kda"), where


def test_a_graph_without_the_delta_rule_lowers_as_before(monkeypatch):
    """Names no op of the graph produces change nothing: two mirrored
    attention layers lower to the same text under the step's policy and
    under one that keeps the attention kernel's two names alone."""
    import jax
    from mxnet_tpu import executor
    sym = _attn_layers(0, True)
    jaxpr, text = _grad_program(sym)[:2]
    monkeypatch.setattr(
        executor, "_MIRROR_POLICY",
        jax.checkpoint_policies.save_only_these_names(
            "flash_attention_out", "flash_attention_lse"))
    jaxpr_b, text_b = _grad_program(sym)[:2]
    assert text == text_b
    assert _kernels(jaxpr) == _kernels(jaxpr_b) == [2, 2, 2]


def test_segment_without_a_named_value_lowers_as_before(monkeypatch):
    """No op of the MLP names a value: the policy keeps nothing and the
    program is the bare checkpoint's, text for text."""
    def lowered():
        return _grad_program(_build(with_bn=True), mirror=True,
                             data=(8, 16), softmax_label=(8,))[1]

    with_policy = lowered()
    _bare_checkpoint(monkeypatch)
    assert with_policy == lowered()
    assert with_policy.count("dot_general") > 14     # it does recompute
