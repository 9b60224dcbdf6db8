#!/usr/bin/env python3
"""The quickest proof that the main path still runs on the chip.

One process, which holds the chip from first touch to exit, drives the
entry points a user calls, once each, and checks what comes out:

1. train   ResNet-50 (full width, batch 128, bf16 data, multi-precision
           SGD-momentum) through ``mx.mod.Module(...).fit`` on a synthetic
           iterator that repeats one batch.
2. serve   ``mx.serving.InferenceEngine`` on the parameters phase 1
           trained, against un-batched ``Predictor`` forwards.
3. decode  ``mx.decode.DecodeEngine`` continuous batching against the same
           engine driven one sequence at a time (toy sizes: no model at
           width goes through this engine yet).
4. kernels both Pallas kernels compiled (not interpreted) against plain
           ``jax.numpy`` references.

``--chips 4`` runs none of these: it runs ``Module.fit`` data-parallel on
four chips against the same global batch on one chip, then one step on a
dp2 x mp2 mesh.

Any failed check is a non-zero exit; nothing is caught and continued.
Without an accelerator the script fails before any phase. ``--rehearse``
runs the same phases at a tiny size wherever JAX runs (the CPU
included, kernels interpreted); it can never print the success line.

The last line of a passing run is the contract's and nothing more:
    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}
"""
import argparse
import functools
import json
import math
import os
import sys
import threading
import time

import numpy as np
import jax
import jax.numpy as jnp
import jaxlib

import mxnet_tpu as mx
from mxnet_tpu import jax_cache, telemetry

HERE = os.path.dirname(os.path.abspath(__file__))
BF16 = np.dtype(jnp.bfloat16)

FULL = dict(layers=50, image=224, classes=1000, batch=128, warm=3, timed=8,
            serve_max_batch=32, serve_requests=36,
            # the four ResNet-50 NHWC stage outputs at batch 128
            bn_shapes=[(128, 56, 56, 256), (128, 28, 28, 512),
                       (128, 14, 14, 1024), (128, 7, 7, 2048)],
            attn_shape=(4, 16, 2048, 128), dp_batch=256)
TINY = dict(layers=18, image=64, classes=10, batch=16, warm=2, timed=3,
            serve_max_batch=4, serve_requests=10,
            bn_shapes=[(2, 8, 8, 128)], attn_shape=(1, 2, 256, 64),
            dp_batch=16)


class SmokeFailure(Exception):
    """A check of this script did not hold."""


def check(cond, msg):
    if not cond:
        raise SmokeFailure(msg)


def report(phase, **fields):
    print(json.dumps(dict(phase=phase, **fields), sort_keys=True,
                     default=str), flush=True)


class Phase:
    """Times one phase and prints its line: seconds, compile seconds,
    persistent-cache hits/misses and peak device bytes, plus whatever the
    phase adds to ``self.out``."""

    def __init__(self, name, watch, device):
        self.name, self.watch, self.device = name, watch, device
        self.out = {}

    def __enter__(self):
        self.t0 = time.perf_counter()
        self.cache0 = self.watch.counts()
        self.compile0 = telemetry.span_seconds("jit_compile")
        return self

    def __exit__(self, exc_type, exc, tb):
        if exc is not None:     # say what was seen, then fail all the same
            self.out["failed"] = "%s: %s" % (exc_type.__name__, exc)
        cache = {k: v - self.cache0[k]
                 for k, v in self.watch.counts().items()}
        stats = self.device.memory_stats() or {}
        compile_s = telemetry.span_seconds("jit_compile") - self.compile0
        report(self.name,
               seconds=round(time.perf_counter() - self.t0, 2),
               compile_seconds=round(compile_s, 2),
               persistent_cache=cache,
               peak_device_bytes=stats.get("peak_bytes_in_use"),
               **self.out)
        return False


def context(device, i=0):
    """``mx.tpu(i)``; the host only where ``--rehearse`` let a CPU in."""
    return mx.cpu(i) if device.platform == "cpu" else mx.tpu(i)


def resnet_symbol(cfg):
    sys.path.insert(0, os.path.join(HERE, "examples",
                                    "image-classification"))
    from symbols.resnet import get_symbol
    return get_symbol(num_classes=cfg["classes"], num_layers=cfg["layers"],
                      image_shape="3,%d,%d" % (cfg["image"], cfg["image"]))


def one_batch_iter(cfg, batch, n, seed=0):
    """Synthetic iterator that hands out the same host-made batch ``n``
    times (bf16 data, float labels), so the loss must fall."""
    from mxnet_tpu.io import DataBatch, DataDesc, DataIter

    rs = np.random.RandomState(seed)
    shape = (batch, 3, cfg["image"], cfg["image"])
    x = rs.uniform(-1, 1, shape).astype(np.float32)
    y = rs.randint(0, cfg["classes"], batch).astype(np.float32)

    class OneBatch(DataIter):
        def __init__(self):
            super().__init__(batch)
            self.batch = DataBatch([mx.nd.array(x).astype(BF16)],
                                   [mx.nd.array(y)], pad=0)
            self.i = 0

        @property
        def provide_data(self):
            return [DataDesc("data", shape, dtype=BF16)]

        @property
        def provide_label(self):
            return [DataDesc("softmax_label", (batch,))]

        def reset(self):
            self.i = 0

        def next(self):
            if self.i >= n:
                raise StopIteration
            self.i += 1
            return self.batch

    return OneBatch(), y.astype(np.int64)


def cross_entropy(probs, labels):
    p = np.asarray(probs, np.float32)
    return float(-np.mean(np.log(np.maximum(
        p[np.arange(len(labels)), labels], 1e-30))))


def optimizer_params(batch):
    """SGD with momentum, with the gradient averaged over the batch as
    the reference's ``Module.init_optimizer`` does by default. This
    repo's default is ``rescale_grad=1`` (the sum): at batch 128 that is
    128 times the step, and the loss here must fall."""
    return {"learning_rate": 0.05, "momentum": 0.9, "multi_precision": True,
            "rescale_grad": 1.0 / batch}


def fit_losses(mod, cfg, batch, kvstore, seed=0):
    """Warm-up epoch, then the timed epoch. Returns the per-batch losses
    of both (device outputs are held and read after the epoch, so the
    loop stays asynchronous), the counter deltas of the timed epoch and
    its img/s."""
    opt = optimizer_params(batch)
    outs, marks = [], []

    def keep(param):
        outs.append(mod.get_outputs()[0])
        marks.append(time.perf_counter())

    warm, labels = one_batch_iter(cfg, batch, cfg["warm"], seed)
    np.random.seed(seed)        # the initializers draw from numpy
    mx.random.seed(seed)
    mod.fit(warm, eval_metric=mx.metric.Accuracy(), num_epoch=1,
            kvstore=kvstore, initializer=mx.initializer.Xavier(),
            optimizer="sgd", optimizer_params=opt, batch_end_callback=keep)
    n_warm = len(outs)
    before = telemetry.counters()
    compiles0 = telemetry.span_count("jit_compile")
    timed, _ = one_batch_iter(cfg, batch, cfg["timed"], seed)
    mod.fit(timed, eval_metric=mx.metric.Accuracy(), num_epoch=1,
            kvstore=kvstore, optimizer="sgd", optimizer_params=opt,
            batch_end_callback=keep)
    # drain the queue before the clock stops: the loop only dispatched
    losses = [cross_entropy(o.asnumpy(), labels) for o in outs]
    dt = time.perf_counter() - marks[n_warm]
    after = telemetry.counters()
    delta = {k: v - before.get(k, 0) for k, v in after.items()
             if k.startswith("dispatch.") and v != before.get(k, 0)}
    return dict(losses=losses, n_warm=n_warm, dispatches=delta,
                compiles_after_warmup=(telemetry.span_count("jit_compile")
                                       - compiles0),
                img_s=batch * (len(marks) - n_warm - 1) / dt)


def no_aot_fallback(cards):
    bad = {k: c["aot_fallback"] for k, c in cards.items()
           if c.get("aot_fallback")}
    check(not bad, "programs fell back from AOT to plain jit: %r" % bad)


def phase_train(cfg, ph, device):
    mod = mx.mod.Module(resnet_symbol(cfg), context=context(device))
    r = fit_losses(mod, cfg, cfg["batch"], "local")
    losses = r["losses"]
    ph.out.update(losses=[round(v, 4) for v in losses],
                  dispatches=r["dispatches"],
                  compiles_after_warmup=r["compiles_after_warmup"],
                  fused_fallback=mod._fused_fallback_reason,
                  img_s_unclaimed=round(r["img_s"], 1))
    check(mod._fused_fallback_reason is None,
          "Module.fit left the fused step: %r" % mod._fused_fallback_reason)
    check(r["dispatches"] == {"dispatch.train_step": cfg["timed"]},
          "expected one train_step dispatch per batch and nothing else, "
          "got %r for %d batches" % (r["dispatches"], cfg["timed"]))
    check(r["compiles_after_warmup"] == 0,
          "%d compilations after warm-up" % r["compiles_after_warmup"])
    no_aot_fallback(telemetry.programs())
    check(all(math.isfinite(v) for v in losses), "loss not finite: %r"
          % losses)
    check(losses[-1] < losses[0], "loss did not fall on a repeated batch: "
          "%r" % losses)
    # donation is real on the chip: every holder must have been handed
    # the new buffers, or this read raises "Array has been deleted"
    arg_params, aux_params = mod.get_params()
    for name, arr in list(arg_params.items()) + list(aux_params.items()):
        check(bool(arr.asnumpy().size), "empty parameter %s" % name)
    on = {d for n in mod._param_names
          for d in mod._exec.arg_dict[n]._data.devices()}
    check(on == {device}, "parameters live on %r, not on %r" % (on, device))
    return arg_params, aux_params


def phase_serve(cfg, ph, device, arg_params, aux_params):
    from mxnet_tpu.predictor import Predictor
    from mxnet_tpu.serving import InferenceEngine

    ctx = context(device)
    sym = resnet_symbol(cfg)
    params = {"arg:" + k: v for k, v in arg_params.items()}
    params.update({"aux:" + k: v for k, v in aux_params.items()})
    row = (3, cfg["image"], cfg["image"])
    rs = np.random.RandomState(1)
    pool = rs.uniform(-1, 1, (16,) + row).astype(np.float32)

    # phase 1 trained bf16-resident weights: serve them as they are
    pred = Predictor(sym, params, {"data": (1,) + row}, ctx=ctx, dtype=BF16)
    want = []
    for x in pool:                      # the un-batched reference
        pred.forward(data=x[None])
        want.append(pred.get_output(0).asnumpy()[0])
    want = np.stack(want)

    engine = InferenceEngine(sym, params, {"data": (1,) + row}, ctx=ctx,
                             dtype=BF16, max_batch=cfg["serve_max_batch"],
                             max_wait_ms=2.0)
    try:
        compiles0 = telemetry.span_count("jit_compile")
        sizes = [1 + (i * 7) % min(8, cfg["serve_max_batch"])
                 for i in range(cfg["serve_requests"])]
        picks = [rs.randint(0, len(pool), n) for n in sizes]
        futs = [None] * len(picks)

        def submit(lo, hi):
            for i in range(lo, hi):
                futs[i] = engine.submit(data=pool[picks[i]])

        half = len(picks) // 2
        threads = [threading.Thread(target=submit, args=(0, half)),
                   threading.Thread(target=submit, args=(half, len(picks)))]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=600)
            check(not t.is_alive(), "a submitting thread hung")
        # Tolerance: both legs run the same bf16-rounded activations, but
        # a bucket of 32 rows and a batch of one tile their convolutions
        # differently, so f32 partial sums differ in the last bits and now
        # and then flip a bf16 rounding (2^-8 relative) that the next
        # ~50 layers carry along. Outputs are softmax probabilities.
        rtol, atol = 5e-2, 1e-4
        excess = []                # per request, beyond rtol * |ref|
        for i, fut in enumerate(futs):
            got = np.asarray(fut.result(timeout=600)[0], np.float32)
            ref = want[picks[i]]
            check(got.shape == ref.shape, "request %d: shape %r, want %r"
                  % (i, got.shape, ref.shape))
            check(np.isfinite(got).all(), "request %d: not finite" % i)
            excess.append(float((np.abs(got - ref)
                                 - rtol * np.abs(ref)).max()))
        ph.out.update(buckets=engine.buckets, requests=len(futs),
                      rows=int(sum(sizes)), rtol=rtol, atol=atol,
                      worst_excess_over_rtol=max(excess))
        worst = int(np.argmax(excess))
        check(excess[worst] <= atol,
              "request %d (%d rows) differs from the un-batched forward "
              "by %g beyond rtol %g" % (worst, sizes[worst], excess[worst],
                                        rtol))
        compiles = telemetry.span_count("jit_compile") - compiles0
        check(compiles == 0, "%d compilations while serving" % compiles)
        cards = engine.program_cards()
        check(len(cards) == len(engine.buckets), "cards %r for buckets %r"
              % (sorted(cards), engine.buckets))
        no_aot_fallback(cards)
        ph.out.update(compiles_after_warmup=compiles)
    finally:
        engine.close()


def phase_decode(ph, device):
    from mxnet_tpu.decode import AttentionDecodeCell, DecodeEngine

    cell = AttentionDecodeCell(vocab=29, embed=16, heads=4, head_dim=8,
                               max_len=48)
    rs = np.random.RandomState(2)
    prompts = [rs.randint(1, 28, n).astype(np.int32)
               for n in (5, 3, 8, 2, 11, 4, 7, 1, 9, 6, 3, 10)]
    new = [10, 4, 7, 10, 3, 9, 5, 10, 2, 8, 6, 10]
    with DecodeEngine(cell, cell.init_params(1), slots=4, max_prompt_len=16,
                      max_new_tokens=10, ctx=context(device)) as eng:
        serial = [eng.generate(p, max_new_tokens=n).tokens
                  for p, n in zip(prompts, new)]
        futs = [eng.submit(p, max_new_tokens=n)
                for p, n in zip(prompts, new)]
        batched = [f.result(timeout=300).tokens for f in futs]
        no_aot_fallback(eng.program_cards())
    ph.out.update(size="toy", sequences=len(prompts),
                  tokens=sum(len(t) for t in serial))
    check(serial == batched, "continuous batching changed the tokens:\n"
          "%r\n%r" % (serial, batched))
    check(all(len(t) == n for t, n in zip(serial, new)), "token counts")


def _compiled(fn, *args):
    """AOT-compile ``fn`` for ``args``, timed as a ``jit_compile`` span
    like the programs the executor builds."""
    with telemetry.span("jit_compile"):
        return jax.jit(fn).lower(*args).compile()


def _has_kernel(compiled, device, what):
    if device.platform != "tpu":
        return "interpreted"
    check("tpu_custom_call" in compiled.as_text(),
          "%s: no tpu_custom_call in the compiled program" % what)
    return "tpu_custom_call"


def _close(got, want, rtol, atol, what):
    """|got - want| <= atol + rtol * |want| everywhere; ``atol`` may be an
    array (a bound worked out per element). Compared on the device: the
    arrays hold up to 1e8 elements, and only the verdict comes back."""
    f32 = jnp.float32

    @jax.jit
    def worst(got, want, atol):
        got, want = got.astype(f32), want.astype(f32)
        over = jnp.abs(got - want) - rtol * jnp.abs(want) - atol
        return jnp.isfinite(got).all(), jnp.max(over)

    finite, over = worst(got, want, atol)
    check(bool(finite), "%s: not finite" % what)
    check(float(over) <= 0, "%s: off by %g beyond its tolerance (rtol %g)"
          % (what, float(over), rtol))


def phase_kernels(cfg, ph, device):
    from mxnet_tpu.pallas.fused_bn import scale_bias_add_relu
    from mxnet_tpu.pallas.flash_attention import flash_attention
    from mxnet_tpu.parallel import attention

    bf16, f32 = jnp.bfloat16, jnp.float32
    modes = set()
    keys = iter(jax.random.split(jax.random.key(3), 16))

    def bn_ref(x, s, b, r):            # f32 throughout, one rounding
        y = (x.astype(f32) * s + b + r.astype(f32))
        return jnp.maximum(y, 0.0).astype(x.dtype)

    @functools.partial(jax.jit, static_argnums=1)
    def bn_inputs(key, shape):
        """(x, s, b, r) with every pre-activation at least 0.25 from
        zero, so that a bf16 rounding cannot flip the ReLU mask the
        gradients share; and the forward bound (see below)."""
        ks, kb, kr, kp, kn = jax.random.split(key, 5)
        c = shape[-1]
        s = jax.random.uniform(ks, (c,), f32, 0.5, 1.5)
        b = jax.random.uniform(kb, (c,), f32, -0.5, 0.5)
        r = jax.random.normal(kr, shape, f32).astype(bf16)
        pre = jax.random.uniform(kp, shape, f32, 0.25, 2.0) \
            * jnp.where(jax.random.bernoulli(kn, 0.5, shape), 1.0, -1.0)
        x = ((pre - b - r.astype(f32)) / s).astype(bf16)
        # The kernel works in bf16 (unit roundoff 2^-8): it rounds s, b,
        # x*s, +b and +r, the reference rounds once at the end. Six
        # roundings of intermediates no larger than |x*s|+|b|+|r| (|r|
        # reaches 5 over 1e8 normal draws, so no single atol serves).
        bound = 6 * 2.0 ** -8 * (jnp.abs(x.astype(f32) * s) + jnp.abs(b)
                                 + jnp.abs(r.astype(f32)))
        return (x, s, b, r), bound

    def loss(f):
        return lambda *a: jnp.sum(f(*a).astype(f32))

    for shape in cfg["bn_shapes"]:
        args, bound = bn_inputs(next(keys), tuple(shape))
        what = "fused_bn %r" % (shape,)
        fwd = _compiled(scale_bias_add_relu, *args)
        modes.add(_has_kernel(fwd, device, what))
        _close(fwd(*args), jax.jit(bn_ref)(*args), 0.0, bound, what)
        grad = _compiled(jax.grad(loss(scale_bias_add_relu),
                                  argnums=(0, 1, 2, 3)), *args)
        modes.add(_has_kernel(grad, device, what + " grad"))
        want = jax.jit(jax.grad(loss(bn_ref), argnums=(0, 1, 2, 3)))(*args)
        # masks agree by construction; dx is bf16(g * bf16(s)) against
        # the reference's one rounding (3 x 2^-8), dscale and dbias are
        # f32 sums over up to 4e5 rows in another order
        for g, w, name in zip(grad(*args), want, "x scale bias res".split()):
            _close(g, w, 2e-2, 1e-2, "%s d%s" % (what, name))

    shape = cfg["attn_shape"]
    q, k, v = (jax.random.normal(next(keys), shape, f32).astype(bf16)
               for _ in range(3))
    what = "flash_attention %r causal" % (shape,)

    def flash(q, k, v):
        return flash_attention(q, k, v, causal=True)

    def attn_ref(q, k, v):             # f32 inputs, full-precision dots
        with jax.default_matmul_precision("highest"):
            return attention(q.astype(f32), k.astype(f32), v.astype(f32),
                             causal=True)

    fwd = _compiled(flash, q, k, v)
    modes.add(_has_kernel(fwd, device, what))
    # the kernel rounds the probabilities to bf16 for the PV product and
    # the output to bf16 (unit roundoff 2^-8 each): at most
    # 2^-8 * (|out| + E_p|v|), with |v| <= ~5
    _close(fwd(q, k, v), jax.jit(attn_ref)(q, k, v), 2e-2, 2e-2, what)
    grad = _compiled(jax.grad(loss(flash), argnums=(0, 1, 2)), q, k, v)
    modes.add(_has_kernel(grad, device, what + " grad"))
    want = jax.jit(jax.grad(loss(attn_ref), argnums=(0, 1, 2)))(q, k, v)
    for g, w, name in zip(grad(q, k, v), want, "qkv"):
        # gradients come back as bf16; compare on the scale of the
        # largest entry (sums over up to 2048 keys)
        scale = jnp.max(jnp.abs(w))
        _close(g.astype(f32) / scale, w / scale, 0.0, 2e-2,
               "%s d%s" % (what, name))
    check(len(modes) == 1, "kernels ran in mixed modes: %r" % modes)
    ph.out.update(kernel_mode=modes.pop(), bn_shapes=cfg["bn_shapes"],
                  attn_shape=shape)


def phase_four_chips(cfg, watch, devices):
    """dp4 ``Module.fit`` against the same global batch on one chip, then
    one step on a dp2 x mp2 mesh."""
    from jax.sharding import PartitionSpec as P
    from mxnet_tpu.parallel import PartitionRules
    from mxnet_tpu.parallel.partition import committed_nbytes

    four = [context(devices[0], i) for i in range(4)]
    batch = cfg["dp_batch"]

    with Phase("one_chip_reference", watch, devices[0]) as ph:
        one = mx.mod.Module(resnet_symbol(cfg), context=four[0])
        ref = fit_losses(one, cfg, batch, "device")
        check(one._fused_fallback_reason is None, "one-chip leg fell back")
        ph.out.update(losses=[round(v, 4) for v in ref["losses"]])
        del one

    with Phase("dp4", watch, devices[0]) as ph:
        mod = mx.mod.Module(resnet_symbol(cfg), context=four)
        got = fit_losses(mod, cfg, batch, "device")
        ph.out.update(losses=[round(v, 4) for v in got["losses"]],
                      dispatches=got["dispatches"],
                      fused_fallback=mod._fused_fallback_reason,
                      img_s_unclaimed=round(got["img_s"], 1))
        check(mod._fused_fallback_reason is None,
              "dp4 Module.fit left the fused SPMD step: %r"
              % mod._fused_fallback_reason)
        check(got["dispatches"] == {"dispatch.train_step": cfg["timed"]},
              "dp4: dispatches %r" % got["dispatches"])
        no_aot_fallback(telemetry.programs())
        holders = set()
        for n in mod._param_names:
            holders |= mod._exec.arg_dict[n]._data.devices()
        data = mod._exec.arg_dict["data"]._data
        shards = data.addressable_shards
        ph.out.update(devices=sorted(str(d) for d in holders),
                      batch_shards=[[str(s.device), list(s.data.shape)]
                                    for s in shards])
        check(holders == set(devices[:4]), "parameters committed to %r"
              % holders)
        check(len({s.device for s in shards}) == 4
              and all(s.data.shape[0] == batch // 4 for s in shards),
              "batch not sharded four ways")
        texts = [rec[0].as_text()
                 for rec in mod._fused_plan["fn"]._cache.values() if rec[2]]
        check(texts and all("all-reduce" in t for t in texts),
              "no all-reduce in the compiled dp4 step")
        ph.out.update(all_reduce=True)
        # Same seed, same global batch, same math: GSPMD reduces the
        # batch-norm statistics and the gradients over the whole batch.
        # What differs is the order of the sums, so bf16 activations
        # round differently here and there. Step 0 is a forward pass of
        # identical parameters: 1%. Step 1 has seen one update, which a
        # missing or doubled all-reduce would scale by 1/4 or 4: 10%.
        # From then on a net that memorises one batch amplifies the
        # rounding (seen on four virtual devices: 33% apart at step 2,
        # both falling), so each run only has to end below its start.
        a, b = got["losses"], ref["losses"]
        for i, tol in enumerate((1e-2, 1e-1)):
            check(abs(a[i] - b[i]) <= tol * abs(b[i]),
                  "step %d: dp4 loss %g, one-chip loss %g (tolerance %g)"
                  % (i, a[i], b[i], tol))
        check(all(math.isfinite(v) for v in a + b), "losses not finite")
        check(a[-1] < a[0] and b[-1] < b[0], "a loss did not fall")
        del mod

    with Phase("dp2_mp2", watch, devices[0]) as ph:
        # README "Sharding rules" grammar; the patterns are ResNet's own
        # parameter names (output channels of every weight, and the
        # per-channel vectors, split over mp)
        rules = PartitionRules([
            (r"weight$", P("mp")),
            (r"(gamma|beta|bias)$", P("mp")),
        ], unmatched="replicate")
        mod = mx.mod.Module(resnet_symbol(cfg), context=four,
                            partition_rules=rules,
                            mesh_axes={"dp": 2, "mp": 2})
        it, labels = one_batch_iter(cfg, batch, 1)
        np.random.seed(0)
        mx.random.seed(0)
        mod.fit(it, eval_metric=mx.metric.Accuracy(), num_epoch=1,
                kvstore="device", initializer=mx.initializer.Xavier(),
                optimizer="sgd",
                optimizer_params=optimizer_params(batch))
        check(mod._fused_fallback_reason is None,
              "dp2 x mp2 left the fused step: %r"
              % mod._fused_fallback_reason)
        loss = cross_entropy(mod.get_outputs()[0].asnumpy(), labels)
        check(math.isfinite(loss), "dp2 x mp2 loss %r" % loss)
        arrs = [mod._exec.arg_dict[n]._data for n in mod._param_names]
        full = sum(int(a.size) * a.dtype.itemsize for a in arrs)
        # committed_nbytes sums the shards over the mesh's four devices
        per_device = sum(committed_nbytes(a) for a in arrs) // 4
        check(0.45 <= per_device / full <= 0.6,
              "per-device parameter bytes %d of %d replicated"
              % (per_device, full))
        no_aot_fallback(telemetry.programs())
        ph.out.update(loss=round(loss, 4), param_bytes_replicated=full,
                      param_bytes_per_device=per_device)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the four-chip path and what it is "
                         "compared with")
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes on whatever JAX finds; never prints "
                         "the success line")
    args = ap.parse_args(argv)

    devices = jax.devices()
    dev = devices[0]
    if not args.rehearse and dev.platform != "tpu":
        raise SmokeFailure(
            "no accelerator: jax.devices()[0] is %r (platform %r)"
            % (dev, dev.platform))
    if len(devices) < args.chips:
        raise SmokeFailure("--chips %d, but JAX reports %d device(s)"
                           % (args.chips, len(devices)))
    # the repo's own executable store stays off: JAX's cache is the one
    # the outside can place
    os.environ.pop("MXNET_COMPILE_CACHE", None)
    cache_dir = jax_cache.place()
    watch = jax_cache.CacheWatch()
    cfg = TINY if args.rehearse else FULL
    report("start", platform=dev.platform, kind=dev.device_kind,
           count=len(devices), jax=jax.__version__,
           jaxlib=jaxlib.__version__, cache_dir=cache_dir,
           native_lib=os.path.isdir(os.path.join(HERE, "mxnet_tpu",
                                                 "_lib")),
           size="tiny" if args.rehearse else "full", chips=args.chips)
    t0 = time.perf_counter()

    if args.chips == 4:
        phase_four_chips(cfg, watch, devices)
    else:
        with Phase("train", watch, dev) as ph:
            arg_params, aux_params = phase_train(cfg, ph, dev)
        with Phase("serve", watch, dev) as ph:
            phase_serve(cfg, ph, dev, arg_params, aux_params)
        with Phase("decode", watch, dev) as ph:
            phase_decode(ph, dev)
        with Phase("kernels", watch, dev) as ph:
            phase_kernels(cfg, ph, dev)

    report("total", seconds=round(time.perf_counter() - t0, 2),
           persistent_cache=watch.counts())
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices)}
    if args.rehearse:
        print(json.dumps({"rehearsal": "passed", "device": device}))
    else:
        print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
