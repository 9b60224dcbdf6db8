"""Executor — compiled execution of Symbol graphs.

Parity: reference ``src/executor/graph_executor.cc`` + ``python/mxnet/
executor.py``. TPU-native design: instead of the reference's pipeline
(nnvm Gradient pass → PlanMemory → per-node OpExecutors → engine pushes,
graph_executor.cc:956-1490), the whole forward graph is ONE traced JAX
function; ``jax.vjp`` over it is the Gradient pass; ``jax.jit`` is
PlanMemory + op fusion + scheduling. One executor therefore makes at most
three XLA programs: forward(train), forward(infer), forward+backward —
each fully fused and memory-planned by XLA for the MXU/HBM.

Random ops get their keys from an explicit key argument folded per-node
(ops/common.rng_scope), keeping compiled programs pure. BatchNorm-style
aux updates come back as extra outputs and are written into aux arrays,
mirroring the reference's in-place aux mutation.
"""
from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

import functools
import itertools
import time

from .base import MXNetError
from .context import current_context
from .ops.common import rng_scope, mx_dtype
from . import random as _random
from . import telemetry
from . import faults
from . import jax_cache

jax_cache.listen()

#: the attribute with which a symbol's op node starts a checkpoint segment
#: (``_GraphProgram.mirror_stages``)
MIRROR_STAGE = "__mirror_stage__"

#: the names an op may give a value (``jax.ad_checkpoint.checkpoint_name``)
#: that only it can produce and that costs more to make again than to hold:
#: a checkpoint segment keeps these between forward and backward and makes
#: everything else again. Outside a checkpoint a name is the identity.
#: The attention kernel's output and log-sum-exp (``pallas.flash_attention``);
#: the delta rule's ``Aqk``, ``W``, ``U`` (``ops.lm._kda``), which the
#: pull-back of its state pair reads: 235 MB a layer at 8,192 positions to
#: hold, a whole ``kda_intra_fwd`` kernel call a layer to make again.
MIRROR_KEEPS = ("flash_attention_out", "flash_attention_lse",
                "kda_intra_aqk", "kda_intra_w", "kda_intra_u")

# what every mirrored evaluation hands ``jax.checkpoint``: nothing is kept
# from inside a segment but the values its ops have named; where no op names
# one, the plain checkpoint. ONE object for every segment: jax caches the
# split of a jitted function inside a segment by the policy's identity, so a
# policy made anew for each segment would lower such a function once a segment
_MIRROR_POLICY = jax.checkpoint_policies.save_only_these_names(*MIRROR_KEEPS)

__all__ = ["Executor", "infer_graph_shapes", "record_dispatch",
           "card_from_compiled", "DeviceMemoryError"]


# ---------------------------------------------------------------------------
# Dispatch accounting
# ---------------------------------------------------------------------------
# One call per jitted-program execution (NOT per eager op): the number of
# device dispatches per train batch is a load-bearing performance
# property on a remoted PJRT backend, so tests pin it. Every dispatch
# fans out through ``telemetry.dispatch_event`` — the counter registry
# plus every ``telemetry.on_dispatch(cb)`` subscriber. ``dispatch_hook``
# remains as the LEGACY single-slot shim (monkeypatch with a callable
# taking one tag string); prefer the multi-subscriber registry, which
# doesn't clobber other listeners.
dispatch_hook = None


def record_dispatch(kind):
    """Report one jitted-program execution to the telemetry dispatch
    registry (and the legacy single-slot ``dispatch_hook`` shim). The
    ONE dispatch-reporting entry point — tools/run_checks.sh lints that
    no other module grows a raw hook call."""
    if dispatch_hook is not None:
        dispatch_hook(kind)
    telemetry.dispatch_event(kind)


# ---------------------------------------------------------------------------
# Instrumented program compilation (program cards)
# ---------------------------------------------------------------------------
# Every jitted entry point in this module compiles through
# ``_InstrumentedProgram`` — explicit ``trace().lower().compile()`` with
# the trace, lower, compile and card phases timed as telemetry spans and
# the compiled executable's own cost/memory analysis captured into a
# PROGRAM CARD in ``telemetry.programs()``. The card is the online
# counterpart of an offline xprof capture: per-program FLOPs, bytes
# accessed, HBM footprint, compile wall-time and dispatch count,
# available at every ``telemetry.snapshot()`` — exactly the per-program
# features TPU cost models are built on (Kaufman et al.
# arXiv:2008.01040, TVM arXiv:1802.04799).

_PROG_SEQ = itertools.count(1)

# once-per-cause recompile warnings: (entry, path, change-kind) pairs
# already reported through log.py
_RECOMPILE_WARNED = set()


class DeviceMemoryError(MXNetError):
    """A device allocation failure (RESOURCE_EXHAUSTED / OOM) re-raised
    with the live buffer ledger and the failing program's memory card
    stitched into the message. The original backend error rides as
    ``__cause__``."""


_OOM_MARKERS = ("RESOURCE_EXHAUSTED", "RESOURCE EXHAUSTED",
                "Out of memory", "out of memory", "OOM")


def _is_oom(exc):
    s = str(exc)
    return any(m in s for m in _OOM_MARKERS)


def _fmt_bytes(n):
    if n is None:
        return "?"
    for unit in ("B", "KiB", "MiB", "GiB"):
        if abs(n) < 1024 or unit == "GiB":
            return "%.1f%s" % (n, unit) if unit != "B" else "%dB" % n
        n /= 1024.0
    return "%d" % n


def _enriched_oom(exc, card):
    """Build the DeviceMemoryError for one dispatch-time OOM: the
    failing program's memory card + the ledger's per-context totals and
    top live buffers + PJRT device stats where the platform exposes
    them. The raw backend message stays first so existing matching on
    it keeps working."""
    lines = ["device memory exhausted dispatching program %r: %s"
             % (card.get("id", "?"), exc)]
    lines.append(
        "program memory card: peak_bytes=%s argument_bytes=%s "
        "output_bytes=%s temp_bytes=%s generated_code_bytes=%s "
        "flops=%s bytes_accessed=%s" % (
            _fmt_bytes(card.get("peak_bytes")),
            _fmt_bytes(card.get("argument_bytes")),
            _fmt_bytes(card.get("output_bytes")),
            _fmt_bytes(card.get("temp_bytes")),
            _fmt_bytes(card.get("generated_code_bytes")),
            card.get("flops"), card.get("bytes_accessed")))
    led = telemetry.ledger()
    if led:
        lines.append("live device-buffer ledger:")
        for ctx, st in sorted(led.items()):
            lines.append("  %s: %d buffers alive / %s (peak %s)"
                         % (ctx, st["alive_count"],
                            _fmt_bytes(st["alive_bytes"]),
                            _fmt_bytes(st["peak_bytes"])))
    top = telemetry.ledger_top(8)
    if top:
        lines.append("top live buffers:")
        for b in top:
            lines.append("  %s %s %s %s [%s]"
                         % (_fmt_bytes(b["nbytes"]),
                            tuple(b["shape"] or ()), b["dtype"], b["ctx"],
                            b["kind"]))
    try:
        from .storage import Storage
        stats = Storage.device_stats()
        if stats:
            lines.append("pjrt device stats: %s" % stats)
    except Exception:
        pass
    return DeviceMemoryError("\n".join(lines))


def _leaf_key(leaf):
    """Hashable (shape, dtype) of one argument leaf — the per-dispatch
    cache key component. Python scalars key by type (jax weak-types
    them; the value never changes the signature)."""
    try:
        return (leaf.shape, leaf.dtype)
    except AttributeError:
        return ((), type(leaf))


def _compiled_cost(compiled):
    """``Compiled.cost_analysis()`` normalised to one flat dict (older
    jaxlibs return a one-element list). Raising backends propagate to
    the caller's graceful-degradation path."""
    ca = compiled.cost_analysis()
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else {}
    return dict(ca or {})


def _compiled_memory(compiled):
    """``Compiled.memory_analysis()`` as a plain dict of byte counts."""
    ma = compiled.memory_analysis()
    return {
        "generated_code_bytes": int(ma.generated_code_size_in_bytes),
        "argument_bytes": int(ma.argument_size_in_bytes),
        "output_bytes": int(ma.output_size_in_bytes),
        "alias_bytes": int(ma.alias_size_in_bytes),
        "temp_bytes": int(ma.temp_size_in_bytes),
    }


def card_from_compiled(kind, compiled, entry=None, signature=None,
                       donated=(), extra=None):
    """Build one JSON-safe program card from an AOT-compiled
    executable. The ONE card builder, so the card schema cannot drift
    between the paths that compile (the executor's instrumented
    wrapper is its user). Cost and
    memory analysis failures degrade to ``None`` fields (older jaxlib /
    backend quirks must never break dispatch)."""
    card = {
        "id": entry or "%s@p%d" % (kind, next(_PROG_SEQ)),
        "kind": kind,
        "signature": signature,
        "donated": sorted(donated),
        "dispatches": 0,
        "flops": None, "bytes_accessed": None, "transcendentals": None,
        "peak_bytes": None, "argument_bytes": None, "output_bytes": None,
        "alias_bytes": None, "temp_bytes": None,
        "generated_code_bytes": None,
    }
    if extra:
        card.update(extra)
    try:
        ca = _compiled_cost(compiled)
        for field, key in (("flops", "flops"),
                           ("bytes_accessed", "bytes accessed"),
                           ("transcendentals", "transcendentals")):
            if key in ca:
                card[field] = float(ca[key])
    except Exception:
        pass
    try:
        mem = _compiled_memory(compiled)
        card.update(mem)
        # peak HBM while the program runs: arguments + outputs + XLA's
        # temp arena + the program text itself, minus donated aliases
        card["peak_bytes"] = (mem["argument_bytes"] + mem["output_bytes"]
                              + mem["temp_bytes"]
                              + mem["generated_code_bytes"]
                              - mem["alias_bytes"])
    except Exception:
        pass
    return card


def _path_str(path, argnames):
    """Human arg path for one signature entry: the top-level tuple
    index renders as the entry point's argument NAME, the rest as
    jax's keystr — so a recompile cause reads ``inputs['data']``, not
    ``[4]['data']``."""
    from jax.tree_util import keystr
    head = ""
    rest = path
    if path and argnames:
        idx = getattr(path[0], "idx", None)
        if idx is not None and idx < len(argnames):
            head = argnames[idx]
            rest = path[1:]
    return head + keystr(tuple(rest))


def _named(fn, name):
    """``fn`` under the name ``name``: jax names the XLA module after
    the function it jits, so the device trace's "XLA Modules" line reads
    ``jit_<kind>(...)`` — a stable name a trace reader can look the step
    program up by — and not ``jit_step`` / ``jit_fn`` / ``jit__lambda_``."""
    @functools.wraps(fn)
    def call(*args):
        return fn(*args)
    call.__name__ = call.__qualname__ = name
    return call


class _InstrumentedProgram:
    """One jitted entry point, compiled through explicit
    ``trace().lower().compile()`` with full introspection:

    * per-signature AOT executables cached on (treedef, leaf
      shapes/dtypes) — the same key jax's own dispatch cache uses,
      minus sharding (an input moving devices under an unchanged
      shape raises from the strict AOT executable and degrades that
      signature to the plain jit path instead of mis-executing);
    * the build inside one ``jit_build`` telemetry span, its phases
      timed as spans AND recorded on the card: tracing (``jit_trace``,
      ``trace_ms``), lowering (``jit_lower``, ``lower_ms``), the XLA
      compile or the load from JAX's persistent cache (``jit_compile``,
      ``compile_ms``) and the cost and memory analyses behind the card
      (``jit_card``, ``card_ms``);
    * a PROGRAM CARD per compile in ``telemetry.programs()``;
    * a structured once-per-cause RECOMPILE warning through log.py
      when a cache miss follows a prior compile, naming exactly which
      argument's shape/dtype (or the signature structure) changed;
    * dispatch-time RESOURCE_EXHAUSTED/OOM errors re-raised as
      ``DeviceMemoryError`` enriched with the buffer ledger and the
      program's memory card.
    """

    __slots__ = ("kind", "entry", "argnames", "_jitted", "_donate",
                 "_cache", "_card", "_meta", "_graph_key",
                 "warn_recompile", "on_compile")

    def __init__(self, kind, fn, jit_kwargs=None, argnames=None,
                 meta=None, graph_key=None):
        self.kind = kind
        self.entry = "%s@p%d" % (kind, next(_PROG_SEQ))
        self.argnames = argnames or ()
        kw = dict(jit_kwargs or {})
        self._donate = tuple(kw.get("donate_argnums", ()) or ())
        self._jitted = jax.jit(_named(fn, kind), **kw)   # the ONE instrumented jit site
        self._cache = {}    # dispatch sig -> [callable, card, aot_bool]
        self._card = None   # last-compiled card: the recompile-diff base
        self._meta = dict(meta or {})
        # JSON-safe fingerprint of everything the traced graph depends
        # on besides the arguments (the owner's symbol hash + entry-
        # point statics): enables the persisted cache's TRACE-SKIP tier
        # (compile_cache.quick_key). None = content-key tier only. A
        # CALLABLE defers the (symbol-JSON hashing) work until the
        # first build WITH the cache enabled — programs in cache-less
        # processes must not pay for fingerprints nobody reads.
        self._graph_key = graph_key
        # deliberate multi-signature callers (the serving engine compiles
        # one program per batch bucket BY DESIGN) flip this off so their
        # planned compiles don't read as recompile storms in the log and
        # the recompile.* counters
        self.warn_recompile = True
        # optional owner hook fired with the fresh card after every
        # signature build (compile OR disk-cache load — the card's
        # "source" field says which): engines that account their own
        # planned compiles (decode counts prefill-bucket builds) attach
        # here instead of re-deriving it from the card registry
        self.on_compile = None

    # -- compile -----------------------------------------------------------
    def _signature_cards(self, args):
        """Full named signature for the card: [[path, shape, dtype,
        sharding], ...] — computed only at compile time."""
        from jax.tree_util import tree_flatten_with_path
        flat, _ = tree_flatten_with_path(args)
        sig = []
        for path, leaf in flat:
            try:
                shape = list(leaf.shape)
                dtype = str(leaf.dtype)
            except AttributeError:
                shape, dtype = [], type(leaf).__name__
            sh = getattr(leaf, "sharding", None)
            sig.append([_path_str(path, self.argnames), shape, dtype,
                        None if sh is None else str(sh)])
        return sig

    def _diff_signature(self, old, new):
        """(path, change-kind, detail) tuples describing why the new
        signature missed the cache against the prior card's."""
        old_map = {e[0]: e for e in (old or [])}
        new_map = {e[0]: e for e in (new or [])}
        causes = []
        for path, e in new_map.items():
            o = old_map.get(path)
            if o is None:
                causes.append((path, "added", "new argument %s %s"
                               % (tuple(e[1]), e[2])))
                continue
            if e[1] != o[1]:
                causes.append((path, "shape", "shape %s -> %s"
                               % (tuple(o[1]), tuple(e[1]))))
            if e[2] != o[2]:
                causes.append((path, "dtype", "dtype %s -> %s"
                               % (o[2], e[2])))
            if e[3] != o[3]:
                causes.append((path, "sharding", "sharding %s -> %s"
                               % (o[3], e[3])))
        for path in old_map:
            if path not in new_map:
                causes.append((path, "removed", "argument gone"))
        return causes

    def _warn_recompile(self, card):
        """The recompile-cause diagnosis: diff against the prior card
        and report each changed field ONCE per (entry, field, kind)
        through log.py — the recompile-storm detector's counters can
        finally say WHY."""
        telemetry.counter_inc("recompile.%s" % self.kind)
        causes = self._diff_signature(self._card.get("signature"),
                                      card.get("signature"))
        if not causes:
            causes = [("<unknown>", "unknown",
                       "signature changed outside the argument list")]
        card["recompile_causes"] = ["%s: %s" % (p, d)
                                    for p, _, d in causes]
        fresh = [(p, k, d) for p, k, d in causes
                 if (self.entry, p, k) not in _RECOMPILE_WARNED]
        if not fresh:
            return
        for p, k, _ in fresh:
            _RECOMPILE_WARNED.add((self.entry, p, k))
        from . import log as _log
        _log.get_logger("mxnet_tpu.executor").warning(
            "recompile entry=%s kind=%s cause=%s — the cached program "
            "cannot serve the new signature; if this repeats every "
            "batch, pad or bucket the offending input "
            "(see telemetry.programs()[%r])",
            self.entry, self.kind,
            "; ".join("%s: %s" % (p, d) for p, _, d in fresh),
            card["id"])

    def _build(self, sig, args):
        """Cache miss: explicit trace().lower().compile(), card capture,
        recompile diagnosis, all inside one ``jit_build`` span. AOT
        failures (backend quirks) degrade to the plain jitted callable
        with a card whose analysis fields stay None — dispatch must
        never break on introspection.

        With the persisted tier on (``MXNET_COMPILE_CACHE``), the
        program is looked up in the on-disk executable store
        (mxnet_tpu/compile_cache.py) and a hit DESERIALIZES instead of
        invoking XLA (``jit_deserialize`` span, zero ``jit_compile``
        spans — the warm-start contract): first via the trace-skip
        quick key (graph fingerprint; no trace or lower at all), then
        via the content key over the lowered StableHLO. A miss compiles
        and persists the fresh executable (plus the quick-key index
        entry) for the next process. Cache load/store failures degrade
        inside compile_cache — only trace/lower/compile errors reach
        the AOT-fallback path here."""
        from . import compile_cache
        with telemetry.span("jit_build"), jax_cache.instrumented_build():
            card_sig = self._signature_cards(args)
            entry_id = "%s/s%d" % (self.entry, len(self._cache))
            aot = True
            compiled = None
            source = "compiled"
            cc_on = compile_cache.enabled() \
                and compile_cache.persistable(self._donate)
            qkey = None
            if cc_on:
                if callable(self._graph_key):
                    self._graph_key = self._graph_key()
                qkey = compile_cache.quick_key(
                    self.kind, self._graph_key, signature=card_sig,
                    donated=self._donate)
            trace_ms = lower_ms = compile_ms = deser_ms = 0.0
            try:
                if qkey is not None:
                    t0 = time.perf_counter()
                    ikey = compile_cache.index_get(qkey)
                    if ikey is not None:
                        compiled = compile_cache.load(ikey, kind=self.kind)
                        if compiled is not None:
                            source = "disk_cache"   # no trace ran at all
                            deser_ms = (time.perf_counter() - t0) * 1e3
                if compiled is None:
                    t0 = time.perf_counter()
                    with telemetry.span("jit_trace"):
                        traced = self._jitted.trace(*args)
                    t1 = time.perf_counter()
                    with telemetry.span("jit_lower"):
                        lowered = traced.lower()
                    t2 = time.perf_counter()
                    trace_ms, lower_ms = (t1 - t0) * 1e3, (t2 - t1) * 1e3
                    ckey = None
                    if cc_on:
                        ckey = compile_cache.lowered_key(
                            self.kind, lowered, signature=card_sig,
                            donated=self._donate)
                        if ckey is not None:
                            t1 = time.perf_counter()
                            compiled = compile_cache.load(ckey,
                                                          kind=self.kind)
                            if compiled is not None:
                                source = "disk_cache"
                                deser_ms = (time.perf_counter() - t1) * 1e3
                                compile_cache.index_put(qkey, ckey)
                    if compiled is None:
                        t1 = time.perf_counter()
                        with telemetry.span("jit_compile"):
                            compiled = lowered.compile()
                        compile_ms = (time.perf_counter() - t1) * 1e3
                        if ckey is not None:
                            compile_cache.store(ckey, compiled,
                                                kind=self.kind,
                                                entry=entry_id,
                                                signature=card_sig)
                            compile_cache.index_put(qkey, ckey)
            except Exception as e:
                aot = False
                aot_err = "%s: %s" % (type(e).__name__, e)
            t0 = time.perf_counter()
            with telemetry.span("jit_card"):
                if aot:
                    card = card_from_compiled(
                        self.kind, compiled, entry=entry_id,
                        signature=card_sig, donated=self._donate,
                        extra=self._meta)
                else:
                    card = card_from_compiled(
                        self.kind, _NoAnalysis(), entry=entry_id,
                        signature=card_sig, donated=self._donate,
                        extra=dict(self._meta, aot_fallback=aot_err))
            card["trace_ms"] = round(trace_ms, 3)
            card["lower_ms"] = round(lower_ms, 3)
            card["compile_ms"] = round(compile_ms, 3)
            card["card_ms"] = round((time.perf_counter() - t0) * 1e3, 3)
            card["source"] = source
            if source == "disk_cache":
                # the XLA compile never ran (compile_ms stays 0): the
                # disk-load cost is its own figure
                card["deserialize_ms"] = round(deser_ms, 3)
            if self._card is not None and self.warn_recompile:
                self._warn_recompile(card)
            self._card = card
            telemetry.record_program(card)
            if self.on_compile is not None:
                try:
                    self.on_compile(card)
                except Exception:
                    pass      # an accounting hook must never break a build
            rec = [compiled if aot else self._jitted, card, aot]
            self._cache[sig] = rec
            return rec

    def lower(self, *args):
        """AOT passthrough (jax.stages signature): callers that lower
        for HLO inspection (tests, tuners) see the same program the
        wrapper would compile."""
        return self._jitted.lower(*args)

    def build(self, *args):
        """Ensure this signature's executable exists (disk-cache load
        or fresh compile + card) WITHOUT dispatching it — the warmup
        path: an engine pre-building its bucket programs should not pay
        one execution per bucket just to force the compiles."""
        leaves, treedef = jax.tree_util.tree_flatten(args)
        sig = (treedef, tuple(_leaf_key(l) for l in leaves))
        if sig not in self._cache:
            self._build(sig, args)

    # -- dispatch ----------------------------------------------------------
    def _invoke(self, fn, args):
        """The one launch site (tests monkeypatch this to fake device
        errors)."""
        return fn(*args)

    def __call__(self, *args):
        leaves, treedef = jax.tree_util.tree_flatten(args)
        sig = (treedef, tuple(_leaf_key(l) for l in leaves))
        rec = self._cache.get(sig)
        if rec is None:
            rec = self._build(sig, args)
        telemetry.program_dispatch(rec[1])
        # chaos site: an injected raise here looks exactly like a
        # backend dispatch failure to every caller (the serving retry
        # budget, the breaker, the fit loop) — which is the point
        faults.fire("dispatch")
        try:
            return self._invoke(rec[0], args)
        except Exception as e:
            if _is_oom(e):
                err = _enriched_oom(e, rec[1])
                # flight-recorder moment: the ledger/card evidence in
                # the enriched error evaporates with the process — dump
                # the window too (no-op without a flight dir)
                from . import flight
                flight.postmortem("device_memory_error", exc=err,
                                  extra={"program": rec[1].get("id"),
                                         "kind": self.kind})
                raise err from e
            if rec[2] and isinstance(e, (TypeError, ValueError)):
                # strict AOT input check (an input moved devices under
                # an unchanged shape/dtype): degrade this signature to
                # the plain jit path, which re-commits inputs itself.
                # The card is registered and shared — mutate it under
                # the registry lock
                rec[0], rec[2] = self._jitted, False
                telemetry.card_update(rec[1],
                                      aot_fallback="input mismatch: %s" % e)
                return self._invoke(rec[0], args)
            raise


class _NoAnalysis:
    """Stand-in 'compiled' whose analyses always fail — the degraded
    card keeps every cost/memory field at None."""

    def cost_analysis(self):
        raise NotImplementedError

    memory_analysis = cost_analysis


# ---------------------------------------------------------------------------
# Divergence sentinel kernel
# ---------------------------------------------------------------------------

_FINITE_PROG = None


def finite_fold_fn():
    """The divergence sentinel's device kernel: one jitted program
    folding ``isfinite(x).all()`` over a list of arrays (loss heads,
    gradients, parameters) into a single scalar bool — the whole check
    ships ONE dispatch and fetches ONE byte, instead of pulling every
    buffer to the host. Compiled through the instrumented wrapper like
    every other program (card, OOM enrichment); one cached program per
    leaf-signature, shared process-wide."""
    global _FINITE_PROG
    if _FINITE_PROG is None:
        def _fold(leaves):
            acc = jnp.asarray(True)
            for x in leaves:
                if jnp.issubdtype(jnp.asarray(x).dtype, jnp.inexact):
                    acc = jnp.logical_and(acc, jnp.isfinite(x).all())
            return acc
        _FINITE_PROG = _InstrumentedProgram("finite_check", _fold)
    return _FINITE_PROG


# differentiable cross-device copy with static endpoints: the plain
# device_put transpose leaves cotangents on the DESTINATION device, so
# the backward of a grouped graph would mix devices mid-computation
_XFER_CACHE = {}


def _context_for_device(dev):
    """Map a concrete jax.Device back to a Context. Index by position in
    the local device list (not ``dev.id``, a GLOBAL id that need not be
    aligned with local indices in multi-process runs) so the round trip
    through ``Context.jax_device()`` lands on the same device."""
    from .context import Context, _accelerator_devices
    if dev.platform == "cpu":
        local = jax.local_devices(backend="cpu")
        return Context("cpu", local.index(dev))
    return Context("tpu", _accelerator_devices().index(dev))


def _device_transfer(v, src, dst):
    key = (src, dst)
    fn = _XFER_CACHE.get(key)
    if fn is None:
        @jax.custom_vjp
        def t(x):
            return jax.device_put(x, dst)

        def t_fwd(x):
            return jax.device_put(x, dst), None

        def t_bwd(_, g):
            return (jax.device_put(g, src),)

        t.defvjp(t_fwd, t_bwd)
        fn = _XFER_CACHE[key] = t   # mxlint: disable=trace-purity -- idempotent memoization of a per-(src,dst) transfer callable; the value is trace-independent
    return fn(v)


# ---------------------------------------------------------------------------
# Graph program: symbol -> pure jax function
# ---------------------------------------------------------------------------

class _GraphProgram:
    """Caches the traced/jitted callables for one Symbol.

    With ``group2dev`` (the reference's group2ctx model parallelism,
    AssignContext + cross-device copy nodes, graph_executor.cc:318-440):
    each op node resolves a device from its ``ctx_group`` attribute and
    inputs crossing a group boundary are ``jax.device_put`` to the
    consumer's device — the cross-device copy. Grouped programs run
    eagerly per segment (arbitrary per-op device pinning is not a GSPMD
    program; data-parallel scaling uses the mesh path instead)."""

    def __init__(self, symbol, group2dev=None, default_device=None):
        self.symbol = symbol
        self.nodes = symbol._topo_nodes()
        self.arg_names = symbol.list_arguments()
        self.aux_names = symbol.list_auxiliary_states()
        self.output_entries = list(symbol._outputs)
        self._jit_cache = {}
        self.node_devices = None
        self.default_device = default_device
        if group2dev:
            self.node_devices = {}
            for node in self.nodes:
                g = (node._extra_attrs.get("ctx_group")
                     or node._extra_attrs.get("__ctx_group__"))
                if g is not None and g in group2dev:
                    self.node_devices[id(node)] = group2dev[g]
            # variables without their own ctx_group live on their first
            # consumer's device (reference AssignContext pulls inputs to
            # the consuming op's group, graph_executor.cc:318-440)
            for node in self.nodes:
                if node.op is None:
                    continue
                ndev = self.node_devices.get(id(node))
                if ndev is None:
                    continue
                for child, _ in node.inputs:
                    if child.op is None and \
                            id(child) not in self.node_devices:
                        self.node_devices[id(child)] = ndev

    def graph_fingerprint(self):
        """JSON-safe fingerprint of this program's GRAPH content for
        the persisted compile cache's trace-skip tier: the symbol's
        JSON plus the ambient layout default (ops consult it at trace
        time). Everything else trace-time-relevant (op source code,
        MXNET_* knobs, backend identity, the abstract signature) is
        folded in by ``compile_cache.quick_key`` itself. None (tier
        disabled) for grouped programs and symbols that cannot
        serialize."""
        cached = self.__dict__.get("_graph_fp", False)
        if cached is not False:
            return cached
        fp = None
        if not self.node_devices:
            try:
                import hashlib
                from . import layout
                js = self.symbol.tojson()
                fp = [hashlib.sha256(js.encode()).hexdigest(),
                      layout.get_default_layout()]
            except Exception:
                fp = None
        self.__dict__["_graph_fp"] = fp
        return fp

    def _entry_graph_key(self, *statics):
        """Graph key for one jitted entry point: the graph fingerprint
        plus the entry's own statics (train flag, grad names, ...),
        deep-normalised to JSON-safe values. Non-primitive statics fall
        back to repr — a repr that varies per process (object
        addresses) degrades to a quick-tier miss, never a false hit
        (the content key still matches after the trace)."""
        fp = self.graph_fingerprint()
        if fp is None:
            return None

        def norm(s):
            if isinstance(s, (str, int, float, bool, type(None))):
                return s
            if isinstance(s, (tuple, list)):
                return [norm(x) for x in s]
            return repr(s)
        return [fp] + [norm(s) for s in statics]

    @property
    def uses_rng(self):
        """True iff any node consumes randomness. RNG-free graphs (most
        inference/training graphs without dropout) skip the per-step
        eager ``jax.random.split`` — one device dispatch per step on a
        remoted PJRT backend."""
        cached = self.__dict__.get("_uses_rng")
        if cached is None:
            cached = any(n.op is not None and n.op.takes_rng
                         for n in self.nodes)
            self.__dict__["_uses_rng"] = cached
        return cached

    # ---- pure evaluation -------------------------------------------------
    def _apply_node(self, node, raw_in, train, aux_dict, aux_updates):
        """Apply one op node; records aux updates into ``aux_updates``."""
        if self.node_devices:
            dev = self.node_devices.get(id(node), self.default_device)
            fixed = []
            for r, (c, _) in zip(raw_in, node.inputs):
                src = self.node_devices.get(id(c), self.default_device)
                if src is not dev:
                    # cross-device copy at the group boundary
                    # (reference cross_device_copy.cc node)
                    r = _device_transfer(r, src, dev)
                fixed.append(r)
            raw_in = fixed
        params = dict(node.op.defaults)
        params.update(node.attrs)
        params.pop("num_args", None)
        params.pop("name", None)
        if node.op.takes_train:
            params["_train"] = train
        if node.op.takes_rng:
            from .ops.common import take_rng
            params["_rng"] = take_rng()
        outs = node.op.apply(raw_in, params)
        if train and node.op.stateful_update is not None:
            ups = node.op.stateful_update(raw_in, outs, params)
            for in_idx, val in ups.items():
                child, _ = node.inputs[in_idx]
                if child.op is None and child.name in aux_dict:
                    aux_updates[child.name] = val
        return outs

    def _bind_variable(self, node, arg_dict, aux_dict):
        if node.name in arg_dict:
            return arg_dict[node.name]
        if node.name in aux_dict:
            return aux_dict[node.name]
        raise MXNetError("unbound variable %r" % node.name)

    def eval_graph(self, arg_dict, aux_dict, rng_key, train):
        """Evaluate the graph. Returns (outputs, aux_updates)."""
        env = {}
        aux_updates = {}
        with rng_scope(rng_key):
            for node in self.nodes:
                if node.op is None:
                    env[id(node)] = (self._bind_variable(
                        node, arg_dict, aux_dict),)
                    continue
                raw_in = [env[id(c)][idx] for c, idx in node.inputs]
                # every op of the node carries the node's name in its
                # metadata (trace time only): the profiler's trace
                # then says which layer a device op belongs to
                with jax.named_scope(node.name):
                    env[id(node)] = self._apply_node(
                        node, raw_in, train, aux_dict, aux_updates)
        outputs = [env[id(n)][idx] for n, idx in self.output_entries]
        return outputs, aux_updates

    @property
    def mirror_stages(self):
        """Whether the symbol marks segments itself: op nodes that carry
        the ``__mirror_stage__`` attribute each start a checkpoint
        segment, and the graph is then evaluated mirrored whatever
        ``MXNET_BACKWARD_DO_MIRROR`` says (a model that cannot hold its
        activations says so in its symbol, not in its user's
        environment)."""
        cached = self.__dict__.get("_mirror_stages")
        if cached is None:
            cached = any(n.op is not None and MIRROR_STAGE in n._extra_attrs
                         for n in self.nodes)
            self.__dict__["_mirror_stages"] = cached
        return cached

    def can_segment(self):
        """Whether mirrored evaluation can split this graph into
        checkpoint segments: needs a jitted single-device program with
        enough op nodes to be worth cutting. The ONE owner of the
        decision — fwd_bwd_fn's whole-graph-checkpoint fallback and
        eval_graph_mirrored's internal guard both call this."""
        return not self.node_devices and \
            sum(1 for n in self.nodes if n.op is not None) >= 4

    def eval_graph_mirrored(self, arg_dict, aux_dict, rng_key, train):
        """MXNET_BACKWARD_DO_MIRROR evaluation: the op graph is split
        into ~sqrt(N) contiguous segments and each runs under
        ``jax.checkpoint``, so the backward pass keeps only segment
        BOUNDARY values resident and recomputes interior activations —
        the reference's per-node mirror policy
        (graph_executor.cc:282-305) recast as TPU-first checkpointing.
        (One checkpoint around the whole graph would save nothing: the
        recomputed forward and the backward would hold every activation
        live at once.)

        Besides its boundary values a segment keeps the values an op
        inside it has named (``MIRROR_KEEPS``: the attention kernel's
        output and log-sum-exp, which only the kernel can make again, and
        the delta rule's ``Aqk``, ``W``, ``U``, a kernel call to make
        again); a segment in which no op names a value lowers as under a bare
        ``jax.checkpoint``."""
        import math

        if not self.can_segment():
            # callers (fwd_bwd_fn) handle these cases with one
            # whole-graph checkpoint instead; segmentation needs a
            # jitted single-device program
            return self.eval_graph(arg_dict, aux_dict, rng_key, train)
        ops = [n for n in self.nodes if n.op is not None]
        if self.mirror_stages:
            # the symbol says where its segments start (one a layer)
            chunks = []
            for n in ops:
                if not chunks or MIRROR_STAGE in n._extra_attrs:
                    chunks.append([])
                chunks[-1].append(n)
        else:
            k = max(2, int(round(math.sqrt(len(ops)))))
            step = (len(ops) + k - 1) // k
            chunks = [ops[i:i + step] for i in range(0, len(ops), step)]

        # val_env: (id(node), out_index) -> traced value
        val_env = {}
        aux_updates = {}
        for node in self.nodes:
            if node.op is None:
                val_env[(id(node), 0)] = self._bind_variable(
                    node, arg_dict, aux_dict)

        with rng_scope(rng_key):
            for ci, chunk in enumerate(chunks):
                chunk_ids = {id(n) for n in chunk}
                # external inputs: produced before this chunk
                ext, seen = [], set()
                for n in chunk:
                    for c, idx in n.inputs:
                        key = (id(c), idx)
                        if id(c) not in chunk_ids and key not in seen:
                            seen.add(key)
                            ext.append(key)
                # values later chunks / graph outputs need from here
                needed, nseen = [], set()
                for later in chunks[ci + 1:]:
                    for n in later:
                        for c, idx in n.inputs:
                            key = (id(c), idx)
                            if id(c) in chunk_ids and key not in nseen:
                                nseen.add(key)
                                needed.append(key)
                for n, idx in self.output_entries:
                    key = (id(n), idx)
                    if id(n) in chunk_ids and key not in nseen:
                        nseen.add(key)
                        needed.append(key)

                def chunk_fn(ext_vals, _chunk=chunk,
                             _chunk_ids=chunk_ids, _ext=ext,
                             _needed=needed):
                    local = dict(zip(_ext, ext_vals))
                    ups = {}
                    for n in _chunk:
                        raw_in = []
                        for c, idx in n.inputs:
                            raw_in.append(local[(id(c), idx)])
                        with jax.named_scope(n.name):
                            outs = self._apply_node(n, raw_in, train,
                                                    aux_dict, ups)
                        for i, v in enumerate(outs):
                            local[(id(n), i)] = v
                    return [local[key] for key in _needed], ups

                out_vals, ups = jax.checkpoint(
                    chunk_fn, policy=_MIRROR_POLICY)(
                    [val_env[key] for key in ext])
                aux_updates.update(ups)
                for key, v in zip(needed, out_vals):
                    val_env[key] = v
        outputs = [val_env[(id(n), idx)] for n, idx in self.output_entries]
        return outputs, aux_updates

    # ---- jitted entry points --------------------------------------------
    def forward_fn(self, train):
        key = ("fwd", bool(train))
        hit = key in self._jit_cache
        telemetry.record_jit("forward", hit)
        if not hit:
            def fn(args, aux, rng):
                with jax.named_scope("forward"):
                    return self.eval_graph(args, aux, rng, train)
            # grouped programs pin ops to concrete devices — eager
            # execution (per-op dispatch), not one jitted program
            self._jit_cache[key] = fn if self.node_devices else \
                _InstrumentedProgram(
                    "forward", fn,
                    argnames=("args", "aux", "rng"),
                    meta={"train": bool(train)},
                    graph_key=lambda: self._entry_graph_key(
                        "fwd", bool(train)))
        return self._jit_cache[key]

    def _vjp_over_graph(self, grad_args, rest, aux, rng, train):
        """``jax.vjp`` over the whole graph under the mirror policy —
        the ONE forward/backward scaffold both the phase-split
        ``fwd_bwd_fn`` and the whole-step ``train_step_fn`` trace, so
        the checkpointing choice and gradient partitioning stay
        identical by construction."""
        from .config import do_mirror
        mirror = do_mirror() or self.mirror_stages
        segmented = mirror and self.can_segment()

        def f(ga):
            ev = self.eval_graph_mirrored if segmented \
                else self.eval_graph
            outs, aux_up = ev({**rest, **ga}, aux, rng, train)
            return tuple(outs), aux_up
        if mirror and not segmented:
            # grouped (eager per-device) or tiny graphs can't be
            # segment-checkpointed; one checkpoint around the whole
            # graph still frees activation buffers between forward and
            # backward
            f = jax.checkpoint(f, policy=_MIRROR_POLICY)
        return jax.vjp(f, grad_args, has_aux=True)

    def fwd_bwd_fn(self, train, grad_names):
        key = ("fwdbwd", bool(train), tuple(grad_names))
        hit = key in self._jit_cache
        telemetry.record_jit("fwd_bwd", hit)
        if not hit:
            def fn(args, aux, rng, head_grads):
                grad_args = {k: args[k] for k in grad_names}
                rest = {k: v for k, v in args.items() if k not in grad_names}
                with jax.named_scope("forward"):
                    outs, vjp, aux_up = self._vjp_over_graph(
                        grad_args, rest, aux, rng, train)
                hg = tuple(
                    head_grads[i] if head_grads[i] is not None
                    else jnp.ones(outs[i].shape, outs[i].dtype)
                    for i in range(len(outs)))
                if self.node_devices:
                    # head gradients must enter the backward committed to
                    # their output node's device
                    hg = tuple(
                        jax.device_put(g, self.node_devices.get(
                            id(n), self.default_device))
                        for g, (n, _) in zip(hg, self.output_entries))
                with jax.named_scope("backward"):
                    grads = vjp(hg)[0]
                return outs, grads, aux_up
            self._jit_cache[key] = fn if self.node_devices else \
                _InstrumentedProgram(
                    "fwd_bwd", fn,
                    argnames=("args", "aux", "rng", "head_grads"),
                    meta={"train": bool(train)},
                    graph_key=lambda: self._entry_graph_key(
                        "fwdbwd", bool(train), tuple(grad_names)))
        return self._jit_cache[key]

    def train_step_fn(self, update_names, add_names, input_dtypes, cache_key,
                      build_update_fn, build_metric_fn, spmd=None,
                      build_shardings=None):
        """Whole-training-step program: forward + backward + optimizer
        update (+ metric accumulation when ``build_metric_fn`` is given)
        traced into ONE jitted XLA function, with the parameter,
        optimizer-state, metric-accumulator, and aux buffers DONATED —
        the step updates weights in place instead of round-tripping every
        parameter buffer (the end-to-end program compilation the TVM /
        Julia-to-TPU line of work keeps proving out; closes the
        Module.fit dispatch gap, PERF.md "Module.fit gap").

        ``update_names`` orders the trained parameters (matching the
        per-parameter ``lrs``/``wds``/``ts`` arrays and the packed state
        list); ``add_names`` marks ``grad_req='add'`` parameters whose
        incoming gradient accumulator rides as a non-donated input.
        ``build_update_fn``/``build_metric_fn`` are invoked only on a
        cache miss; ``cache_key`` must capture everything their closures
        depend on (optimizer statics, state layout, metric identity).
        Grouped (group2ctx) programs cannot ride — callers fall back to
        the phase-split path.

        ``spmd`` (a ``parallel.spmd.DataParallelSpec``) selects the SPMD
        variant: the SAME step is jitted with explicit NamedShardings —
        batch inputs split over the data axis, params/optimizer state/
        metric accumulator/aux replicated (still donated) — so XLA GSPMD
        compiles ONE program over the whole mesh with the cross-replica
        gradient psum, the optimizer update and the metric reduction
        fused INSIDE the step (no software kvstore staging, no host-side
        batch splitting: the global batch arrives via one sharded
        device_put). The replicated metric accumulator comes back already
        psummed across replicas, so fetching it needs no extra program.

        ``build_shardings`` (rule-sharded dp x mp meshes — a spec whose
        ``rules`` is a ``PartitionRules`` tree) is invoked on a cache
        miss like ``build_update_fn`` and returns the PER-LEAF
        NamedSharding pytrees ``{"params": {name: sh}, "states":
        [tuple(sh, ...)], "aux": {name: sh}, "add_grads": {name: sh}}``
        threaded into ``in_shardings`` — mp-sharded parameters and
        their optimizer state stay sharded INSIDE the donated step
        (never all-gathered), while GSPMD still reduces gradients over
        ``dp`` only because each gradient carries its parameter's mp
        placement. The batch inputs/step scalars keep the dp/replicated
        layout above.
        """
        if self.node_devices:
            raise MXNetError("train_step_fn: grouped programs run eagerly "
                             "per segment and cannot fuse the train step")
        key = ("train_step", tuple(update_names), tuple(sorted(add_names)),
               tuple(sorted(input_dtypes.items(), key=lambda kv: kv[0])),
               cache_key, spmd)
        fn = self._jit_cache.get(key)
        telemetry.record_jit("train_step", fn is not None)
        if fn is not None:
            return fn
        update_fn = build_update_fn()
        metric_fn = build_metric_fn() if build_metric_fn is not None else None
        grad_set = frozenset(update_names)

        def step(params, opt_states, metric_acc, aux, inputs, rng,
                 lrs, wds, ts, add_grads):
            # inputs adopt the bound argument dtypes (a bf16 DataDesc
            # keeps binding a bf16 program even though the batch arrays
            # are fed functionally, without a copy into bound storage)
            ins = {k: (v.astype(input_dtypes[k])
                       if v.dtype != input_dtypes[k] else v)
                   for k, v in inputs.items()}
            grad_args = {k: params[k] for k in update_names}
            rest = {k: v for k, v in params.items() if k not in grad_set}
            rest.update(ins)
            # the four phases carry names (``jax.named_scope``, metadata
            # only): a device op's path in the profiler's trace reads
            # forward/jvp(<node>)/..., backward/transpose(jvp(<node>))/
            # ..., optimizer/... or metric/...
            with jax.named_scope("forward"):
                outs, vjp, aux_up = self._vjp_over_graph(
                    grad_args, rest, aux, rng, True)
            hg = tuple(jnp.ones(o.shape, o.dtype) for o in outs)
            # gradients pass through the bound grad-array dtype (the
            # phase-split path stores them there before the optimizer
            # reads them — bit-parity demands the same rounding). Only
            # ``grad_req='add'`` accumulators are MATERIALIZED as program
            # outputs (they feed the next step); 'write' grads live and
            # die inside the program — emitting them would be pure
            # output-buffer overhead nothing consumes
            gs, grads_out = [], {}
            with jax.named_scope("backward"):
                grads = vjp(hg)[0]
                for k in update_names:
                    g = grads[k].astype(params[k].dtype)
                    if k in add_names:
                        g = add_grads[k] + g
                        grads_out[k] = g
                    gs.append(g)
            ws = [params[k] for k in update_names]
            with jax.named_scope("optimizer"):
                new_ws, new_states = update_fn(ws, opt_states, gs, lrs,
                                               wds, ts)
            new_params = dict(params)
            new_params.update(zip(update_names, new_ws))
            new_aux = dict(aux)
            new_aux.update({k: v for k, v in aux_up.items() if k in aux})
            new_acc = metric_acc
            if metric_fn:
                with jax.named_scope("metric"):
                    new_acc = metric_fn(outs, ins, metric_acc)
            return new_params, new_states, new_acc, new_aux, outs, grads_out

        step_argnames = ("params", "opt_states", "metric_acc", "aux",
                         "inputs", "rng", "lrs", "wds", "ts", "add_grads")
        # cache_key captures the optimizer/metric closure statics — its
        # repr rides in the graph key (a per-process repr degrades to a
        # quick-tier miss, never a false hit)
        def step_graph_key():
            if spmd is None:
                layout = None
            else:
                # mesh shape + rule-tree identity: two layouts over the
                # same graph must key distinct persisted programs (the
                # repr degrades to a quick-tier miss at worst, never a
                # false hit)
                layout = (spmd.num_devices,
                          repr(sorted(dict(spmd.mesh.shape).items())),
                          repr(getattr(spmd, "rules", None)))
            return self._entry_graph_key(
                "train_step", tuple(update_names),
                tuple(sorted(add_names)),
                tuple("%s=%s" % (k, v) for k, v in
                      sorted(input_dtypes.items())), cache_key, layout)
        if spmd is None:
            fn = _InstrumentedProgram(
                "train_step", step,
                jit_kwargs={"donate_argnums": (0, 1, 2, 3)},
                argnames=step_argnames,
                graph_key=step_graph_key)
        else:
            repl, dsh = spmd.repl_sharding, spmd.data_sharding
            # args: (params, opt_states, metric_acc, aux, inputs, rng,
            #        lrs, wds, ts, add_grads) — each entry is a pytree
            # PREFIX broadcast over its subtree. The batch-sharded inputs
            # plus replicated (or rule-sharded, below) params force GSPMD
            # to insert the gradient all-reduce (psum over the dp axis)
            # inside the step; output shardings are propagated (params/
            # state/acc come out on their input placement, per-example
            # outputs batch-sharded), which keeps donation
            # buffer-compatible.
            param_sh = state_sh = aux_sh = ag_sh = repl
            meta = {"spmd_devices": spmd.num_devices}
            if getattr(spmd, "rules", None) is not None \
                    and build_shardings is not None:
                shs = build_shardings()
                param_sh, state_sh = shs["params"], shs["states"]
                aux_sh, ag_sh = shs["aux"], shs["add_grads"]
                base_step = step

                def step(params, opt_states, metric_acc, aux, inputs,
                         rng, lrs, wds, ts, add_grads):
                    # pin the DONATED outputs to their declared input
                    # placements: GSPMD would otherwise propagate
                    # whatever layout the body implies (e.g. BatchNorm
                    # moving stats derived from mp-sharded activations
                    # drift to an mp sharding), and the NEXT call's
                    # explicit in_shardings would reject the donated
                    # buffer it just produced
                    wsc = jax.lax.with_sharding_constraint
                    new_params, new_states, new_acc, new_aux, outs, \
                        grads_out = base_step(
                            params, opt_states, metric_acc, aux,
                            inputs, rng, lrs, wds, ts, add_grads)
                    new_params = wsc(new_params, param_sh)
                    new_states = [wsc(s, sh) for s, sh in
                                  zip(new_states, state_sh)]
                    if new_acc is not None:     # metric-less step
                        new_acc = wsc(new_acc, repl)
                    new_aux = wsc(new_aux, aux_sh)
                    grads_out = {k: wsc(v, ag_sh[k])
                                 for k, v in grads_out.items()}
                    return (new_params, new_states, new_acc, new_aux,
                            outs, grads_out)
                n_sharded = sum(1 for s in param_sh.values()
                                if tuple(s.spec))
                meta["partition"] = {
                    "mesh_axes": {str(k): int(v)
                                  for k, v in spmd.mesh.shape.items()},
                    "data_axis": spmd.data_axis,
                    "sharded_params": n_sharded,
                    "replicated_params": len(param_sh) - n_sharded,
                    "rules": spmd.rules.describe(),
                }
            fn = _InstrumentedProgram(
                "train_step", step,
                jit_kwargs={"in_shardings": (param_sh, state_sh, repl,
                                             aux_sh, dsh, repl, repl,
                                             repl, repl, ag_sh),
                            "donate_argnums": (0, 1, 2, 3)},
                argnames=step_argnames,
                meta=meta,
                graph_key=step_graph_key)
        self._jit_cache[key] = fn
        return fn


# ---------------------------------------------------------------------------
# Shape inference over the graph
# ---------------------------------------------------------------------------

def infer_graph_attrs(symbol, known_shapes, known_types=None, partial=False,
                      default_dtype=np.float32):
    """Joint shape+dtype inference (parity: the reference's InferShape AND
    InferType passes, src/executor/infer_graph_attr_pass.cc — one walk
    here because jax.eval_shape propagates both attributes at once).

    Variable dtypes resolve in priority order: ``known_types`` (the
    simple_bind ``type_dict``) > a ``__dtype__`` attr on the Variable >
    dtype filled by the consuming op (learnable inputs follow the op's
    first float input — the reference's per-op InferType rule — unless
    the op has a ``param_dtype_infer`` hook, e.g. BatchNorm pins its
    scale/shift/moving stats to fp32) > ``default_dtype``.
    """
    nodes = symbol._topo_nodes()
    var_shape = dict(known_shapes)
    var_type = {k: np.dtype(v) for k, v in (known_types or {}).items()}
    shapes = {}  # id(node) -> tuple of output shapes
    types = {}   # id(node) -> tuple of output dtypes

    for node in nodes:
        if node.op is None:
            shp = var_shape.get(node.name)
            if shp is None and "__shape__" in node._extra_attrs:
                import ast
                shp = tuple(ast.literal_eval(node._extra_attrs["__shape__"]))
                var_shape[node.name] = shp
            dt = var_type.get(node.name)
            if dt is None and "__dtype__" in node._extra_attrs:
                dt = np.dtype(node._extra_attrs["__dtype__"])
                var_type[node.name] = dt
            shapes[id(node)] = (shp,)
            types[id(node)] = (dt,)
            continue
        in_shapes = [shapes[id(c)][idx] for c, idx in node.inputs]
        in_types = [types[id(c)][idx] for c, idx in node.inputs]
        params = dict(node.op.defaults)
        params.update(node.attrs)
        params.pop("num_args", None)
        # fill unknown learnable-input shapes
        if node.op.param_shape_infer is not None and in_shapes[0] is not None:
            fills = node.op.param_shape_infer(in_shapes, params)
            for i, shp in fills.items():
                if i < len(node.inputs) and in_shapes[i] is None:
                    child, _ = node.inputs[i]
                    if child.op is None:
                        var_shape[child.name] = tuple(shp)
                        shapes[id(child)] = (tuple(shp),)
                        in_shapes[i] = tuple(shp)
        # fill unknown input dtypes: per-op hook first, then the op's
        # first known float input, then the session default
        dtype_fills = {}
        if node.op.param_dtype_infer is not None:
            dtype_fills = node.op.param_dtype_infer(in_types, params)
        # jnp.issubdtype, not np: bfloat16 is an ml_dtypes extension type
        # that numpy does not classify under np.floating
        anchor = next((t for t in in_types
                       if t is not None and jnp.issubdtype(t, jnp.floating)),
                      np.dtype(default_dtype))
        for i in range(len(in_types)):
            if in_types[i] is None:
                dt = np.dtype(dtype_fills.get(i, anchor))
                child, idx = node.inputs[i]
                if child.op is None:
                    var_type[child.name] = dt
                    types[id(child)] = (dt,)
                in_types[i] = dt
        if any(s is None for s in in_shapes):
            if partial:
                shapes[id(node)] = tuple([None] * node.num_outputs())
                # dtype-only propagation still works without shapes (the
                # reference InferType pass is shape-independent): outputs
                # follow the promoted float input dtype; Cast follows its
                # param.
                if node.op.name == "Cast":
                    dt = np.dtype(params.get("dtype", "float32"))
                elif node.op.param_dtype_infer is not None:
                    # ops that pin param dtypes (BatchNorm's fp32 stats)
                    # still emit the DATA dtype — don't promote across the
                    # pinned fp32 params
                    dt = anchor
                else:
                    floats = [t for t in in_types
                              if t is not None
                              and jnp.issubdtype(t, jnp.floating)]
                    dt = np.dtype(jnp.result_type(*floats)) if floats else None
                types[id(node)] = tuple([dt] * node.num_outputs())
                continue
            missing = [node.inputs[i][0].name for i, s in enumerate(in_shapes)
                       if s is None]
            raise MXNetError("infer_shape: cannot infer %r (missing inputs %s)"
                             % (node.name, missing))
        # eval_shape through the op function: XLA's abstract evaluation is
        # both FInferShape and FInferType
        if node.op.takes_train:
            params["_train"] = False
        if node.op.takes_rng:
            params["_rng"] = jax.random.key(0)
        structs = [jax.ShapeDtypeStruct(s, t)
                   for s, t in zip(in_shapes, in_types)]
        try:
            out = jax.eval_shape(lambda *a: node.op.fn(*a, **params), *structs)
        except Exception as e:
            if partial:
                shapes[id(node)] = tuple([None] * node.num_outputs())
                types[id(node)] = tuple([None] * node.num_outputs())
                continue
            raise MXNetError("infer_shape failed at %s(%s): %s"
                             % (node.op.name, node.name, e))
        outs = out if isinstance(out, tuple) else (out,)
        shapes[id(node)] = tuple(tuple(o.shape) for o in outs)
        types[id(node)] = tuple(np.dtype(o.dtype) for o in outs)

    arg_shapes = [var_shape.get(n) for n in symbol.list_arguments()]
    aux_shapes = [var_shape.get(n) for n in symbol.list_auxiliary_states()]
    arg_types = [var_type.get(n) for n in symbol.list_arguments()]
    aux_types = [var_type.get(n) for n in symbol.list_auxiliary_states()]
    out_shapes, out_types = [], []
    for n, idx in symbol._outputs:
        s = shapes.get(id(n))
        t = types.get(id(n))
        out_shapes.append(None if s is None or idx >= len(s) else s[idx])
        out_types.append(None if t is None or idx >= len(t) else t[idx])
    return (arg_shapes, out_shapes, aux_shapes,
            arg_types, out_types, aux_types)


def infer_graph_shapes(symbol, known_shapes, partial=False,
                       default_dtype=np.float32):
    """Shape-only view of infer_graph_attrs (kept for existing callers)."""
    res = infer_graph_attrs(symbol, known_shapes, partial=partial,
                            default_dtype=default_dtype)
    return res[0], res[1], res[2]


# ---------------------------------------------------------------------------
# Executor
# ---------------------------------------------------------------------------

class Executor:
    """Bound, compiled graph (parity: python/mxnet/executor.py)."""

    def __init__(self, symbol, ctx, arg_arrays, grad_arrays, grad_req,
                 aux_arrays, program=None, group2ctx=None,
                 owns_arrays=False, out_shapes=None):
        from .ndarray.ndarray import NDArray
        self._symbol = symbol
        self._ctx = ctx or current_context()
        group2dev = {g: c.jax_device() for g, c in group2ctx.items()} \
            if group2ctx else None
        default_dev = self._ctx.jax_device() if group2dev else None
        self._prog = program or _GraphProgram(
            symbol, group2dev=group2dev, default_device=default_dev)
        if self._prog.node_devices:
            # commit parameter/aux storage to its group device so weights
            # are NOT re-copied across the boundary every step; retag the
            # NDArray's context too, so subsequent writes (x[:] = ...,
            # copyto) keep the placement instead of pulling the storage
            # back to the bind context. Only arrays this executor
            # allocated (simple_bind) may be moved; caller-owned arrays
            # on the wrong device raise instead of being mutated behind
            # the caller's back (reference AssignContext CHECKs
            # placement, graph_executor.cc:318-440). owns_arrays may
            # also be a collection naming the movable subset (e.g. the
            # aux arrays _bind auto-allocates).
            if owns_arrays is True:
                movable = None          # everything movable
            else:
                movable = frozenset(owns_arrays or ())
            by_name = {n.name: self._prog.node_devices[id(n)]
                       for n in self._prog.nodes
                       if n.op is None and id(n) in self._prog.node_devices}
            for name, arr in list(zip(self._prog.arg_names, arg_arrays)) + \
                    list(zip(self._prog.aux_names, aux_arrays)) + \
                    list(zip(self._prog.arg_names, grad_arrays)):
                dev = by_name.get(name)
                if dev is None or arr is None:
                    continue
                if list(arr._data.devices())[0] == dev:
                    continue
                if movable is not None and name not in movable:
                    raise MXNetError(
                        "bind: array %r lives on %s but its ctx_group "
                        "maps to %s; allocate it on the group's context"
                        % (name, arr.context, _context_for_device(dev)))
                arr._set_data(jax.device_put(arr._data, dev))
                arr._ctx = _context_for_device(dev)
        self.arg_arrays = list(arg_arrays)
        self.grad_arrays = list(grad_arrays)
        self.aux_arrays = list(aux_arrays)
        self._arg_names = self._prog.arg_names
        self._aux_names = self._prog.aux_names
        if isinstance(grad_req, str):
            grad_req = {n: grad_req for n in self._arg_names}
        elif isinstance(grad_req, (list, tuple)):
            grad_req = dict(zip(self._arg_names, grad_req))
        self._grad_req = {n: grad_req.get(n, "null") for n in self._arg_names}
        self.outputs = []
        self._monitor_callback = None
        self._monitor_all = False
        # reference parity: outputs are allocated (zero) NDArrays from
        # bind time, readable before the first forward. out_shapes may be
        # threaded in by the bind paths that already ran shape inference.
        try:
            if out_shapes is None:
                shapes = {n: a.shape for n, a in
                          zip(self._arg_names, self.arg_arrays)
                          if a is not None}
                _, out_shapes, _ = self._symbol.infer_shape_partial(**shapes)
            from .ndarray import zeros as _zeros
            self.outputs = [_zeros(s, ctx=self._ctx) if s is not None
                            else None for s in out_shapes]
            if any(o is None for o in self.outputs):
                self.outputs = []    # unknown head shape: defer to forward
        except Exception:
            pass

    # -- dict views --------------------------------------------------------
    @property
    def arg_dict(self):
        return dict(zip(self._arg_names, self.arg_arrays))

    @property
    def grad_dict(self):
        return dict(zip(self._arg_names, self.grad_arrays))

    @property
    def aux_dict(self):
        return dict(zip(self._aux_names, self.aux_arrays))

    @property
    def output_dict(self):
        return dict(zip(self._symbol.list_outputs(), self.outputs))

    # -- binding helpers (called from Symbol) ------------------------------
    @staticmethod
    def _simple_bind(symbol, ctx, grad_req, type_dict, shape_kwargs,
                     group2ctx=None):
        from .ndarray import zeros
        (arg_shapes, out_shapes, aux_shapes, arg_types, _, aux_types) = \
            infer_graph_attrs(symbol, shape_kwargs, known_types=type_dict)
        arg_names = symbol.list_arguments()
        arg_arrays = [zeros(s, ctx=ctx, dtype=t if t is not None else "float32")
                      for s, t in zip(arg_shapes, arg_types)]
        if isinstance(grad_req, str):
            reqs = {n: grad_req for n in arg_names}
        elif isinstance(grad_req, (list, tuple)):
            reqs = dict(zip(arg_names, grad_req))
        else:
            reqs = {n: grad_req.get(n, "null") for n in arg_names}
        # gradients carry the dtype of their argument (reference InferType:
        # grad entries share the arg entry's dtype)
        grad_arrays = [zeros(s, ctx=ctx, dtype=t if t is not None else "float32")
                       if reqs.get(n, "null") != "null" else None
                       for n, s, t in zip(arg_names, arg_shapes, arg_types)]
        aux_arrays = [zeros(s, ctx=ctx, dtype=t if t is not None else "float32")
                      for s, t in zip(aux_shapes, aux_types)]
        return Executor(symbol, ctx, arg_arrays, grad_arrays, reqs,
                        aux_arrays, group2ctx=group2ctx, owns_arrays=True,
                        out_shapes=out_shapes)

    @staticmethod
    def _bind(symbol, ctx, args, args_grad, grad_req, aux_states,
              group2ctx=None, shared_exec=None):
        from .ndarray.ndarray import NDArray
        arg_names = symbol.list_arguments()
        aux_names = symbol.list_auxiliary_states()
        # shared_exec (reference bind parity): the new executor reuses
        # the donor's _GraphProgram, so every signature already traced/
        # compiled for the donor (the _InstrumentedProgram per-shape AOT
        # cache) is a cache HIT for the new binding — this is what makes
        # Predictor.reshape and the serving engine's bucket cache free of
        # silent re-traces. Only valid when both executors run the same
        # graph; grouped (group2ctx) programs pin concrete devices and
        # cannot be shared across binds.
        program = None
        if shared_exec is not None and group2ctx is None \
                and shared_exec._symbol is symbol \
                and not shared_exec._prog.node_devices:
            program = shared_exec._prog

        def _as_list(spec, names, what):
            if spec is None:
                return [None] * len(names)
            if isinstance(spec, dict):
                return [spec.get(n) for n in names]
            if isinstance(spec, (list, tuple)):
                if len(spec) != len(names):
                    raise MXNetError("%s length mismatch: %d vs %d"
                                     % (what, len(spec), len(names)))
                return list(spec)
            raise MXNetError("%s must be list or dict" % what)

        arg_arrays = _as_list(args, arg_names, "args")
        if any(a is None for a in arg_arrays):
            missing = [n for n, a in zip(arg_names, arg_arrays) if a is None]
            raise MXNetError("bind: missing arguments %s" % missing)
        grad_arrays = _as_list(args_grad, arg_names, "args_grad")
        aux_arrays = _as_list(aux_states, aux_names, "aux_states")
        auto_aux = set()
        if any(a is None for a in aux_arrays):
            # allocate zeros for missing aux; these are executor-owned,
            # so grouped binds may move them to their group device
            from .ndarray import zeros as _z
            auto_aux = {n for n, a in zip(aux_names, aux_arrays)
                        if a is None}
            shapes = {n: a.shape for n, a in zip(arg_names, arg_arrays)}
            _, _, aux_shapes = symbol.infer_shape_partial(**shapes)
            aux_arrays = [a if a is not None else _z(s, ctx=ctx)
                          for a, s in zip(aux_arrays, aux_shapes)]
        return Executor(symbol, ctx, arg_arrays, grad_arrays, grad_req,
                        aux_arrays, program=program, group2ctx=group2ctx,
                        owns_arrays=auto_aux)

    # -- execution ---------------------------------------------------------
    def _raw_args(self):
        return {n: a._data for n, a in zip(self._arg_names, self.arg_arrays)}

    def _raw_aux(self):
        return {n: a._data for n, a in zip(self._aux_names, self.aux_arrays)}

    def _out_ctx(self, out_index):
        """Context for output i: in grouped mode, the output node's group
        device (so NDArray.context reports where the data actually
        lives); otherwise the bind context. Static per executor — cached
        so the per-step hot path skips the device-list lookups."""
        cache = self.__dict__.setdefault("_out_ctx_cache", {})
        ctx = cache.get(out_index)
        if ctx is not None:
            return ctx
        nd_map = self._prog.node_devices
        if not nd_map:
            ctx = self._ctx
        else:
            node, _ = self._prog.output_entries[out_index]
            dev = nd_map.get(id(node), self._prog.default_device)
            if dev is None or dev == self._ctx.jax_device():
                ctx = self._ctx
            else:
                ctx = _context_for_device(dev)
        cache[out_index] = ctx
        return ctx

    def _step_key(self):
        """Fresh RNG key for one step — but only graphs that actually
        consume randomness (dropout etc.) pay the eager ``split``
        dispatch; RNG-free graphs reuse one cached, already-committed
        key so the hot loop ships no new buffer for it."""
        if self._prog.uses_rng:
            return _random.take_key()
        k = getattr(self, "_static_key", None)
        if k is None:
            k = self._static_key = _random.take_key()
        return k

    def forward(self, is_train=False, **kwargs):
        """Run forward (parity: executor.py forward:113)."""
        from .ndarray.ndarray import NDArray, _wrap
        if kwargs:
            with telemetry.span("feed"):
                self._feed_kwargs(kwargs)
        self._last_key = self._step_key()
        fn = self._prog.forward_fn(bool(is_train))
        if not self._prog.node_devices:
            record_dispatch("forward")
        with telemetry.span("step"):
            outs, aux_up = fn(self._raw_args(), self._raw_aux(),
                              self._last_key)
        self._write_aux(aux_up)
        self.outputs = [_wrap(o, self._out_ctx(i))
                        for i, o in enumerate(outs)]
        if self._monitor_callback is not None:
            self._emit_monitor(is_train)
        return self.outputs

    def _emit_monitor(self, is_train):
        """Feed the monitor callback EVERY op's output, not just the graph
        heads (parity: the engine-level monitor tap — reference
        graph_executor.cc monitor_callback_ fires per op). Runs a cached
        internals program; monitoring is a debug lane, so the extra
        compile/execute cost is acceptable."""
        from .ndarray.ndarray import _wrap
        if self._prog.node_devices:
            # grouped (group2ctx) executors: the internals program has no
            # device map — emit the graph heads only
            for name, arr in zip(self._symbol.list_outputs(), self.outputs):
                self._monitor_callback(name, arr)
            return
        if getattr(self, "_mon_prog", None) is None:
            from .symbol.symbol import Group
            internals = self._symbol.get_internals()
            # op outputs only (incl. multi-output "%s_output%d" names) —
            # variable echoes aren't computed nodes
            var_names = set(internals.list_arguments()) | \
                set(internals.list_auxiliary_states())
            self._mon_names = [n for n in internals.list_outputs()
                               if n not in var_names]
            self._mon_prog = _GraphProgram(
                Group([internals[n] for n in self._mon_names]))
        fn = self._mon_prog.forward_fn(bool(is_train))
        record_dispatch("monitor")
        args = {n: self.arg_dict[n]._data for n in self._mon_prog.arg_names}
        aux = {n: self.aux_dict[n]._data for n in self._mon_prog.aux_names}
        key = getattr(self, "_last_key", None)
        if key is None:
            key = self._step_key()
        outs, _ = fn(args, aux, key)
        for name, o in zip(self._mon_names, outs):
            self._monitor_callback(name, _wrap(o, self._ctx))
        if self._monitor_all:        # inputs/params too (reference
            for name, arr in self.arg_dict.items():   # monitor_all=True)
                self._monitor_callback(name, arr)
            for name, arr in self.aux_dict.items():
                self._monitor_callback(name, arr)

    def backward(self, out_grads=None, is_train=True):
        """Run backward (parity: executor.py backward:154). Recomputes the
        forward inside the fused fwd+bwd XLA program (rematerialisation is
        cheaper than keeping all activations resident in HBM; XLA CSEs what
        it can)."""
        self._run_fwd_bwd(out_grads, is_train=is_train, update_outputs=False)

    def forward_backward(self, out_grads=None, is_train=True, **kwargs):
        """Fused forward+backward in one compiled call — the Module fast
        path (one XLA program per train step)."""
        if kwargs:
            with telemetry.span("feed"):
                self._feed_kwargs(kwargs)
        self._last_key = self._step_key()
        self._run_fwd_bwd(out_grads, is_train=is_train, update_outputs=True)
        return self.outputs

    def _feed_kwargs(self, kwargs):
        """Install keyword-fed inputs into bound storage (the ONE
        kwargs copy-in both forward and forward_backward use); numpy
        feeds count toward the telemetry h2d register."""
        from .ndarray.ndarray import NDArray
        for k, v in kwargs.items():
            if k in self.arg_dict:
                if isinstance(v, NDArray):
                    v.copyto(self.arg_dict[k])
                else:
                    raw = np.asarray(v)
                    telemetry.record_transfer(raw.nbytes)
                    self.arg_dict[k][:] = raw

    def _run_fwd_bwd(self, out_grads, is_train, update_outputs):
        from .ndarray.ndarray import NDArray, _wrap
        grad_names = tuple(n for n in self._arg_names
                           if self._grad_req[n] != "null")
        if not grad_names:
            if update_outputs:
                self.forward(is_train=is_train)
            return
        key = getattr(self, "_last_key", None)
        if key is None:
            key = self._step_key()
        fn = self._prog.fwd_bwd_fn(bool(is_train), grad_names)
        if out_grads is None:
            hg = [None] * self.output_entries_len()
        else:
            if isinstance(out_grads, NDArray):
                out_grads = [out_grads]
            hg = [g._data if isinstance(g, NDArray) else
                  (jnp.asarray(g) if g is not None else None)
                  for g in out_grads]
        # None head grads must be static for jit: substitute ones at trace
        # time; pass a tuple with None markers replaced lazily
        hg_concrete = []
        for i, g in enumerate(hg):
            hg_concrete.append(g)
        if not self._prog.node_devices:
            record_dispatch("fwd_bwd")
        with telemetry.span("step"):
            outs, grads, aux_up = fn(self._raw_args(), self._raw_aux(), key,
                                     tuple(hg_concrete))
        self._write_aux(aux_up)
        if update_outputs:
            self.outputs = [_wrap(o, self._out_ctx(i))
                            for i, o in enumerate(outs)]
        gdict = dict(zip(self._arg_names, self.grad_arrays))
        for n in grad_names:
            garr = gdict[n]
            if garr is None:
                continue
            if self._grad_req[n] == "add":
                garr._set_data(garr._data + grads[n].astype(garr._data.dtype))
            else:
                garr._set_data(grads[n].astype(garr._data.dtype))

    def output_entries_len(self):
        return len(self._prog.output_entries)

    def publish_aux_counters(self, steps=0):
        """Add to the telemetry counters what the ops' auxiliary states
        have summed since the last call (``OpDef.aux_counters``: the
        routed-expert layer's rows). The sums grow on the device inside
        the step; this fetch is the only host sync, so it is called where
        a sync is due anyway (the end of an epoch), never per batch.
        What an op's shapes fix (``OpDef.step_counters``: the state-space
        recurrence's chunks; ``OpDef.step_total`` once for all of an op's
        nodes) is counted here on the host, ``steps`` times: the training
        steps the caller has run since the last call."""
        fixed = [n for n in self._prog.nodes
                 if steps and n.op is not None and n.op.step_counters]
        if fixed:
            inner = self._symbol.get_internals()
            shapes = dict(zip(inner.list_outputs(), inner.infer_shape(
                **{k: v.shape for k, v in self.arg_dict.items()})[1]))
            for node in fixed:
                node.op.step_counters(shapes[node.name + "_output"],
                                      node.attrs, steps)
            for op in {node.op for node in fixed if node.op.step_total}:
                op.step_total(steps)
        seen = self.__dict__.setdefault("_aux_published", {})
        for node in self._prog.nodes:
            table = node.op.aux_counters if node.op is not None else None
            for idx, publish in (table or {}).items():
                aux = node.inputs[idx][0].name
                if aux not in self.aux_dict:
                    continue
                now = np.asarray(self.aux_dict[aux]._data, np.float64)   # mxlint: disable=host-sync -- the one fetch of the summed counters, at an epoch's end
                publish(now - seen.get(aux, 0.0))
                seen[aux] = now

    def _write_aux(self, aux_up):
        if not aux_up:
            return
        d = self.aux_dict
        for name, val in aux_up.items():
            if name in d:
                d[name]._set_data(val)

    # -- misc --------------------------------------------------------------
    def copy_params_from(self, arg_params, aux_params=None,
                         allow_extra_params=False):
        """(parity: executor.py copy_params_from)"""
        for name, arr in (arg_params or {}).items():
            if name in self.arg_dict:
                arr.copyto(self.arg_dict[name])
            elif not allow_extra_params:
                raise MXNetError("unknown argument %r" % name)
        for name, arr in (aux_params or {}).items():
            if name in self.aux_dict:
                arr.copyto(self.aux_dict[name])
            elif not allow_extra_params:
                raise MXNetError("unknown aux state %r" % name)

    def reshape(self, partial_shaping=False, allow_up_sizing=False, **kwargs):
        """Return a new executor for new input shapes (parity: executor
        reshape; on TPU this is simply a new jit signature — compilation is
        cached per shape like CachedOp)."""
        shapes = dict(kwargs)
        arg_shapes, _, aux_shapes = self._symbol.infer_shape_partial(**shapes)
        from .ndarray import zeros
        new_args = []
        for name, arr, s in zip(self._arg_names, self.arg_arrays, arg_shapes):
            if s is None or tuple(s) == arr.shape:
                new_args.append(arr)
            else:
                new_args.append(zeros(s, ctx=self._ctx))
        new_grads = []
        for arr, s in zip(self.grad_arrays, arg_shapes):
            if arr is None:
                new_grads.append(None)
            elif s is None or tuple(s) == arr.shape:
                new_grads.append(arr)
            else:
                new_grads.append(zeros(s, ctx=self._ctx))
        return Executor(self._symbol, self._ctx, new_args, new_grads,
                        self._grad_req, self.aux_arrays, program=self._prog,
                        owns_arrays=True)

    def set_monitor_callback(self, callback, monitor_all=False):
        self._monitor_callback = callback
        self._monitor_all = bool(monitor_all)

    def debug_str(self):
        lines = ["Symbol outputs: %s" % self._symbol.list_outputs()]
        for n in self._prog.nodes:
            lines.append("%s%s" % (n.name, "" if n.op is None
                                   else " = %s" % n.op.name))
        return "\n".join(lines)
