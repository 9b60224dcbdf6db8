"""Persisted AOT executable cache + the program-card corpus store.

No reference counterpart — the reference recompiled its graph executors
per process and called it cheap (CUDA kernels were prebuilt; only graph
planning ran at bind). On XLA the per-process cost is an actual
compiler invocation per program signature: serving warmup compiles one
program per batch bucket, and a bench round compiles the train step
before it can measure anything. This module is the zero-cold-
start tier ROADMAP item 3 calls for — the tune-once-serve-forever loop
of TVM (arXiv:1802.04799) native to our runtime:

* **executable store** — ``executor._InstrumentedProgram`` hands every
  freshly compiled executable to ``store()``, which serializes it (the
  PJRT executable serialization behind
  ``jax.experimental.serialize_executable``) into a content-addressed
  file keyed on sha256 of (StableHLO module text, abstract signature
  incl. shardings, donation set, backend platform, device topology,
  jax+jaxlib versions). The next process ``load()``s the key and
  deserializes INSTEAD of invoking XLA — restart, serving warmup and
  bench rounds skip the compiler entirely.

* **graceful degradation** — any mismatch (corrupt blob, stale
  jax/jaxlib version tag, different backend or mesh/device topology,
  deserialization failure) REJECTS the entry and falls back to a fresh
  compile, with one structured warning per (entry, cause) and a
  ``compile_cache.reject`` counter bump. A cache must never be able to
  break dispatch.

* **telemetry** — ``compile_cache.hit`` / ``.miss`` / ``.store`` /
  ``.reject`` counters plus ``.bytes_read`` / ``.bytes_written``, and
  the deserialize phase timed as a ``jit_deserialize`` span, so
  program cards and ``telemetry.snapshot()`` distinguish disk-hits
  from compiles (the warm-smoke lane gates on exactly this).

* **card corpus** — an append-only JSONL store persisting the program
  cards (FLOPs, bytes-accessed, compile ms) and measured serving data
  (rows histogram, per-bucket step ms) across runs:
  ``corpus_append()`` / ``corpus_records()``. The corpus is the raw
  material for the learned-cost-model line of work (Kaufman et al.
  arXiv:2008.01040); ``tuner.plan_serving`` reads it to pick serving
  bucket sets and ``max_inflight`` from measured data instead of
  pow-2 defaults.

Enablement: ``MXNET_COMPILE_CACHE=<dir>`` (empty/``0`` disables — the
default, so tests stay hermetic). The corpus lives at
``MXNET_CARD_CORPUS`` or ``<cache dir>/card_corpus.jsonl``.
"""
from __future__ import annotations

import hashlib
import json
import os
import pickle
import tempfile
import threading
import time

import jax
from jax.experimental import serialize_executable

from . import telemetry
from . import faults
from .log import get_logger

__all__ = ["enabled", "cache_dir", "lowered_key", "quick_key",
           "index_get", "index_put", "load", "store",
           "corpus_path", "corpus_append", "corpus_records", "env_meta",
           "source_fingerprint"]

_log = get_logger("mxnet_tpu.compile_cache")

# one structured warning per (key, cause-kind): a poisoned entry that
# every bucket trips over must not log a storm
_WARNED = set()      # guarded by: _lock
_lock = threading.Lock()

_MAGIC = b"MXTPUCC1"
_FORMAT_VERSION = 1


# ---------------------------------------------------------------------------
# Enablement / environment identity
# ---------------------------------------------------------------------------

def cache_dir():
    """The cache directory (``MXNET_COMPILE_CACHE``), or None when the
    persisted tier is off (unset/empty/``0``)."""
    d = os.environ.get("MXNET_COMPILE_CACHE", "")
    if not d or d == "0":
        return None
    return d


_DIR_TRUST = {}      # guarded by: _lock


def _trusted_dir():
    """The cache dir, or None when it must not be trusted: entries are
    PICKLE payloads, so loading from a directory another user can
    write into is local arbitrary code execution. The dir must either
    not exist yet (we create it with default umask perms) or be owned
    by this uid and not group/world-writable. Distrust warns once and
    disables the persisted tier — never an error."""
    d = cache_dir()
    if d is None:
        return None
    with _lock:
        t = _DIR_TRUST.get(d)
    if t is None:
        try:
            st = os.stat(d)
            t = bool(st.st_uid == os.getuid()
                     and not (st.st_mode & 0o022))
        except FileNotFoundError:
            t = True            # created by us on first store
        except OSError:
            t = False
        if not t:
            _log.warning(
                "compile_cache: %s is not owned by this user or is "
                "group/world-writable — the persisted executable tier "
                "is DISABLED (a foreign-writable store could feed "
                "arbitrary pickles to deserialization)", d)
        # the stat/warn runs unlocked (filesystem I/O must not hold the
        # registry lock); a concurrent first-call races to the same
        # verdict and the write below is idempotent
        with _lock:
            _DIR_TRUST[d] = t
    return d if t else None


def enabled():
    """Whether executables persist to disk this process (requires a
    TRUSTED cache dir — see ``_trusted_dir``)."""
    return _trusted_dir() is not None


def persistable(donated=()):
    """Whether a program with this donation set may use the persisted
    tier. Donated-buffer programs are EXCLUDED by default: on jaxlib
    0.4.36, executing a deserialized input-donating executable
    intermittently corrupted the process heap (glibc ``corrupted
    double-linked list`` at a later free — seen through Module.fit's
    fused train step; forward/serving programs were stable). On the
    installed jax/jaxlib 0.9.0 the opted-in round trip passes on the
    CPU backend (tests/test_compile_cache.py) and 20 warm starts of
    the fused step in a row showed no abort there; it has not been
    tried on a TPU, so the default stays off and
    ``MXNET_COMPILE_CACHE_DONATED=1`` is the opt-in. JAX's own
    persistent cache (``jax_cache.place``) has no such exclusion and
    is what the chip entry points use."""
    if not donated:
        return True
    return os.environ.get("MXNET_COMPILE_CACHE_DONATED", "") == "1"


def env_meta():
    """The identity of THIS process's compile environment — everything
    a serialized executable is only valid under: jax/jaxlib versions,
    backend platform, and the local device topology (a cache written
    on an 8-device mesh must not load into a 1-device process)."""
    import jaxlib
    devs = jax.devices()
    return {
        "jax": jax.__version__,
        "jaxlib": jaxlib.__version__,
        "backend": jax.default_backend(),
        "devices": [[d.platform, int(d.id)] for d in devs],
    }


# ---------------------------------------------------------------------------
# Content-addressed key
# ---------------------------------------------------------------------------

def lowered_key(kind, lowered, signature=None, donated=()):
    """sha256 key for one lowered program: the StableHLO module text
    (the graph content), the named abstract signature incl. sharding
    strings (placement), the donation set, and the environment identity
    from ``env_meta()``. Returns None when the program cannot be keyed
    (exotic lowerings without a text form) — the caller then simply
    skips the persisted tier for that program."""
    try:
        text = lowered.as_text()
    except Exception:
        return None
    h = hashlib.sha256()
    h.update(_MAGIC)
    meta = env_meta()
    h.update(json.dumps(
        [kind, meta["jax"], meta["jaxlib"], meta["backend"],
         meta["devices"], list(donated or ()), signature],
        sort_keys=True).encode())
    h.update(text.encode())
    return h.hexdigest()


def entry_path(key):
    """On-disk path of one cache entry (two-level fan-out so a hot
    cache directory stays listable)."""
    d = cache_dir()
    if d is None:
        return None
    return os.path.join(d, key[:2], key + ".mxcc")


# ---------------------------------------------------------------------------
# Quick-key index: the trace-skip tier
# ---------------------------------------------------------------------------
# The content key above is bulletproof (it hashes the actual StableHLO)
# but computing it requires TRACING the program — a visible slice of a
# warm start (per-bucket jit_trace is ~15% of a cold serving warmup).
# The quick key is computable WITHOUT tracing, from everything that
# determines what the trace WOULD produce:
#   * the caller's graph fingerprint (``_GraphProgram`` hashes its
#     symbol JSON + the ambient layout default),
#   * a fingerprint of the package source tree ((relpath, size,
#     mtime_ns) of every .py file — editing any op implementation
#     invalidates every quick entry),
#   * every ``MXNET_*`` env knob except the cache's own (framework
#     flags like MXNET_FUSED_BN_ADD_RELU change trace-time lowering),
#   * the abstract signature incl. shardings, the donation set, and
#     ``env_meta()``.
# A quick-key hit resolves through a tiny index file to the content
# entry (which still verifies versions/backend/topology/checksum), so
# the worst a stale index can do is a rejected load -> fresh compile.

_SRC_FP = None       # guarded by: _lock

# cache/corpus/telemetry toggles do not change what a trace produces —
# including them would split the cache for no reason
_GRAPH_ENV_EXCLUDE = frozenset((
    "MXNET_COMPILE_CACHE", "MXNET_CARD_CORPUS", "MXNET_TELEMETRY"))


def source_fingerprint():
    """sha256 over this package's .py files as (relpath, size,
    mtime_ns) — any source edit (or a fresh checkout) invalidates the
    trace-skip tier, which then falls back to trace + content key."""
    global _SRC_FP
    with _lock:
        fp = _SRC_FP
    if fp is None:
        root = os.path.dirname(os.path.abspath(__file__))
        items = []
        for dirpath, dirnames, filenames in os.walk(root):
            dirnames[:] = sorted(d for d in dirnames if d != "__pycache__")
            for fn in sorted(filenames):
                if not fn.endswith(".py"):
                    continue
                p = os.path.join(dirpath, fn)
                try:
                    st = os.stat(p)
                except OSError:
                    continue
                items.append([os.path.relpath(p, root), st.st_size,
                              st.st_mtime_ns])
        fp = hashlib.sha256(
            json.dumps(items, sort_keys=True).encode()).hexdigest()
        # the tree walk runs unlocked; first writer wins (both racers
        # hashed the same tree)
        with _lock:
            if _SRC_FP is None:
                _SRC_FP = fp
            fp = _SRC_FP
    return fp


def _graph_env():
    env = {k: v for k, v in os.environ.items()
           if k.startswith("MXNET_") and k not in _GRAPH_ENV_EXCLUDE}
    # MXTPU_IMAGE_LAYOUT seeds the layout default at import
    if "MXTPU_IMAGE_LAYOUT" in os.environ:
        env["MXTPU_IMAGE_LAYOUT"] = os.environ["MXTPU_IMAGE_LAYOUT"]
    return env


def quick_key(kind, graph_key, signature=None, donated=()):
    """Trace-free cache key (see the tier comment above). ``graph_key``
    is the caller's JSON-safe graph fingerprint; None disables the
    tier for that program."""
    if graph_key is None:
        return None
    h = hashlib.sha256()
    h.update(b"MXTPUQK1")
    try:
        h.update(json.dumps(
            [kind, graph_key, source_fingerprint(), _graph_env(),
             env_meta(), list(donated or ()), signature],
            sort_keys=True).encode())
    except (TypeError, ValueError):
        return None
    return h.hexdigest()


def _index_path(qkey):
    d = cache_dir()
    if d is None:
        return None
    return os.path.join(d, "index", qkey[:2], qkey + ".json")


def index_get(qkey):
    """Content key the quick key resolves to, or None. A mangled index
    file reads as a miss (the content entry's own verification is the
    real gate)."""
    if qkey is None:
        return None
    p = _index_path(qkey)
    if p is None or not os.path.exists(p):
        return None
    try:
        with open(p) as f:
            rec = json.load(f)
        key = rec.get("key")
        return key if isinstance(key, str) else None
    except (OSError, ValueError):
        return None


def index_put(qkey, content_key):
    """Point the quick key at a stored content entry (atomic write;
    failures are warn-once no-ops like store())."""
    if qkey is None or content_key is None:
        return False
    p = _index_path(qkey)
    if p is None:
        return False
    tmp = None
    try:
        os.makedirs(os.path.dirname(p), exist_ok=True)
        fd, tmp = tempfile.mkstemp(dir=os.path.dirname(p), suffix=".tmp")
        with os.fdopen(fd, "w") as f:
            json.dump({"key": content_key, "created": time.time()}, f)
        os.replace(tmp, p)
        return True
    except OSError as e:
        if tmp is not None:
            try:
                os.unlink(tmp)
            except OSError:
                pass
        _warn_once(qkey, "index_write", str(e))
        return False


# ---------------------------------------------------------------------------
# Entry file format: MAGIC + u32 meta-length + meta JSON + pickled blob
# ---------------------------------------------------------------------------

def _write_entry(path, meta, blob):
    """Atomic write (tmp + rename) of one cache entry."""
    os.makedirs(os.path.dirname(path), exist_ok=True)
    mj = json.dumps(meta, sort_keys=True).encode()
    fd, tmp = tempfile.mkstemp(dir=os.path.dirname(path),
                               suffix=".tmp")
    try:
        with os.fdopen(fd, "wb") as f:
            f.write(_MAGIC)
            f.write(len(mj).to_bytes(4, "little"))
            f.write(mj)
            f.write(blob)
        os.replace(tmp, path)
    except BaseException:
        try:
            os.unlink(tmp)
        except OSError:
            pass
        raise
    return len(mj) + len(blob) + len(_MAGIC) + 4


def _read_entry(path):
    """(meta, blob) of one entry file; raises ValueError on a mangled
    container (bad magic / truncated header)."""
    with open(path, "rb") as f:
        raw = f.read()
    if raw[:len(_MAGIC)] != _MAGIC:
        raise ValueError("bad magic")
    off = len(_MAGIC)
    mlen = int.from_bytes(raw[off:off + 4], "little")
    off += 4
    meta = json.loads(raw[off:off + mlen].decode())
    blob = raw[off + mlen:]
    return meta, blob


def _warn_once(key, cause, detail):
    """ONE structured warning per (key, cause) through log.py — the
    single-warning contract the poisoning tests pin."""
    with _lock:
        if (key, cause) in _WARNED:
            return
        _WARNED.add((key, cause))
    _log.warning(
        "compile_cache: rejected entry %s cause=%s (%s) — falling back "
        "to a fresh compile; delete the entry (or the cache dir) to "
        "stop paying the load attempt", key[:12], cause, detail)


def _reject(key, cause, detail):
    telemetry.counter_inc("compile_cache.reject")
    telemetry.counter_inc("compile_cache.reject.%s" % cause)
    _warn_once(key, cause, detail)
    return None


# ---------------------------------------------------------------------------
# Load / store
# ---------------------------------------------------------------------------

def load(key, kind=None):
    """Deserialize the executable stored under ``key``, or None.

    Every mismatch degrades to None (the caller compiles fresh):
    missing entry (``compile_cache.miss``), corrupt container or blob,
    stale jax/jaxlib version tag, different backend platform or
    device/mesh topology, or a deserialization error — each rejected
    with a single structured warning and a ``compile_cache.reject``
    counter bump. The deserialize phase records as a
    ``jit_deserialize`` telemetry span, the disk-tier counterpart of
    ``jit_compile``."""
    path = entry_path(key)
    if path is None or _trusted_dir() is None:
        return None
    # chaos site: an injected raise behaves exactly like a mangled
    # entry — the reject path fires and the caller compiles fresh (a
    # cache must never be able to break dispatch, injected or not)
    try:
        faults.fire("compile_cache.load")
    except faults.InjectedFault as e:
        return _reject(key, "injected", str(e))
    if not os.path.exists(path):
        telemetry.counter_inc("compile_cache.miss")
        return None
    try:
        meta, blob = _read_entry(path)
    except (OSError, ValueError, UnicodeDecodeError) as e:
        return _reject(key, "corrupt", "unreadable entry: %s" % e)
    env = env_meta()
    for field in ("jax", "jaxlib"):
        if meta.get(field) != env[field]:
            return _reject(
                key, "version",
                "%s %s in entry vs %s running" % (field, meta.get(field),
                                                  env[field]))
    if meta.get("backend") != env["backend"]:
        return _reject(key, "backend", "entry compiled for backend %r, "
                       "process runs %r" % (meta.get("backend"),
                                            env["backend"]))
    if meta.get("devices") != env["devices"]:
        return _reject(
            key, "mesh",
            "entry compiled for device topology %s, process has %s"
            % (meta.get("devices"), env["devices"]))
    if meta.get("blob_sha256") != hashlib.sha256(blob).hexdigest():
        return _reject(key, "corrupt", "blob checksum mismatch")
    # the devices the program was compiled for: left to its default,
    # deserialize_and_load loads for EVERY device of the backend, and a
    # one-device program then wants one shard per device at dispatch
    by_id = {int(d.id): d for d in jax.devices()}
    try:
        exec_devs = [by_id[i] for i in meta["execution_devices"]]
    except (KeyError, TypeError):
        return _reject(key, "mesh", "entry names execution devices %r "
                       "this process does not have"
                       % (meta.get("execution_devices"),))
    try:
        with telemetry.span("jit_deserialize"):
            payload, in_tree, out_tree = pickle.loads(blob)
            compiled = serialize_executable.deserialize_and_load(
                payload, in_tree, out_tree, execution_devices=exec_devs)
    except Exception as e:
        return _reject(key, "deserialize",
                       "%s: %s" % (type(e).__name__, e))
    telemetry.counter_inc("compile_cache.hit")
    telemetry.counter_inc("compile_cache.bytes_read", len(blob))
    return compiled


def store(key, compiled, kind=None, entry=None, signature=None):
    """Serialize one freshly compiled executable under ``key``. All
    failures (backends without executable serialization, unpicklable
    trees, full disk) degrade to a warning-once no-op — persisting is
    an optimisation, never a requirement. Returns the stored byte
    count (0 when skipped)."""
    path = entry_path(key)
    if path is None:
        return 0
    try:
        payload, in_tree, out_tree = serialize_executable.serialize(compiled)
        blob = pickle.dumps((payload, in_tree, out_tree))
        exec_devs = [int(d.id) for d in
                     compiled.runtime_executable().local_devices()]
    except Exception as e:
        _warn_once(key, "serialize", "%s: %s" % (type(e).__name__, e))
        telemetry.counter_inc("compile_cache.store_fail")
        return 0
    meta = dict(env_meta())
    meta.update({
        "format": _FORMAT_VERSION,
        "kind": kind,
        "entry": entry,
        "signature": signature,
        "created": time.time(),
        "blob_sha256": hashlib.sha256(blob).hexdigest(),
        "blob_bytes": len(blob),
        "execution_devices": exec_devs,
    })
    try:
        n = _write_entry(path, meta, blob)
    except OSError as e:
        _warn_once(key, "write", str(e))
        telemetry.counter_inc("compile_cache.store_fail")
        return 0
    telemetry.counter_inc("compile_cache.store")
    telemetry.counter_inc("compile_cache.bytes_written", n)
    return n


# ---------------------------------------------------------------------------
# Program-card corpus (append-only JSONL across runs)
# ---------------------------------------------------------------------------

def corpus_path():
    """The JSONL corpus file: ``MXNET_CARD_CORPUS`` if set (``0``/empty
    disables), else ``<cache dir>/card_corpus.jsonl``, else None."""
    p = os.environ.get("MXNET_CARD_CORPUS", "")
    if p == "0":
        return None
    if p:
        return p
    d = cache_dir()
    return os.path.join(d, "card_corpus.jsonl") if d else None


def corpus_append(record, path=None):
    """Append one JSON record (a dict; a ``kind`` field keys readers)
    to the corpus. Returns True when written. Never raises — the
    corpus is telemetry, not state."""
    path = path or corpus_path()
    if path is None or not isinstance(record, dict):
        return False
    try:
        line = json.dumps(record, sort_keys=True)
    except (TypeError, ValueError) as e:
        _log.warning("compile_cache: corpus record not JSON-safe: %s", e)
        return False
    try:
        os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
        with _lock:
            with open(path, "a") as f:
                f.write(line + "\n")
    except OSError as e:
        _log.warning("compile_cache: corpus append to %s failed: %s",
                     path, e)
        return False
    telemetry.counter_inc("compile_cache.corpus_append")
    return True


def corpus_records(path=None, kind=None):
    """All parseable corpus records, oldest first (``kind`` filters on
    the record's ``kind`` field). Unparseable lines — a run killed
    mid-append — are skipped, not fatal."""
    path = path or corpus_path()
    if path is None or not os.path.exists(path):
        return []
    out = []
    try:
        with open(path) as f:
            lines = f.readlines()
    except OSError as e:
        _log.warning("compile_cache: corpus read from %s failed: %s",
                     path, e)
        return []
    for line in lines:
        line = line.strip()
        if not line:
            continue
        try:
            rec = json.loads(line)
        except ValueError:
            continue
        if isinstance(rec, dict) and (kind is None
                                      or rec.get("kind") == kind):
            out.append(rec)
    return out


def programs_record(extra=None):
    """One corpus record snapshotting ``telemetry.programs()`` plus the
    fit/serve span stats — what a run banks so the NEXT run's autotuner
    has measured step-ms next to each card's FLOPs/bytes."""
    snap_spans = telemetry.span_stats()
    rec = {
        "kind": "programs",
        "ts": time.time(),
        "env": env_meta(),
        "cards": telemetry.programs(),
        "spans": {k: v for k, v in snap_spans.items()
                  if k in telemetry.FIT_PHASE_SPANS
                  or k in telemetry.SERVE_SPANS
                  or k in telemetry.COMPILE_SPANS},
    }
    if extra:
        rec.update(extra)
    return rec
