"""Multi-process runtime wiring: jax.distributed with a survivable client.

Parity: the reference's multi-host tier is ps-lite — ``KVStore::InitPSEnv``
reads ``DMLC_PS_ROOT_URI``/``DMLC_RANK`` and wires scheduler/server/worker
roles (kvstore.h:254, SURVEY.md §5.3). The TPU-native rebuild has no
roles: every worker is the SAME single program on a process-spanning
mesh, discovered through ``jax.distributed`` exactly as multi-host TPU
pods are driven (the one-program-across-hosts model of the Julia-to-TPU
line, arXiv 1810.09868). ``tools/launch.py`` exports the env this module
reads at import.

Two deviations from a stock ``jax.distributed.initialize``, both in
service of ELASTIC recovery (a dead worker must not take the survivors
down with it):

* the client is built with ``shutdown_on_destruction=False`` and a
  WIDE missed-heartbeat budget: when a peer dies, the coordination
  service's default posture is "ensure all processes shut down if any
  process dies" — precisely wrong for a runtime whose fit loop detects
  the death itself (heartbeat.py liveness), re-meshes over the
  survivors and resumes from the last checkpoint. The coordination
  service keeps its roles (rendezvous, topology exchange); the
  LIVENESS authority is the heartbeat directory.
* shutdown is explicit and conditional: :func:`finalize` runs the
  clean shutdown barrier only when every peer is still live —
  after a member loss (:func:`mark_member_lost`) the survivor skips
  the barrier (it would time out against the dead peer and the
  propagated error would fatally terminate the process mid-exit).

On the CPU backend (the 2-process-on-one-box tier-1 lane) cross-process
collectives need the gloo transport — selected automatically before
backend init.
"""
from __future__ import annotations

import os
import threading

__all__ = ["init_from_env", "initialized", "rank", "process_count",
           "live_ranks", "mark_member_lost", "dead_ranks", "finalize",
           "abort", "ENV_COORDINATOR"]

ENV_COORDINATOR = "MXNET_TPU_COORDINATOR"
ENV_NUM_PROCESSES = "MXNET_TPU_NUM_PROCESSES"
ENV_PROCESS_ID = "MXNET_TPU_PROCESS_ID"
# coordination-service heartbeat posture (distinct from the liveness
# heartbeats in heartbeat.py): interval seconds x max missed = how long
# the SERVICE tolerates a silent peer before it propagates a fatal
# error to every task. Elastic recovery needs this window wider than
# the time a survivor takes to detect the death itself and re-mesh.
ENV_HEARTBEAT_S = "MXNET_TPU_DIST_HEARTBEAT_S"
ENV_MAX_MISSED = "MXNET_TPU_DIST_MAX_MISSED"

_lock = threading.Lock()
_state = {"initialized": False,    # guarded by: _lock
          "owns_client": False,    # guarded by: _lock
          "member_lost": False,    # guarded by: _lock
          "dead": frozenset()}     # guarded by: _lock


def _force_cpu_collectives():
    """Select the gloo transport for cross-process CPU collectives when
    the job runs on the host platform (the tier-1 lane; the default CPU
    client has no multi-process collectives at all). A no-op when the
    platform is an accelerator."""
    plats = os.environ.get("JAX_PLATFORMS", "")
    force_cpu = os.environ.get("MXNET_TPU_FORCE_CPU", "") in ("1", "true")
    if not (force_cpu or "cpu" in plats.split(",")):
        return
    import jax
    jax.config.update("jax_cpu_collectives_implementation", "gloo")


def init_from_env():
    """Wire this process into the job described by the launch env
    (``MXNET_TPU_COORDINATOR``/``_NUM_PROCESSES``/``_PROCESS_ID``, set
    by ``tools/launch.py``). Must run before any backend touch, hence
    from ``mxnet_tpu/__init__``. Returns True when a multi-process
    runtime was (or already is) initialised.

    Connection errors propagate: a worker that cannot reach the
    coordinator must die loudly, not train as a 1-process job.
    """
    addr = os.environ.get(ENV_COORDINATOR)
    if not addr:
        return False
    _force_cpu_collectives()
    import jax
    with _lock:
        if _state["initialized"] or jax.distributed.is_initialized():
            _state["initialized"] = True
            return True
        nproc = int(os.environ.get(ENV_NUM_PROCESSES, "1"))
        pid = int(os.environ.get(ENV_PROCESS_ID, "0"))
        _survivable_initialize(addr, nproc, pid)
        _state["owns_client"] = True
        _state["initialized"] = True
    return True


def _survivable_initialize(addr, nproc, pid):
    """``jax.distributed.initialize`` with the elastic posture: a wide
    service/client missed-heartbeat budget and no shutdown-on-destruction
    barrier (see module docstring). Mirrors
    ``jax._src.distributed.State.initialize`` field for field so
    ``jax.distributed.is_initialized()`` and every ``process_index``
    consumer see a normally-initialised runtime."""
    from jax._src import distributed as _jdist
    from jax._src.lib import _jax
    # jaxlib 0.9.0's service and client take one timeout: interval x
    # missed beats, the window the two env knobs describe
    hb_timeout = (int(os.environ.get(ENV_HEARTBEAT_S, "10"))
                  * int(os.environ.get(ENV_MAX_MISSED, "10")))
    gs = _jdist.global_state
    if gs.client is not None:
        raise RuntimeError("distributed client already initialised")
    if pid == 0 and gs.service is None:
        port = addr.rsplit(":", 1)[1]
        gs.service = _jax.get_distributed_runtime_service(
            "[::]:" + port, nproc, heartbeat_timeout=hb_timeout)
    client = _jax.get_distributed_runtime_client(
        addr, pid,
        init_timeout=int(os.environ.get("MXNET_TPU_DIST_INIT_TIMEOUT",
                                        "300")),
        heartbeat_timeout=hb_timeout,
        shutdown_on_destruction=False, use_compression=True)
    client.connect()
    gs.client = client
    gs.process_id = pid
    gs.num_processes = nproc
    gs.coordinator_address = addr
    if gs.preemption_sync_manager is None:
        gs.initialize_preemption_sync_manager()


def initialized():
    """Whether a multi-process runtime is live."""
    import jax
    return jax.distributed.is_initialized()


def rank():
    """This process's index in the job (0 in a single-process run)."""
    if not initialized():
        return int(os.environ.get("DMLC_RANK", 0))
    import jax
    return jax.process_index()


def process_count():
    """Total processes LAUNCHED into the job (dead ones included — use
    :func:`live_ranks` for the surviving membership)."""
    if not initialized():
        return int(os.environ.get("DMLC_NUM_WORKER", 1))
    import jax
    return jax.process_count()


def live_ranks():
    """Sorted surviving process ranks: everything launched minus the
    ranks recorded dead by :func:`mark_member_lost`. The elastic
    re-mesh builds the new dp mesh from exactly this set."""
    with _lock:
        dead = _state["dead"]
    return tuple(r for r in range(process_count()) if r not in dead)


def dead_ranks():
    """Sorted ranks recorded dead so far."""
    with _lock:
        return tuple(sorted(_state["dead"]))


def mark_member_lost(ranks):
    """Record dead peers. From then on :func:`live_ranks` excludes them
    and :func:`finalize` skips the all-tasks shutdown barrier (it can
    never complete against a dead peer, and the propagated barrier
    error would fatally terminate this surviving process)."""
    with _lock:
        _state["member_lost"] = True
        _state["dead"] = _state["dead"] | frozenset(int(r) for r in ranks)


def finalize():
    """Clean multi-process teardown. With every peer live this runs the
    coordination shutdown barrier (all workers should call it at job
    end); after a member loss it only drops the local references, so
    the surviving process exits 0 instead of aborting in the barrier.
    Idempotent; a no-op in single-process runs."""
    with _lock:
        if not _state["initialized"]:
            return
        _state["initialized"] = False
        owns, lost = _state["owns_client"], _state["member_lost"]
    if not owns:
        # stock-initialized runtime: jax.distributed.shutdown owns it
        return
    try:
        from jax._src import distributed as _jdist
    except ImportError:
        return
    gs = _jdist.global_state
    if lost:
        # LEAK the client/service deliberately: destroying them
        # mid-interpreter cancels the coordination channels, the
        # surviving client's error-poll thread observes the
        # cancellation and this jaxlib's default handler FATALLY
        # terminates the process — after the survivor did all the
        # work of recovering. The OS reclaims everything at exit;
        # a survivor that must guarantee a destructor-free exit can
        # call :func:`abort`.
        return
    client, service = gs.client, gs.service
    gs.client = None
    gs.service = None
    gs.preemption_sync_manager = None
    if client is not None:
        client.shutdown()
    if service is not None:
        service.shutdown()


def abort(code=0):
    """Exit the process immediately WITHOUT running destructors — the
    only guaranteed-safe exit on this jaxlib once a peer has died
    abnormally: any teardown of the coordination client/service can
    trip its fatal error-propagation path (a worker dying with a
    Python exception runs C++ destructors whose shutdown-barrier RPC
    drags every surviving peer into a fatal abort ~15 s later; a
    SIGKILL'd or ``abort()``-ed worker does not). Flushes stdio
    first. Dist workers that crash should die THROUGH this; the
    launcher treats any nonzero code as a member death.

    ``os._exit`` skips atexit AND sys.excepthook, so a crashing worker
    aborting here would die with its flight recorder unsaved — exactly
    the rank whose last seconds the fleet postmortem needs (the
    survivor's ``dead_worker`` view gathers peers' dumps from the
    shared flight dir). Bank a ``worker_abort`` postmortem first on
    any nonzero code; best-effort, a recorder failure must not stop
    the exit."""
    import sys
    if int(code) != 0:
        try:
            from . import flight as _flight
            # called from inside an except block (the dist child's
            # crash handler), sys.exc_info() carries the killing
            # exception — the victim's dump should name its killer
            _flight.postmortem("worker_abort", exc=sys.exc_info()[1],
                               extra={"exit_code": int(code)},
                               force=True)
        except Exception:
            pass
    try:
        sys.stdout.flush()
        sys.stderr.flush()
    except Exception:
        pass
    os._exit(int(code))
