"""mxnet_tpu — a TPU-native deep-learning framework with the capabilities
of Apache MXNet 0.12.1.

Brand-new design (not a port): JAX/XLA is the compute substrate, PJRT the
async engine, pjit/shard_map over device meshes the distributed backend.
See SURVEY.md for the reference's structure this framework mirrors at the
API level, and the per-module docstrings for the TPU-first design of each
subsystem.

Typical use matches the reference::

    import mxnet_tpu as mx
    x = mx.nd.ones((2, 3), ctx=mx.tpu(0))
    with mx.autograd.record():
        y = (x * 2).sum()
    y.backward()
"""
import os as _os

if _os.environ.get("MXNET_TPU_FORCE_CPU", "") in ("1", "true"):
    # debugging/CI escape hatch (the reference's MXNET_ENGINE_TYPE=
    # NaiveEngine analogue): force the host platform before any backend
    # init, from code that cannot set JAX_PLATFORMS=cpu in the
    # environment (tools/diagnose.py keeps its own process off the chip
    # this way)
    import jax as _jax
    _jax.config.update("jax_platforms", "cpu")

# multi-process SPMD wiring, set by tools/launch.py (parity:
# KVStore::InitPSEnv reading DMLC_PS_ROOT_URI etc., kvstore.h:254).
# Must run before any backend touch, hence at import. A no-op without
# MXNET_TPU_COORDINATOR; connection errors propagate — a worker that
# cannot reach the coordinator must die loudly, not train as a
# 1-process job. See mxnet_tpu/dist.py for the elastic posture.
from . import dist
dist.init_from_env()

from .base import MXNetError
from .context import Context, cpu, gpu, tpu, cpu_pinned, current_context, num_gpus
from . import layout
from . import config
from . import ops
from . import imperative
from . import ndarray
from . import ndarray as nd
from .ndarray import NDArray
from . import autograd
from . import random
from .random import seed

# re-export sampler conveniences onto mx.random (parity: mx.random.uniform)
random.uniform = nd.random.uniform
random.normal = nd.random.normal

from . import symbol                 # noqa: E402
from . import symbol as sym          # noqa: E402
from .symbol import Symbol           # noqa: E402
from .executor import Executor       # noqa: E402
from . import initializer            # noqa: E402
from .initializer import init_registry  # noqa: E402
from . import optimizer              # noqa: E402
from . import lr_scheduler           # noqa: E402
from . import metric                 # noqa: E402
from . import io                     # noqa: E402
from . import recordio               # noqa: E402
from . import kvstore                # noqa: E402
from . import kvstore as kv          # noqa: E402  (reference: mx.kv)
from .kvstore import KVStore         # noqa: E402
from . import gradient_compression  # noqa: E402
from . import predictor              # noqa: E402
from . import serving                # noqa: E402
from . import decode                 # noqa: E402
from . import callback               # noqa: E402
from . import model                  # noqa: E402
from . import module                 # noqa: E402
from . import module as mod          # noqa: E402
from . import gluon                  # noqa: E402
from . import parallel               # noqa: E402

__version__ = "0.1.0"
from . import operator               # noqa: E402
from . import rnn                    # noqa: E402
from . import telemetry              # noqa: E402
from . import faults                 # noqa: E402
from . import checkpoint             # noqa: E402
from .checkpoint import CheckpointManager  # noqa: E402
from . import flight                 # noqa: E402

# flight recorder env knobs (MXNET_FLIGHT_DIR / MXNET_METRICS_INTERVAL_MS
# / MXNET_METRICS_PORT) take effect at import; all three default off
flight._maybe_autostart()
from . import compile_cache          # noqa: E402
from . import profiler               # noqa: E402
from . import tuner                  # noqa: E402
from . import monitor                # noqa: E402
from .monitor import Monitor         # noqa: E402
from . import visualization          # noqa: E402
from . import visualization as viz   # noqa: E402
from . import test_utils             # noqa: E402
from . import image                  # noqa: E402
from . import image as img           # noqa: E402
from . import engine                 # noqa: E402
from . import storage                # noqa: E402
from . import resource               # noqa: E402
from . import name                   # noqa: E402
from .attribute import AttrScope     # noqa: E402
from . import attribute              # noqa: E402
from . import registry               # noqa: E402
from . import log                    # noqa: E402
from . import libinfo                # noqa: E402
from . import rtc                    # noqa: E402
from . import contrib                # noqa: E402
from . import executor_manager       # noqa: E402
from . import kvstore_server         # noqa: E402
from . import torch                  # noqa: E402
from . import torch as th            # noqa: E402
from . import initializer as init    # noqa: E402
from . import monitor as mon         # noqa: E402
from . import random as rnd          # noqa: E402
