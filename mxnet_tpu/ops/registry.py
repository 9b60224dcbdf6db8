"""Operator registry — the single source of truth for the op surface.

TPU-native re-design of the reference's NNVM op registry
(``nnvm::Op`` + attr functors ``FCompute``/``FInferShape``, see reference
``include/mxnet/op_attr_types.h:45-264`` and SURVEY.md §2.1). In the
reference every op carries a C++ shape/type/storage-inference functor and
per-backend kernels; here every op is ONE pure JAX function — XLA is the
backend, shape/dtype inference falls out of ``jax.eval_shape``, and
gradients fall out of ``jax.vjp``. The Python ``mx.nd.*`` / ``mx.sym.*``
namespaces are code-generated from this registry exactly like the
reference generates them from the C op registry
(``python/mxnet/ndarray/register.py:142-168``).
"""
from __future__ import annotations

import functools
import inspect

from ..base import MXNetError

__all__ = ["OpDef", "register", "get_op", "list_ops", "alias"]

_OPS = {}


class OpDef:
    """One operator.

    Parameters
    ----------
    name : canonical MXNet op name (e.g. ``"FullyConnected"``).
    fn : pure function ``fn(*jax_arrays, **params) -> array | tuple``.
    nin : number of tensor inputs; -1 = variadic (first arg is a list).
    nout : number of outputs (static).
    arg_names : names of the tensor inputs, in order (for symbol binding
        and kwargs-style calls, e.g. ``data/weight/bias``).
    mutate : indices of inputs mutated in place by the imperative wrapper
        (optimizer update ops — reference ``optimizer_op.cc:39-299``).
    no_grad : op is non-differentiable; tape records zero-grad.
    """

    def __init__(self, name, fn, nin=1, nout=1, arg_names=None, defaults=None,
                 mutate=(), no_grad=False, doc=None, jit=False):
        self.name = name
        self.fn = fn
        self.nin = nin
        self.nout = nout
        self.arg_names = list(arg_names) if arg_names is not None else (
            ["data"] if nin in (1, -1) else ["lhs", "rhs"] if nin == 2 else
            ["arg%d" % i for i in range(max(nin, 0))])
        self.defaults = dict(defaults or {})
        self.mutate = tuple(mutate)
        self.no_grad = no_grad
        # Composite ops (scan-heavy RNN/CTC, conv, per-step optimizer
        # updates) re-trace their whole Python body on every eager call;
        # jit=True caches one compiled program per (static-params, avals)
        # signature — the eager analogue of the reference's cached engine
        # ops (graph_executor.cc InitCachedOps). Off by default: ops fed
        # varying shapes (image augmenters) would thrash the cache.
        self.jit_cache = jit
        self._jit_fns = {}
        self.doc = doc or (fn.__doc__ if fn is not None else None)
        # Execution-context needs, discovered from the signature: ops that
        # behave differently at train time declare a `_train` kwarg, random
        # ops a `_rng` kwarg (see ops/common.py).
        try:
            params = inspect.signature(fn).parameters
            self.takes_train = "_train" in params
            self.takes_rng = "_rng" in params
        except (TypeError, ValueError):
            self.takes_train = self.takes_rng = False
        # How many outputs user code sees (reference: num_visible_outputs —
        # e.g. BatchNorm computes 3 but exposes 1).
        self.visible_outputs = None
        # Indices of inputs that are auxiliary states (reference: aux states
        # like BatchNorm moving_mean/var — not arguments, never differentiated).
        self.aux_inputs = ()
        # Optional hook(raw_inputs, raw_outputs, params) -> {input_idx: new
        # raw value}; models reference ops that mutate aux states in place.
        self.stateful_update = None
        # Optional hook(input_shapes, params) -> {input_idx: shape} filling
        # learnable-input shapes (reference FInferShape; see ops/shape_infer.py).
        self.param_shape_infer = None
        # Optional hook(input_dtypes, params) -> {input_idx: dtype} for ops
        # whose learnable inputs do NOT follow the data dtype (reference
        # FInferType; e.g. BatchNorm pins scale/shift/moving stats to fp32
        # under low-precision data, the cudnn_batch_norm behaviour).
        self.param_dtype_infer = None
        # Optional {aux input index: fn(growth)}: the auxiliary state holds
        # running sums, and ``Executor.publish_aux_counters`` hands ``fn``
        # what they grew by since its last call, for telemetry counters.
        self.aux_counters = None
        # Optional fn(output_shape, params, steps): adds to the telemetry
        # counters the work that the op's shapes fix, for the ``steps``
        # fused steps run since ``Executor.publish_aux_counters`` last
        # called it.
        self.step_counters = None

    def __repr__(self):
        return "OpDef(%s)" % self.name

    def accepted_params(self):
        """Names this op accepts as keyword params — derived from the fn
        signature (registry defaults alone miss params that exist only as
        fn keyword defaults). None means the fn takes **kwargs (accept
        anything)."""
        cached = getattr(self, "_accepted_params", False)
        if cached is not False:
            return cached
        keys = set(self.defaults) | {"num_args", "num_outputs"}
        try:
            sig = inspect.signature(self.fn)
            for p in sig.parameters.values():
                if p.kind == inspect.Parameter.VAR_KEYWORD:
                    self._accepted_params = None
                    return None
                if p.kind in (inspect.Parameter.KEYWORD_ONLY,
                              inspect.Parameter.POSITIONAL_OR_KEYWORD) \
                        and p.default is not inspect.Parameter.empty:
                    keys.add(p.name)
        except (TypeError, ValueError):
            pass
        keys -= set(self.arg_names)
        keys -= {"_train", "_rng"}
        self._accepted_params = keys
        return keys

    def apply(self, arrays, params):
        """Run the op on raw jax arrays. Returns a tuple of outputs."""
        out = self.fn(*arrays, **params)
        return out if isinstance(out, tuple) else (out,)

    def jitted(self, params):
        """Return (jitted_fn, dynamic_params) for this op.

        ``jitted_fn(arrays_tuple, dynamic_params_dict)`` runs the cached
        compiled program; hashable params are baked in as statics,
        array-valued ones (the rng key) stay traced operands.
        """
        import jax
        static, dynamic = [], {}
        for k, v in params.items():
            if isinstance(v, (list, tuple)):
                v = tuple(v)
            try:
                hash(v)
                static.append((k, v))
            except TypeError:
                dynamic[k] = v
        key = (tuple(sorted(static)), tuple(sorted(dynamic)))
        fn = self._jit_fns.get(key)
        if fn is None:
            static_params = dict(static)
            op_fn = self.fn

            def _pure(arrs, dyn):
                out = op_fn(*arrs, **static_params, **dyn)
                return out if isinstance(out, tuple) else (out,)

            fn = jax.jit(_pure)
            self._jit_fns[key] = fn
        return fn, dynamic


def register(name, nin=1, nout=1, arg_names=None, defaults=None, mutate=(),
             no_grad=False, aliases=(), jit=False):
    """Decorator registering a pure-jax function as an operator."""

    def _reg(fn):
        op = OpDef(name, fn, nin=nin, nout=nout, arg_names=arg_names,
                   defaults=defaults, mutate=mutate, no_grad=no_grad,
                   jit=jit)
        if name in _OPS:
            raise MXNetError("op %r already registered" % name)
        _OPS[name] = op
        for a in aliases:
            _OPS[a] = op
        return fn

    return _reg


def alias(existing, *names):
    op = get_op(existing)
    for n in names:
        _OPS[n] = op


def get_op(name):
    if name not in _OPS:
        raise MXNetError("operator %r is not registered" % (name,))
    return _OPS[name]


def list_ops():
    return sorted(_OPS)


def canonical_params(op, kwargs):
    """Merge defaults, normalise unhashable values for cache keys."""
    params = dict(op.defaults)
    params.update(kwargs)
    return params


@functools.lru_cache(maxsize=None)
def _noop():  # placeholder keeping functools imported for future caching
    return None
