"""Weight initializers.

Parity: reference ``python/mxnet/initializer.py`` (registry + Xavier/MSRA/
Uniform/Normal/Orthogonal/Bilinear/LSTMBias/Load/Mixed and the name-based
default rules for bias/gamma/beta/moving stats).
"""
from __future__ import annotations

import json
import re

import numpy as np

from .base import registry_create, MXNetError

__all__ = ["InitDesc", "Initializer", "Uniform", "Normal", "Zero", "One",
           "Constant", "Orthogonal", "Xavier", "MSRAPrelu", "Bilinear",
           "LSTMBias", "Load", "Mixed", "register"]

register, _alias, create, _get = registry_create("initializer")
init_registry = {"register": register, "create": create}


class InitDesc(str):
    """Name + attrs describing a parameter (parity: initializer.InitDesc)."""

    def __new__(cls, name, attrs=None, global_init=None):
        obj = super().__new__(cls, name)
        obj.attrs = attrs or {}
        obj.global_init = global_init
        return obj


class Initializer:
    """Base initializer; callable on (InitDesc/str, NDArray)."""

    def __init__(self, **kwargs):
        self._kwargs = kwargs
        self._verbose = False
        self._print_func = None

    def set_verbosity(self, verbose=False, print_func=None):
        self._verbose = verbose
        self._print_func = print_func
        return self

    def dumps(self):
        return json.dumps([self.__class__.__name__.lower(), self._kwargs])

    def __call__(self, desc, arr):
        if not isinstance(desc, str):
            raise TypeError("first argument must be a name string/InitDesc")
        if isinstance(desc, InitDesc) and desc.attrs.get("__init__"):
            spec = desc.attrs["__init__"]
            try:
                cls_name, kwargs = json.loads(spec)
            except (ValueError, TypeError):
                # plain registry name (e.g. Variable(init='zeros'))
                cls_name, kwargs = spec, {}
            create(cls_name, **kwargs)._init_weight(desc, arr)
            return
        # name-based dispatch (parity with reference rules)
        if desc.endswith("weight") or desc.endswith("parameters"):
            # fused RNN blobs ("*_parameters") initialise as weights —
            # the FusedRNN initializer unpacks them per gate
            self._init_weight(desc, arr)
        elif desc.endswith("bias"):
            self._init_bias(desc, arr)
        elif desc.endswith("gamma"):
            self._init_gamma(desc, arr)
        elif desc.endswith("beta"):
            self._init_beta(desc, arr)
        elif desc.endswith("moving_mean") or desc.endswith("running_mean"):
            self._init_zero(desc, arr)
        elif desc.endswith("moving_var") or desc.endswith("running_var"):
            self._init_one(desc, arr)
        elif desc.endswith(("moving_inv_var", "moving_avg", "running_sum")):
            self._init_zero(desc, arr)
        else:
            self._init_default(desc, arr)

    # -- fill helpers ------------------------------------------------------
    def _set(self, arr, value):
        arr[:] = value

    def _init_zero(self, _, arr):
        self._set(arr, np.zeros(arr.shape, dtype=np.float32))

    def _init_one(self, _, arr):
        self._set(arr, np.ones(arr.shape, dtype=np.float32))

    def _init_bias(self, _, arr):
        self._init_zero(_, arr)

    def _init_gamma(self, _, arr):
        self._init_one(_, arr)

    def _init_beta(self, _, arr):
        self._init_zero(_, arr)

    def _init_weight(self, name, arr):
        raise NotImplementedError

    def _init_default(self, name, arr):
        raise MXNetError(
            "Unknown initialization pattern for %s; name a parameter "
            "*_weight/*_bias/... or use a Mixed initializer" % name)


@register
class Uniform(Initializer):
    def __init__(self, scale=0.07):
        super().__init__(scale=scale)
        self.scale = scale

    def _init_weight(self, _, arr):
        self._set(arr, np.random.uniform(-self.scale, self.scale, arr.shape)
                  .astype(np.float32))


@register
class Normal(Initializer):
    def __init__(self, sigma=0.01):
        super().__init__(sigma=sigma)
        self.sigma = sigma

    def _init_weight(self, _, arr):
        self._set(arr, np.random.normal(0, self.sigma, arr.shape)
                  .astype(np.float32))


@register
class Zero(Initializer):
    def _init_weight(self, _, arr):
        self._init_zero(_, arr)


_alias("zeros", Zero)


@register
class One(Initializer):
    def _init_weight(self, _, arr):
        self._init_one(_, arr)


_alias("ones", One)


@register
class Constant(Initializer):
    def __init__(self, value=0.0):
        super().__init__(value=value)
        self.value = value

    def _init_weight(self, _, arr):
        self._set(arr, np.full(arr.shape, self.value, dtype=np.float32))


@register
class Orthogonal(Initializer):
    def __init__(self, scale=1.414, rand_type="uniform"):
        super().__init__(scale=scale, rand_type=rand_type)
        self.scale = scale
        self.rand_type = rand_type

    def _init_weight(self, _, arr):
        nout = arr.shape[0]
        nin = int(np.prod(arr.shape[1:]))
        if self.rand_type == "uniform":
            tmp = np.random.uniform(-1.0, 1.0, (nout, nin))
        else:
            tmp = np.random.normal(0.0, 1.0, (nout, nin))
        u, _, v = np.linalg.svd(tmp, full_matrices=False)
        q = u if u.shape == (nout, nin) else v
        self._set(arr, (self.scale * q).reshape(arr.shape).astype(np.float32))


@register
class Xavier(Initializer):
    """(parity: initializer.Xavier — the default for conv/FC nets)"""

    def __init__(self, rnd_type="uniform", factor_type="avg", magnitude=3):
        super().__init__(rnd_type=rnd_type, factor_type=factor_type,
                         magnitude=magnitude)
        self.rnd_type = rnd_type
        self.factor_type = factor_type
        self.magnitude = float(magnitude)

    def _init_weight(self, name, arr):
        shape = arr.shape
        hw_scale = float(np.prod(shape[2:])) if len(shape) > 2 else 1.0
        fan_in = shape[1] * hw_scale if len(shape) >= 2 else shape[0]
        fan_out = shape[0] * hw_scale
        if self.factor_type == "avg":
            factor = (fan_in + fan_out) / 2.0
        elif self.factor_type == "in":
            factor = fan_in
        elif self.factor_type == "out":
            factor = fan_out
        else:
            raise MXNetError("invalid factor_type %r" % self.factor_type)
        scale = np.sqrt(self.magnitude / factor)
        if self.rnd_type == "uniform":
            w = np.random.uniform(-scale, scale, shape)
        elif self.rnd_type == "gaussian":
            w = np.random.normal(0, scale, shape)
        else:
            raise MXNetError("invalid rnd_type %r" % self.rnd_type)
        self._set(arr, w.astype(np.float32))


@register
class MSRAPrelu(Xavier):
    def __init__(self, factor_type="avg", slope=0.25):
        magnitude = 2.0 / (1 + slope ** 2)
        super().__init__("gaussian", factor_type, magnitude)
        self._kwargs = {"factor_type": factor_type, "slope": slope}


@register
class Bilinear(Initializer):
    """Bilinear upsampling kernel (for UpSampling deconv weights)."""

    def _init_weight(self, _, arr):
        shape = arr.shape
        weight = np.zeros(int(np.prod(shape)), dtype=np.float32)
        f = np.ceil(shape[3] / 2.0)
        c = (2 * f - 1 - f % 2) / (2.0 * f)
        for i in range(int(np.prod(shape))):
            x = i % shape[3]
            y = (i // shape[3]) % shape[2]
            weight[i] = (1 - abs(x / f - c)) * (1 - abs(y / f - c))
        self._set(arr, weight.reshape(shape))


@register
class LSTMBias(Initializer):
    """Forget-gate bias = forget_bias, others 0 (parity: LSTMBias)."""

    def __init__(self, forget_bias=1.0):
        super().__init__(forget_bias=forget_bias)
        self.forget_bias = forget_bias

    def _init_weight(self, name, arr):
        b = np.zeros(arr.shape, dtype=np.float32)
        num_hidden = arr.shape[0] // 4
        b[num_hidden:2 * num_hidden] = self.forget_bias  # i, f, c, o layout
        self._set(arr, b)

    _init_bias = _init_weight


@register
class Load(Initializer):
    """Init from a .params file or dict of arrays, fall back to
    default_init (parity: initializer.Load, which accepts both —
    reference initializer.py:303-306)."""

    def __init__(self, param, default_init=None, verbose=False):
        super().__init__()
        if isinstance(param, str):
            from .ndarray import load as _nd_load
            param = _nd_load(param)
        self.param = {k[4:] if k.startswith(("arg:", "aux:")) else k: v
                      for k, v in param.items()}
        self.default_init = default_init

    def __call__(self, name, arr):
        if name in self.param:
            if tuple(self.param[name].shape) != tuple(arr.shape):
                raise MXNetError("Load: shape mismatch for %s" % name)
            arr[:] = self.param[name].asnumpy() if hasattr(self.param[name],
                                                           "asnumpy") \
                else self.param[name]
        else:
            if self.default_init is None:
                raise MXNetError("Load: no init for %s" % name)
            self.default_init(name, arr)


@register
class Mixed(Initializer):
    """Regex-pattern dispatch to sub-initializers (parity: Mixed)."""

    def __init__(self, patterns, initializers):
        super().__init__()
        if len(patterns) != len(initializers):
            raise MXNetError("Mixed: patterns/initializers length mismatch")
        self.map = [(re.compile(p), init) for p, init in
                    zip(patterns, initializers)]

    def __call__(self, name, arr):
        for prog, init in self.map:
            if prog.match(name):
                init(name, arr)
                return
        raise MXNetError("Mixed: no pattern matches %r; add a '.*' catch-all"
                         % name)


@register
class FusedRNN(Initializer):
    """Initialize a fused RNN parameter blob by unpacking it, applying an
    inner initializer per unfused array, and repacking (parity:
    initializer.FusedRNN — including the LSTM forget-gate bias)."""

    def __init__(self, init, num_hidden, num_layers, mode,
                 bidirectional=False, forget_bias=1.0):
        if isinstance(init, str):
            klass, kwargs = json.loads(init)
            init = create(klass, **kwargs)
        super().__init__(init=init.dumps() if init is not None else None,
                         num_hidden=num_hidden, num_layers=num_layers,
                         mode=mode, bidirectional=bidirectional,
                         forget_bias=forget_bias)
        self._init = init
        self._num_hidden = num_hidden
        self._num_layers = num_layers
        self._mode = mode
        self._bidirectional = bidirectional
        self._forget_bias = forget_bias

    def _init_weight(self, desc, arr):
        from .rnn import rnn_cell
        cell = rnn_cell.FusedRNNCell(
            self._num_hidden, self._num_layers, self._mode,
            self._bidirectional, forget_bias=self._forget_bias, prefix="")
        args = cell.unpack_weights({"parameters": arr})
        for name in args:
            if self._mode == "lstm" and name.endswith("_f_bias"):
                args[name][:] = self._forget_bias
            elif self._init is not None:
                self._init(InitDesc(name), args[name])
        arr[:] = cell.pack_weights(args)["parameters"]
