"""Evaluation metrics.

Parity: reference ``python/mxnet/metric.py`` — EvalMetric base + registry
and the 16 built-ins (SURVEY.md §5.5).
"""
from __future__ import annotations

import math

import numpy as _numpy

from .base import MXNetError, registry_create
from .ndarray.ndarray import NDArray
from . import telemetry

__all__ = ["EvalMetric", "CompositeEvalMetric", "Accuracy", "TopKAccuracy",
           "F1", "Perplexity", "MAE", "MSE", "RMSE", "CrossEntropy",
           "NegativeLogLikelihood", "PearsonCorrelation", "Loss", "Torch",
           "Caffe", "CustomMetric", "np", "create", "register"]

register, _alias, _create, _get = registry_create("metric")


def create(metric, *args, **kwargs):
    """(parity: metric.create) Accepts name, callable, instance, or list."""
    if callable(metric):
        return CustomMetric(metric, *args, **kwargs)
    if isinstance(metric, EvalMetric):
        return metric
    if isinstance(metric, (list, tuple)):
        composite = CompositeEvalMetric()
        for child in metric:
            composite.add(create(child, *args, **kwargs))
        return composite
    return _create(str(metric), *args, **kwargs)


def _as_numpy(x):
    return x.asnumpy() if isinstance(x, NDArray) else _numpy.asarray(x)


def _acc_chain(p, l, a, axis):
    """Pure accuracy accumulate: (optional argmax) + compare + sum +
    running-sum add. The ONE definition both the phase-split jitted
    program (``_acc_fused``) and the whole-step fused metric kernel
    (``Accuracy.device_kernel``) trace — bit-identical paths by
    construction, not by hand-synchronised copies. ``axis`` is None
    when predictions are already class ids."""
    import jax.numpy as jnp
    if axis is not None:
        p = jnp.argmax(p, axis=axis)
    p = p.astype(jnp.int32).reshape(-1)
    l = l.astype(jnp.int32).reshape(-1)
    return a + jnp.sum(p == l).astype(jnp.float32)


def _acc_fused(pred, label, acc, argmax_axis):
    """Accuracy accumulate as one compiled program (jitted
    ``_acc_chain``; ``argmax_axis`` is static)."""
    import jax
    global _ACC_FUSED_JIT
    if _ACC_FUSED_JIT is None:
        _ACC_FUSED_JIT = jax.jit(_acc_chain, static_argnames="axis")
    from .executor import record_dispatch
    record_dispatch("metric")
    return _ACC_FUSED_JIT(pred, label, acc, axis=argmax_axis)


_ACC_FUSED_JIT = None


def _colocate(ref, x):
    """Reshard ``x`` to ``ref``'s placement (mesh-DP outputs are sharded
    over the device mesh while labels arrive single-device). A ``ref``
    whose rank differs from ``x``'s (an mp-sharded prediction spec like
    ``P('dp','mp')`` against a rank-1 label) cannot be applied verbatim
    — ``x`` then shards over the leading dims the two share and
    replicates the rest, landing on the SAME mesh so the jitted
    accumulate accepts the pair."""
    import jax
    sh = getattr(ref, "sharding", None)
    if sh is None:
        return x
    try:
        if getattr(x, "sharding", None) == sh:
            return x
    except ValueError:
        pass
    try:
        return jax.device_put(x, sh)
    except (TypeError, ValueError):
        pass
    mesh = getattr(sh, "mesh", None)
    if mesh is None:
        return x
    from jax.sharding import NamedSharding, PartitionSpec
    entries = tuple(sh.spec)[:x.ndim]
    try:
        return jax.device_put(x, NamedSharding(mesh,
                                               PartitionSpec(*entries)))
    except (TypeError, ValueError):
        return jax.device_put(x, NamedSharding(mesh, PartitionSpec()))


def check_label_shapes(labels, preds, shape=False):
    if (not shape and len(labels) != len(preds)) or \
            (shape and labels.shape != preds.shape):
        raise MXNetError("Shape of labels %s does not match shape of "
                         "predictions %s" % (len(labels), len(preds)))


class EvalMetric:
    """Base metric (parity: metric.EvalMetric)."""

    def __init__(self, name, output_names=None, label_names=None, **kwargs):
        self.name = str(name)
        self.output_names = output_names
        self.label_names = label_names
        self._kwargs = kwargs
        self.reset()

    def __str__(self):
        return "EvalMetric: {}".format(dict(self.get_name_value()))

    def get_config(self):
        config = dict(self._kwargs)
        config.update({"metric": self.__class__.__name__, "name": self.name,
                       "output_names": self.output_names,
                       "label_names": self.label_names})
        return config

    def update_dict(self, label, pred):
        if self.output_names is not None:
            pred = [pred[name] for name in self.output_names]
        else:
            pred = list(pred.values())
        if self.label_names is not None:
            label = [label[name] for name in self.label_names]
        else:
            label = list(label.values())
        self.update(label, pred)

    def update(self, labels, preds):
        raise NotImplementedError

    def reset(self):
        self.num_inst = 0
        self.sum_metric = 0.0
        self._dev_sum = None

    # -- async device accumulation ----------------------------------------
    # Hot metrics reduce ON DEVICE and enqueue the scalar without a host
    # sync; get() is the only synchronisation point. On a remoted PJRT
    # backend a per-batch logits pull would otherwise serialise the
    # training pipeline (no reference counterpart — the reference's
    # metrics run in-process where the copy is cheap, metric.py:39).
    def _accum_device(self, scalar, n):
        prev = getattr(self, "_dev_sum", None)
        self._dev_sum = scalar if prev is None else prev + scalar
        self.num_inst += n

    def _flush_device(self):
        if getattr(self, "_dev_sum", None) is not None:
            # THE metric synchronisation point: the only blocking fetch
            # the async accumulate paths ever issue
            telemetry.record_host_sync("metric_fetch")
            with telemetry.span("metric_fetch"):
                self.sum_metric += float(self._dev_sum)
            self._dev_sum = None

    # -- whole-train-step fusion hooks -------------------------------------
    def device_kernel(self):
        """Pure accumulate function for the Module whole-step fused
        training program: ``(labels, preds, acc) -> new_acc`` over traced
        arrays, or None when this metric can only accumulate eagerly
        (Module.fit then falls back to the phase-split ``update`` path
        for the metric — see module/module.py ``_fused_batch_step``).

        Under the dp-mesh SPMD step the kernel traces over BATCH-SHARDED
        labels/preds and a replicated accumulator: the reduction to the
        scalar makes GSPMD insert the cross-replica psum inside the step
        program, so the accumulator handed back to ``_install_fused`` is
        already the GLOBAL sum — fetching it costs no extra program."""
        return None

    def _install_fused(self, dev_sum, n):
        """Adopt the accumulator returned by a fused train step (the
        device value is fetched lazily at ``get()``, like the eager
        ``_accum_device`` path). ``dev_sum`` is the global (mesh-psummed)
        running sum and ``n`` the GLOBAL instance count."""
        self._dev_sum = dev_sum
        self.num_inst += n

    def get(self):
        self._flush_device()
        if self.num_inst == 0:
            return (self.name, float("nan"))
        return (self.name, self.sum_metric / self.num_inst)

    def get_name_value(self):
        name, value = self.get()
        if not isinstance(name, list):
            name = [name]
        if not isinstance(value, list):
            value = [value]
        return list(zip(name, value))


class CompositeEvalMetric(EvalMetric):
    """(parity: metric.CompositeEvalMetric)"""

    def __init__(self, metrics=None, name="composite", output_names=None,
                 label_names=None):
        super().__init__(name, output_names, label_names)
        self.metrics = [create(m) for m in (metrics or [])]

    def add(self, metric):
        self.metrics.append(create(metric))

    def get_metric(self, index):
        return self.metrics[index]

    def update_dict(self, labels, preds):
        for metric in self.metrics:
            metric.update_dict(labels, preds)

    def update(self, labels, preds):
        for metric in self.metrics:
            metric.update(labels, preds)

    def reset(self):
        for metric in getattr(self, "metrics", []):
            metric.reset()

    def get(self):
        names, values = [], []
        for metric in self.metrics:
            name, value = metric.get()
            names.append(name) if not isinstance(name, list) else names.extend(name)
            values.append(value) if not isinstance(value, list) else values.extend(value)
        return (names, values)


@register
@register(name="acc")
class Accuracy(EvalMetric):
    def __init__(self, axis=1, name="accuracy", output_names=None,
                 label_names=None):
        super().__init__(name, output_names, label_names, axis=axis)
        self.axis = axis

    def device_kernel(self):
        """Fused-step accumulate: traces the SAME ``_acc_chain`` the
        phase-split ``_acc_fused`` program jits, so the two paths are
        bit-identical."""
        axis = self.axis

        def kernel(labels, preds, acc):
            for l, p in zip(labels, preds):
                ax = axis % p.ndim if p.ndim > l.ndim else None
                acc = _acc_chain(p, l, acc, ax)
            return acc

        return kernel

    def update(self, labels, preds):
        import jax.numpy as jnp
        check_label_shapes(labels, preds)
        for label, pred in zip(labels, preds):
            if isinstance(label, NDArray) and isinstance(pred, NDArray):
                p, l = pred._data, label._data
                # shape agreement checked host-side so the whole
                # argmax+compare+sum+accumulate chain runs as ONE
                # dispatched program — eager op-by-op execution costs
                # several dispatches per batch
                n = int(_numpy.prod(l.shape))
                if p.ndim > l.ndim:
                    ax = self.axis % p.ndim
                    p_n = int(_numpy.prod(p.shape[:ax]
                                          + p.shape[ax + 1:]))
                else:
                    p_n = int(_numpy.prod(p.shape))
                if p_n != n:
                    raise MXNetError(
                        "Shape of labels %s does not match shape of "
                        "predictions %s" % (l.shape, p.shape))
                l = _colocate(p, l)
                prev = getattr(self, "_dev_sum", None)
                if prev is None:
                    prev = jnp.zeros((), jnp.float32)
                self._dev_sum = _acc_fused(p, l, prev,
                                           self.axis % p.ndim
                                           if p.ndim > l.ndim else None)
                self.num_inst += n
                continue
            label = _as_numpy(label)
            pred = _as_numpy(pred)
            if pred.ndim > label.ndim:
                pred = _numpy.argmax(pred, axis=self.axis)
            pred = pred.astype(_numpy.int32).flatten()
            label = label.astype(_numpy.int32).flatten()
            check_label_shapes(label, pred, shape=True)
            self.sum_metric += float((pred == label).sum())
            self.num_inst += len(label)


@register
@register(name="top_k_accuracy")
@register(name="top_k_acc")
class TopKAccuracy(EvalMetric):
    def __init__(self, top_k=1, name="top_k_accuracy", output_names=None,
                 label_names=None):
        super().__init__(name, output_names, label_names, top_k=top_k)
        self.top_k = top_k
        self.name += "_%d" % top_k

    def update(self, labels, preds):
        import jax
        import jax.numpy as jnp
        check_label_shapes(labels, preds)
        for label, pred in zip(labels, preds):
            if isinstance(label, NDArray) and isinstance(pred, NDArray):
                p, l = pred._data, label._data
                assert p.ndim == 2
                _, topk = jax.lax.top_k(p, self.top_k)
                l = _colocate(topk, l.astype(jnp.int32).reshape(-1, 1))
                hits = jnp.sum(topk == l)
                self._accum_device(hits.astype(jnp.float32),
                                   int(l.shape[0]))
                continue
            pred = _as_numpy(pred)
            label = _as_numpy(label).astype(_numpy.int32)
            assert pred.ndim == 2
            topk = _numpy.argsort(pred, axis=1)[:, -self.top_k:]
            for j in range(self.top_k):
                self.sum_metric += float((topk[:, j] == label.flatten()).sum())
            self.num_inst += len(label)


@register
class F1(EvalMetric):
    def __init__(self, name="f1", output_names=None, label_names=None):
        super().__init__(name, output_names, label_names)

    def update(self, labels, preds):
        check_label_shapes(labels, preds)
        for label, pred in zip(labels, preds):
            pred = _as_numpy(pred)
            label = _as_numpy(label).astype(_numpy.int32)
            pred_label = _numpy.argmax(pred, axis=1)
            if len(_numpy.unique(label)) > 2:
                raise MXNetError("F1 currently only supports binary labels")
            tp = float(((pred_label == 1) & (label == 1)).sum())
            fp = float(((pred_label == 1) & (label == 0)).sum())
            fn = float(((pred_label == 0) & (label == 1)).sum())
            precision = tp / (tp + fp) if tp + fp > 0 else 0.0
            recall = tp / (tp + fn) if tp + fn > 0 else 0.0
            f1 = 2 * precision * recall / (precision + recall) \
                if precision + recall > 0 else 0.0
            self.sum_metric += f1
            self.num_inst += 1


@register
class Perplexity(EvalMetric):
    def __init__(self, ignore_label=None, axis=-1, name="perplexity",
                 output_names=None, label_names=None):
        super().__init__(name, output_names, label_names,
                         ignore_label=ignore_label)
        self.ignore_label = ignore_label
        self.axis = axis

    def update(self, labels, preds):
        check_label_shapes(labels, preds)
        loss = 0.0
        num = 0
        for label, pred in zip(labels, preds):
            label = _as_numpy(label).astype(_numpy.int32).flatten()
            pred = _as_numpy(pred)
            pred = pred.reshape(-1, pred.shape[-1])
            probs = pred[_numpy.arange(label.size), label]
            if self.ignore_label is not None:
                ignore = (label == self.ignore_label)
                probs = _numpy.where(ignore, 1.0, probs)
                num -= int(ignore.sum())
            loss += -_numpy.log(_numpy.maximum(1e-10, probs)).sum()
            num += label.size
        self.sum_metric += float(math.exp(loss / max(num, 1))) * num
        self.num_inst += num

    def get(self):
        if self.num_inst == 0:
            return (self.name, float("nan"))
        return (self.name, self.sum_metric / self.num_inst)


@register
class MAE(EvalMetric):
    def __init__(self, name="mae", output_names=None, label_names=None):
        super().__init__(name, output_names, label_names)

    def update(self, labels, preds):
        check_label_shapes(labels, preds)
        for label, pred in zip(labels, preds):
            label = _as_numpy(label)
            pred = _as_numpy(pred)
            if label.ndim == 1:
                label = label.reshape(label.shape[0], 1)
            if pred.ndim == 1:
                pred = pred.reshape(pred.shape[0], 1)
            self.sum_metric += float(_numpy.abs(label - pred).mean())
            self.num_inst += 1


@register
class MSE(EvalMetric):
    def __init__(self, name="mse", output_names=None, label_names=None):
        super().__init__(name, output_names, label_names)

    def update(self, labels, preds):
        check_label_shapes(labels, preds)
        for label, pred in zip(labels, preds):
            label = _as_numpy(label)
            pred = _as_numpy(pred)
            if label.ndim == 1:
                label = label.reshape(label.shape[0], 1)
            if pred.ndim == 1:
                pred = pred.reshape(pred.shape[0], 1)
            self.sum_metric += float(((label - pred) ** 2).mean())
            self.num_inst += 1


@register
class RMSE(EvalMetric):
    def __init__(self, name="rmse", output_names=None, label_names=None):
        super().__init__(name, output_names, label_names)

    def update(self, labels, preds):
        check_label_shapes(labels, preds)
        for label, pred in zip(labels, preds):
            label = _as_numpy(label)
            pred = _as_numpy(pred)
            if label.ndim == 1:
                label = label.reshape(label.shape[0], 1)
            if pred.ndim == 1:
                pred = pred.reshape(pred.shape[0], 1)
            self.sum_metric += float(_numpy.sqrt(((label - pred) ** 2).mean()))
            self.num_inst += 1


@register
@register(name="ce")
class CrossEntropy(EvalMetric):
    def __init__(self, eps=1e-12, name="cross-entropy", output_names=None,
                 label_names=None):
        super().__init__(name, output_names, label_names, eps=eps)
        self.eps = eps

    def update(self, labels, preds):
        import jax.numpy as jnp
        check_label_shapes(labels, preds)
        for label, pred in zip(labels, preds):
            if isinstance(label, NDArray) and isinstance(pred, NDArray):
                p, l = pred._data, label._data.reshape(-1).astype(jnp.int32)
                assert l.shape[0] == p.shape[0]
                l = _colocate(p, l)
                prob = jnp.take_along_axis(
                    p.astype(jnp.float32), l[:, None], axis=1)[:, 0]
                self._accum_device(-jnp.sum(jnp.log(prob + self.eps)),
                                   int(l.shape[0]))
                continue
            label = _as_numpy(label).ravel().astype(_numpy.int32)
            pred = _as_numpy(pred)
            assert label.shape[0] == pred.shape[0]
            prob = pred[_numpy.arange(label.shape[0]), label]
            self.sum_metric += float((-_numpy.log(prob + self.eps)).sum())
            self.num_inst += label.shape[0]


@register
@register(name="nll_loss")
class NegativeLogLikelihood(EvalMetric):
    def __init__(self, eps=1e-12, name="nll-loss", output_names=None,
                 label_names=None):
        super().__init__(name, output_names, label_names, eps=eps)
        self.eps = eps

    update = CrossEntropy.update


@register
@register(name="pearsonr")
class PearsonCorrelation(EvalMetric):
    def __init__(self, name="pearsonr", output_names=None, label_names=None):
        super().__init__(name, output_names, label_names)

    def update(self, labels, preds):
        check_label_shapes(labels, preds)
        for label, pred in zip(labels, preds):
            label = _as_numpy(label).ravel()
            pred = _as_numpy(pred).ravel()
            self.sum_metric += float(_numpy.corrcoef(pred, label)[0, 1])
            self.num_inst += 1


@register
class Loss(EvalMetric):
    """Mean of the raw outputs (parity: metric.Loss)."""

    def __init__(self, name="loss", output_names=None, label_names=None):
        super().__init__(name, output_names, label_names)

    def update(self, _, preds):
        for pred in preds:
            pred = _as_numpy(pred)
            self.sum_metric += float(pred.sum())
            self.num_inst += pred.size

    def device_kernel(self):
        """Fused-step accumulate: the sum of the outputs in float32. The
        step counts ``num_inst`` as the labels' sizes, which for a loss
        head (one output a label) is ``update``'s ``pred.size``."""
        def kernel(labels, preds, acc):
            import jax.numpy as jnp
            for l, p in zip(labels, preds):
                if p.size != l.size:
                    raise MXNetError(
                        "Loss in the fused step counts one output a label: "
                        "got outputs %s for labels %s" % (p.shape, l.shape))
                acc = acc + jnp.sum(p.astype(jnp.float32))
            return acc

        return kernel


@register
class Torch(Loss):
    def __init__(self, name="torch", output_names=None, label_names=None):
        super().__init__(name, output_names, label_names)


@register
class Caffe(Loss):
    def __init__(self, name="caffe", output_names=None, label_names=None):
        super().__init__(name, output_names, label_names)


@register
class CustomMetric(EvalMetric):
    """Wrap a feval(label, pred) function (parity: metric.CustomMetric)."""

    def __init__(self, feval, name=None, allow_extra_outputs=False,
                 output_names=None, label_names=None):
        if name is None:
            name = feval.__name__
            if name.find("<") != -1:
                name = "custom(%s)" % name
        super().__init__(name, output_names, label_names, feval=feval,
                         allow_extra_outputs=allow_extra_outputs)
        self._feval = feval
        self._allow_extra_outputs = allow_extra_outputs

    def update(self, labels, preds):
        if not self._allow_extra_outputs:
            check_label_shapes(labels, preds)
        for pred, label in zip(preds, labels):
            label = _as_numpy(label)
            pred = _as_numpy(pred)
            reval = self._feval(label, pred)
            if isinstance(reval, tuple):
                num_inst, sum_metric = reval
                self.sum_metric += sum_metric
                self.num_inst += num_inst
            else:
                self.sum_metric += reval
                self.num_inst += 1


def np(numpy_feval, name=None, allow_extra_outputs=False):
    """(parity: metric.np) wrap a numpy feval as a metric."""
    def feval(label, pred):
        return numpy_feval(label, pred)
    feval.__name__ = numpy_feval.__name__
    return CustomMetric(feval, name, allow_extra_outputs)
