"""Neural-network operators.

Parity: reference ``src/operator/`` legacy layer ops (fully_connected-inl.h,
convolution-inl.h + cudnn_convolution, pooling-inl.h, batch_norm.cc,
activation-inl.h, leaky_relu-inl.h, dropout-inl.h, lrn-inl.h,
l2_normalization-inl.h, instance_norm-inl.h, upsampling-inl.h,
softmax_output-inl.h, regression_output-inl.h, make_loss-inl.h) and
``src/operator/nn/softmax-inl.h``.

TPU-first notes: convs/matmuls map directly onto the MXU via
``lax.conv_general_dilated`` / ``jnp.dot`` — XLA picks layouts and fuses
the elementwise epilogues (bias, activation, BN scale) into them, which
is what the reference needed cuDNN fused kernels for. Ops that behave
differently in train vs inference (BatchNorm, Dropout) take a ``_train``
flag injected by the execution layer; random ops take ``_rng``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ..base import MXNetError
from .common import as_tuple, channels_last, mx_dtype
from .registry import register, get_op


# ---------------------------------------------------------------------------
# FullyConnected
# ---------------------------------------------------------------------------

@register("FullyConnected", nin=3, arg_names=["data", "weight", "bias"],
          defaults={"num_hidden": 0, "no_bias": False, "flatten": True})
def fully_connected(data, weight, bias=None, num_hidden=0, no_bias=False,
                    flatten=True):
    """y = x W^T + b (reference fully_connected-inl.h:69-114, linalg_gemm).

    Weight layout (num_hidden, in_units) matches the reference exactly.
    """
    if flatten and data.ndim > 2:
        data = data.reshape(data.shape[0], -1)
    out = jnp.dot(data, weight.T)
    if not no_bias and bias is not None:
        out = out + bias
    return out


# ---------------------------------------------------------------------------
# Convolution / Deconvolution
# ---------------------------------------------------------------------------

def _s2d_applicable(data, kernel, stride, dilate, pad, num_group, is_cl,
                    ndim):
    """The ResNet/VGG stem pattern a TPU hates: channels-last 7x7/s2 conv
    with tiny input depth (C=3 wastes 125/128 MXU input lanes).
    MXNET_CONV_S2D_STEM=0 disables the rewrite (the PERF.md A/B knob);
    read at trace time, so flipping it requires a fresh jit cache."""
    from ..base import get_env
    return (ndim == 2 and is_cl and tuple(kernel) == (7, 7)
            and tuple(stride) == (2, 2) and tuple(pad) == (3, 3)
            and tuple(dilate) == (1, 1) and int(num_group) == 1
            and data.shape[-1] <= 4
            and data.shape[1] % 2 == 0 and data.shape[2] % 2 == 0
            and bool(get_env("MXNET_CONV_S2D_STEM", 1, int)))


def _conv_s2d_7x7s2(data, weight):
    """Space-to-depth rewrite of the 7x7/s2 stem conv (the MLPerf trick;
    PERF.md 'next levers'). Exactly equivalent: pad the kernel to 8x8
    (one leading zero row/col), fold 2x2 input blocks into channels
    (C -> 4C, making the MXU's input-lane dimension useful), and run a
    4x4/s1 conv with the correspondingly folded weights. Pure reshapes +
    one conv — XLA folds the weight transform at compile time, and the
    backward falls out of jax.vjp through the linear ops."""
    N, H, W, C = data.shape
    O = weight.shape[0]
    # kernel 7->8 with a LEADING zero (index shift dy -> dy+1), then
    # split each spatial 8 into (4 taps x 2 phases)
    w8 = jnp.pad(weight, ((0, 0), (1, 0), (1, 0), (0, 0)))
    w4 = w8.reshape(O, 4, 2, 4, 2, C).transpose(0, 1, 3, 2, 4, 5) \
        .reshape(O, 4, 4, 4 * C)
    # space-to-depth: (N,H,W,C) -> (N,H/2,W/2,4C), channel=(by*2+bx)*C+c
    y = data.reshape(N, H // 2, 2, W // 2, 2, C).transpose(0, 1, 3, 2, 4, 5) \
        .reshape(N, H // 2, W // 2, 4 * C)
    # original pad 3/s2 maps to asymmetric (2,1)/s1 on the folded grid
    return jax.lax.conv_general_dilated(
        y, w4, window_strides=(1, 1), padding=[(2, 1), (2, 1)],
        dimension_numbers=("NHWC", "OHWI", "NHWC"))


def _conv_nd(data, weight, bias, kernel, stride, dilate, pad, num_group,
             no_bias, transposed=False, adj=None, target_shape=None,
             layout=None):
    ndim = len(kernel)
    stride = stride or (1,) * ndim
    dilate = dilate or (1,) * ndim
    pad = pad or (0,) * ndim
    spatial = "DHW"[3 - ndim:]
    is_cl = channels_last(layout, ndim)
    # Channels-first: NC+spatial data, OIHW weight (deconv: IOHW in the
    # reference; we keep OIHW at this layer and Deconvolution adapts).
    # Channels-last (the MXU-native layout — channels land in the lane
    # dimension with no relayout): N+spatial+C data, O+spatial+I weight.
    lhs_spec = ("N" + spatial + "C") if is_cl else ("NC" + spatial)
    rhs_spec = ("O" + spatial + "I") if is_cl else ("OI" + spatial)
    if not transposed:
        if _s2d_applicable(data, kernel, stride, dilate, pad, num_group,
                           is_cl, ndim):
            out = _conv_s2d_7x7s2(data, weight)
        else:
            out = jax.lax.conv_general_dilated(
                data, weight, window_strides=stride,
                padding=[(p, p) for p in pad],
                rhs_dilation=dilate,
                dimension_numbers=(lhs_spec, rhs_spec, lhs_spec),
                feature_group_count=int(num_group))
        if not no_bias and bias is not None:
            out = out + (bias if is_cl
                         else bias.reshape((1, -1) + (1,) * ndim))
        return out
    if is_cl:
        raise MXNetError("Deconvolution supports channels-first layouts only")
    # transposed conv = lhs-dilated conv with the flipped kernel.
    # weight arrives in the reference Deconvolution layout
    # (in_channels, num_filter/g, *kernel); the dilated conv needs
    # (num_filter, in_channels/g, *kernel) OIHW.
    adj = adj or (0,) * ndim
    g = int(num_group)
    k_eff = [(k - 1) * d + 1 for k, d in zip(kernel, dilate)]
    padding = [(ke - 1 - p, ke - 1 - p + a)
               for ke, p, a in zip(k_eff, pad, adj)]
    w = jnp.flip(weight, axis=tuple(range(2, 2 + ndim)))
    c_in = w.shape[0]
    f_per_g = w.shape[1]
    wspatial = w.shape[2:]
    w = w.reshape((g, c_in // g, f_per_g) + wspatial)
    w = jnp.swapaxes(w, 1, 2)                    # (g, F/g, C_in/g, ...)
    w = w.reshape((g * f_per_g, c_in // g) + wspatial)
    dn_t = jax.lax.conv_dimension_numbers(
        data.shape, w.shape, (lhs_spec, "OI" + spatial, lhs_spec))
    out = jax.lax.conv_general_dilated(
        data, w, window_strides=(1,) * ndim, padding=padding,
        rhs_dilation=dilate, lhs_dilation=stride,
        dimension_numbers=dn_t, feature_group_count=g)
    if not no_bias and bias is not None:
        out = out + bias.reshape((1, -1) + (1,) * ndim)
    return out


@register("Convolution", nin=3, jit=True, arg_names=["data", "weight", "bias"],
          defaults={"kernel": (), "stride": (), "dilate": (), "pad": (),
                    "num_filter": 0, "num_group": 1, "no_bias": False,
                    "workspace": 1024, "cudnn_tune": None, "cudnn_off": False,
                    "layout": None})
def convolution(data, weight, bias=None, kernel=(), stride=(), dilate=(),
                pad=(), num_filter=0, num_group=1, no_bias=False,
                workspace=1024, cudnn_tune=None, cudnn_off=False, layout=None):
    """N-D convolution (reference convolution-inl.h). layout=None means the
    reference NCHW/NCDHW; NWC/NHWC/NDHWC run channels-last — the MXU-native
    layout (weight is then (num_filter, *kernel, in_channels/g), matching
    the reference's NHWC cuDNN convention).

    workspace/cudnn_* knobs are accepted for API parity and ignored — XLA
    owns algorithm choice and scratch on TPU.
    """
    kernel = as_tuple(kernel)
    ndim = len(kernel)
    return _conv_nd(data, weight, bias, kernel, as_tuple(stride, ndim),
                    as_tuple(dilate, ndim), as_tuple(pad, ndim), num_group,
                    no_bias, layout=layout)


@register("Deconvolution", nin=3, jit=True, arg_names=["data", "weight", "bias"],
          defaults={"kernel": (), "stride": (), "dilate": (), "pad": (),
                    "adj": (), "target_shape": (), "num_filter": 0,
                    "num_group": 1, "no_bias": True, "workspace": 512,
                    "cudnn_tune": None, "cudnn_off": False, "layout": None})
def deconvolution(data, weight, bias=None, kernel=(), stride=(), dilate=(),
                  pad=(), adj=(), target_shape=(), num_filter=0, num_group=1,
                  no_bias=True, workspace=512, cudnn_tune=None,
                  cudnn_off=False, layout=None):
    """Transposed convolution (reference deconvolution-inl.h). Weight layout
    (in_channels, num_filter/g, *kernel) as in the reference."""
    kernel = as_tuple(kernel)
    ndim = len(kernel)
    return _conv_nd(data, weight, bias, kernel,
                    as_tuple(stride, ndim), as_tuple(dilate, ndim),
                    as_tuple(pad, ndim), num_group, no_bias, transposed=True,
                    adj=as_tuple(adj, ndim) if adj else None)


# ---------------------------------------------------------------------------
# Pooling
# ---------------------------------------------------------------------------

@register("Pooling", defaults={"kernel": (), "pool_type": "max", "stride": (),
                               "pad": (), "global_pool": False,
                               "pooling_convention": "valid", "cudnn_off": False,
                               "layout": None})
def pooling(data, kernel=(), pool_type="max", stride=(), pad=(),
            global_pool=False, pooling_convention="valid", cudnn_off=False,
            layout=None):
    """Max/avg/sum pooling (reference pooling-inl.h). layout=None means the
    reference NC+spatial; channels-last layouts window over the middle dims.

    'full' convention (ceil division of output size) is implemented by
    right-padding up to what ceil needs, matching reference behaviour.
    """
    ndim = data.ndim - 2
    is_cl = channels_last(layout, ndim)
    sp0 = 1 if is_cl else 2  # first spatial dim index
    if global_pool:
        axes = tuple(range(sp0, sp0 + ndim))
        if pool_type == "max":
            out = jnp.max(data, axis=axes, keepdims=True)
        elif pool_type in ("avg", "sum"):
            out = jnp.sum(data, axis=axes, keepdims=True)
            if pool_type == "avg":
                out = out / np.prod([data.shape[a] for a in axes])
        else:
            raise MXNetError("bad pool_type %r" % pool_type)
        return out
    kernel = as_tuple(kernel, ndim)
    stride = as_tuple(stride, ndim) or (1,) * ndim
    pad = as_tuple(pad, ndim) or (0,) * ndim

    pads = []
    for i in range(ndim):
        lo = hi = pad[i]
        if pooling_convention == "full":
            size = data.shape[sp0 + i] + 2 * pad[i]
            rem = (size - kernel[i]) % stride[i]
            if rem:
                hi += stride[i] - rem
        pads.append((lo, hi))
    if is_cl:
        window = (1,) + kernel + (1,)
        strides = (1,) + stride + (1,)
        padding = [(0, 0)] + pads + [(0, 0)]
    else:
        window = (1, 1) + kernel
        strides = (1, 1) + stride
        padding = [(0, 0), (0, 0)] + pads
    if pool_type == "max":
        init = -jnp.inf if jnp.issubdtype(data.dtype, jnp.floating) else jnp.iinfo(data.dtype).min
        return jax.lax.reduce_window(data, init, jax.lax.max, window, strides, padding)
    if pool_type in ("avg", "sum"):
        out = jax.lax.reduce_window(data, 0.0, jax.lax.add, window, strides, padding)
        if pool_type == "avg":
            # reference avg pooling counts padded cells in the divisor only
            # when pad>0 was explicit; MXNet divides by full kernel size.
            out = out / np.prod(kernel)
        return out
    raise MXNetError("bad pool_type %r" % pool_type)


# ---------------------------------------------------------------------------
# Activations
# ---------------------------------------------------------------------------

@register("Activation", defaults={"act_type": "relu"})
def activation(data, act_type="relu"):
    """(reference activation-inl.h; act types relu/sigmoid/tanh/softrelu/softsign)"""
    if act_type == "relu":
        return jnp.maximum(data, 0)
    if act_type == "sigmoid":
        return jax.nn.sigmoid(data)
    if act_type == "tanh":
        return jnp.tanh(data)
    if act_type == "softrelu":
        return jax.nn.softplus(data)
    if act_type == "softsign":
        return data / (1 + jnp.abs(data))
    raise MXNetError("unknown act_type %r" % act_type)


@register("LeakyReLU", nin=2, arg_names=["data", "gamma"],
          defaults={"act_type": "leaky", "slope": 0.25, "lower_bound": 0.125,
                    "upper_bound": 0.334})
def leaky_relu(data, gamma=None, act_type="leaky", slope=0.25,
               lower_bound=0.125, upper_bound=0.334, _train=False, _rng=None):
    """(reference leaky_relu-inl.h: leaky/prelu/elu/rrelu)"""
    if act_type == "leaky":
        return jnp.where(data >= 0, data, slope * data)
    if act_type == "elu":
        return jnp.where(data >= 0, data, slope * jnp.expm1(data))
    if act_type == "prelu":
        g = gamma.reshape((1, -1) + (1,) * (data.ndim - 2)) if gamma.ndim == 1 else gamma
        return jnp.where(data >= 0, data, g * data)
    if act_type == "rrelu":
        if _train and _rng is not None:
            s = jax.random.uniform(_rng, data.shape, dtype=data.dtype,
                                   minval=lower_bound, maxval=upper_bound)
        else:
            s = (lower_bound + upper_bound) / 2.0
        return jnp.where(data >= 0, data, s * data)
    raise MXNetError("unknown act_type %r" % act_type)


# ---------------------------------------------------------------------------
# Normalisation
# ---------------------------------------------------------------------------

@register("BatchNorm", nin=5, jit=True,
          arg_names=["data", "gamma", "beta", "moving_mean", "moving_var"],
          nout=3,
          defaults={"eps": 1e-3, "momentum": 0.9, "fix_gamma": True,
                    "use_global_stats": False, "output_mean_var": False,
                    "axis": 1, "cudnn_off": False})
def batch_norm(data, gamma, beta, moving_mean, moving_var, eps=1e-3,
               momentum=0.9, fix_gamma=True, use_global_stats=False,
               output_mean_var=False, axis=1, cudnn_off=False, _train=False):
    """Batch normalisation (reference batch_norm.cc / cudnn_batch_norm-inl.h).

    Returns (out, mean, var): in training mode mean/var are the batch
    statistics the executor uses to update the moving aux states
    (moving = momentum*moving + (1-momentum)*batch, as the reference kernel
    does in-place); in inference mode they echo the moving stats.
    """
    axis = int(axis) % data.ndim
    red = tuple(i for i in range(data.ndim) if i != axis)
    shape = [1] * data.ndim
    shape[axis] = data.shape[axis]

    g = jnp.ones_like(gamma) if fix_gamma else gamma
    mean, var = _bn_stats(data, moving_mean, moving_var, red, _train,
                          use_global_stats)
    if data.dtype in (jnp.bfloat16, jnp.float16):
        # scale/offset in fp32, one fused multiply-add over the activations
        # in their own dtype (no fp32 upcast of the big tensor).
        inv = jax.lax.rsqrt(var.astype(jnp.float32) + eps)
        s = inv * g.astype(jnp.float32)
        b = beta.astype(jnp.float32) - mean.astype(jnp.float32) * s
        out = data * s.astype(data.dtype).reshape(shape) \
            + b.astype(data.dtype).reshape(shape)
    else:
        inv = jax.lax.rsqrt(var + eps)
        out = (data - mean.reshape(shape)) * (inv * g).reshape(shape) \
            + beta.reshape(shape)
    return out, mean, var


def _bn_stats(data, moving_mean, moving_var, red, _train,
              use_global_stats):
    """Shared BN statistics: batch mean/var in training mode (fp32
    accumulation for half dtypes — the reference's cudnn BN behaviour),
    stop-gradiented moving stats otherwise. One source of truth for
    BatchNorm and the fused _contrib_BatchNormAddReLU.

    Half-precision data gives both moments from ONE read (sum(d), sum(d*d)
    with d = data - c), so they ride in the epilogue of the op that
    produces ``data`` and the derivative is elementwise: a two-pass
    ``jnp.var`` costs a second read forward and, transposed, a reduction
    of a term that is zero backward. E[d^2] - E[d]^2 loses the digits of
    z*z, z = (mean - c) / std: the fp32 sums hold 16 bits more than the
    data, so up to z = 256 that is below the data's own rounding, and
    ``c``, the moving mean, is known before the pass and puts z near 0
    once training runs. Data as wide as its accumulation has no digits to
    spare and keeps the two passes."""
    if _train and not use_global_stats:
        if data.dtype not in (jnp.bfloat16, jnp.float16):
            return (jnp.mean(data, axis=red).astype(moving_mean.dtype),
                    jnp.var(data, axis=red).astype(moving_var.dtype))
        shape = [1 if i in red else n for i, n in enumerate(data.shape)]
        c = jax.lax.stop_gradient(moving_mean).astype(jnp.float32)
        d = data.astype(jnp.float32) - c.reshape(shape)
        m1 = jnp.mean(d, axis=red)
        m2 = jnp.mean(d * d, axis=red)
        mean = (c + m1).astype(moving_mean.dtype)
        var = jnp.maximum(m2 - m1 * m1, 0).astype(moving_var.dtype)
        return mean, var
    return (jax.lax.stop_gradient(moving_mean),
            jax.lax.stop_gradient(moving_var))


def _make_bn_stateful_update(mean_idx, var_idx):
    """Moving-stat update the reference BatchNorm kernel does in place;
    parameterized by the aux-input positions (BN: 3/4, fused: 4/5)."""

    def update(raw_inputs, raw_outputs, params):
        if not params.get("_train") or params.get("use_global_stats"):
            return {}
        momentum = params.get("momentum", 0.9)
        _, mean, var = raw_outputs[:3]
        new_mean = momentum * raw_inputs[mean_idx] + (1 - momentum) * mean
        new_var = momentum * raw_inputs[var_idx] + (1 - momentum) * var
        return {mean_idx: new_mean, var_idx: new_var}

    return update


_bn_stateful_update = _make_bn_stateful_update(3, 4)


def _make_bn_param_dtypes(first_param_idx):
    """gamma/beta/moving stats stay fp32 under bf16/fp16 data (reference
    cudnn_batch_norm-inl.h keeps scale/bias/saved stats in fp32)."""
    idxs = tuple(range(first_param_idx, first_param_idx + 4))

    def infer(in_types, params):
        return {i: np.float32 for i in idxs}

    return infer


_bn_param_dtypes = _make_bn_param_dtypes(1)


_bn = get_op("BatchNorm")
_bn.visible_outputs = 1
_bn.aux_inputs = (3, 4)
_bn.stateful_update = _bn_stateful_update


@register("_contrib_BatchNormAddReLU", nin=6, jit=True,
          arg_names=["data", "addend", "gamma", "beta", "moving_mean",
                     "moving_var"],
          nout=3,
          defaults={"eps": 1e-3, "momentum": 0.9, "fix_gamma": True,
                    "use_global_stats": False, "axis": 1,
                    "cudnn_off": False})
def batch_norm_add_relu(data, addend, gamma, beta, moving_mean, moving_var,
                        eps=1e-3, momentum=0.9, fix_gamma=True,
                        use_global_stats=False, axis=1, cudnn_off=False,
                        _train=False):
    """Fused BN + residual-add + ReLU — the ResNet block tail as one op
    (contrib extension; the reference's cudnn era added the equivalent
    BNAddRelu fusion for the same reason). Statistics follow BatchNorm
    exactly; the apply+add+relu runs as ONE device pass (Pallas kernel
    mxnet_tpu/pallas/fused_bn.py) when the channel axis is last — the
    MXU-native layout — and as the composed XLA chain otherwise.

    Returns (out, mean, var) with the same aux/moving-stat contract as
    BatchNorm (the executor updates moving stats from outputs 1/2).
    """
    axis = int(axis) % data.ndim
    red = tuple(i for i in range(data.ndim) if i != axis)
    shape = [1] * data.ndim
    shape[axis] = data.shape[axis]

    g = jnp.ones_like(gamma) if fix_gamma else gamma
    mean, var = _bn_stats(data, moving_mean, moving_var, red, _train,
                          use_global_stats)
    # folded apply coefficients, fp32 (same folding as batch_norm above)
    inv = jax.lax.rsqrt(var.astype(jnp.float32) + eps)
    s = inv * g.astype(jnp.float32)
    b = beta.astype(jnp.float32) - mean.astype(jnp.float32) * s
    if axis == data.ndim - 1:
        from ..pallas.fused_bn import scale_bias_add_relu
        out = scale_bias_add_relu(data, s, b, addend)
    else:
        out = jnp.maximum(
            data * s.astype(data.dtype).reshape(shape)
            + b.astype(data.dtype).reshape(shape) + addend,
            jnp.zeros((), data.dtype))
    return out, mean, var


_bnar = get_op("_contrib_BatchNormAddReLU")
_bnar.visible_outputs = 1
_bnar.aux_inputs = (4, 5)
_bnar.stateful_update = _make_bn_stateful_update(4, 5)
_bnar.param_dtype_infer = _make_bn_param_dtypes(2)
_bn.param_dtype_infer = _bn_param_dtypes


@register("LRN", defaults={"alpha": 1e-4, "beta": 0.75, "knorm": 2.0, "nsize": 5})
def lrn(data, alpha=1e-4, beta=0.75, knorm=2.0, nsize=5):
    """Local response norm across channels (reference lrn-inl.h)."""
    nsize = int(nsize)
    sq = jnp.square(data)
    # sum over a window of nsize channels centred at each channel
    pad = nsize // 2
    sq_p = jnp.pad(sq, [(0, 0), (pad, pad)] + [(0, 0)] * (data.ndim - 2))
    win = sum(sq_p[:, i:i + data.shape[1]] for i in range(nsize))
    return data * jnp.power(knorm + alpha * win / nsize, -beta)


@register("L2Normalization", defaults={"eps": 1e-10, "mode": "instance"})
def l2_normalization(data, eps=1e-10, mode="instance"):
    """(reference l2_normalization-inl.h; modes instance/channel/spatial)"""
    if mode == "instance":
        axes = tuple(range(1, data.ndim))
    elif mode == "channel":
        axes = (1,)
    elif mode == "spatial":
        axes = tuple(range(2, data.ndim))
    else:
        raise MXNetError("unknown mode %r" % mode)
    norm = jnp.sqrt(jnp.sum(jnp.square(data), axis=axes, keepdims=True) + eps)
    return data / norm


@register("InstanceNorm", nin=3, arg_names=["data", "gamma", "beta"],
          defaults={"eps": 1e-3})
def instance_norm(data, gamma, beta, eps=1e-3):
    axes = tuple(range(2, data.ndim))
    mean = jnp.mean(data, axis=axes, keepdims=True)
    var = jnp.var(data, axis=axes, keepdims=True)
    shape = (1, -1) + (1,) * (data.ndim - 2)
    return (data - mean) * jax.lax.rsqrt(var + eps) * gamma.reshape(shape) \
        + beta.reshape(shape)


@register("LayerNorm", nin=3, arg_names=["data", "gamma", "beta"],
          defaults={"axis": -1, "eps": 1e-5, "output_mean_var": False})
def layer_norm(data, gamma, beta, axis=-1, eps=1e-5, output_mean_var=False):
    """Layer normalisation (new-framework addition; needed for attention)."""
    axis = int(axis) % data.ndim
    mean = jnp.mean(data, axis=axis, keepdims=True)
    var = jnp.var(data, axis=axis, keepdims=True)
    out = (data - mean) * jax.lax.rsqrt(var + eps)
    shape = [1] * data.ndim
    shape[axis] = data.shape[axis]
    return out * gamma.reshape(shape) + beta.reshape(shape)


# ---------------------------------------------------------------------------
# Dropout
# ---------------------------------------------------------------------------

@register("Dropout", defaults={"p": 0.5, "mode": "training", "axes": ()})
def dropout(data, p=0.5, mode="training", axes=(), _train=False, _rng=None):
    """(reference dropout-inl.h). Scales by 1/(1-p) at train time."""
    if (not _train and mode != "always") or p <= 0 or _rng is None:
        return data
    shape = data.shape
    axes = as_tuple(axes) or ()
    if axes:
        shape = tuple(1 if i in axes else s for i, s in enumerate(shape))
    keep = jax.random.bernoulli(_rng, 1.0 - p, shape)
    return jnp.where(keep, data / (1.0 - p), jnp.zeros_like(data))


# ---------------------------------------------------------------------------
# Softmax family
# ---------------------------------------------------------------------------

@register("softmax", defaults={"axis": -1, "temperature": None})
def softmax(data, axis=-1, temperature=None):
    """(reference src/operator/nn/softmax-inl.h)"""
    if temperature is not None and temperature != 1.0:
        data = data / temperature
    return jax.nn.softmax(data, axis=int(axis))


@register("log_softmax", defaults={"axis": -1, "temperature": None})
def log_softmax(data, axis=-1, temperature=None):
    if temperature is not None and temperature != 1.0:
        data = data / temperature
    return jax.nn.log_softmax(data, axis=int(axis))


@register("SoftmaxActivation", defaults={"mode": "instance"})
def softmax_activation(data, mode="instance"):
    if mode == "channel":
        return jax.nn.softmax(data, axis=1)
    return jax.nn.softmax(data.reshape(data.shape[0], -1), axis=-1).reshape(data.shape)


@register("softmax_cross_entropy", nin=2, arg_names=["data", "label"])
def softmax_cross_entropy(data, label):
    """(reference src/operator/loss_binary_op.cc): scalar summed CE."""
    logp = jax.nn.log_softmax(data, axis=-1)
    picked = jnp.take_along_axis(logp, label.astype(jnp.int32)[:, None], axis=-1)
    return -jnp.sum(picked).reshape((1,))


def _softmax_out_grad(prob, label, grad_scale, ignore_label, use_ignore,
                      normalization, multi_output):
    """Shared SoftmaxOutput backward: prob - one_hot(label)."""
    if multi_output:
        # prob: (n, k, d1...), label: (n, d1...)
        oh = jax.nn.one_hot(label.astype(jnp.int32), prob.shape[1],
                            dtype=prob.dtype, axis=1)
    else:
        oh = jax.nn.one_hot(label.astype(jnp.int32), prob.shape[-1],
                            dtype=prob.dtype)
    grad = prob - oh
    valid = None
    if use_ignore:
        mask = (label.astype(jnp.int32) != int(ignore_label))
        if multi_output:
            grad = grad * mask[:, None].astype(prob.dtype)
        else:
            grad = grad * mask.reshape(mask.shape + (1,) * (grad.ndim - mask.ndim)).astype(prob.dtype)
        valid = jnp.maximum(jnp.sum(mask.astype(prob.dtype)), 1.0)
    if normalization == "valid" and valid is not None:
        grad = grad / valid
    elif normalization == "batch":
        grad = grad / prob.shape[0]
    return grad * grad_scale


@register("SoftmaxOutput", nin=2, arg_names=["data", "label"],
          defaults={"grad_scale": 1.0, "ignore_label": -1.0, "multi_output": False,
                    "use_ignore": False, "preserve_shape": False,
                    "normalization": "null", "out_grad": False,
                    "smooth_alpha": 0.0},
          aliases=("Softmax",))
def softmax_output(data, label, grad_scale=1.0, ignore_label=-1.0,
                   multi_output=False, use_ignore=False, preserve_shape=False,
                   normalization="null", out_grad=False, smooth_alpha=0.0):
    """Softmax with implicit cross-entropy gradient
    (reference softmax_output-inl.h). Forward = softmax(data); backward
    ignores the incoming head gradient (it is a loss layer) and emits
    (p - onehot(label)) * grad_scale, exactly as the reference kernel.
    Implemented with jax.custom_vjp since the gradient is not the vjp of
    the forward function.
    """
    axis = 1 if multi_output else -1
    # the reference's InferShape rejects a label that is not data minus
    # the class axis; without this check a bad label broadcasts into a
    # wrong-shaped cotangent and dies as a bare assertion inside vjp
    expected = ((data.shape[0],) + tuple(data.shape[2:]) if multi_output
                else tuple(data.shape[:-1]))
    if tuple(label.shape) != expected:
        flat = (data.shape[0],
                int(np.prod(data.shape[2:])) if data.ndim > 2 else 1)
        if multi_output and tuple(label.shape) == flat:
            # the reference's InferShape actually assigns the label the
            # FLATTENED Shape2(n, prod(rest)) form — accept and reshape
            label = label.reshape(expected)
        else:
            raise MXNetError(
                "SoftmaxOutput: label shape %s is inconsistent with data "
                "shape %s (expected label %s)"
                % (tuple(label.shape), tuple(data.shape), expected))

    @jax.custom_vjp
    def _fwd(d, l):
        return jax.nn.softmax(d, axis=axis)

    def _fwd_fwd(d, l):
        p = jax.nn.softmax(d, axis=axis)
        return p, (p, l)

    def _fwd_bwd(res, g):
        p, l = res
        grad = _softmax_out_grad(p, l, grad_scale, ignore_label, use_ignore,
                                 normalization, multi_output)
        return grad.astype(p.dtype), jnp.zeros_like(l)

    _fwd.defvjp(_fwd_fwd, _fwd_bwd)
    return _fwd(data, label)


def _regression_output(transform, grad_fn):
    def op(data, label, grad_scale=1.0):
        @jax.custom_vjp
        def _fwd(d, l):
            return transform(d)

        def _fwd_fwd(d, l):
            out = transform(d)
            return out, (out, l)

        def _fwd_bwd(res, g):
            out, l = res
            grad = grad_fn(out, l.reshape(out.shape)) * grad_scale
            return grad.astype(out.dtype), jnp.zeros_like(l)

        _fwd.defvjp(_fwd_fwd, _fwd_bwd)
        return _fwd(data, label)
    return op


register("LinearRegressionOutput", nin=2, arg_names=["data", "label"],
         defaults={"grad_scale": 1.0})(
    _regression_output(lambda d: d, lambda o, l: o - l))
register("MAERegressionOutput", nin=2, arg_names=["data", "label"],
         defaults={"grad_scale": 1.0})(
    _regression_output(lambda d: d, lambda o, l: jnp.sign(o - l)))
register("LogisticRegressionOutput", nin=2, arg_names=["data", "label"],
         defaults={"grad_scale": 1.0})(
    _regression_output(jax.nn.sigmoid, lambda o, l: o - l))


@register("MakeLoss", defaults={"grad_scale": 1.0, "valid_thresh": 0.0,
                                "normalization": "null"})
def make_loss(data, grad_scale=1.0, valid_thresh=0.0, normalization="null"):
    """(reference make_loss-inl.h): forward identity, backward = grad_scale."""
    @jax.custom_vjp
    def _fwd(d):
        return d

    def _fwd_fwd(d):
        return d, d

    def _fwd_bwd(d, g):
        scale = grad_scale
        if normalization == "batch":
            scale = scale / d.shape[0]
        elif normalization == "valid":
            valid = jnp.maximum(jnp.sum((d > valid_thresh).astype(d.dtype)), 1.0)
            return ((jnp.ones_like(d) * scale) / valid,)
        return (jnp.full_like(d, scale),)

    _fwd.defvjp(_fwd_fwd, _fwd_bwd)
    return _fwd(data)


@register("SVMOutput", nin=2, arg_names=["data", "label"],
          defaults={"margin": 1.0, "regularization_coefficient": 1.0,
                    "use_linear": False})
def svm_output(data, label, margin=1.0, regularization_coefficient=1.0,
               use_linear=False):
    """(reference svm_output-inl.h). Forward identity; backward hinge-loss grad."""
    @jax.custom_vjp
    def _fwd(d, l):
        return d

    def _fwd_fwd(d, l):
        return d, (d, l)

    def _fwd_bwd(res, g):
        d, l = res
        oh = jax.nn.one_hot(l.astype(jnp.int32), d.shape[-1], dtype=d.dtype)
        score_y = jnp.sum(d * oh, axis=-1, keepdims=True)
        if use_linear:
            viol = ((d - score_y + margin) > 0).astype(d.dtype) * (1 - oh)
            grad = viol - oh * jnp.sum(viol, axis=-1, keepdims=True)
        else:
            dist = jnp.maximum(d - score_y + margin, 0) * (1 - oh)
            grad = 2 * dist - oh * jnp.sum(2 * dist, axis=-1, keepdims=True)
        return (grad * regularization_coefficient).astype(d.dtype), jnp.zeros_like(l)

    _fwd.defvjp(_fwd_fwd, _fwd_bwd)
    return _fwd(data, label)


# ---------------------------------------------------------------------------
# UpSampling
# ---------------------------------------------------------------------------

@register("UpSampling", nin=-1,
          defaults={"scale": 1, "sample_type": "nearest", "num_filter": 0,
                    "multi_input_mode": "concat", "num_args": 1, "workspace": 512})
def upsampling(*args, scale=1, sample_type="nearest", num_filter=0,
               multi_input_mode="concat", num_args=1, workspace=512):
    """(reference upsampling-inl.h). nearest mode; bilinear mode uses the
    deconvolution path like the reference."""
    scale = int(scale)
    if sample_type == "nearest":
        outs = []
        for d in args:
            o = jnp.repeat(jnp.repeat(d, scale, axis=2), scale, axis=3)
            outs.append(o)
        if len(outs) == 1:
            return outs[0]
        h = max(o.shape[2] for o in outs)
        outs = [o if o.shape[2] == h else
                jnp.repeat(jnp.repeat(o, h // o.shape[2], axis=2),
                           h // o.shape[3], axis=3) for o in outs]
        if multi_input_mode == "sum":
            return sum(outs)
        return jnp.concatenate(outs, axis=1)
    if sample_type == "bilinear":
        data, weight = args
        kernel = 2 * scale - scale % 2
        pad = int(np.ceil((scale - 1) / 2.0))
        return _conv_nd(data, weight, None,
                        (kernel, kernel), (scale, scale), None, (pad, pad),
                        num_group=data.shape[1], no_bias=True, transposed=True)
    raise MXNetError("unknown sample_type %r" % sample_type)


# ---------------------------------------------------------------------------
# Sequence ops (reference src/operator/sequence_*.cc)
# ---------------------------------------------------------------------------

@register("SequenceLast", nin=2, arg_names=["data", "sequence_length"],
          defaults={"use_sequence_length": False, "axis": 0})
def sequence_last(data, sequence_length=None, use_sequence_length=False, axis=0):
    axis = int(axis)
    if not use_sequence_length or sequence_length is None:
        return jnp.take(data, data.shape[axis] - 1, axis=axis)
    idx = (sequence_length.astype(jnp.int32) - 1)
    batch = jnp.arange(data.shape[1 - axis])
    if axis == 0:
        return data[idx, batch]
    return data[batch, idx]


@register("SequenceMask", nin=2, arg_names=["data", "sequence_length"],
          defaults={"use_sequence_length": False, "value": 0.0, "axis": 0})
def sequence_mask(data, sequence_length=None, use_sequence_length=False,
                  value=0.0, axis=0):
    if not use_sequence_length or sequence_length is None:
        return data
    axis = int(axis)
    T = data.shape[axis]
    steps = jnp.arange(T)
    mask = steps[:, None] < sequence_length.astype(jnp.int32)[None, :]  # (T, B)
    if axis == 1:
        mask = mask.T
    mask = mask.reshape(mask.shape + (1,) * (data.ndim - 2))
    return jnp.where(mask, data, jnp.asarray(value, data.dtype))


@register("SequenceReverse", nin=2, arg_names=["data", "sequence_length"],
          defaults={"use_sequence_length": False, "axis": 0})
def sequence_reverse(data, sequence_length=None, use_sequence_length=False, axis=0):
    if not use_sequence_length or sequence_length is None:
        return jnp.flip(data, axis=0)
    T = data.shape[0]
    steps = jnp.arange(T)[:, None]
    lens = sequence_length.astype(jnp.int32)[None, :]
    src = jnp.where(steps < lens, lens - 1 - steps, steps)  # (T, B)
    return jnp.take_along_axis(
        data, src.reshape(src.shape + (1,) * (data.ndim - 2)), axis=0)
