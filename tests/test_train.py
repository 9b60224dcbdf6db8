"""End-to-end convergence tests — parity with reference tests/python/train/
(test_mlp.py / test_conv.py): train small nets to a threshold accuracy."""
import numpy as np

import mxnet_tpu as mx


def _synthetic_classification(n=512, dim=16, classes=4, seed=7):
    rng = np.random.RandomState(seed)
    centers = rng.uniform(-3, 3, size=(classes, dim)).astype(np.float32)
    labels = rng.randint(0, classes, size=n)
    x = centers[labels] + rng.normal(scale=0.5, size=(n, dim)).astype(np.float32)
    return x.astype(np.float32), labels.astype(np.float32)


def test_mlp_module_fit_converges():
    x, y = _synthetic_classification()
    train = mx.io.NDArrayIter(x, y, batch_size=64, shuffle=True)
    val = mx.io.NDArrayIter(x, y, batch_size=64)

    data = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(data=data, num_hidden=32, name="fc1")
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.FullyConnected(net, num_hidden=4, name="fc2")
    net = mx.sym.SoftmaxOutput(net, name="softmax")

    mod = mx.mod.Module(net, data_names=["data"], label_names=["softmax_label"],
                        context=mx.current_context())
    mod.fit(train, eval_data=val, optimizer="sgd",
            optimizer_params={"learning_rate": 0.5, "momentum": 0.9,
                              "rescale_grad": 1.0 / 64},
            num_epoch=8, eval_metric="acc")
    val.reset()
    score = mod.score(val, mx.metric.Accuracy())
    acc = dict(score)["accuracy"]
    assert acc > 0.95, "MLP failed to converge: acc=%f" % acc


def test_lenet_style_conv_converges():
    rng = np.random.RandomState(3)
    n = 256
    # images of vertical vs horizontal bars
    x = np.zeros((n, 1, 8, 8), dtype=np.float32)
    y = rng.randint(0, 2, size=n)
    for i in range(n):
        pos = rng.randint(0, 8)
        if y[i] == 0:
            x[i, 0, :, pos] = 1.0
        else:
            x[i, 0, pos, :] = 1.0
    train = mx.io.NDArrayIter(x, y.astype(np.float32), batch_size=32,
                              shuffle=True)

    data = mx.sym.Variable("data")
    net = mx.sym.Convolution(data=data, num_filter=8, kernel=(3, 3),
                             pad=(1, 1), name="conv1")
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.Pooling(net, kernel=(2, 2), stride=(2, 2), pool_type="max")
    net = mx.sym.Flatten(net)
    net = mx.sym.FullyConnected(net, num_hidden=2, name="fc")
    net = mx.sym.SoftmaxOutput(net, name="softmax")

    mod = mx.mod.Module(net, context=mx.current_context())
    mod.fit(train, optimizer="adam",
            optimizer_params={"learning_rate": 0.01,
                              "rescale_grad": 1.0 / 32},
            num_epoch=6, eval_metric="acc")
    train.reset()
    acc = dict(mod.score(train, mx.metric.Accuracy()))["accuracy"]
    assert acc > 0.95, "conv net failed to converge: acc=%f" % acc


def test_gluon_training_converges():
    from mxnet_tpu import gluon
    from mxnet_tpu.gluon import nn
    x, y = _synthetic_classification(n=256, dim=8, classes=3, seed=11)
    net = nn.HybridSequential()
    with net.name_scope():
        net.add(nn.Dense(32, activation="relu"))
        net.add(nn.Dense(3))
    net.initialize(mx.initializer.Xavier())
    net.hybridize()
    trainer = gluon.Trainer(net.collect_params(), "adam",
                            {"learning_rate": 0.01})
    l2 = gluon.loss.SoftmaxCrossEntropyLoss()
    xs, ys = mx.nd.array(x), mx.nd.array(y)
    for _ in range(60):
        with mx.autograd.record():
            loss = l2(net(xs), ys).mean()
        loss.backward()
        trainer.step(x.shape[0])
    pred = net(xs).asnumpy().argmax(axis=1)
    acc = (pred == y).mean()
    assert acc > 0.95, "gluon training failed to converge: acc=%f" % acc


def test_cifar_shape_conv_bf16_converges():
    """The reference-scale dtype workload (tests/python/train/
    test_dtype.py run_cifar10 shape: conv+BN stack on 3x32x32, low-
    precision data iterator): bf16 activations with fp32 master weights
    (multi_precision) and fp32 BN params via the InferType pass — the
    exact numeric regime the ``resnet50.fit`` cell's measurement relies
    on.
    Must clear an accuracy threshold far above the reference's 0.08."""
    import jax.numpy as jnp
    rng = np.random.RandomState(5)
    n, classes = 384, 4
    # separable color-geometry task: class = which quadrant carries the
    # dominant channel energy
    x = rng.uniform(0, 0.3, size=(n, 3, 32, 32)).astype(np.float32)
    y = rng.randint(0, classes, size=n)
    for i in range(n):
        q = y[i]
        r0, c0 = (q // 2) * 16, (q % 2) * 16
        x[i, :, r0:r0 + 16, c0:c0 + 16] += 0.7
    bf16 = np.dtype(jnp.bfloat16)

    train = mx.io.NDArrayIter(x.astype(bf16), y.astype(np.float32),
                              batch_size=32, shuffle=True)

    data = mx.sym.Variable("data")
    net = mx.sym.Convolution(data=data, num_filter=8, kernel=(3, 3),
                             pad=(1, 1), name="conv1")
    net = mx.sym.BatchNorm(net, fix_gamma=False, name="bn1")
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.Pooling(net, kernel=(2, 2), stride=(2, 2),
                         pool_type="max")
    net = mx.sym.Convolution(net, num_filter=16, kernel=(3, 3),
                             pad=(1, 1), name="conv2")
    net = mx.sym.BatchNorm(net, fix_gamma=False, name="bn2")
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.Pooling(net, kernel=(2, 2), stride=(2, 2),
                         pool_type="max")
    net = mx.sym.Flatten(net)
    net = mx.sym.FullyConnected(net, num_hidden=classes, name="fc")
    net = mx.sym.SoftmaxOutput(net, name="softmax")

    mod = mx.mod.Module(net, context=mx.current_context())
    mod.fit(train, optimizer="sgd",
            optimizer_params={"learning_rate": 0.1, "momentum": 0.9,
                              "rescale_grad": 1.0 / 32,
                              "multi_precision": True},
            num_epoch=6, eval_metric="acc")
    # executor ran bf16 end to end (InferType pinned the data path)
    assert mod._exec.arg_dict["data"].dtype == bf16
    assert mod._exec.arg_dict["conv1_weight"].dtype == bf16
    # fp32 master weights exist in the optimizer (mp_sgd scheme):
    # multi-precision states are (state, fp32 master) tuples
    updater = mod._updater
    if updater is None and mod._kvstore is not None:
        updater = mod._kvstore._updater
    states = getattr(updater, "states", {})
    assert any(
        isinstance(st, tuple) and len(st) == 2
        and getattr(st[1], "dtype", None) == np.float32
        for st in states.values()), \
        "no fp32 master weights found (multi_precision was a no-op)"
    train.reset()
    acc = dict(mod.score(train, mx.metric.Accuracy()))["accuracy"]
    assert acc > 0.9, "bf16 conv net failed to converge: acc=%f" % acc
