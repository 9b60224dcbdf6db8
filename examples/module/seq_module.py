"""Module-API tour (mirrors reference example/module/ —
sequential_module.py, python_loss.py and mnist_mlp.py in one tree).

Three stages, each exercising a container no other example touches:

1. ``SequentialModule`` chaining two independently-built ``Module``s
   with ``auto_wiring`` (module 2's data is module 1's output) and
   ``take_labels`` (the label flows to the last module only).
2. ``PythonLossModule`` as the chain's head: the multiclass hinge
   gradient is computed in numpy on the host (the reference used
   numba; plain numpy keeps it dependency-free) and injected into the
   backward pass — the loss itself never exists as a graph node.
3. The intermediate-level API on a plain ``Module``
   (bind/init_params/forward/backward/update by hand) plus the
   prediction surface: ``iter_predict``, ``predict`` with and without
   ``merge_batches``, and ``score``.

Synthetic separable digits (10 Gaussian prototypes) stand in for
MNIST so the tree is egress-free.
"""
import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

import numpy as np

import mxnet_tpu as mx


def make_data(rs, n, protos):
    y = rs.randint(0, 10, n).astype(np.float32)
    x = protos[y.astype(int)] + 0.25 * rs.normal(size=(n, protos.shape[1])
                                                 ).astype(np.float32)
    return x, y


def mc_hinge_grad(scores, labels):
    """Multiclass hinge gradient, computed on the host in numpy."""
    scores = scores.asnumpy()
    labels = labels.asnumpy().astype(int)
    n, _ = scores.shape
    grad = np.zeros_like(scores)
    for i in range(n):
        margin = 1.0 + scores[i] - scores[i, labels[i]]
        margin[labels[i]] = 0.0
        pred = int(margin.argmax())
        if margin[pred] > 0:
            grad[i, labels[i]] -= 1.0
            grad[i, pred] += 1.0
    return grad / n


def feature_module():
    data = mx.sym.Variable("data")
    fc1 = mx.sym.FullyConnected(data, name="fc1", num_hidden=128)
    act1 = mx.sym.Activation(fc1, name="relu1", act_type="relu")
    return mx.mod.Module(act1, label_names=[], context=mx.current_context())


def head_module():
    data = mx.sym.Variable("data")
    fc2 = mx.sym.FullyConnected(data, name="fc2", num_hidden=64)
    act2 = mx.sym.Activation(fc2, name="relu2", act_type="relu")
    fc3 = mx.sym.FullyConnected(act2, name="fc3", num_hidden=10)
    sm = mx.sym.SoftmaxOutput(fc3, name="softmax")
    return mx.mod.Module(sm, context=mx.current_context())


def scores_module():
    data = mx.sym.Variable("data")
    fc2 = mx.sym.FullyConnected(data, name="fc2b", num_hidden=64)
    act2 = mx.sym.Activation(fc2, name="relu2b", act_type="relu")
    fc3 = mx.sym.FullyConnected(act2, name="fc3b", num_hidden=10)
    return mx.mod.Module(fc3, label_names=[], context=mx.current_context())


def run_sequential(args, train_it, val_it):
    mod_seq = mx.mod.SequentialModule()
    mod_seq.add(feature_module()) \
           .add(head_module(), take_labels=True, auto_wiring=True)
    # SoftmaxOutput's gradient is the batch's SUM (rescale_grad is 1.0
    # by default here): three layers deep, 0.02 diverged on one seed in
    # five
    mod_seq.fit(train_it,
                optimizer_params={"learning_rate": 0.005},
                initializer=mx.initializer.Xavier(),
                num_epoch=args.num_epochs)
    metric = mx.metric.Accuracy()
    val_it.reset()
    mod_seq.score(val_it, metric)
    return metric.get()[1]


def run_python_loss(args, train_it, val_it):
    mod = mx.mod.SequentialModule() \
            .add(feature_module()) \
            .add(scores_module(), auto_wiring=True) \
            .add(mx.mod.PythonLossModule(grad_func=mc_hinge_grad),
                 take_labels=True, auto_wiring=True)
    # hinge grads are batch-normalised (unlike SoftmaxOutput's summed
    # grads), so this stage takes a proportionally larger step size
    mod.fit(train_it,
            optimizer_params={"learning_rate": 0.1},
            initializer=mx.initializer.Xavier(),
            num_epoch=args.num_epochs)
    # PythonLossModule's forward is identity, so scoring runs on the
    # raw scores emitted by the trailing FullyConnected.
    correct = total = 0
    val_it.reset()
    for preds, _, batch in mod.iter_predict(val_it):
        pred = preds[0].asnumpy().argmax(axis=1)
        lab = batch.label[0].asnumpy().astype(int)
        correct += int((pred == lab).sum())
        total += len(lab)
    return correct / float(total)


def run_intermediate(args, train_it, val_it):
    data = mx.sym.Variable("data")
    fc1 = mx.sym.FullyConnected(data, name="ifc1", num_hidden=128)
    act1 = mx.sym.Activation(fc1, act_type="relu")
    fc3 = mx.sym.FullyConnected(act1, name="ifc3", num_hidden=10)
    sm = mx.sym.SoftmaxOutput(fc3, name="softmax")

    mod = mx.mod.Module(sm, context=mx.current_context())
    mod.bind(data_shapes=train_it.provide_data,
             label_shapes=train_it.provide_label)
    mod.init_params(initializer=mx.initializer.Xavier())
    mod.init_optimizer(
        optimizer_params={"learning_rate": 0.02})
    metric = mx.metric.Accuracy()
    for _ in range(args.num_epochs):
        train_it.reset()
        metric.reset()
        for batch in train_it:
            mod.forward(batch)
            mod.update_metric(metric, batch.label)
            mod.backward()
            mod.update()

    # prediction-surface tour
    val_it.reset()
    for preds, i_batch, batch in mod.iter_predict(val_it):
        if i_batch == 0:
            assert preds[0].shape[1] == 10
    val_it.reset()
    merged = mod.predict(val_it)
    val_it.reset()
    unmerged = mod.predict(val_it, merge_batches=False)
    assert merged.shape[0] == sum(p[0].shape[0] for p in unmerged)
    val_it.reset()
    metric.reset()
    mod.score(val_it, metric)
    return metric.get()[1]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--num-epochs", type=int, default=6)
    ap.add_argument("--batch-size", type=int, default=64)
    args = ap.parse_args()

    mx.random.seed(5)
    # Xavier and NDArrayIter's shuffle draw from numpy's GLOBAL generator
    np.random.seed(5)
    rs = np.random.RandomState(7)
    protos = rs.normal(0, 1.0, (10, 64)).astype(np.float32)
    xtr, ytr = make_data(rs, 1024, protos)
    xva, yva = make_data(rs, 256, protos)
    train_it = mx.io.NDArrayIter(xtr, ytr, batch_size=args.batch_size,
                                 shuffle=True, label_name="softmax_label")
    val_it = mx.io.NDArrayIter(xva, yva, batch_size=args.batch_size,
                               label_name="softmax_label")

    acc_seq = run_sequential(args, train_it, val_it)
    train_it.reset()
    acc_hinge = run_python_loss(args, train_it, val_it)
    train_it.reset()
    acc_mid = run_intermediate(args, train_it, val_it)

    print("sequential acc %.3f" % acc_seq)
    print("python-loss acc %.3f" % acc_hinge)
    print("intermediate acc %.3f" % acc_mid)
    # the hinge stage updates only the worst-violating class per sample,
    # so it converges slower than the softmax heads
    assert acc_seq > 0.85 and acc_hinge > 0.65 and acc_mid > 0.85
    print("module tour ok")


if __name__ == "__main__":
    main()
