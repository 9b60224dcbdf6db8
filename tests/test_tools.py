"""tools/ suite — im2rec packing, parse_log, launch.py multi-process SPMD
(parity model: the reference exercised tools/launch.py --launcher local in
tests/nightly/dist_sync_kvstore.py)."""
import os
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.join(os.path.dirname(__file__), "..")
TOOLS = os.path.join(REPO, "tools")


def _run(cmd, **kw):
    env = dict(kw.pop("env", None) or os.environ)
    env["MXNET_TPU_FORCE_CPU"] = "1"
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    kw.setdefault("timeout", 300)
    return subprocess.run([sys.executable] + cmd, capture_output=True,
                          text=True, env=env, **kw)


def test_im2rec_roundtrip(tmp_path):
    PIL = pytest.importorskip("PIL.Image")
    for cls in ("a", "b"):
        os.makedirs(tmp_path / cls)
        for i in range(2):
            arr = (np.random.rand(16, 16, 3) * 255).astype(np.uint8)
            PIL.fromarray(arr).save(str(tmp_path / cls / ("%d.jpg" % i)))
    prefix = str(tmp_path / "data")
    p = _run([os.path.join(TOOLS, "im2rec.py"), prefix, str(tmp_path),
              "--list", "--recursive"])
    assert p.returncode == 0, p.stderr
    p = _run([os.path.join(TOOLS, "im2rec.py"), prefix, str(tmp_path)])
    assert p.returncode == 0, p.stderr
    assert os.path.exists(prefix + ".rec") and os.path.exists(prefix + ".idx")

    from mxnet_tpu import recordio
    rec = recordio.MXIndexedRecordIO(prefix + ".idx", prefix + ".rec", "r")
    header, img = recordio.unpack(rec.read_idx(0))
    assert len(img) > 0
    assert header.label in (0.0, 1.0)


def test_parse_log():
    log = ("INFO:root:Epoch[0] Batch [20]\tSpeed: 100.5 samples/sec\t"
           "accuracy=0.5\n"
           "INFO:root:Epoch[0] Train-accuracy=0.9\n"
           "INFO:root:Epoch[0] Validation-accuracy=0.8\n")
    p = subprocess.run([sys.executable, os.path.join(TOOLS, "parse_log.py"),
                        "-", "--format", "tsv"], input=log,
                       capture_output=True, text=True, timeout=60)
    assert p.returncode == 0, p.stderr
    lines = p.stdout.strip().splitlines()
    assert lines[0].split("\t") == ["epoch", "speed", "train-accuracy",
                                    "validation-accuracy"]
    assert lines[1].split("\t") == ["0", "100.5", "0.9", "0.8"]


def test_launch_local_two_process_spmd(tmp_path):
    """launch.py forks 2 workers that form one jax.distributed job and
    run a cross-process allgather (the dist_sync smoke)."""
    script = tmp_path / "worker.py"
    script.write_text(
        "import sys; sys.path.insert(0, %r)\n" % REPO +
        "import mxnet_tpu as mx\n"
        "import jax, jax.numpy as jnp\n"
        "from jax.experimental import multihost_utils\n"
        "assert jax.process_count() == 2\n"
        "kv = mx.kv.create('dist_sync')\n"
        "v = multihost_utils.process_allgather("
        "jnp.array([float(kv.rank + 1)]))\n"
        "assert float(v.sum()) == 3.0\n"
        "print('OK rank', kv.rank)\n")
    p = _run([os.path.join(TOOLS, "launch.py"), "-n", "2",
              "--force-cpu", "--port", "9411",
              sys.executable, str(script)])
    assert p.returncode == 0, p.stderr
    assert p.stdout.count("OK rank") == 2


def test_launch_local_dist_kvstore_push_pull(tmp_path):
    """2-process dist_sync kvstore: batched dense push reduces on device
    across processes; row_sparse keeps the union of pushed rows even when
    the global sum of a row is zero (reference dist-server semantics,
    kvstore_dist_server.h:261-312)."""
    script = tmp_path / "worker_kv.py"
    script.write_text(
        "import sys; sys.path.insert(0, %r)\n" % REPO +
        "import numpy as np\n"
        "import mxnet_tpu as mx\n"
        "from mxnet_tpu import nd\n"
        "from mxnet_tpu.ndarray import sparse as sp\n"
        "import jax\n"
        "assert jax.process_count() == 2\n"
        "kv = mx.kv.create('dist_sync')\n"
        "r = kv.rank\n"
        "kv.init(['a', 'b'], [nd.zeros((2, 3)), nd.zeros((4,))])\n"
        "# batched push of two keys at once -> one jitted collective\n"
        "kv.push(['a', 'b'], [nd.ones((2, 3)) * (r + 1),\n"
        "                     nd.ones((4,)) * (10 * (r + 1))])\n"
        "oa, ob = nd.zeros((2, 3)), nd.zeros((4,))\n"
        "kv.pull(['a', 'b'], out=[oa, ob])\n"
        "np.testing.assert_allclose(oa.asnumpy(), np.full((2, 3), 3.0))\n"
        "np.testing.assert_allclose(ob.asnumpy(), np.full((4,), 30.0))\n"
        "# row_sparse: rank0 pushes +1 on row 1, rank1 pushes -1 on row 1\n"
        "# (sum 0) and +2 on row 3; union must keep BOTH rows 1 and 3\n"
        "val = np.array([[1.0, 1.0]]) if r == 0 else np.array([[-1.0, -1.0]])\n"
        "rows = [1] if r == 0 else [1, 3]\n"
        "if r == 1:\n"
        "    val = np.array([[-1.0, -1.0], [2.0, 2.0]])\n"
        "g = sp.row_sparse_array((val.astype(np.float32), rows), shape=(5, 2))\n"
        "kv.init('c', sp.zeros('row_sparse', (5, 2)))\n"
        "kv.push('c', g)\n"
        "got = kv._store['c']\n"
        "assert sorted(np.asarray(got._rsp_indices).tolist()) == [1, 3], \\\n"
        "    np.asarray(got._rsp_indices)\n"
        "dense = got.tostype('default').asnumpy()\n"
        "np.testing.assert_allclose(dense[3], [2.0, 2.0])\n"
        "np.testing.assert_allclose(dense[1], [0.0, 0.0])\n"
        "print('KV OK rank', r)\n")
    p = _run([os.path.join(TOOLS, "launch.py"), "-n", "2",
              "--force-cpu", "--port", "9413",
              sys.executable, str(script)])
    assert p.returncode == 0, p.stderr + p.stdout
    assert p.stdout.count("KV OK rank") == 2


def test_bandwidth_probe():
    p = _run([os.path.join(TOOLS, "bandwidth", "measure.py"),
              "--force-cpu", "--size-mb", "1", "--rounds", "2"])
    assert p.returncode == 0, p.stderr
    assert "GB/s" in p.stdout


def test_recordio_multilabel_pack_roundtrip():
    from mxnet_tpu import recordio
    header = recordio.IRHeader(0, [1.0, 2.5, -3.0], 7, 0)
    s = recordio.pack(header, b"payload")
    back, payload = recordio.unpack(s)
    assert payload == b"payload"
    np.testing.assert_allclose(np.asarray(back.label), [1.0, 2.5, -3.0])
    assert back.id == 7


def test_im2rec_chunked_pack(tmp_path):
    PIL = pytest.importorskip("PIL.Image")
    for i in range(4):
        arr = (np.random.rand(8, 8, 3) * 255).astype(np.uint8)
        PIL.fromarray(arr).save(str(tmp_path / ("%d.jpg" % i)))
    prefix = str(tmp_path / "data")
    p = _run([os.path.join(TOOLS, "im2rec.py"), prefix, str(tmp_path),
              "--list", "--chunks", "2"])
    assert p.returncode == 0, p.stderr
    p = _run([os.path.join(TOOLS, "im2rec.py"), prefix, str(tmp_path)])
    assert p.returncode == 0, p.stderr
    assert os.path.exists(prefix + "_0.rec")
    assert os.path.exists(prefix + "_1.rec")


def test_launch_dist_sync_kvstore(tmp_path):
    """2-worker dist_sync push/pull exactness (parity model: reference
    tests/nightly/dist_sync_kvstore.py run via launch.py local mode)."""
    script = tmp_path / "dist_kv.py"
    script.write_text(
        "import sys; sys.path.insert(0, %r)\n" % REPO +
        "import numpy as np\n"
        "import mxnet_tpu as mx\n"
        "kv = mx.kv.create('dist_sync')\n"
        "kv.init(3, mx.nd.zeros((4, 2)))\n"
        "kv.barrier()\n"
        "kv.push(3, mx.nd.ones((4, 2)) * (kv.rank + 1))\n"
        "out = mx.nd.zeros((4, 2))\n"
        "kv.pull(3, out=out)\n"
        "np.testing.assert_allclose(out.asnumpy(), 3.0)\n"  # 1 + 2
        "kv.barrier()\n"
        "print('DIST_KV_OK rank', kv.rank)\n")
    p = _run([os.path.join(TOOLS, "launch.py"), "-n", "2",
              "--force-cpu", "--port", "9413",
              sys.executable, str(script)])
    assert p.returncode == 0, p.stderr
    assert p.stdout.count("DIST_KV_OK") == 2


def test_launch_dist_wire_compression_and_sparse_payload(tmp_path):
    """The dist wire actually shrinks: 2-bit pushes ship packed words
    (~16x smaller than fp32) and row_sparse pushes ship only touched rows
    (O(nnz), not O(full embedding)) — reference gradient_compression.cc
    and kvstore_dist.h:430-496 payload semantics."""
    script = tmp_path / "wire_kv.py"
    script.write_text(
        "import sys; sys.path.insert(0, %r)\n" % REPO +
        "import numpy as np\n"
        "import mxnet_tpu as mx\n"
        "from mxnet_tpu.ndarray import sparse as sp\n"
        "import jax\n"
        "assert jax.process_count() == 2\n"
        "kv = mx.kv.create('dist_sync')\n"
        "kv.set_gradient_compression({'type': '2bit', 'threshold': 0.5})\n"
        "kv.init(0, mx.nd.zeros((64, 64)))\n"
        "kv.push(0, mx.nd.ones((64, 64)) * 0.3)\n"
        "dense_bytes = 64 * 64 * 4\n"
        "wire = kv.wire_bytes_last_push\n"
        "assert wire <= dense_bytes // 16 + 64, (wire, dense_bytes)\n"
        "out = mx.nd.zeros((64, 64))\n"
        "kv.pull(0, out=out)\n"
        "# 0.3 < threshold 0.5 -> quantised to 0 on both ranks\n"
        "np.testing.assert_allclose(out.asnumpy(), 0.0)\n"
        "# error feedback: residual 0.3 + new 0.3 = 0.6 >= 0.5 -> +0.5\n"
        "kv.push(0, mx.nd.ones((64, 64)) * 0.3)\n"
        "kv.pull(0, out=out)\n"
        "np.testing.assert_allclose(out.asnumpy(), 1.0)\n"
        "# row_sparse payload: a (1000, 4) embedding, <=3 touched rows\n"
        "kv2 = mx.kv.create('dist_sync')\n"
        "kv2.init('e', sp.zeros('row_sparse', (1000, 4)))\n"
        "r = kv2.rank\n"
        "rows = [5, 17, 900] if r == 0 else [17, 42]\n"
        "vals = np.ones((len(rows), 4), np.float32) * (r + 1)\n"
        "g = sp.row_sparse_array((vals, rows), shape=(1000, 4))\n"
        "kv2.push('e', g)\n"
        "wire2 = kv2.wire_bytes_last_push\n"
        "full_bytes = 1000 * 4 * 4\n"
        "assert wire2 <= 512, (wire2, full_bytes)\n"
        "got = kv2._store['e']\n"
        "assert sorted(np.asarray(got._rsp_indices).tolist()) == \\\n"
        "    [5, 17, 42, 900]\n"
        "dense = got.tostype('default').asnumpy()\n"
        "np.testing.assert_allclose(dense[17], 3.0)\n"
        "np.testing.assert_allclose(dense[5], 1.0)\n"
        "np.testing.assert_allclose(dense[42], 2.0)\n"
        "np.testing.assert_allclose(dense[900], 1.0)\n"
        "print('WIRE OK rank', r)\n")
    p = _run([os.path.join(TOOLS, "launch.py"), "-n", "2",
              "--force-cpu", "--port", "9417",
              sys.executable, str(script)])
    assert p.returncode == 0, p.stderr + p.stdout
    assert p.stdout.count("WIRE OK rank") == 2


def test_launch_dead_node_visibility(tmp_path):
    """A worker that dies is visible to survivors via num_dead_node
    (parity: reference get_num_dead_node over scheduler heartbeats,
    include/mxnet/kvstore.h:338)."""
    script = tmp_path / "dead_kv.py"
    script.write_text(
        "import sys, time, os; sys.path.insert(0, %r)\n" % REPO +
        "import mxnet_tpu as mx\n"
        # fast beats + a WIDE staleness margin (25 beats): this test
        # pins visibility semantics, not detection latency — under
        # full-suite load a 1s-interval beat thread can gap past a 2s
        # timeout and a live peer reads as dead (flaky)
        "os.environ['MXTPU_HEARTBEAT_INTERVAL'] = '0.2'\n"
        "os.environ['MXTPU_HEARTBEAT_TIMEOUT'] = '5'\n"
        "kv = mx.kv.create('dist_sync')\n"
        "kv.barrier()\n"
        "assert kv.num_dead_node() == 0, kv.num_dead_node()\n"
        # rank 1 may die only once rank 0 has looked: a stopped
        # heartbeat reads as dead at once, and a rank 0 held up after
        # the first barrier saw 1 here
        "kv.barrier()\n"
        "if kv.rank == 1:\n"
        "    from mxnet_tpu import heartbeat\n"
        "    heartbeat.stop_heartbeat()\n"
        "    print('DEAD OK rank 1')\n"
        "    os._exit(0)   # worker dies (cleanly, to keep exit code 0)\n"
        "deadline = time.time() + 20\n"
        "while time.time() < deadline and kv.num_dead_node() == 0:\n"
        "    time.sleep(0.5)\n"
        "assert kv.num_dead_node() == 1, kv.num_dead_node()\n"
        "print('DEAD OK rank 0', flush=True)\n"
        "os._exit(0)  # skip jax's shutdown barrier (peer already gone)\n")
    p = _run([os.path.join(TOOLS, "launch.py"), "-n", "2",
              "--force-cpu", "--port", "9419",
              sys.executable, str(script)])
    assert p.returncode == 0, p.stderr + p.stdout
    assert p.stdout.count("DEAD OK") == 2


def test_launch_push_discipline_mismatch_fails_loudly(tmp_path):
    """Workers pushing DIFFERENT keys must die with a clear error, not
    deadlock or silently corrupt (SPMD collective discipline; the
    reference's server tolerated arbitrary arrival,
    kvstore_dist_server.h:173-310 — we guard instead)."""
    script = tmp_path / "bad_kv.py"
    script.write_text(
        "import sys; sys.path.insert(0, %r)\n" % REPO +
        "import mxnet_tpu as mx\n"
        "kv = mx.kv.create('dist_sync')\n"
        "kv.init(['a', 'b'], [mx.nd.zeros((2, 2)), mx.nd.zeros((3,))])\n"
        "kv.barrier()\n"
        "# rank 0 pushes key 'a', rank 1 pushes key 'b': mismatch\n"
        "key = 'a' if kv.rank == 0 else 'b'\n"
        "val = mx.nd.ones((2, 2)) if kv.rank == 0 else mx.nd.ones((3,))\n"
        "kv.push(key, val)\n"
        "print('UNREACHABLE rank', kv.rank)\n")
    p = _run([os.path.join(TOOLS, "launch.py"), "-n", "2",
              "--force-cpu", "--port", "9421",
              sys.executable, str(script)])
    assert p.returncode != 0
    combined = p.stdout + p.stderr
    assert "discipline violated" in combined, combined
    assert "UNREACHABLE" not in p.stdout


def test_accnn_low_rank_factorization(tmp_path):
    """tools/accnn: SVD-split convs + FCs. Full rank reproduces the
    original network almost exactly; reduced rank shrinks params and
    stays close (reference tools/accnn workflow)."""
    import json
    import numpy as np
    import mxnet_tpu as mx
    from mxnet_tpu.io import DataBatch

    np.random.seed(0)
    data = mx.sym.Variable("data")
    net = mx.sym.Convolution(data, kernel=(3, 3), num_filter=8,
                             pad=(1, 1), name="conv1")
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.Flatten(net)
    net = mx.sym.FullyConnected(net, num_hidden=16, name="fc1")
    net = mx.sym.SoftmaxOutput(
        mx.sym.FullyConnected(net, num_hidden=4, name="fc2"),
        name="softmax")
    mod = mx.mod.Module(net, context=mx.cpu())
    mod.bind(data_shapes=[("data", (2, 3, 6, 6))], for_training=False)
    mod.init_params(mx.initializer.Xavier())
    prefix = str(tmp_path / "m")
    mod.save_checkpoint(prefix, 0)

    def run_acc(ranks, out):
        p = _run([os.path.join(TOOLS, "accnn", "accnn.py"),
                  "--model", prefix, "--epoch", "0",
                  "--ranks", json.dumps(ranks), "--output", out])
        assert p.returncode == 0, p.stderr[-1500:]
        return p.stdout

    x = mx.nd.array(np.random.rand(2, 3, 6, 6).astype(np.float32))
    mod.forward(DataBatch([x]), is_train=False)
    ref = mod.get_outputs()[0].asnumpy()

    def run_net(out_prefix):
        sym2, a2, x2 = mx.model.load_checkpoint(out_prefix, 0)
        m2 = mx.mod.Module(sym2, context=mx.cpu())
        m2.bind(data_shapes=[("data", (2, 3, 6, 6))], for_training=False)
        m2.set_params(a2, x2)
        m2.forward(DataBatch([x]), is_train=False)
        return m2.get_outputs()[0].asnumpy()

    # full rank: numerically faithful
    run_acc({"conv1": 64, "fc1": 64}, prefix + "-full")
    np.testing.assert_allclose(run_net(prefix + "-full"), ref,
                               atol=1e-4)

    # reduced rank: smaller and still close
    out = run_acc({"conv1": 4, "fc1": 6}, prefix + "-lo")
    pct = float(out.split("(")[1].split("%")[0])
    assert pct < 100.0
    assert np.abs(run_net(prefix + "-lo") - ref).max() < 0.2


def test_rec2idx_roundtrip(tmp_path):
    from mxnet_tpu import recordio
    rec = str(tmp_path / "a.rec")
    w = recordio.MXRecordIO(rec, "w")
    for i in range(5):
        w.write(recordio.pack(recordio.IRHeader(0, float(i), i * 10, 0),
                              b"x" * (10 + i)))
    w.close()
    idx = str(tmp_path / "a.idx")
    p = _run([os.path.join(TOOLS, "rec2idx.py"), rec, idx])
    assert p.returncode == 0, p.stderr
    r = recordio.MXIndexedRecordIO(idx, rec, "r")
    hdr, payload = recordio.unpack(r.read_idx(30))
    assert hdr.label == 3.0 and payload == b"x" * 13


def test_diagnose_runs():
    p = _run([os.path.join(TOOLS, "diagnose.py"), "--accelerator", "0"])
    assert p.returncode == 0, p.stderr
    # the line reports the state the tree is in: nothing requires `make`
    # (every consumer of mxnet_tpu/_lib has a pure-Python path)
    lib = os.path.join(os.path.dirname(TOOLS), "mxnet_tpu", "_lib",
                       "libmxtpu_c_api.so")
    want = "built" if os.path.exists(lib) else "NOT BUILT (run `make`)"
    assert "Framework" in p.stdout
    assert "native C ABI : " + want in p.stdout


def test_rec2idx_duplicate_ids_key_sequentially(tmp_path):
    from mxnet_tpu import recordio
    rec = str(tmp_path / "dup.rec")
    w = recordio.MXRecordIO(rec, "w")
    for i in range(4):
        w.write(recordio.pack(recordio.IRHeader(0, float(i), 0, 0),
                              bytes([i]) * 4))
    w.close()
    idx = str(tmp_path / "dup.idx")
    p = _run([os.path.join(TOOLS, "rec2idx.py"), rec, idx])
    assert p.returncode == 0, p.stderr
    r = recordio.MXIndexedRecordIO(idx, rec, "r")
    for i in range(4):
        hdr, payload = recordio.unpack(r.read_idx(i))
        assert payload == bytes([i]) * 4


def test_accnn_speedup_rank_selection(tmp_path):
    """--speedup picks conv ranks automatically and the factored graph's
    conv FLOPs land at or under cost/speedup."""
    import json
    import numpy as np
    import mxnet_tpu as mx

    np.random.seed(1)
    data = mx.sym.Variable("data")
    net = mx.sym.Convolution(data, kernel=(3, 3), num_filter=16,
                             pad=(1, 1), name="c1")
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.Convolution(net, kernel=(5, 5), num_filter=16,
                             pad=(2, 2), name="c2")
    net = mx.sym.Flatten(net)
    net = mx.sym.SoftmaxOutput(
        mx.sym.FullyConnected(net, num_hidden=4, name="fc"),
        name="softmax")
    mod = mx.mod.Module(net, context=mx.cpu())
    mod.bind(data_shapes=[("data", (1, 3, 10, 10))], for_training=False)
    mod.init_params(mx.initializer.Xavier())
    prefix = str(tmp_path / "m")
    mod.save_checkpoint(prefix, 0)

    p = _run([os.path.join(TOOLS, "accnn", "accnn.py"),
              "--model", prefix, "--epoch", "0", "--speedup", "2.0",
              "--data-shape", "1,3,10,10", "--output", prefix + "-sp"])
    assert p.returncode == 0, p.stderr[-1500:]
    ranks = json.loads(p.stdout.split("selected ranks:")[1]
                       .strip().splitlines()[0])
    assert set(ranks) == {"c1", "c2"}
    assert all(1 <= r for r in ranks.values())
    # the central property: factored conv cost <= original cost / 2
    # (10x10 outputs at pad=same; cost model from select_ranks)
    xy = 100
    full = (3 * 3 * 16 * 3 + 5 * 5 * 16 * 16) * xy
    cost = (ranks["c1"] * (3 * 3 + 3 * 16)
            + ranks["c2"] * (5 * 16 + 5 * 16)) * xy
    assert cost <= full / 2.0, (ranks, cost, full)
    # the factored net loads and runs
    sym2, a2, x2 = mx.model.load_checkpoint(prefix + "-sp", 0)
    m2 = mx.mod.Module(sym2, context=mx.cpu())
    m2.bind(data_shapes=[("data", (1, 3, 10, 10))], for_training=False)
    m2.set_params(a2, x2)
    from mxnet_tpu.io import DataBatch
    m2.forward(DataBatch([mx.nd.ones((1, 3, 10, 10))]), is_train=False)
    assert m2.get_outputs()[0].shape == (1, 4)
