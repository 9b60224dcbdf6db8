"""IO tests (parity model: reference tests/python/unittest/test_io.py)."""
import os

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import nd, recordio
from mxnet_tpu.base import MXNetError
from mxnet_tpu.io import (NDArrayIter, ResizeIter, PrefetchingIter,
                          ImageRecordIter, CSVIter)


def test_ndarray_iter_basic():
    data = np.arange(40).reshape(10, 4).astype(np.float32)
    label = np.arange(10).astype(np.float32)
    it = NDArrayIter(data, label, batch_size=4)
    batches = list(it)
    assert len(batches) == 3  # 10/4 padded
    assert batches[0].data[0].shape == (4, 4)
    assert batches[2].pad == 2
    it.reset()
    first = next(iter(it))
    np.testing.assert_allclose(first.data[0].asnumpy(), data[:4])


def test_ndarray_iter_discard_and_shuffle():
    data = np.arange(20).reshape(10, 2).astype(np.float32)
    it = NDArrayIter(data, np.zeros(10), batch_size=3,
                     last_batch_handle="discard")
    assert len(list(it)) == 3
    it2 = NDArrayIter(data, np.arange(10), batch_size=5, shuffle=True)
    b = next(iter(it2))
    # shuffled but data/label stay aligned
    d = b.data[0].asnumpy()
    lbl = b.label[0].asnumpy()
    np.testing.assert_allclose(d[:, 0] // 2, lbl)


def test_ndarray_iter_dict_input():
    it = NDArrayIter({"a": np.zeros((6, 2)), "b": np.ones((6, 3))},
                     batch_size=2)
    assert sorted(d.name for d in it.provide_data) == ["a", "b"]


def test_resize_iter():
    it = NDArrayIter(np.zeros((10, 2)), np.zeros(10), batch_size=2)
    r = ResizeIter(it, 8)
    assert len(list(r)) == 8


def test_prefetching_iter():
    it = NDArrayIter(np.arange(24).reshape(12, 2).astype(np.float32),
                     np.zeros(12), batch_size=4)
    p = PrefetchingIter(it)
    batches = list(p)
    assert len(batches) == 3
    p.reset()
    batches2 = list(p)
    assert len(batches2) == 3


def test_prefetching_iter_reset_survives_wedged_backing():
    """reset() must neither hang NOR proceed when the worker is
    blocked INSIDE backing.next() (stalled data source): a
    replacement worker would race the wedged one's in-flight next()
    on the shared backing iterator. It waits reset_join_timeout, then
    raises a diagnosable error; once the source unblocks (the worker
    exits via its closure-captured stop), reset() is re-entrant and
    the next epoch is a full clean pass."""
    import threading
    import time

    release = threading.Event()
    base = NDArrayIter(np.arange(24).reshape(12, 2).astype(np.float32),
                       np.zeros(12), batch_size=4)

    class Wedged:
        """First next() after arming blocks until released."""
        batch_size = 4

        def __init__(self):
            self.armed = False

        @property
        def provide_data(self):
            return base.provide_data

        @property
        def provide_label(self):
            return base.provide_label

        def reset(self):
            base.reset()

        def next(self):
            if self.armed:
                release.wait()
            return base.next()

    w = Wedged()
    p = PrefetchingIter([w], prefetch_depth=1)
    p.next()                      # worker running
    w.armed = True
    p.next()                      # steer the worker into a blocked next()
    time.sleep(0.05)
    w.armed = False               # after the wedge clears, stay clear
    p.reset_join_timeout = 0.3
    t0 = time.monotonic()
    with pytest.raises(MXNetError, match="blocked inside the backing"):
        p.reset()                 # bounded: raises, never hangs/races
    took = time.monotonic() - t0
    assert took < 3.0, took
    release.set()                 # source unblocks; worker sees ITS
    time.sleep(0.2)               # stop (set by the failed reset), dies
    p.reset()                     # re-entrant retry: clean this time
    assert len(list(p)) == 3      # full epoch, nothing stolen
    p.reset()
    assert len(list(p)) == 3


def test_recordio_roundtrip(tmp_path):
    path = str(tmp_path / "t.rec")
    w = recordio.MXRecordIO(path, "w")
    payloads = [b"hello", b"world!!", b"x" * 100]
    for p in payloads:
        w.write(p)
    w.close()
    r = recordio.MXRecordIO(path, "r")
    got = []
    while True:
        s = r.read()
        if s is None:
            break
        got.append(bytes(s))
    assert got == payloads


def test_indexed_recordio(tmp_path):
    path = str(tmp_path / "t.rec")
    idx_path = str(tmp_path / "t.idx")
    w = recordio.MXIndexedRecordIO(idx_path, path, "w")
    for i in range(5):
        w.write_idx(i, b"rec%d" % i)
    w.close()
    r = recordio.MXIndexedRecordIO(idx_path, path, "r")
    assert bytes(r.read_idx(3)) == b"rec3"
    assert bytes(r.read_idx(0)) == b"rec0"


def test_pack_unpack():
    hdr = recordio.IRHeader(0, 2.5, 7, 0)
    s = recordio.pack(hdr, b"payload")
    h2, payload = recordio.unpack(s)
    assert h2.label == 2.5 and h2.id == 7
    assert bytes(payload) == b"payload"


def _write_image_rec(path, n=8, shape=(3, 8, 8)):
    w = recordio.MXRecordIO(path, "w")
    imgs = []
    for i in range(n):
        img = np.random.randint(0, 255, shape, dtype=np.uint8)
        imgs.append(img)
        w.write(recordio.pack(recordio.IRHeader(0, float(i % 4), i, 0),
                              img.tobytes()))
    w.close()
    return imgs


def test_image_record_iter(tmp_path):
    path = str(tmp_path / "imgs.rec")
    imgs = _write_image_rec(path)
    it = ImageRecordIter(path_imgrec=path, data_shape=(3, 8, 8), batch_size=4)
    batch = it.next()
    assert batch.data[0].shape == (4, 3, 8, 8)
    np.testing.assert_allclose(batch.data[0].asnumpy()[0],
                               imgs[0].astype(np.float32))
    np.testing.assert_allclose(batch.label[0].asnumpy(), [0, 1, 2, 3])


def test_image_record_iter_native_normalisation(tmp_path):
    path = str(tmp_path / "imgs.rec")
    imgs = _write_image_rec(path, n=4)
    it = ImageRecordIter(path_imgrec=path, data_shape=(3, 8, 8), batch_size=2,
                         mean_r=10.0, mean_g=20.0, mean_b=30.0, std_r=2.0,
                         std_g=2.0, std_b=2.0)
    batch = it.next()
    expect = (imgs[0].astype(np.float32)
              - np.array([10, 20, 30], np.float32).reshape(3, 1, 1)) / 2.0
    np.testing.assert_allclose(batch.data[0].asnumpy()[0], expect, rtol=1e-5)


def test_csv_iter(tmp_path):
    data_csv = str(tmp_path / "d.csv")
    label_csv = str(tmp_path / "l.csv")
    data = np.random.uniform(size=(10, 3)).astype(np.float32)
    labels = np.arange(10).astype(np.float32)
    np.savetxt(data_csv, data, delimiter=",")
    np.savetxt(label_csv, labels, delimiter=",")
    it = CSVIter(data_csv=data_csv, data_shape=(3,), label_csv=label_csv,
                 batch_size=5)
    b = next(iter(it))
    np.testing.assert_allclose(b.data[0].asnumpy(), data[:5], rtol=1e-5)
    np.testing.assert_allclose(b.label[0].asnumpy(), labels[:5])


def test_mnist_iter_from_idx_files(tmp_path):
    """Write idx-format files and read them back (MNISTIter parity)."""
    import gzip
    import struct
    img_path = str(tmp_path / "train-images-idx3-ubyte")
    lbl_path = str(tmp_path / "train-labels-idx1-ubyte")
    imgs = np.random.randint(0, 255, (20, 28, 28), dtype=np.uint8)
    lbls = np.random.randint(0, 10, 20).astype(np.uint8)
    with open(img_path, "wb") as f:
        f.write(struct.pack(">IIII", 2051, 20, 28, 28))
        f.write(imgs.tobytes())
    with open(lbl_path, "wb") as f:
        f.write(struct.pack(">II", 2049, 20))
        f.write(lbls.tobytes())
    from mxnet_tpu.io import MNISTIter
    it = MNISTIter(image=img_path, label=lbl_path, batch_size=5,
                   shuffle=False)
    b = next(iter(it))
    assert b.data[0].shape == (5, 1, 28, 28)
    np.testing.assert_allclose(b.label[0].asnumpy(), lbls[:5])


def test_iterator_num_parts_sharding():
    """num_parts/part_index shard the data per worker (parity: dmlc
    InputSplit through the reference iterators' kwargs)."""
    x = np.arange(24, dtype=np.float32).reshape(12, 2)
    y = np.arange(12, dtype=np.float32)
    full = mx.io.NDArrayIter(x, y, batch_size=2)
    p0 = mx.io.NDArrayIter(x, y, batch_size=2, num_parts=3, part_index=0)
    p1 = mx.io.NDArrayIter(x, y, batch_size=2, num_parts=3, part_index=1)
    assert p0.num_data == p1.num_data == 4
    seen = []
    for it in (p0, p1):
        for b in it:
            seen.extend(b.label[0].asnumpy().tolist())
    assert sorted(seen) == [0, 1, 3, 4, 6, 7, 9, 10]
    assert full.num_data == 12


def test_prefetch_decodes_ahead_of_the_consumer():
    """The pipeline must DECODE WHILE THE CONSUMER RUNS (reference
    iter_image_recordio_2.cc decode-parallel design): while the
    consumer holds batch 0 and asks for nothing, the prefetcher draws
    the batches after it from the backing iterator. An ordering, held
    by events (the waits are hang guards), where a ratio of two wall
    clocks used to stand."""
    import threading
    base = NDArrayIter(np.arange(24).reshape(12, 2).astype(np.float32),
                       np.zeros(12), batch_size=4)
    drawn = [threading.Event() for _ in range(3)]

    class Backing:
        batch_size = 4
        provide_data = base.provide_data
        provide_label = base.provide_label
        i = 0

        def reset(self):
            base.reset()

        def next(self):
            batch = base.next()
            drawn[self.i].set()
            self.i += 1
            return batch

    p = PrefetchingIter([Backing()], prefetch_depth=1)
    p.next()                      # the consumer's "train step" begins
    assert drawn[1].wait(30), "nothing was decoded behind the consumer"
    assert drawn[2].wait(30), "the double buffer's second slot stayed idle"
    assert len(list(p)) == 2      # and the epoch's other batches arrive


def test_feed_probe_runs_and_sizes_cores():
    """tools/feed_probe.py runs end to end (in a SUBPROCESS: its module
    body pins jax_platforms=cpu, which must not leak into this session)
    and its core-sizing arithmetic is exactly ceil(target / per-core
    rate). What its rates read on this CPU is nobody's gate: that the
    pipeline overlaps decode with consumption is held above."""
    import json
    import math
    import subprocess
    import sys as _sys
    repo = os.path.join(os.path.dirname(__file__), "..")
    env = dict(os.environ)
    env["MXNET_TPU_FORCE_CPU"] = "1"
    env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
    p = subprocess.run(
        [_sys.executable, os.path.join(repo, "tools", "feed_probe.py"),
         "--threads", "1", "--images", "96", "--size", "64x64",
         "--batch", "16", "--target-fraction", "0.5"],
        capture_output=True, text=True, timeout=300, env=env)
    assert p.returncode == 0, p.stderr
    res = json.loads(p.stdout.strip().splitlines()[-1])
    assert res["cores_needed_for_target"] == int(
        math.ceil(res["target_img_s"] / res["per_core_img_s"])), res


def test_worker_decode_scaling_probe():
    """Process-based decode workers (the multi-core feed-scaling model,
    PERF.md): N workers on disjoint num_parts shards must cover every
    image exactly once. Subprocess for the same jax_platforms isolation
    as the probe above."""
    import json
    import subprocess
    import sys as _sys
    repo = os.path.join(os.path.dirname(__file__), "..")
    env = dict(os.environ)
    env["MXNET_TPU_FORCE_CPU"] = "1"
    env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
    cmd = [_sys.executable,
           os.path.join(repo, "tools", "feed_probe.py"),
           "--workers", "2", "--images", "64", "--size", "64x64",
           "--batch", "16"]
    p = subprocess.run(cmd, capture_output=True, text=True,
                       timeout=600, env=env)
    assert p.returncode == 0, p.stderr
    res = json.loads(p.stdout.strip().splitlines()[-1])
    assert res["workers"] == 2 and len(res["per_worker_img_s"]) == 2, res
    assert res["shard_exact_cover"], res


def test_native_im2rec_roundtrip(tmp_path):
    """The native C++ im2rec (src/im2rec.cc, parity: reference
    tools/im2rec.cc): packs a .lst of image files into .rec/.idx in the
    shared wire format, single- and multi-label rows, num_parts
    sharding — and the Python side reads every record back."""
    import subprocess
    repo = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
    exe = os.path.join(repo, "tools", "im2rec")
    if not os.path.exists(exe):
        import pytest as _pytest
        _pytest.skip("native im2rec not built (run make)")
    from mxnet_tpu import recordio
    # three fake "images" (arbitrary bytes — im2rec streams encoded
    # bytes through untouched)
    blobs = [os.urandom(100 + 13 * i) for i in range(3)]
    for i, b in enumerate(blobs):
        (tmp_path / ("img%d.jpg" % i)).write_bytes(b)
    lst = tmp_path / "train.lst"
    lst.write_text(
        "0\t1.0\timg0.jpg\n"
        "1\t2.0\t3.0\timg1.jpg\n"       # multi-label row
        "2\t0.0\timg2.jpg\n")
    out = tmp_path / "train"
    p = subprocess.run([exe, str(lst), str(tmp_path), str(out)],
                      capture_output=True, text=True, timeout=120)
    assert p.returncode == 0, p.stdout + p.stderr
    rec = recordio.MXIndexedRecordIO(str(out) + ".idx", str(out) + ".rec",
                                     "r")
    hdr0, s0 = recordio.unpack(rec.read_idx(0))
    assert hdr0.label == 1.0 and s0 == blobs[0]
    hdr1, s1 = recordio.unpack(rec.read_idx(1))
    assert list(hdr1.label) == [2.0, 3.0] and s1 == blobs[1]
    hdr2, s2 = recordio.unpack(rec.read_idx(2))
    assert hdr2.label == 0.0 and s2 == blobs[2]

    # sharded packing covers disjoint rows
    for part in (0, 1):
        op = tmp_path / ("shard%d" % part)
        subprocess.run([exe, str(lst), str(tmp_path), str(op), "2",
                        str(part)], check=True, timeout=120)
    r0 = recordio.MXRecordIO(str(tmp_path / "shard0.rec"), "r")
    r1 = recordio.MXRecordIO(str(tmp_path / "shard1.rec"), "r")
    ids = []
    for r in (r0, r1):
        while True:
            buf = r.read()
            if buf is None:
                break
            ids.append(recordio.unpack(buf)[0].id)
    assert sorted(ids) == [0, 1, 2]


def _write_jpeg_rec(path, n=7, size=(12, 12), gray=False):
    import io as pyio
    from PIL import Image
    w = recordio.MXRecordIO(path, "w")
    for i in range(n):
        arr = np.random.randint(0, 255, size + ((1,) if gray else (3,)),
                                dtype=np.uint8)
        img = Image.fromarray(arr[:, :, 0] if gray else arr,
                              mode="L" if gray else "RGB")
        buf = pyio.BytesIO()
        img.save(buf, format="JPEG")
        w.write(recordio.pack(recordio.IRHeader(0, float(i), i, 0),
                              buf.getvalue()))
    w.close()


def test_image_record_iter_jpeg_decode_and_round_batch(tmp_path):
    """Encoded payloads decode via PIL; round_batch wraps + reports pad."""
    path = str(tmp_path / "jpeg.rec")
    _write_jpeg_rec(path, n=7)
    it = ImageRecordIter(path_imgrec=path, data_shape=(3, 8, 8),
                         batch_size=4, rand_crop=True, rand_mirror=True)
    b0 = it.next()
    assert b0.data[0].shape == (4, 3, 8, 8) and b0.pad == 0
    b1 = it.next()   # 3 records left -> wraps 1, pad=1
    assert b1.data[0].shape == (4, 3, 8, 8) and b1.pad == 1
    np.testing.assert_allclose(b1.label[0].asnumpy(), [4, 5, 6, 0])
    import pytest as _pytest
    with _pytest.raises(StopIteration):
        it.next()
    # round_batch=False drops the partial tail instead
    it2 = ImageRecordIter(path_imgrec=path, data_shape=(3, 8, 8),
                          batch_size=4, rand_crop=True, round_batch=False)
    it2.next()
    with _pytest.raises(StopIteration):
        it2.next()


def test_image_record_iter_grayscale_jpeg(tmp_path):
    path = str(tmp_path / "gray.rec")
    _write_jpeg_rec(path, n=4, gray=True)
    it = ImageRecordIter(path_imgrec=path, data_shape=(1, 8, 8),
                         batch_size=4, mean_r=1.0, std_r=2.0)
    batch = it.next()
    assert batch.data[0].shape == (4, 1, 8, 8)


def test_image_record_iter_smaller_than_batch(tmp_path):
    path = str(tmp_path / "tiny.rec")
    _write_jpeg_rec(path, n=3)
    it = ImageRecordIter(path_imgrec=path, data_shape=(3, 8, 8),
                         batch_size=8, rand_crop=True)
    batch = it.next()   # wraps repeatedly to fill, pad = 8-3 = 5
    assert batch.data[0].shape == (8, 3, 8, 8) and batch.pad == 5
    np.testing.assert_allclose(batch.label[0].asnumpy(),
                               [0, 1, 2, 0, 1, 2, 0, 1])


def test_image_record_iter_raw_payload_with_magic_prefix(tmp_path):
    """Raw pixels starting with a JPEG signature still raw-decode."""
    path = str(tmp_path / "trap.rec")
    w = recordio.MXRecordIO(path, "w")
    arr = np.random.randint(0, 255, (3, 8, 8), dtype=np.uint8)
    arr.flat[0], arr.flat[1] = 0xFF, 0xD8   # JPEG SOI magic
    w.write(recordio.pack(recordio.IRHeader(0, 5.0, 0, 0), arr.tobytes()))
    w.close()
    it = ImageRecordIter(path_imgrec=path, data_shape=(3, 8, 8),
                         batch_size=1)
    batch = it.next()
    np.testing.assert_allclose(batch.data[0].asnumpy()[0],
                               arr.astype(np.float32))


def test_image_record_iter_jpeg_bypasses_native_loader(tmp_path):
    """Encoded payloads must never hit the native raw-pixel loader, even
    in its sweet spot (no augmentation, batch divides evenly)."""
    import io as pyio
    from PIL import Image
    path = str(tmp_path / "enc.rec")
    w = recordio.MXRecordIO(path, "w")
    arr = np.full((8, 8, 3), 200, np.uint8)
    for i in range(4):
        buf = pyio.BytesIO()
        Image.fromarray(arr).save(buf, format="JPEG", quality=95)
        w.write(recordio.pack(recordio.IRHeader(0, float(i), i, 0),
                              buf.getvalue()))
    w.close()
    it = ImageRecordIter(path_imgrec=path, data_shape=(3, 8, 8),
                         batch_size=4)
    assert it._native is None
    batch = it.next()
    # decoded pixels, not compressed bytes: a near-uniform 200 plane
    got = batch.data[0].asnumpy()
    assert abs(got.mean() - 200.0) < 5.0 and got.std() < 10.0
