"""Sustained-feed probe: decode running CONCURRENTLY with a consumer.

`tools/decode_bench.py` measures raw decode capacity; this probe proves
the property that actually matters for keeping the chip busy — the
pipeline (threaded JPEG decode -> batch assembly -> prefetch double
buffer, the reference's iter_image_recordio_2.cc:660-760 design)
OVERLAPS decode with consumption, so feeding a consumer that takes
`t_step` per batch costs max(decode, consume) wall-clock, not the sum.

A deployment points `--target-img-s` at its measured train throughput
(the ledger's ``train_img_s``): the probe reports whether the feed sustained it,
the overlap efficiency, and how many decode cores at the measured
per-core rate the target needs.

Usage:
    python tools/feed_probe.py [--threads N] [--images M] [--size HxW]
                               [--batch B] [--target-img-s R]
Prints one JSON line.
"""
import argparse
import io as _io
import json
import os
import sys
import tempfile
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

# host-side probe: never touch the accelerator (image decode
# throughput is a property of the host, and a process that touched the
# chip would hold it)
import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")


def pack_synthetic_rec(rec_path, images, h, w, seed=0):
    from PIL import Image
    from mxnet_tpu import recordio
    rs = np.random.RandomState(seed)
    rec = recordio.MXRecordIO(rec_path, "w")
    for i in range(images):
        arr = rs.randint(0, 255, (h, w, 3), np.uint8)
        buf = _io.BytesIO()
        Image.fromarray(arr).save(buf, format="JPEG", quality=90)
        rec.write(recordio.pack(
            recordio.IRHeader(0, float(i % 10), i, 0), buf.getvalue()))
    rec.close()


def run_probe(threads, images, h, w, batch, target_img_s=None, epochs=2,
              target_fraction=1.0):
    """Returns the probe result dict (no printing). ``target_fraction``
    scales the default target (measured decode capacity) — a deployment
    sizes decode cores with headroom, so sustaining ~100% of capacity on
    the same cores is not the operative claim."""
    from mxnet_tpu.image import ImageIter
    from mxnet_tpu.io import PrefetchingIter

    with tempfile.TemporaryDirectory() as td:
        rec_path = os.path.join(td, "probe.rec")
        pack_synthetic_rec(rec_path, images, h, w)

        def make_iter():
            return ImageIter(batch_size=batch, data_shape=(3, h, w),
                             path_imgrec=rec_path,
                             preprocess_threads=threads)

        # phase 1: decode-only capacity (warm epoch first)
        it = make_iter()
        for _ in it:
            pass
        n = 0
        t0 = time.perf_counter()
        for _ in range(epochs):
            it.reset()
            for b in it:
                n += b.data[0].shape[0]
        decode_img_s = n / (time.perf_counter() - t0)

        # consumer pace: the measured train rate, or decode capacity
        # scaled by target_fraction
        if target_img_s is not None:
            target = float(target_img_s)
            if target <= 0:
                raise ValueError("--target-img-s must be positive, got %r"
                                 % target_img_s)
        else:
            target = decode_img_s * float(target_fraction)
        t_step = batch / target

        # phase 2: decode CONCURRENT with a paced consumer behind the
        # prefetch double buffer
        feed = PrefetchingIter(make_iter())
        for _ in feed:   # warm epoch
            pass
        n = 0
        t0 = time.perf_counter()
        for _ in range(epochs):
            feed.reset()
            for b in feed:
                time.sleep(t_step)  # the "train step"
                n += b.data[0].shape[0]
        wall = time.perf_counter() - t0
        delivered_img_s = n / wall

        consume_time = n / target
        decode_time = n / decode_img_s
        serial_time = consume_time + decode_time
        ideal_time = max(consume_time, decode_time)
        # 1.0 = perfect overlap (wall == max of the two phases);
        # 0.0 = fully serialised (wall == sum)
        overlap = (serial_time - wall) / (serial_time - ideal_time) \
            if serial_time > ideal_time else 1.0

        per_core = decode_img_s / max(threads, 1)
        return {
            "metric": "sustained_feed",
            "value": round(delivered_img_s, 1),
            "unit": "img/s",
            "decode_img_s": round(decode_img_s, 1),
            "target_img_s": round(target, 1),
            "sustained": bool(delivered_img_s >= 0.85 * min(target,
                                                            decode_img_s)),
            "overlap_efficiency": round(max(0.0, min(overlap, 1.0)), 3),
            "threads": threads,
            "per_core_img_s": round(per_core, 1),
            "cores_needed_for_target": int(np.ceil(target / per_core)),
            "image_size": "%dx%d" % (h, w),
            "batch": batch,
        }


def _worker_decode(rec_path, h, w, batch, num_parts, part_index, epochs,
                   conn):
    """One decode worker: its shard of the rec (num_parts/part_index —
    the dmlc-core sharded-read contract every reference iterator
    honours), reporting (images, seconds, checksum-of-ids)."""
    from mxnet_tpu.image import ImageIter
    it = ImageIter(batch_size=batch, data_shape=(3, h, w),
                   path_imgrec=rec_path, preprocess_threads=1,
                   num_parts=num_parts, part_index=part_index)
    for _ in it:  # warm epoch (JIT/caches)
        pass
    n = 0
    ids = 0
    t0 = time.perf_counter()
    for _ in range(epochs):
        it.reset()
        for b in it:
            bs = b.data[0].shape[0] - b.pad
            n += bs
            ids += int(np.sum(np.asarray(b.label[0].asnumpy()[:bs])))
    conn.send((n, time.perf_counter() - t0, ids))
    conn.close()


def run_worker_probe(workers, images, h, w, batch, epochs=2):
    """Aggregate decode rate across N worker PROCESSES, each on its own
    shard — the process-scaling model behind PERF.md's multi-core feed
    sizing (per-core rate x N cores). On a 1-core host the processes
    time-slice, so the validated claims are (a) sharding covers every
    image exactly once and (b) aggregation adds no coordination loss
    beyond the scheduler (aggregate ~= single-process rate); the rate
    MULTIPLIES only with real cores."""
    import multiprocessing as mp
    with tempfile.TemporaryDirectory() as td:
        rec_path = os.path.join(td, "probe.rec")
        pack_synthetic_rec(rec_path, images, h, w)

        # single-process baseline on the full set
        parent, child = mp.Pipe()
        _worker_decode(rec_path, h, w, batch, 1, 0, epochs, child)
        base_n, base_dt, base_ids = parent.recv()
        base_rate = base_n / base_dt

        ctx = mp.get_context("spawn")
        pipes, procs = [], []
        t0 = time.perf_counter()
        for i in range(workers):
            pr, cw = ctx.Pipe()
            p = ctx.Process(target=_worker_decode,
                            args=(rec_path, h, w, batch, workers, i,
                                  epochs, cw))
            p.start()
            # drop the parent's child-end reference so a worker dying
            # before send() surfaces as EOFError instead of a hang
            cw.close()
            pipes.append(pr)
            procs.append(p)
        try:
            results = [pr.recv() for pr in pipes]
        except EOFError:
            for p in procs:
                p.terminate()
            raise RuntimeError("a decode worker died before reporting "
                               "(see its stderr above)")
        for p in procs:
            p.join()
        wall = time.perf_counter() - t0

        total = sum(r[0] for r in results)
        ids = sum(r[2] for r in results)
        # aggregate = sum of the workers' CONCURRENT decode rates (their
        # timed loops overlap); parent wall additionally pays per-process
        # interpreter+jax startup (~seconds), which a real deployment
        # pays once per epoch-spanning worker, not per measurement
        agg_rate = sum(r[0] / r[1] for r in results)
        return {
            "metric": "worker_decode_scaling",
            "value": round(agg_rate, 1),
            "unit": "img/s",
            "workers": workers,
            "single_process_img_s": round(base_rate, 1),
            "per_worker_img_s": [round(r[0] / r[1], 1) for r in results],
            "images_total": total,
            "shard_exact_cover": bool(total == base_n and ids == base_ids),
            "host_cores": os.cpu_count() or 1,
            "wall_with_startup_s": round(wall, 2),
            # on >=N-core hosts the model predicts ~N * per-core rate;
            # on fewer cores the workers time-slice and this ratio is the
            # scheduler overhead, not the scaling multiple
            "scaling_efficiency_vs_single": round(agg_rate / base_rate, 3),
        }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--threads", type=int, default=os.cpu_count() or 1)
    ap.add_argument("--images", type=int, default=256)
    ap.add_argument("--size", default="224x224")
    ap.add_argument("--batch", type=int, default=128)
    ap.add_argument("--target-img-s", type=float, default=None,
                    help="consumer rate to sustain (e.g. the chip's "
                         "measured img/s); default: decode capacity "
                         "scaled by --target-fraction")
    ap.add_argument("--target-fraction", type=float, default=1.0)
    ap.add_argument("--workers", type=int, default=0,
                    help="N>0: measure aggregate decode across N worker "
                         "PROCESSES on disjoint shards instead of the "
                         "threaded overlap probe")
    args = ap.parse_args()
    h, w = (int(x) for x in args.size.split("x"))
    if args.workers > 0:
        print(json.dumps(run_worker_probe(args.workers, args.images, h, w,
                                          args.batch)))
        return
    print(json.dumps(run_probe(args.threads, args.images, h, w, args.batch,
                               args.target_img_s,
                               target_fraction=args.target_fraction)))


if __name__ == "__main__":
    main()
