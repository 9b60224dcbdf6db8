"""JPEG decode+augment throughput probe.

Parity: the reference measures its input pipeline via
iter_image_recordio_2's multithreaded decode (src/io/
iter_image_recordio_2.cc:660-760); this probe packs synthetic JPEGs into
RecordIO and measures ImageIter decode img/s at a given thread count, so
a deployment can check the pipeline feeds the accelerator (compare
against the ledger's ``train_img_s`` for ``resnet50.fit``).

Usage: python tools/decode_bench.py [--threads N] [--images M]
                                    [--size HxW] [--batch B]
Prints one JSON line: {"metric": "jpeg_decode_throughput", ...}
"""
import argparse
import json
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

# host-side probe: never touch the accelerator (image decode
# throughput is a property of the host, and a process that touched the
# chip would hold it)
import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--threads", type=int, default=os.cpu_count() or 1)
    ap.add_argument("--images", type=int, default=256)
    ap.add_argument("--size", default="224x224")
    ap.add_argument("--batch", type=int, default=128)
    ap.add_argument("--epochs", type=int, default=2)
    args = ap.parse_args()
    h, w = (int(x) for x in args.size.split("x"))

    from mxnet_tpu.image import ImageIter
    # one packing methodology for both probes: PERF.md compares their
    # numbers, so the JPEG quality/seed/header must not drift apart
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from feed_probe import pack_synthetic_rec

    with tempfile.TemporaryDirectory() as td:
        rec_path = os.path.join(td, "probe.rec")
        pack_synthetic_rec(rec_path, args.images, h, w)

        it = ImageIter(batch_size=args.batch, data_shape=(3, h, w),
                       path_imgrec=rec_path,
                       preprocess_threads=args.threads)
        # warm epoch (thread pool spin-up, page cache)
        for _ in it:
            pass
        n = 0
        t0 = time.perf_counter()
        for _ in range(args.epochs):
            it.reset()
            for batch in it:
                n += batch.data[0].shape[0]
        dt = time.perf_counter() - t0
        print(json.dumps({
            "metric": "jpeg_decode_throughput",
            "value": round(n / dt, 1),
            "unit": "img/s",
            "threads": args.threads,
            "image_size": "%dx%d" % (h, w),
            "batch": args.batch,
        }))


if __name__ == "__main__":
    main()
