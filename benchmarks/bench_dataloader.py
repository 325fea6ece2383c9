#!/usr/bin/env python
"""Input-pipeline feed rate: can the host feed the TPU at training rate?

Measures images/sec on a synthetic JPEG RecordIO file through the three
feed paths and prints ONE JSON line:
  * native    — C++ pipeline (`native/recordio_pipeline.cc`): decode +
                crop/mirror + normalize + batch, thread pool + ring buffer
  * python    — ImageRecordIter python fallback (threaded decode pool)
  * dataloader— gluon DataLoader (thread workers) over a decoded-array
                dataset with a python augmenter chain (the GIL-bound path
                the VERDICT asked to measure)

This is a HOST benchmark: it needs no chip and pins jax to the CPU, and
its row says so (platform "cpu"). Compare against the ResNet-50 step rate
(img/s/chip) measured on the chip — the native path is the one that must
keep up. Any path that fails (the native library not building included)
fails the script.
"""
import io as _io
import json
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                ".."))
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np


# ci's contract check shrinks the workload via env; defaults unchanged
_N_IMAGES = int(os.environ.get("MXNET_TPU_BENCH_DL_IMAGES", "512"))
_MIN_ITER = int(os.environ.get("MXNET_TPU_BENCH_DL_MIN", "600"))
_MIN_DL = int(os.environ.get("MXNET_TPU_BENCH_DL_MIN_DL", "256"))


def make_rec(tmp, n=_N_IMAGES, h=256, w=256, seed=0):
    from PIL import Image
    from mxnet_tpu.io.recordio import IndexedRecordIO, IRHeader, pack

    rng = np.random.RandomState(seed)
    prefix = os.path.join(tmp, "data")
    rec = IndexedRecordIO(prefix + ".idx", prefix + ".rec", "w")
    for i in range(n):
        arr = rng.randint(0, 255, (h, w, 3), np.uint8)
        buf = _io.BytesIO()
        Image.fromarray(arr).save(buf, format="JPEG", quality=90)
        rec.write_idx(i, pack(IRHeader(0, float(i % 10), i, 0),
                              buf.getvalue()))
    rec.close()
    return prefix


def time_iter(make, batch_size, min_images=_MIN_ITER):
    it = make()
    n, t0 = 0, time.perf_counter()
    while n < min_images:
        try:
            batch = next(iter([it.next()]))
        except StopIteration:
            it.reset()
            continue
        n += batch_size - batch.pad
    return n / (time.perf_counter() - t0)


def main():
    import jax

    jax.config.update("jax_platforms", "cpu")
    from mxnet_tpu.io import ImageRecordIter
    from benchmarks import _provenance

    batch = 64
    shape = (3, 224, 224)
    out = {"metric": "input_pipeline_images_per_sec", "unit": "images/s"}
    with tempfile.TemporaryDirectory() as tmp:
        prefix = make_rec(tmp)

        def native():
            return ImageRecordIter(prefix + ".rec", shape, batch,
                                   use_native=True, rand_crop=True,
                                   rand_mirror=True, preprocess_threads=8)

        def python_path():
            return ImageRecordIter(prefix + ".rec", shape, batch,
                                   use_native=False, rand_crop=True,
                                   rand_mirror=True, preprocess_threads=8)

        out["native"] = round(time_iter(native, batch), 1)
        out["python"] = round(time_iter(python_path, batch), 1)

        # gluon DataLoader: decoded uint8 arrays + python augmenter chain
        from mxnet_tpu.gluon.data import ArrayDataset, DataLoader
        from mxnet_tpu.gluon.data.vision import transforms as T

        rng = np.random.RandomState(0)
        n_ds = max(_N_IMAGES, batch)
        imgs = rng.randint(0, 255, (n_ds, 256, 256, 3), np.uint8)
        labels = rng.randint(0, 10, (n_ds,)).astype(np.float32)
        from mxnet_tpu import nd

        ds = ArrayDataset(imgs, labels)
        tf = T.Compose([T.RandomResizedCrop(224), T.RandomFlipLeftRight(),
                        T.ToTensor()])

        def rate_of(dl):
            n, t0 = 0, time.perf_counter()
            while n < _MIN_DL:
                for x, y in dl:
                    n += x.shape[0]
                    if n >= _MIN_DL:
                        break
            return round(n / (time.perf_counter() - t0), 1)

        def dl_rate(workers):
            # thread path: NDArray transforms are allowed here
            return rate_of(DataLoader(
                ds.transform_first(lambda a: tf(nd.array(a))),
                batch_size=batch, num_workers=workers, shuffle=True,
                thread_pool=True))

        out["dataloader_w1"] = dl_rate(1)
        out["dataloader_w8"] = dl_rate(8)

        # PROCESS workers (reference default, r5): numpy-only transform
        # chain forked across cores — the path that beats the GIL
        def dl_rate_procs(workers):
            return rate_of(DataLoader(
                ds.transform_first(tf), batch_size=batch,
                num_workers=workers, shuffle=True))

        out["dataloader_w1_procs"] = dl_rate_procs(1)
        out["dataloader_w8_procs"] = dl_rate_procs(8)
    out.update(_provenance.device_fields())
    print(json.dumps(out), flush=True)
    _provenance.ledger_append("bench_dataloader", [out])


if __name__ == "__main__":
    main()
