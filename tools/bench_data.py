"""Real-data pipeline benchmark (VERDICT r2 #4; parity: the reference's
north star of ImageNet training *from data* with the multithreaded decode
pipeline keeping the accelerator fed, src/io/iter_image_recordio.cc:149-481).

Measures, on one host + one TPU chip:
1. ImageRecordIter alone: JPEG decode + augment + batch img/s at
   --threads decoder threads (no device work).
2. ResNet-50 train-from-RecordIO end to end: PrefetchingIter staging +
   run_steps(stacked=True) fused minibatch-SGD chunks.

Usage: python tools/bench_data.py [--images 1536] [--threads 8] [--batch 32]
"""
import argparse
import io
import json
import os
import sys
import tempfile
import time

import numpy as np

sys.path.insert(0, ".")


def build_dataset(rec_path, num_images, size=256, quality=85,
                  pass_through=False):
    """Pack synthetic images into RecordIO (JPEG, or raw pass-through
    records that skip decode at read time — im2rec --pass-through)."""
    from PIL import Image
    from mxnet_tpu import recordio
    rec = recordio.MXRecordIO(rec_path, "w")
    rng = np.random.RandomState(0)
    base = rng.randint(0, 255, (size, size, 3), dtype=np.uint8)
    for i in range(num_images):
        # cheap variety without re-randomising every pixel
        img = np.roll(base, shift=int(rng.randint(0, size)), axis=0)
        img = np.roll(img, shift=int(rng.randint(0, size)), axis=1)
        header = recordio.IRHeader(0, float(i % 1000), i, 0)
        if pass_through:
            rec.write(recordio.pack_raw_img(header, img))
        else:
            buf = io.BytesIO()
            Image.fromarray(img).save(buf, format="JPEG", quality=quality)
            rec.write(recordio.pack(header, buf.getvalue()))
    rec.close()


def bench_loader(rec_path, batch, threads, epochs=3):
    from mxnet_tpu import image as image_mod
    it = image_mod.ImageRecordIter(
        path_imgrec=rec_path, data_shape=(3, 224, 224), batch_size=batch,
        shuffle=True, rand_crop=True, rand_mirror=True,
        preprocess_threads=threads)
    n = 0
    for _ in it:           # warm one epoch (thread pool spin-up)
        n += batch
    it.reset()
    t0 = time.perf_counter()
    total = 0
    for _ in range(epochs):
        for _ in it:
            total += batch
        it.reset()
    return total / (time.perf_counter() - t0)


def _u8_resnet():
    """ResNet-50 composed on a device-side prologue: the data input is raw
    uint8 pixels, cast + normalised ((x-127.5)/127.5) in bf16 ON DEVICE —
    the host ships 1/4 the bytes and does no float math (parity: the
    reference's ImageRecordUInt8Iter feeding path,
    iter_image_recordio.cc:481)."""
    import mxnet_tpu as mx
    from mxnet_tpu.models import resnet
    u8 = mx.sym.Variable("data")
    # cast straight to the compute dtype: under TrainStep(dtype="bfloat16")
    # the params are bf16 and the graph must match
    prep = (mx.sym.Cast(u8, dtype="bfloat16") - 127.5) * (1.0 / 127.5)
    return resnet.get_symbol(num_classes=1000, num_layers=50,
                             image_shape="3,224,224", data=prep)


def bench_e2e(rec_path, batch, threads, chunk=8, chunks=12, uint8=False):
    """ResNet-50 train-from-RecordIO: stacked run_steps chunks with
    DOUBLE-BUFFERED device staging — chunk k+1 is device_put (async) while
    chunk k computes, so host->device transfer overlaps device compute."""
    import jax
    import mxnet_tpu as mx
    from mxnet_tpu import image as image_mod
    from mxnet_tpu.io import PrefetchingIter
    from mxnet_tpu.models import resnet
    from mxnet_tpu.train import TrainStep

    it = image_mod.ImageRecordIter(
        path_imgrec=rec_path, data_shape=(3, 224, 224), batch_size=batch,
        shuffle=True, rand_crop=True, rand_mirror=True,
        preprocess_threads=threads,
        dtype="uint8" if uint8 else "float32")
    it = PrefetchingIter(it)
    net = _u8_resnet() if uint8 else resnet.get_symbol(
        num_classes=1000, num_layers=50, image_shape="3,224,224")
    opt = mx.optimizer.SGD(learning_rate=0.1, momentum=0.9,
                           rescale_grad=1.0 / batch, wd=1e-4)
    ts = TrainStep(net, opt, dtype="bfloat16")
    params, state, aux = ts.init({"data": (batch, 3, 224, 224)},
                                 {"softmax_label": (batch,)})
    dev = jax.devices()[0]

    def next_stack(k):
        data, label = [], []
        nonlocal it
        while len(data) < k:
            try:
                b = next(it)
            except StopIteration:
                it.reset()
                continue
            data.append(np.asarray(b.data[0].asnumpy()))
            label.append(np.asarray(b.label[0].asnumpy()))
        # async stage: device_put returns immediately, the transfer runs
        # while the previous chunk's compute is still in flight
        return {"data": jax.device_put(np.stack(data), dev),
                "softmax_label": jax.device_put(np.stack(label), dev)}

    st = next_stack(chunk + 1)          # warm: compile the stacked chunk
    params, state, aux, outs = ts.run_steps(params, state, aux, st, chunk,
                                            stacked=True)
    np.asarray(outs[0])
    nxt = next_stack(chunk + 1)
    t0 = time.perf_counter()
    for _ in range(chunks):
        st, nxt = nxt, None
        params, state, aux, outs = ts.run_steps(params, state, aux, st,
                                                chunk, stacked=True)
        nxt = next_stack(chunk + 1)     # overlaps the in-flight chunk
    np.asarray(outs[0])
    return batch * (chunk + 1) * chunks / (time.perf_counter() - t0)


def bench_feed_rate(rec_path, batch, threads, uint8=True, batches=80):
    """Sustained feeding rate of the full pipeline WITHOUT model compute:
    records -> decode/augment pool -> batch -> device staging -> a trivial
    on-device reduction.  This is 'can the chip be fed' isolated from both
    the model's FLOPs and (on a co-located host) the link."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu import image as image_mod
    from mxnet_tpu.io import PrefetchingIter
    it = image_mod.ImageRecordIter(
        path_imgrec=rec_path, data_shape=(3, 224, 224), batch_size=batch,
        shuffle=True, rand_crop=True, rand_mirror=True,
        preprocess_threads=threads,
        dtype="uint8" if uint8 else "float32")
    it = PrefetchingIter(it)
    consume = jax.jit(lambda x: jnp.sum(x, dtype=jnp.int32)
                      if uint8 else jnp.sum(x))
    dev = jax.devices()[0]
    # warm: compile the consumer + first transfer outside the timed window
    warm = next(it)
    np.asarray(consume(jax.device_put(
        np.asarray(warm.data[0].asnumpy()), dev)))
    out = None
    n = 0
    t0 = time.perf_counter()
    while n < batches * batch:
        try:
            b = next(it)
        except StopIteration:
            it.reset()
            continue
        out = consume(jax.device_put(np.asarray(b.data[0].asnumpy()), dev))
        n += batch
    np.asarray(out)
    return n / (time.perf_counter() - t0)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--images", type=int, default=1536)
    ap.add_argument("--threads", type=int, default=8)
    ap.add_argument("--batch", type=int, default=32)
    ap.add_argument("--pass-through", action="store_true",
                    help="raw records (no JPEG decode at read time)")
    ap.add_argument("--uint8", action="store_true",
                    help="stage raw uint8 batches, normalise on device "
                         "(orthogonal to the record format)")
    args = ap.parse_args()
    import bench
    from mxnet_tpu.base import enable_compile_cache
    enable_compile_cache()
    print(json.dumps({"device": bench.device_stamp()}), flush=True)
    with tempfile.TemporaryDirectory() as td:
        rec = os.path.join(td, "data.rec")
        t0 = time.perf_counter()
        build_dataset(rec, args.images, pass_through=args.pass_through)
        pack_s = time.perf_counter() - t0
        loader = bench_loader(rec, args.batch, args.threads)
        print(json.dumps({"metric": "imagerecorditer_img_per_sec"
                                    + ("_pass_through" if args.pass_through
                                       else ""),
                          "value": round(loader, 1), "unit": "img/s",
                          "threads": args.threads,
                          "pack_seconds": round(pack_s, 1)}), flush=True)
        feed = bench_feed_rate(rec, args.batch, args.threads, uint8=True)
        print(json.dumps({"metric": "pipeline_feed_rate_uint8",
                          "value": round(feed, 1), "unit": "img/s",
                          "threads": args.threads}), flush=True)
        e2e = bench_e2e(rec, args.batch, args.threads, uint8=args.uint8)
        print(json.dumps({"metric": "resnet50_train_from_recordio_b32"
                                    + ("_uint8" if args.uint8 else ""),
                          "value": round(e2e, 1), "unit": "img/s",
                          "threads": args.threads}), flush=True)


if __name__ == "__main__":
    main()
