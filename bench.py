"""Benchmark harness (parity: reference example/image-classification/
benchmark_score.py + train_imagenet.py --benchmark 1).

Trains ResNet-50 batch-32 on synthetic ImageNet-shaped data with the fused
SPMD TrainStep (one donated XLA computation per step: forward + backward +
SGD update) and prints ONE JSON line:

    {"metric": ..., "value": N, "unit": "img/s", "vs_baseline": N}

vs_baseline is measured against the strongest published reference number:
ResNet-50 train 181.53 img/s on P100 (reference docs/how_to/perf.md:128-137).
"""
import json
import sys
import time

import numpy as np


def bench_resnet50_train(batch=32, image=224, chunk=40, rounds=10,
                         dtype="bfloat16", policy=None):
    import jax
    import mxnet_tpu as mx
    from mxnet_tpu.models import resnet
    from mxnet_tpu.train import TrainStep

    net = resnet.get_symbol(num_classes=1000, num_layers=50,
                            image_shape="3,%d,%d" % (image, image))
    opt = mx.optimizer.SGD(learning_rate=0.1, momentum=0.9,
                           rescale_grad=1.0 / batch, wd=1e-4)
    # cost attribution for the MFU headline: armed only when roofline
    # peaks resolve (the device-kind table on a TPU; MXNET_PEAK_FLOPS in
    # the CPU harness) — the warmup chunk compile below then captures the
    # fused program's FLOP count.  Peaks unset keeps this strictly off.
    from mxnet_tpu import cost as cost_mod
    from mxnet_tpu import sanitize as san
    if cost_mod.enabled():
        san.cost_arm()
    # policy (bench default: the bf16 AMP policy unless MXNET_AMP=0) adds
    # f32 master weights + dynamic loss scaling on top of the bf16 cast
    if policy is not None:
        ts = TrainStep(net, opt, policy=policy)
    else:
        ts = TrainStep(net, opt, dtype=dtype)
    params, state, aux = ts.init(
        {"data": (batch, 3, image, image)}, {"softmax_label": (batch,)})

    rng = np.random.RandomState(0)
    data = rng.uniform(-1, 1, (batch, 3, image, image)).astype(np.float32)
    label = rng.randint(0, 1000, (batch,)).astype(np.float32)
    batch_dev = ts.shard_batch({"data": data, "softmax_label": label})

    # chunks of `chunk`+1 steps fused into one XLA program (lax.scan): the
    # TPU-idiomatic training loop — no host dispatch between steps
    params, state, aux, outs = ts.run_steps(params, state, aux, batch_dev,
                                            chunk)
    # sync by fetching ONE scalar (not the logits): it waits for the whole
    # step chain, and this warm-up also compiles the tiny slice program, so
    # the timed sync below adds one scalar copy to rounds*(chunk+1) steps
    np.asarray(outs[0][0, 0])

    # telemetry mode (MXNET_TELEMETRY / MXNET_METRICS_PORT set): each round
    # is synced and fed into a per-step latency histogram, so the bench
    # JSON carries p50/p99, not just the mean.  The per-round sync is the
    # price of the distribution — img/s is then measured over the synced
    # loop, so the headline number stays honest about what was timed.
    from mxnet_tpu import telemetry as tel
    telem = tel.enabled()
    t0 = time.perf_counter()
    for _ in range(rounds):
        r0 = time.perf_counter() if telem else 0.0
        params, state, aux, outs = ts.run_steps(params, state, aux,
                                                batch_dev, chunk)
        if telem:
            np.asarray(outs[0][0, 0])
            tel.histogram("bench.step", (time.perf_counter() - r0)
                          / (chunk + 1) * 1e6, chunk=chunk)
    np.asarray(outs[0][0, 0])
    dt = time.perf_counter() - t0
    img_per_sec = batch * (chunk + 1) * rounds / dt

    # MFU over the timed region: the captured chunk program's FLOPs
    # (covers chunk+1 fused steps) times the dispatches, over measured
    # wall time, against the resolved peak.  None when peaks are unset.
    mfu = None
    if cost_mod.enabled():
        row = next((r for n, r in san.cost_ledger().items()
                    if n.startswith("train_step.run_steps")), None)
        if row and row.get("flops"):
            mfu = cost_mod.mfu(row["flops"] * rounds, dt)

    # input-pipeline measurement round (outside the timed region): re-stage
    # the host batch for each chunk through the depth-2 device prefetcher
    # vs synchronously, and stamp the measured data_wait share into the
    # BENCH json.  Reuses the already-compiled chunk program.
    pipeline = measure_data_wait(
        ts, params, state, aux,
        {"data": data, "softmax_label": label}, chunk)
    return img_per_sec, pipeline, mfu


def measure_data_wait(ts, params, state, aux, host_batch, chunk, chunks=2,
                      stage=None):
    """Data-wait share of a staged chunk pipeline, prefetch on vs off.

    Runs ``chunks + 1`` scan chunks per mode (the first is the cold
    pipeline fill and is excluded), staging ``host_batch`` to the device
    fresh for every chunk: with the depth-2 ``DevicePrefetchIter`` chunk
    N+1's host->device transfer overlaps chunk N's compute, without it the
    transfer serialises in front of each chunk.  Each measured chunk feeds
    the ``data_wait`` and ``step`` telemetry spans (when a session is
    recording), so the overlap win is visible in the standard step-time
    breakdown.  ``stage`` defaults to a blocking ``TrainStep.shard_batch``
    (the block runs on the producer thread in prefetch mode — that IS the
    overlap).  Returns ``{"data_wait_share": .., "data_wait_share_sync":
    .., "device_prefetch": depth}`` — prefetch-off runs
    (MXNET_DEVICE_PREFETCH=0) only measure and stamp the sync share."""
    import jax
    from mxnet_tpu import telemetry as tel
    from mxnet_tpu.io import DevicePrefetchIter, device_prefetch_depth

    if stage is None:
        def stage(b):
            staged = ts.shard_batch(b)
            jax.block_until_ready(list(staged.values()))
            return staged
    depth = device_prefetch_depth()
    carry = [params, state, aux]

    def one_round(prefetch):
        src = (dict(host_batch) for _ in range(chunks + 1))
        it = DevicePrefetchIter(src, stage=stage, depth=depth) if prefetch \
            else iter(stage(b) for b in src)
        waits, walls = [], []
        first = True
        while True:
            wall = time.time()
            t0 = time.perf_counter()
            try:
                staged = next(it)
            except StopIteration:
                break
            wait = time.perf_counter() - t0
            carry[0], carry[1], carry[2], outs = ts.run_steps(
                carry[0], carry[1], carry[2], staged, chunk)
            np.asarray(outs[0][0, 0])   # drain: the span covers device time
            total = time.perf_counter() - t0
            if first:
                first = False   # cold fill: no overlap possible yet
                continue
            waits.append(wait)
            walls.append(total)
            tel.record_span("data_wait", wall, wait, cat="bench",
                            prefetch=int(prefetch))
            tel.record_span("step", wall, total, cat="bench",
                            prefetch=int(prefetch))
        return (sum(waits) / sum(walls)) if walls and sum(walls) else 0.0

    started = False
    if not tel.enabled():
        tel.start()   # in-memory session: default runs still stamp shares
        started = True
    try:
        share_sync = one_round(False)
        stats = {"data_wait_share_sync": round(share_sync, 4),
                 "device_prefetch": depth}
        if depth:
            stats["data_wait_share"] = round(one_round(True), 4)
        else:
            stats["data_wait_share"] = stats["data_wait_share_sync"]
    finally:
        if started:
            tel.stop()
            tel.reset()
    return stats


def bench_serving(n_clients=24, requests_per_client=40, max_batch=16,
                  wait_ms=2.0, dim=256, hidden=512, classes=64, seed=0):
    """Serving round: N synthetic concurrent clients against the dynamic
    bucketed-batching server (mxnet_tpu/serving.py) vs the serialized
    one-at-a-time baseline (a single batch-1 ``Predictor`` behind a lock
    — the pre-serving inference story), at equal request count.  Both
    bind on ``mx.tpu(0)``: the chip, or a virtual host device when a test
    imports this function under the JAX_PLATFORMS=cpu harness.

    Clients fire their next request as soon as the previous one resolves,
    so the batcher sees continuous load and steady-state batch size
    approaches the outstanding-client count (capped at ``max_batch``).
    Returns the record stamped into BENCH json under ``"serving"``:
    client-observed ``serve_qps`` / ``serve_p50_ms`` / ``serve_p99_ms``
    and the batched-vs-serialized ratio ``serve_speedup`` as gated
    metrics (``tools/run_compare.py --check``, like the training
    numbers); the serialized baseline's absolute qps and the mean batch
    occupancy (requests / bucket slots) ride the ``config`` context
    block — informative, never gated."""
    import threading
    import mxnet_tpu as mx
    from mxnet_tpu import serving
    from mxnet_tpu.predictor import Predictor

    data = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(data, num_hidden=hidden, name="sfc1")
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.FullyConnected(net, num_hidden=hidden, name="sfc2")
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.FullyConnected(net, num_hidden=classes, name="sfc3")
    net = mx.sym.SoftmaxOutput(net, name="softmax")
    rng = np.random.RandomState(seed)
    shapes, _, _ = net.infer_shape(data=(1, dim))
    params = {n: mx.nd.array((rng.randn(*s) * 0.05).astype(np.float32))
              for n, s in zip(net.list_arguments(), shapes)
              if n not in ("data", "softmax_label")}
    x = rng.uniform(-1, 1, (n_clients, requests_per_client, dim)) \
        .astype(np.float32)

    def drive(call):
        """Client-observed latencies + wall time at equal request count.
        A failed client invalidates the round loudly — a record computed
        over silently-dropped requests would break the equal-request-
        count premise the speedup gate stands on."""
        lats = [[] for _ in range(n_clients)]
        errors = []

        def client(ci):
            try:
                for ri in range(requests_per_client):
                    t0 = time.perf_counter()
                    call(x[ci, ri])
                    lats[ci].append(time.perf_counter() - t0)
            except Exception as exc:   # surfaced after join
                errors.append(exc)

        threads = [threading.Thread(target=client, args=(ci,))
                   for ci in range(n_clients)]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        wall = time.perf_counter() - t0
        if errors:
            raise errors[0]
        flat = sorted(v for l in lats for v in l)
        n = len(flat)
        assert n == n_clients * requests_per_client
        return {"qps": n / wall, "p50_ms": flat[n // 2] * 1e3,
                "p99_ms": flat[min(n - 1, int(n * 0.99))] * 1e3}

    # serialized baseline: every request pays its own batch-1 forward,
    # one at a time (warmed so the jit compile is outside the clock)
    p1 = Predictor(net, params, {"data": (1, dim)}, dev_type="tpu")
    p1.forward(data=x[0, 0][None])
    p1.get_output(0)
    lock = threading.Lock()

    def serial_call(row):
        with lock:
            p1.forward(data=row[None])
            p1.get_output(0)

    serial = drive(serial_call)

    model = serving.ServedModel(net, params, {"data": (dim,)}, name="bench",
                                max_batch=max_batch, max_wait_ms=wait_ms,
                                dev_type="tpu")
    model.warm()   # whole ladder compiled before the clock starts
    batched = drive(lambda row: model.predict({"data": row}, timeout=60.0))
    stats = model.stats()
    model.close()

    # gated metrics at the top level (run_compare --check); context that
    # must NOT trip the gate — the serialized baseline's noise-sensitive
    # absolute qps, and occupancy (which legitimately drops when a faster
    # forward drains the queue before buckets fill) — rides config
    return {
        "serve_qps": round(batched["qps"], 1),
        "serve_p50_ms": round(batched["p50_ms"], 3),
        "serve_p99_ms": round(batched["p99_ms"], 3),
        "serve_speedup": round(batched["qps"] / serial["qps"], 2),
        "config": {"clients": n_clients,
                   "requests": n_clients * requests_per_client,
                   "max_batch": max_batch, "wait_ms": wait_ms,
                   "model": "mlp%dx%d" % (dim, hidden),
                   "serve_qps_serial": round(serial["qps"], 1),
                   "serve_batch_occupancy": round(stats["occupancy"], 4),
                   "batches_by_bucket": stats["batches_by_bucket"]},
    }


def telemetry_summary():
    """Tail-latency summary from the live telemetry registry (None while
    telemetry is off): p50/p99/mean per step-like histogram — the bench's
    own ``bench.step`` plus whatever a fit-based bench left behind — and
    the data-wait share of step wall time.  Embedded into the emitted
    BENCH_*.json so the perf trajectory carries tail latency."""
    from mxnet_tpu import telemetry as tel
    if not tel.enabled():
        return None
    hists = tel.histograms()
    out = {}
    for name in ("bench.step", "step", "fused_step", "train_step"):
        h = hists.get(name)
        if not h or not h.get("count"):
            continue
        out[name] = {
            "count": h["count"],
            "mean_ms": round(h["sum"] / h["count"] / 1e3, 3),
            "p50_ms": round(tel.quantile(name, 0.50) / 1e3, 3),
            "p99_ms": round(tel.quantile(name, 0.99) / 1e3, 3),
        }
    dw, st = hists.get("data_wait"), hists.get("step")
    if dw and st and st.get("sum"):
        out["data_wait_share"] = round(dw["sum"] / st["sum"], 4)
    return out or None


def run_meta(config):
    """Run identity stamped into the emitted JSON: the benchmark config,
    the launch-contract world size/rank, and — when telemetry is recording
    — the path of this process's event/scalar stream.  That last field is
    what lets ``tools/run_compare.py`` chain from a BENCH_*.json record to
    the training curves behind it (same-directory relative paths are
    resolved against the BENCH file)."""
    from mxnet_tpu import telemetry as tel
    from mxnet_tpu.base import get_env
    meta = {
        "config": dict(config),
        "world_size": int(get_env("MXTPU_PROCESS_COUNT", 1)),
        "rank": get_env("MXTPU_PROCESS_ID"),
    }
    path = tel.sink_path()
    if path:
        meta["telemetry_scalars"] = path
    return meta


def device_stamp():
    """The device every number of this run belongs to, as jax reports it.
    A benchmark measures the chip or nothing: off a TPU this raises
    instead of timing XLA's CPU backend under a device metric's name."""
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(
            "bench.py measures a TPU; jax.devices()[0].platform is %r "
            "(JAX_PLATFORMS=%r).  Run it on the chip machine; the CPU "
            "harness is for tests." % (dev.platform,
                                       jax.config.jax_platforms))
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(jax.devices())}


def main():
    from mxnet_tpu import amp as amp_mod
    from mxnet_tpu.base import enable_compile_cache
    enable_compile_cache()
    device = device_stamp()
    # bench default: train with the bf16 mixed-precision policy (master
    # f32 weights + dynamic loss scaling); MXNET_AMP=0 restores the pure
    # bf16-cast step, MXNET_AMP/MXNET_LOSS_SCALE tune it
    policy = amp_mod.resolve_policy(default=amp_mod.Policy("bfloat16"))
    cfg = dict(batch=32, image=224, chunk=40, rounds=10, dtype="bfloat16")
    img_per_sec, pipeline, mfu = bench_resnet50_train(policy=policy, **cfg)
    cfg["amp"] = policy.describe() if policy is not None else None
    baseline_p100 = 181.53
    # efficiency denominators (null-safe: peaks unset -> mfu None, no
    # cost capture -> compile seconds None) so the perf trajectory
    # finally carries an MFU next to its img/s headline
    from mxnet_tpu import sanitize as san
    comp = san.compile_seconds()
    rec = {
        "metric": "resnet50_train_img_per_sec_b32",
        "value": round(img_per_sec, 2),
        "unit": "img/s",
        "device": device,
        "vs_baseline": round(img_per_sec / baseline_p100, 3),
        "mfu": round(mfu, 4) if mfu is not None else None,
        "compile_seconds": comp.get("total") if comp else None,
        "meta": run_meta(cfg),
    }
    if mfu is not None:
        # structured twin of the headline fields: run_compare ingests
        # the cost block's numerics as gated metrics (mfu up-hint,
        # compile_sec down-hint)
        rec["cost"] = {"mfu": round(mfu, 4)}
        if comp:
            rec["cost"]["compile_sec"] = round(comp["total"], 3)
    summary = telemetry_summary() or {}
    # measured input-pipeline shares (prefetch on vs synchronous staging)
    summary.update(pipeline)
    rec["telemetry"] = summary
    # numerics-monitor context (null-safe: MXNET_MONITOR unset -> None).
    # The bench's scan-fused run_steps chain is deliberately unmonitored
    # (docs/observability.md), so an armed monitor rides as CONTEXT —
    # what was sampled outside the timed region — never a gated metric;
    # the gated overhead number lives in MULTICHIP_NUM_* records
    from mxnet_tpu import numerics as num_mod
    mspec = num_mod.spec()
    rec["monitor"] = None if mspec is None else {
        "every_n": mspec.every_n,
        "stats": list(mspec.stats),
        "sampled": len(num_mod.history()),
        "last_global_grad_norm": num_mod.last_global_norm(),
    }
    # serving round: concurrent batched server vs serialized baseline
    # (run_compare ingests the numeric fields as gated metrics)
    rec["serving"] = bench_serving()
    print(json.dumps(rec))


if __name__ == "__main__":
    sys.exit(main())
