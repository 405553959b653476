#!/usr/bin/env python
"""Communication / memory bandwidth measurement.

Reference analog: ``tools/bandwidth/measure.py`` (kvstore comm bandwidth
per GPU).  TPU-native version measures the three lanes that matter here:

- host -> device staging (device_put), the input-pipeline lane;
- device -> host readback (device_get), the eval/checkpoint lane;
- on-device copy bandwidth (HBM), via a jitted identity-plus;
- all-reduce bandwidth over the mesh (ICI on hardware, shared-memory on
  the virtual CPU mesh) — the kvstore='tpu' gradient lane, using the
  standard 2(n-1)/n ring-bytes accounting.

    python tools/bandwidth.py --mb 64 --iters 10
    XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
        python tools/bandwidth.py --mesh dp=8
"""
import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _fence(x):
    """Host read — a completion fence that also drains the dispatch
    queue."""
    import numpy as onp

    return onp.asarray(x).ravel()[0]


def measure(mb=64, iters=10, mesh_spec=""):
    import jax
    import jax.numpy as jnp
    import numpy as onp

    n = mb * (1 << 20) // 4
    host = onp.random.RandomState(0).rand(n).astype(onp.float32)
    results = {}

    # host -> device
    dev = jax.device_put(host)
    _fence(dev)
    t0 = time.perf_counter()
    for _ in range(iters):
        dev = jax.device_put(host)
    _fence(dev)
    dt = time.perf_counter() - t0
    results["h2d_GBps"] = mb * iters / 1024 / dt

    # device -> host: read a FRESH device buffer each iteration — jax
    # caches the host copy of an unchanged array, which would measure a
    # memcpy (or nothing) instead of the transfer.  The distinct buffers
    # are produced (and completed) BEFORE the timed region so readback is
    # the only thing on the clock — bumping inside the loop would mix a
    # kernel dispatch+execute into the figure.
    bump = jax.jit(lambda x, k: x + k)
    # chunked so the pool of distinct live buffers stays bounded (~2 GiB)
    # regardless of --mb/--iters; per-chunk: produce + fence OUTSIDE the
    # clock, then time only the readbacks and sum across chunks
    chunk = max(1, min(iters, (2 << 10) // max(mb, 1)))
    dt = 0.0
    done = 0
    while done < iters:
        k = min(chunk, iters - done)
        bufs = [bump(dev, float(done + i)) for i in range(k)]
        # drain the dispatch queue with ONE host read of a sentinel, then
        # block on each buffer WITHOUT
        # reading it — _fence(b) would populate jax's cached host copy
        # and turn the timed readback into a no-op
        _fence(bump(dev, -1.0))
        for b in bufs:
            b.block_until_ready()
        t0 = time.perf_counter()
        for b in bufs:
            out = onp.asarray(b)
        dt += time.perf_counter() - t0
        del bufs
        done += k
    results["d2h_GBps"] = mb * iters / 1024 / dt

    # on-device (read+write one buffer each way)
    f = jax.jit(lambda x: x + 1.0)
    _fence(f(dev))
    t0 = time.perf_counter()
    y = dev
    for _ in range(iters):
        y = f(y)
    _fence(y)
    dt = time.perf_counter() - t0
    results["hbm_GBps"] = 2 * mb * iters / 1024 / dt

    # all-reduce over the device mesh: a REAL psum via shard_map, so every
    # timed iteration moves bytes across devices (a plain jitted reduce
    # would produce a replicated output and communicate only once)
    if mesh_spec:
        from jax import shard_map
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

        ndev = 1
        for part in mesh_spec.split(","):
            _, v = part.split("=")
            ndev *= int(v)
        devices = jax.devices()[:ndev]
        if len(devices) < ndev:
            raise SystemExit(f"--mesh wants {ndev} devices, "
                             f"have {len(devices)}")
        flat = Mesh(devices, ("all",))
        # kvstore-gradient semantics: EVERY device holds a full mb-sized
        # gradient; the all-reduce moves 2(n-1)/n * mb per device.  Shape
        # (ndev, n) sharded on the leading axis gives each device one
        # full-payload row.
        sharding = NamedSharding(flat, P("all", None))
        # one row per device, one row on the host — device_put of a
        # broadcast view would materialize ndev full copies host-side
        row = host[None, :]
        sharded = jax.make_array_from_callback(
            (ndev, n), sharding, lambda idx: row)
        ar = jax.jit(shard_map(
            lambda x: jax.lax.psum(x, "all"), mesh=flat,
            in_specs=P("all", None), out_specs=P(None, None)))
        _fence(ar(sharded))
        t0 = time.perf_counter()
        for _ in range(iters):
            out = ar(sharded)               # fresh psum each iteration
        _fence(out)
        dt = time.perf_counter() - t0
        ring_bytes = 2 * (ndev - 1) / ndev * mb * iters
        results["allreduce_GBps"] = ring_bytes / 1024 / dt
        results["mesh"] = mesh_spec

    return results


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--mb", type=int, default=64,
                    help="payload size in MiB")
    ap.add_argument("--iters", type=int, default=10)
    ap.add_argument("--mesh", default="",
                    help="axis spec for the all-reduce lane, e.g. dp=8")
    args = ap.parse_args()
    import json

    import jax

    res = measure(args.mb, args.iters, args.mesh)
    res["platform"] = jax.default_backend()
    res["payload_mb"] = args.mb
    # 4 decimals: tiny payloads on a loaded host must not round to 0.0
    print(json.dumps({k: (round(v, 4) if isinstance(v, float) else v)
                      for k, v in res.items()}))


if __name__ == "__main__":
    main()
