#!/usr/bin/env python
"""Measure allreduce (KVStore push+pull) bandwidth over the device mesh.

The reference ships ``tools/bandwidth/measure.py``: it binds a network's
weight-shaped arrays, runs kvstore push+pull in a loop, and reports per-GPU
bandwidth for a given kvstore type.  The TPU-native equivalent measures the
XLA collective that KVStore lowers to — a ``psum`` over the ICI mesh inside
one jitted module — which is the "KVStore allreduce BW" north-star metric in
BASELINE.md.

Algorithmic bandwidth is reported the standard allreduce way:
``2 * (n-1)/n * bytes / time`` per chip (ring lower bound), plus the naive
``bytes/time`` rate.  On a single chip the collective is the identity; the
tool then reports device-copy bandwidth and says so.

Usage::

    python tools/bandwidth.py [--sizes 1M,16M,64M] [--iters 20] [--dtype float32]

Runs on whatever devices are visible: the real TPU chip(s), or a virtual
8-device CPU mesh under ``./dev.sh``.
"""
from __future__ import annotations

import argparse
import json
import time

import numpy as np


def _parse_size(s):
    s = s.strip().upper()
    mult = 1
    if s.endswith("K"):
        mult, s = 1 << 10, s[:-1]
    elif s.endswith("M"):
        mult, s = 1 << 20, s[:-1]
    elif s.endswith("G"):
        mult, s = 1 << 30, s[:-1]
    return int(float(s) * mult)


def measure(sizes, iters=20, dtype="float32", warmup=3):
    import jax
    import jax.numpy as jnp
    from jax import shard_map
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    devs = np.array(jax.devices())
    n = len(devs)
    mesh = Mesh(devs, ("dp",))
    repl = NamedSharding(mesh, P())
    itemsize = jnp.dtype(dtype).itemsize

    results = []
    for size in sizes:
        elems = max(n, size // itemsize // n * n)  # divisible by mesh
        x_host = np.ones((elems,), dtype=dtype)
        # replicated operand: every chip contributes a FULL gradient copy,
        # exactly what kv.push of a per-device gradient does (kvstore.py →
        # parallel/collectives.py); nbytes below is the per-rank message size
        x = jax.device_put(x_host, repl)
        if n > 1:
            f = jax.jit(shard_map(
                lambda v: jax.lax.psum(v, "dp"),
                mesh=mesh, in_specs=P(), out_specs=P()))
        else:
            # single chip: collective is the identity; time a device round
            # trip instead so the tool still reports a number
            f = jax.jit(lambda v: v + 0)
        out = f(x)
        jax.block_until_ready(out)
        for _ in range(warmup):
            out = f(x)
        jax.block_until_ready(out)
        t0 = time.perf_counter()
        for _ in range(iters):
            out = f(x)
        jax.block_until_ready(out)
        dt = (time.perf_counter() - t0) / iters
        nbytes = elems * itemsize
        algo_bw = (2 * (n - 1) / max(n, 1)) * nbytes / dt if n > 1 else nbytes / dt
        results.append({
            "size_bytes": nbytes,
            "n_devices": n,
            "avg_time_ms": round(dt * 1e3, 4),
            "busbw_GBps": round(algo_bw / 1e9, 3),
            "algbw_GBps": round(nbytes / dt / 1e9, 3),
            "collective": "psum" if n > 1 else "copy (single device)",
        })
    return results


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--sizes", default="1M,16M,64M",
                   help="comma list of payload sizes (K/M/G suffixes)")
    p.add_argument("--iters", type=int, default=20)
    p.add_argument("--dtype", default="float32",
                   help="float32 | bfloat16 (2-bit-compression analog: "
                        "halve bytes on the wire, reference "
                        "gradient_compression.h)")
    args = p.parse_args(argv)
    sizes = [_parse_size(s) for s in args.sizes.split(",")]
    for r in measure(sizes, iters=args.iters, dtype=args.dtype):
        print(json.dumps(r))


if __name__ == "__main__":
    main()
