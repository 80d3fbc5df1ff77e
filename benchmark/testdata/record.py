"""Records the small traces ``tests/benchmark`` checks ``trace_reduce`` on.

Run on the chip, once, from the root of the checkout::

    python3 benchmark/testdata/record.py <out_dir>

A tiny jitted step (three matrix products and an element-wise tail; with
more than one device also a ``psum`` and a ``ppermute`` under ``shard_map``)
is dispatched eight times inside the harness's own host spans, with a 20 ms
sleep inside one ``bench.fetch`` so that the device has one long idle gap
with a known host span over it.  Writes ``<out_dir>/tiny_<n>chip.xplane.pb``.
"""

import os
import shutil
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def main(out_dir):
    devices = jax.devices()
    n = len(devices)
    if devices[0].platform != "tpu":
        raise SystemExit("record.py needs a TPU")
    mesh = Mesh(np.array(devices), ("data",))

    def step(x, w):
        for _ in range(3):
            x = jnp.tanh(x @ w)
        if n > 1:
            x = jax.lax.psum(x, "data") / n
            x = jax.lax.ppermute(x, "data", [(i, (i + 1) % n) for i in range(n)])
        return x * 0.5 + 1.0

    fn = jax.jit(jax.shard_map(step, mesh=mesh, in_specs=(P("data"), P()),
                               out_specs=P("data")))
    x = jax.device_put(jnp.ones((n * 1024, 1024), jnp.bfloat16),
                       NamedSharding(mesh, P("data")))
    w = jax.device_put(jnp.full((1024, 1024), 1e-3, jnp.bfloat16),
                       NamedSharding(mesh, P()))
    for _ in range(3):
        x = fn(x, w)
    jax.block_until_ready(x)

    trace_dir = os.path.join(out_dir, "raw")
    shutil.rmtree(trace_dir, ignore_errors=True)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    prev = None
    for i in range(8):
        with jax.profiler.TraceAnnotation("bench.dispatch"):
            x = fn(x, w)
        if prev is not None:
            with jax.profiler.TraceAnnotation("bench.fetch"):
                jax.device_get(prev[0, 0])
                if i == 4:
                    jax.block_until_ready(x)
                    time.sleep(0.02)
        prev = x
    with jax.profiler.TraceAnnotation("bench.fetch"):
        jax.block_until_ready(x)
    jax.profiler.stop_trace()
    for base, _, files in os.walk(trace_dir):
        for name in files:
            if name.endswith(".xplane.pb"):
                dst = os.path.join(out_dir, f"tiny_{n}chip.xplane.pb")
                shutil.copy(os.path.join(base, name), dst)
                print(dst, os.path.getsize(dst), "bytes")
    shutil.rmtree(trace_dir, ignore_errors=True)


if __name__ == "__main__":
    main(sys.argv[1])
