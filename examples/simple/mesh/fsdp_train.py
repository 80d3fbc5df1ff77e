"""DP×FSDP training on the mesh frontend — declare once, derive all.

The :class:`apex_tpu.parallel.mesh.MeshPlan` showcase (ISSUE 12): one
declaration of the mesh (``--dp``/``--fsdp``, default pure FSDP over
every device) derives the batch sharding, the ZeRO state partitioning
(``--zero 2`` shards optimizer state; ``--zero 3`` shards the params
themselves as flat buckets, gathered per-bucket inside the step), the
AOT-warmed pipelined hot loop, and the elastic checkpoint layout — the
same script drives 1 chip, an 8-device CPU mesh, or a pod:

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python fsdp_train.py --zero 3 --steps 32

    # multi-host, emulated on the CPU backend: one process per "host",
    # env from the launcher (on a TPU host ONE process drives all chips)
    JAX_PLATFORMS=cpu python -m apex_tpu.parallel.multiproc --nproc 2 \
        fsdp_train.py --zero 3
"""

import os as _os
import sys as _sys

try:
    import apex_tpu  # noqa: F401
except ModuleNotFoundError:  # running from a source checkout
    _sys.path.insert(0, _os.path.abspath(_os.path.join(
        _os.path.dirname(__file__), *[_os.pardir] * 3)))

import argparse

import jax
import jax.numpy as jnp
import numpy as np

from apex_tpu import runtime, training
from apex_tpu.parallel import mesh, multiproc

D_in, D_hidden, D_out = 256, 512, 64


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--dp", type=int, default=None,
                    help="data-parallel axis size (default: 1)")
    ap.add_argument("--fsdp", type=int, default=None,
                    help="state-sharding axis size (default: all devices)")
    ap.add_argument("--zero", type=int, default=2, choices=(1, 2, 3))
    ap.add_argument("--steps", type=int, default=32)
    ap.add_argument("--steps-per-call", type=int, default=4)
    ap.add_argument("--batch", type=int, default=16,
                    help="per-data-shard batch size")
    ap.add_argument("--opt-level", default="O2")
    ap.add_argument("--rank", type=int, default=None,
                    help="(set by the multiproc launcher; env wins)")
    args = ap.parse_args(argv)

    # Multi-host: a no-op single-process unless the launcher env is set.
    pid, nproc = multiproc.initialize()

    devices = jax.devices()
    if _os.environ.get("JAX_PLATFORMS", "") == "cpu" and nproc == 1:
        # Single-process CPU-mesh recipe; under multi-host jax.devices()
        # already spans every process and the default device must stay
        # a LOCAL one.
        devices = jax.devices("cpu")
        jax.config.update("jax_default_device", devices[0])
    if args.fsdp is None and args.dp is None:
        plan = mesh.MeshPlan.auto(devices=devices)
    else:
        dp = args.dp or 1
        fsdp = args.fsdp or len(devices) // dp
        plan = mesh.MeshPlan(dp=dp, fsdp=fsdp,
                             devices=devices[:dp * fsdp])
    if multiproc.is_coordinator():
        print(f"{plan} zero={args.zero} opt_level={args.opt_level} "
              f"process {pid}/{nproc}")

    rng = np.random.RandomState(0)
    params = {
        "l1": {"w": jnp.asarray(rng.randn(D_in, D_hidden) * 0.05,
                                jnp.float32),
               "b": jnp.zeros((D_hidden,), jnp.float32)},
        "l2": {"w": jnp.asarray(rng.randn(D_hidden, D_out) * 0.05,
                                jnp.float32),
               "b": jnp.zeros((D_out,), jnp.float32)},
    }

    def loss_fn(p, batch):
        x, y = batch
        h = jax.nn.relu(x @ p["l1"]["w"].astype(x.dtype)
                        + p["l1"]["b"].astype(x.dtype))
        pred = h @ p["l2"]["w"].astype(x.dtype) + p["l2"]["b"].astype(x.dtype)
        return jnp.mean((pred.astype(jnp.float32) - y) ** 2)

    ms = mesh.make_mesh_train_step(loss_fn, training.adam(1e-3), plan,
                                   zero=args.zero,
                                   opt_level=args.opt_level,
                                   loss_scale="dynamic")
    state = ms.init(params)
    if multiproc.is_coordinator():
        led = plan.state_bytes((state.params, state.opt_state))
        print(f"state: {led['global_bytes'] / 1e6:.2f} MB global, "
              f"{led['bytes_per_device'] / 1e6:.2f} MB/device "
              f"(ratio {led['ratio']})")

    K = args.steps_per_call
    pipe = runtime.StepPipeline(ms.step_fn, K, wrap=ms.pipeline_wrap(state))
    # each data shard sees its own stream; the K axis stays unsharded
    local_rows = args.batch * plan.data_world // max(nproc, 1)

    def batches():
        r = np.random.RandomState(1 + pid)
        for _ in range(args.steps):
            yield (r.randn(local_rows, D_in).astype(np.float32),
                   r.randn(local_rows, D_out).astype(np.float32) * 0.1)

    windows = [(plan.device_put_window(w), n) for w, n in
               runtime.window_batches(batches(), K)]
    pipe.warmup(state, windows[0][0])        # AOT: sharded, zero retraces
    reader = runtime.DeferredMetrics()
    for window, n_valid in windows:
        state, metrics = pipe.step_window(state, window, n_valid)
        prev = reader.push(metrics, n_valid)
        if prev is not None and multiproc.is_coordinator():
            host = prev.fetch()
            print(f"step {prev.step:4d}  loss "
                  f"{float(np.ravel(host['loss'])[0]):.6f}")  # jaxlint: disable=J001 -- DeferredMetrics contract: one batched fetch, one dispatch behind the hot loop
    final = reader.last()
    if multiproc.is_coordinator():
        print(f"final loss {float(np.ravel(final['loss'])[-1]):.6f}")
        print("done")


if __name__ == "__main__":
    main()
