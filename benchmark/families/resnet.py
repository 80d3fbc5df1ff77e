"""ResNet family: ``examples/imagenet/main_amp.py``'s own ``parse`` and ``build``.

The adapter passes the example only what defines the work (architecture,
global batch, image size, opt level, loss scaling, the SGD recipe,
``--sync_bn``) and takes back the objects the example trains with.  The
example exposes no ``loss_fn``, so the system's gradient is read from the
timed executable itself: two steps on the tiled check window from the
initial state, and the SGD recipe solved for the gradient of the second.
"""

import os
import sys
import types

import jax
import numpy as np

import benchmark
from benchmark import compare, flops, traffic_gen
from benchmark.reference import resnet as reference

_EXAMPLE_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(benchmark.__file__))), "examples", "imagenet")


def build(config, traffic, devices, seed):
    if _EXAMPLE_DIR not in sys.path:
        sys.path.insert(0, _EXAMPLE_DIR)
    import main_amp as imagenet

    m, r = config["model"], config["recipe"]
    n_dev = len(devices)
    batch = traffic["batch_per_chip"] * n_dev
    argv = ["--synthetic", "-a", m["arch"], "-b", str(batch),
            "--image-size", str(m["image_size"]),
            "--opt-level", r["opt_level"], "--loss-scale", r["loss_scale"],
            "--lr", str(r["lr_per_256"]), "--momentum", str(r["momentum"]),
            "--weight-decay", str(r["weight_decay"])]
    if traffic.get("sync_bn"):
        argv.append("--sync_bn")
    print(f"examples/imagenet/main_amp.py {' '.join(argv)}", flush=True)
    run = imagenet.build(imagenet.parse(argv))
    if run.n_dev != n_dev:
        raise SystemExit(f"the example built for {run.n_dev} devices, the "
                         f"cell has {n_dev}")
    pipe, k = run.pipe, run.spc

    size = m["image_size"]
    specs = [{"shape": [batch, size, size, 3], "dtype": "float32",
              "dist": "normal"},
             {"shape": [batch], "dtype": "int32", "dist": "randint",
              "low": 0, "high": m["num_classes"]}]
    window, _ = traffic_gen.window(specs, k, seed, run.data_sh)
    check_window, sample = traffic_gen.window(
        specs, k, seed + 1, run.data_sh, tile_from=traffic["check_sample"])

    cell = types.SimpleNamespace(
        state=run.state, pipe=pipe, k=k, window=window,
        samples_per_step=batch,
        flops_per_step=flops.resnet_train(m, batch))
    kept = {}

    def first_dispatch():
        """Two drained steps from the initial state.  With momentum SGD
        and zero initial momentum, ``m1 = (p0 - p1) / lr`` and
        ``p2 = p1 - lr (mu m1 + g2 + wd p1)``, so the gradient at ``p1``
        follows from three copies of the parameters and the recipe.  The
        second step is used because the first sits at the zero-initialised
        last BatchNorm scale of every block, where the gradient of every
        convolution inside a block is exactly zero."""
        if k != 1:
            raise SystemExit(
                f"the ResNet check solves single optimizer steps; the "
                f"example now dispatches {k} per call and the check has to "
                f"follow in a benchmark PR")
        params = [jax.device_get(cell.state.params)]
        losses = []
        for _ in range(2):
            cell.state, metrics = pipe.step_window(cell.state, check_window, k)
            host = jax.device_get(metrics)
            if np.ravel(host["overflow"])[0]:
                raise SystemExit("the loss scaler skipped a check step")
            losses.append(float(np.ravel(host["loss"])[0]))
            params.append(jax.device_get(cell.state.params))
        kept.update(params=params, loss=losses[1])

    def check():
        p0, p1, p2 = kept["params"]
        lr = r["lr_per_256"] * batch / 256.0
        mu, wd = r["momentum"], r["weight_decay"]
        sys_grads = jax.tree_util.tree_map(
            lambda a, b, c: (b - c) / lr - mu * (a - b) / lr - wd * b,
            p0, p1, p2)
        # what the subtraction of two float32 parameters cannot resolve
        noise = jax.tree_util.tree_map(
            lambda b: (1 + mu) * np.spacing(np.abs(b).max()) / lr, p1)
        ref_loss, ref_grads = reference.loss_and_grads(
            jax.device_put(p1, devices[0]),
            *jax.device_put(sample, devices[0]))
        return compare.verdict(kept["loss"], float(ref_loss), sys_grads,
                               ref_grads, config["tolerance"], noise=noise)

    cell.first_dispatch, cell.check = first_dispatch, check
    return cell
