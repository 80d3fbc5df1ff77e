"""The one traffic generator: a window of batches made on the device.

A training cell's traffic is a stacked window of ``k`` batches that the timed
loop cycles (``k`` is whatever the program's examples dispatch at once).  A
family turns its configuration and a traffic file into *array specs*::

    {"shape": [256, 224, 224, 3], "dtype": "float32", "dist": "normal"}
    {"shape": [256], "dtype": "int32", "dist": "randint", "low": 0, "high": 1000}

and this module makes the arrays: one jitted program from the seed, written
straight into the sharding the step expects.  Nothing is made on the host
and nothing is uploaded.
"""

import jax
import jax.numpy as jnp


def _draw(key, spec, lead):
    shape = tuple(lead) + tuple(spec["shape"])
    dtype = jnp.dtype(spec["dtype"])
    if spec["dist"] == "normal":
        return jax.random.normal(key, shape, dtype)
    if spec["dist"] == "randint":
        return jax.random.randint(key, shape, spec["low"], spec["high"], dtype)
    raise ValueError(f"unknown dist {spec['dist']!r} in array spec {spec}")


def window(specs, k, seed, sharding=None, tile_from=None):
    """A tuple of device arrays, one per spec, each ``[k, *shape]``.

    ``tile_from=n`` draws only ``n`` rows of the batch axis and repeats them
    to fill it: the *check window*, on which a full-batch step has the loss
    and gradients of the ``n``-row sample (batch statistics included), so the
    timed executable itself can be held against a reference that only has to
    hold ``n`` rows.  Returns ``(arrays, sample)``; ``sample`` is ``None``
    without ``tile_from``, else the un-tiled ``[n, ...]`` arrays.
    """
    def make(key):
        keys = jax.random.split(key, len(specs))
        if tile_from is None:
            return tuple(_draw(kk, s, (k,)) for kk, s in zip(keys, specs)), None
        sample, tiled = [], []
        for kk, s in zip(keys, specs):
            batch = s["shape"][0]
            if batch % tile_from:
                raise ValueError(f"batch {batch} is not a multiple of the "
                                 f"sample of {tile_from}")
            small = _draw(kk, dict(s, shape=[tile_from] + list(s["shape"][1:])), ())
            sample.append(small)
            reps = (k, batch // tile_from) + (1,) * (small.ndim - 1)
            tiled.append(jnp.tile(small[None], reps))
        return tuple(tiled), tuple(sample)

    out_sh = None if sharding is None else (tuple(sharding for _ in specs), None)
    return jax.jit(make, out_shardings=out_sh)(jax.random.PRNGKey(seed))
