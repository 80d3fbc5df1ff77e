"""The comparison that decides ``correct``: the system against the plain reference.

Three numbers, each with a tolerance the configuration's file states and
gives the reason for: the loss (relative), the cosine between the system's
and the reference's gradient over all parameters, and the ratio of their
norms.  A fourth guards what a global cosine cannot see, a small leaf that
is wrong beside large leaves that are right: every single leaf's error
``|s - r|`` has to stay within ``leaf_rel`` of that leaf's own norm, or
within ``leaf_abs`` of the whole gradient's norm (a leaf whose true gradient
cancels to zero, such as a key bias under softmax, has no scale of its own),
or within the noise the system's gradient was read with.
"""

import jax
import numpy as np


def _flat(tree):
    return [(jax.tree_util.keystr(path), np.asarray(leaf, np.float64).ravel())
            for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]]


def _norm(x):
    return float(np.sqrt(x @ x))


def verdict(sys_loss, ref_loss, sys_grads, ref_grads, tol, noise=None):
    """``{"correct": bool, ...the numbers behind it}``.

    ``tol``: ``loss_rel``, ``grad_cos_min``, ``grad_norm_ratio`` (low, high),
    ``leaf_rel``, ``leaf_abs``.  ``noise``: per leaf, the absolute error of
    one element of the system's gradient as it was read out (a tree like the
    gradients, of scalars), or None where it was read exactly.
    """
    sys_l = _flat(jax.device_get(sys_grads))
    ref_l = _flat(jax.device_get(ref_grads))
    if [n for n, _ in sys_l] != [n for n, _ in ref_l]:
        raise ValueError("the system's and the reference's gradients are "
                         "different trees")
    noise_l = ([float(x[0]) for _, x in _flat(noise)] if noise is not None
               else [0.0] * len(ref_l))
    dot = sum(float(s @ r) for (_, s), (_, r) in zip(sys_l, ref_l))
    ns = np.sqrt(sum(float(s @ s) for _, s in sys_l))
    nr = np.sqrt(sum(float(r @ r) for _, r in ref_l))
    worst = (None, 0.0)
    for (name, s), (_, r), eps in zip(sys_l, ref_l, noise_l):
        allowed = max(tol["leaf_rel"] * _norm(r), tol["leaf_abs"] * nr,
                      3 * eps * np.sqrt(r.size))
        ratio = _norm(s - r) / max(allowed, 1e-300)
        if ratio > worst[1]:
            worst = (name, ratio)
    out = {"loss": sys_loss, "ref_loss": ref_loss,
           "loss_rel": abs(sys_loss - ref_loss) / abs(ref_loss),
           "grad_cos": dot / max(ns * nr, 1e-300),
           "grad_norm_ratio": ns / max(nr, 1e-300),
           "leaf_err_over_allowed": worst[1], "leaf_err_worst_at": worst[0],
           "leaves": len(ref_l)}
    lo, hi = tol["grad_norm_ratio"]
    out["correct"] = bool(
        np.isfinite(sys_loss) and out["loss_rel"] <= tol["loss_rel"]
        and out["grad_cos"] >= tol["grad_cos_min"]
        and lo <= out["grad_norm_ratio"] <= hi
        and out["leaf_err_over_allowed"] <= 1.0)
    return out
