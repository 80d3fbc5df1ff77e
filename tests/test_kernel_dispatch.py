"""Every registered Pallas kernel is one the library selects (ISSUE 29).

The tune registry once held two kernels that nothing chose: the
automatic dispatch took XLA at every conv and BatchNorm site, and only
the tuner, a smoke sweep and the tests kept the Mosaic code compiling.
Here each registered spec's public op is traced with default arguments
as if on the TPU (``_use_pallas`` patched; ``pallas_call`` binds
abstractly, nothing compiles) at a shape a benchmark cell dispatches, and
must reach its kernel; and ``chip_smoke.py``'s sweep, which compiles the
same kernels on the chip, is rehearsed up to the trace.
"""

import importlib
import importlib.util
import os

import jax
import jax.numpy as jnp
import pytest

from apex_tpu.tune import registry

TOKENS, HIDDEN, VOCAB = 8192, 768, 50257      # both gpt2_small_o2 cells


def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


def _flash():
    """``gpt2_small_o2.seq1024``: 8 sequences, 12 heads of 64, causal."""
    from apex_tpu.ops import flash_attention
    qkv = _sds((8, 1024, 12, 64), jnp.bfloat16)
    return (lambda q, k, v: flash_attention(q, k, v, causal=True),
            (qkv, qkv, qkv))


def _layer_norm():
    """``ln1``/``ln2``/``ln_f`` of both GPT cells."""
    from apex_tpu.normalization import fused_layer_norm_affine
    vec = _sds((HIDDEN,), jnp.float32)
    return (lambda x, w, b: fused_layer_norm_affine(x, w, b, (HIDDEN,)),
            (_sds((TOKENS, HIDDEN), jnp.bfloat16), vec, vec))


def _xentropy():
    """The loss of both GPT cells, over float32 logits."""
    from apex_tpu.contrib.xentropy import softmax_cross_entropy_loss
    return (lambda logits, labels: softmax_cross_entropy_loss(
                logits, labels, smoothing=0.0, padding_idx=-1),
            (_sds((TOKENS, VOCAB), jnp.float32),
             _sds((TOKENS,), jnp.int32)))


def _quantized_matmul():
    """GPT-2 small's ``mlp_up`` as amp O4 would run it.  No cell runs O4
    yet (PERF.md section 7); the shape is the one such a cell brings."""
    from apex_tpu.quant.kernels import quantized_matmul
    return (lambda x, w: quantized_matmul(x, w, x_scale=4.0 / 127.0),
            (_sds((TOKENS, HIDDEN), jnp.bfloat16),
             _sds((HIDDEN, 4 * HIDDEN), jnp.bfloat16)))


SITES = {"flash_attention": ("ops.flash_attention", _flash),
         "fused_layer_norm": ("normalization.fused_layer_norm",
                              _layer_norm),
         "xentropy": ("contrib.xentropy", _xentropy),
         "quantized_matmul": ("quant.kernels", _quantized_matmul)}


def _as_if_on_tpu(monkeypatch):
    for module, _ in SITES.values():
        monkeypatch.setattr(importlib.import_module("apex_tpu." + module),
                            "_use_pallas", lambda: True)


def test_registry_holds_only_kernels_with_a_site():
    """A spec added to the registry comes with the site that selects it."""
    assert [s.name for s in registry.all_specs()] == sorted(SITES)


@pytest.mark.parametrize("name", sorted(SITES))
def test_automatic_dispatch_reaches_the_kernel(name, monkeypatch):
    _, site = SITES[name]

    def traced():
        fn, args = site()      # a new function: no trace is read back
        return str(jax.make_jaxpr(fn)(*args))

    assert "pallas_call" not in traced()                  # the CPU's path
    _as_if_on_tpu(monkeypatch)
    assert "pallas_call" in traced()


def _pallas_calls(jaxpr):
    """Every ``pallas_call`` equation of a jaxpr, inner jaxprs included."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            found.append(eqn)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found += _pallas_calls(sub)
    return found


def test_the_loss_under_differentiation_is_one_kernel_in_place(monkeypatch):
    """ISSUE 36, at the GPT cells' ``[8192, 50257]``: the primal loss is one
    Mosaic call, value and gradient together are one too (no backward
    kernel), and that one writes ``softmax - target`` over its logits."""
    _as_if_on_tpu(monkeypatch)
    fn, args = _xentropy()
    primal = _pallas_calls(jax.make_jaxpr(fn)(*args).jaxpr)
    assert len(primal) == 1
    assert not primal[0].params["input_output_aliases"]

    def mean_loss(logits, labels):
        return jnp.mean(fn(logits, labels))

    both = _pallas_calls(
        jax.make_jaxpr(jax.value_and_grad(mean_loss))(*args).jaxpr)
    assert len(both) == 1
    (call,) = both
    assert tuple(call.params["input_output_aliases"]) == ((0, 1),)
    logits, r = call.invars[0].aval, call.outvars[1].aval
    assert (logits.shape, logits.dtype) == (r.shape, r.dtype) \
        == ((TOKENS, VOCAB), jnp.float32)


def _chip_smoke():
    """``chip_smoke.py`` of this checkout as a module (its import touches
    neither JAX nor the chip)."""
    path = os.path.join(os.path.dirname(__file__), os.pardir,
                        "chip_smoke.py")
    spec = importlib.util.spec_from_file_location("chip_smoke", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.mark.parametrize("name", sorted(SITES))
def test_chip_smoke_sweep_rehearsal(name, monkeypatch):
    """What ``check_against_reference`` does before it compiles: the
    default config is legal at every shape of the sweep and the case
    traces the kernel, not its jnp fallback."""
    _as_if_on_tpu(monkeypatch)
    spec = registry.get_spec(name)
    shapes = _chip_smoke()._sweep_shapes(spec)
    assert shapes[0] == spec.example_shape
    for shape in shapes:
        cfg = spec.defaults(shape)
        assert spec.constraint(shape, cfg), (shape, cfg)
        case = spec.build(shape, False)
        text = str(jax.make_jaxpr(lambda: case.run(cfg))())
        assert "pallas_call" in text, shape


def test_chip_smoke_refuses_to_run_off_the_tpu():
    """No CPU fallback on a measurement path: exit 2, the platform found
    named, nothing run."""
    import subprocess
    import sys
    path = os.path.join(os.path.dirname(__file__), os.pardir,
                        "chip_smoke.py")
    done = subprocess.run([sys.executable, path], capture_output=True,
                          text=True, timeout=120,
                          env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert done.returncode == 2, done.stderr[-2000:]
    assert "needs a TPU" in done.stderr and "'cpu'" in done.stderr
    assert '"ok"' not in done.stdout
