"""Rehearsal compiles of the flash backward for a described TPU v5e.

Interpret mode runs the kernel body under jnp and cannot see what
Mosaic refuses: a slice off the tiling, more scoped VMEM than the call
asked for.  The TPU's compiler is installed beside the CPU backend and
compiles for a chip that is described and not attached, so these cases
hold ``flash_bwd`` to it at the shapes the benchmark's cells run and at
the key lengths where its whole-length dk/dv residents set the VMEM
limit.  Nothing runs: a compile that passes says nothing about results
or times.

The topology is described inside a fixture (never at import: one
process at a time may load the TPU's library, and every xdist worker
imports every test file), and all of these cases live in this one file.
"""

import importlib
import os

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    with pytest.MonkeyPatch.context() as mp:     # undone when the module ends
        if "TPU_LOG_DIR" not in os.environ:
            mp.setenv("TPU_LOG_DIR", "disabled")         # else logs in /tmp
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler here, or its library is held
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield SingleDeviceSharding(topo.devices[0])


# (batch, heads, kv_heads, seq, head_dim, block, key-padding bias,
#  window, flash_bwd calls expected)
CASES = {
    "gpt2_small_o2.seq1024": (8, 12, 12, 1024, 64, 1024, False, None, 1),
    "granite4_h_micro_o2.b2_seq4096": (2, 32, 8, 4096, 64, 1024, False,
                                       None, 1),
    "many_tiles_gqa_key_bias": (1, 4, 2, 2048, 64, 256, True, None, 1),
    "bounded_window_mqa": (1, 4, 1, 8192, 64, 512, True, 1024, 1),
    "resident_budget_16k_keys_d128": (1, 2, 1, 16384, 128, 1024, False,
                                      None, 1),
    "two_kv_chunks_32k_keys": (1, 2, 2, 32768, 64, 1024, False, None, 2),
    "bounded_window_over_two_kv_chunks": (1, 2, 1, 32768, 64, 1024, True,
                                          4096, 2),
}


@pytest.mark.parametrize("case", list(CASES))
def test_flash_bwd_compiles_under_mosaic(one_chip, case):
    fa = importlib.import_module("apex_tpu.ops.flash_attention")
    b, h, h_kv, t, d, blk, with_bias, window, calls = CASES[case]

    def sds(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    q, kv = sds((b, h, t, d)), sds((b, h_kv, t, d))
    lse = sds((b, h, t, 1), jnp.float32)
    kb = sds((b, t), jnp.float32) if with_bias else None

    def bwd(q, k, v, out, lse, do, kb):
        return fa._flash_bwd_pallas(
            q, k, v, kb, out, lse, do, sm_scale=d ** -0.5, causal=True,
            block_q=blk, block_k=blk, window=window)

    # refused here = refused on the chip (VMEM limit, tiling, lowering)
    compiled = jax.jit(bwd).lower(q, kv, kv, q, lse, q, kb).compile()
    assert compiled.as_text().count(
        'custom_call_target="tpu_custom_call"') == calls


# the expert cells' grouped products: (sorted rows a layer or a wave, experts
# held, the product's contraction and width)
GROUPED = {
    "lfm2_24b_a2b_o2.in": (65536, 16, 2048, 1536),
    "lfm2_24b_a2b_o2.out": (65536, 16, 1536, 2048),
    "smallthinker_21b_a3b_o2.in": (98304, 16, 2560, 768),
    "smallthinker_21b_a3b_o2.out": (98304, 16, 768, 2560),
    "nemotron3_super_120b_o2.in": (16384, 8, 1024, 2688),
    "nemotron3_super_120b_o2.out": (16384, 8, 2688, 1024),
}


@pytest.mark.parametrize("product", list(GROUPED))
def test_grouped_matmul_compiles_under_mosaic_at_the_cells_widths(
        one_chip, monkeypatch, product):
    """The expert layers' grouped products at the three expert cells' sizes,
    into the expert width and out of it, forward and both gradients: on the
    TPU ``ops.moe`` takes the grouped-matmul kernel of ``jax.experimental``,
    each of its three kernels with the tiles ``_tiling`` gives its shape, and
    a tile whose blocks overflow the scoped VMEM is refused here (this file
    lives with the flash cases because every rehearsal compile has to: one
    process may describe the topology)."""
    moe = importlib.import_module("apex_tpu.ops.moe")
    monkeypatch.setattr(moe, "_use_pallas", lambda: True)
    rows, g, d, f = GROUPED[product]

    def sds(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def both(x, w, sizes):
        out, vjp = jax.vjp(lambda x, w: moe._grouped_matmul(x, w, sizes), x, w)
        return out, vjp(out)

    # the suite's global "highest" would reach the library kernel's dots,
    # which take bf16 operands at the default precision as the chip runs them
    with jax.default_matmul_precision("default"):
        compiled = jax.jit(both).lower(
            sds((rows, d)), sds((g, d, f)), sds((g,), jnp.int32)).compile()
    assert compiled.as_text().count(
        'custom_call_target="tpu_custom_call"') == 3     # gmm twice, tgmm
    # rows that do not fill the kernel's row tiles take the compiler's own
    # grouped matmul
    with jax.default_matmul_precision("default"):
        ragged = jax.jit(lambda x, w, s: moe._grouped_matmul(x, w, s)).lower(
            sds((rows + 8, d)), sds((g, d, f)), sds((g,), jnp.int32)).compile()
    assert "ragged-dot" in ragged.as_text()


@pytest.mark.parametrize("checkpoint", ["none", "keeps_routed", "input_only"])
def test_latent_layer_compiles_under_mosaic_and_no_array_has_every_pair(
        one_chip, monkeypatch, checkpoint):
    """``ops.latent_moe_layer`` at ``nemotron3_super_120b_o2.b2_seq8192``'s
    sizes (16,384 tokens, 22 of 512 experts a token, 8 held, latent 1,024,
    expert width 2,688), forward and backward: the waves' loops hold the
    grouped-matmul kernels, and of the 360,448 (token, slot) pairs the
    compiled program holds vectors only, no array of rows.  Under a
    checkpoint that keeps ``ROUTED``, as the model's layers are, the program
    sorts the ``[16384, 512]`` scores (top-22) and the pairs once, as it
    does without a checkpoint; under one that keeps the input only, twice."""
    import re

    moe = importlib.import_module("apex_tpu.ops.moe")
    monkeypatch.setattr(moe, "_use_pallas", lambda: True)
    n, d, lat, f, e, g, k = 16384, 4096, 1024, 2688, 512, 8, 22

    def sds(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def both(x, latent, w_gate, bias, w1, w2):
        layer = lambda x, latent, w_gate, w1, w2: moe.latent_moe_layer(
            x, latent, w_gate, bias, w1, w2, top_k=k,
            routed_scaling_factor=5.0)[0]
        if checkpoint != "none":
            layer = jax.checkpoint(layer, policy=(
                jax.checkpoint_policies.save_only_these_names(moe.ROUTED)
                if checkpoint == "keeps_routed" else None))
        out, vjp = jax.vjp(layer, x, latent, w_gate, w1, w2)
        return out, vjp(out)

    with jax.default_matmul_precision("default"):
        text = jax.jit(both).lower(
            sds((n, d)), sds((n, lat)), sds((d, e), jnp.float32),
            sds((e,), jnp.float32), sds((g, lat, f)),
            sds((g, f, lat))).compile().as_text()
    # forward: two products; backward: those again, their two input
    # gradients and two weight gradients
    assert text.count('custom_call_target="tpu_custom_call"') == 8
    # the two loops of waves (the compiler adds loops of its own)
    assert len(re.findall(r" while\(", text)) >= 2
    assert re.search(r"\[%d\]" % (n * k), text)
    assert not re.search(r"\[%d,\d" % (n * k), text)
    assert f"[{moe._WAVE_ROWS},{f}]" in text
    sorts = [m.group(1) for m in (
        re.search(r"= \((\w+\[[\d,]+\])", line)
        for line in text.splitlines() if " sort(" in line) if m]
    routes = 2 if checkpoint == "input_only" else 1
    assert sorts.count(f"f32[{n},{e}]") == routes       # top-22 of 512
    assert sorts.count(f"s32[{n * k}]") == routes       # the pairs by expert


# the language-model cells' heads: (batch, sequence, hidden, vocabulary, how
# the model writes the head's product)
HEADS = {
    "gpt2_small_o2": (8, 1024, 768, 50257, "gpt"),
    "granite4_h_micro_o2": (2, 4096, 2048, 50176, "granite"),
    "lfm2_24b_a2b_o2": (4, 4096, 2048, 16384, "hybrid"),
}


@pytest.mark.parametrize("cell", list(HEADS))
def test_the_loss_reads_its_logits_once_in_the_compiled_head(
        one_chip, monkeypatch, cell):
    """ISSUE 36 at the cells' head shapes: the head's product, the fused
    loss and the whole backward hold ONE Mosaic call, which takes the
    logits' buffer for ``r = softmax - target``; nothing of ``[N, V]`` is
    written after it (XLA fuses ``g * r`` into the head's two gradient
    products, which it does only because the heads multiply over flattened
    tokens), so the step's temporaries hold one float32 ``[N, V]`` array."""
    import re

    xe = importlib.import_module("apex_tpu.contrib.xentropy")
    monkeypatch.setattr(xe, "_use_pallas", lambda: True)
    hybrid = importlib.import_module("apex_tpu.models.granite_hybrid")
    b, t, d, v, kind = HEADS[cell]

    def sds(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    ids = sds((b, t), jnp.int32)
    if kind == "gpt":      # the model itself without a block: embed, ln_f, head
        gpt = importlib.import_module("apex_tpu.models.gpt")
        model = gpt.GPT(vocab_size=v, hidden_size=d, num_layers=0,
                        num_heads=12, max_len=t, dtype=jnp.bfloat16)
        params = jax.eval_shape(
            lambda: model.init(jax.random.PRNGKey(0),
                               jnp.zeros((1, 8), jnp.int32))["params"])
        logits_of = lambda p, ids: model.apply({"params": p}, ids)
    else:
        scale = 8.0 if kind == "granite" else None
        params = {"h": sds((b, t, d)), "w": sds((v, d))}
        logits_of = lambda p, ids: hybrid.head_logits(p["h"], p["w"], scale)
    params = jax.tree_util.tree_map(lambda a: sds(a.shape, a.dtype), params)

    def mean_loss(p, ids):
        losses = xe.softmax_cross_entropy_loss(
            logits_of(p, ids).reshape(-1, v), ids.reshape(-1))
        return jnp.mean(losses) * 1024.0

    compiled = jax.jit(jax.value_and_grad(mean_loss)).lower(
        params, ids).compile()
    text = compiled.as_text()
    calls = [line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert len(calls) == 1
    assert "output_to_operand_aliasing={{1}: (0, {})}" in calls[0]
    entry = text[text.index("ENTRY"):]
    wide = re.compile(r"= \(?(f32|bf16)\[(%d,%d|%d,%d,%d)\]"
                      % (b * t, v, b, t, v))
    written = [line.split(" = ")[0].strip() for line in entry.splitlines()
               if wide.search(line) and " fusion(" in line]
    assert len(written) == 1, written        # the logits' product alone
    logits_bytes = 4 * b * t * v
    assert compiled.memory_analysis().temp_size_in_bytes < 1.1 * logits_bytes


@pytest.mark.parametrize("rows,columns", [(8192, 50257), (16384, 16384)])
def test_the_loss_kernel_under_differentiation_compiles_under_mosaic(
        one_chip, rows, columns):
    """The forward kernel that also writes ``r``, alone and with label
    smoothing on (more live ``[R, H]`` intermediates than the cells'
    smoothing of 0, which the head cases above compile), inside the 16 MB
    of scoped VMEM at the GPT cells' and the LFM2 and Nemotron cells'
    shapes."""
    xe = importlib.import_module("apex_tpu.contrib.xentropy")
    logits = jax.ShapeDtypeStruct((rows, columns), jnp.float32,
                                  sharding=one_chip)
    labels = jax.ShapeDtypeStruct((rows,), jnp.int32, sharding=one_chip)
    compiled = jax.jit(
        lambda l, y: xe._fwd_grad_pallas(l, y, 0.1),
        donate_argnums=0).lower(logits, labels).compile()
    assert compiled.as_text().count(
        'custom_call_target="tpu_custom_call"') == 1
