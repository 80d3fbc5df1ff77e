"""``models.SmallThinker``: window and global layers by the two published
lists, what is held as constructor arguments, what amp O2 keeps float32, the
model's state (load counts), the model against the plain reference in
float32 (forward, loss, gradients, routing), what a window and the rotary
angles reach, the O2 train step at a tiny size, the scopes in the compiled
step, and the test that ties the share to the model: the four expert shares
of a layer, with attention counted once, add up to what the uncut reference
gives.  (The example's tiny preset runs in ``tests/test_examples_smoke.py``,
beside the other models'.)"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu import models, training
from apex_tpu.amp import policy
from apex_tpu.contrib.xentropy import softmax_cross_entropy_loss
from apex_tpu.models import smallthinker
from apex_tpu.ops import moe
from benchmark.reference import smallthinker as reference

BATCH, SEQ = 2, 48
#: what the reference reads of a configuration, at the tiny preset's sizes
CFG = dict(rms_norm_eps=1e-6, moe_num_active_primary_experts=4,
           expert_offset=0, rope_theta=1.5e6, sliding_window_size=16,
           sliding_window_layout=[0, 1, 1, 1], rope_layout=[0, 1, 1, 1])


def _ids(batch=BATCH, seq=SEQ + 1, vocab=1024):
    return jax.random.randint(jax.random.PRNGKey(1), (batch, seq), 1, vocab)


def _init(model, seed=0):
    return model.init(jax.random.PRNGKey(seed), jnp.zeros((1, 8), jnp.int32))


def _count(tree):
    return sum(a.size for a in jax.tree_util.tree_leaves(tree))


def test_the_default_is_the_published_model_and_the_cut_is_its_share():
    model = models.SmallThinker()
    assert (model.hidden_size, model.num_heads, model.num_kv_heads,
            model.head_dim, model.moe_dim, model.num_experts, model.top_k,
            model.sliding_window_size, model.vocab_size) == (
        2560, 28, 4, 128, 768, 64, 6, 4096, 151936)
    assert model.sliding_window_layout == model.rope_layout == (
        (0, 1, 1, 1) * 13)
    assert model.rope_theta == 1.5e6 and model.eps == 1e-6
    # the cell's cut: one period, 16 of 64 experts, a quarter of the rows
    cut = models.SmallThinker(sliding_window_layout=(0, 1, 1, 1),
                              rope_layout=(0, 1, 1, 1), experts_held=16,
                              vocab_size=37984)
    shapes = jax.eval_shape(lambda: _init(cut))["params"]
    assert _count(shapes) == 656_529_920
    layer = shapes["layer_1"]
    assert layer["experts"]["router"].shape == (2560, 64)
    assert layer["experts"]["w1"].shape == (16, 2560, 768)
    assert layer["experts"]["w2"].shape == (16, 768, 2560)
    assert layer["attention"]["query"]["kernel"].shape == (2560, 28, 128)
    assert layer["attention"]["key"]["kernel"].shape == (2560, 4, 128)
    assert _count(layer) == 115_512_320
    assert shapes["wte"].shape == shapes["head"].shape == (37984, 2560)
    with pytest.raises(ValueError, match="different numbers of layers"):
        jax.eval_shape(lambda: _init(models.smallthinker_tiny(
            rope_layout=(0, 1))))


def test_initialisation_and_state_are_the_ones_on_file():
    variables = _init(models.smallthinker_tiny())
    params = variables["params"]
    # the embedding nn.Embedding's normal(0, 1), the head as the matrices
    assert abs(float(jnp.std(params["wte"])) - 1.0) < 0.05
    assert abs(float(jnp.std(params["head"])) - 0.02) < 2e-3
    assert not np.array_equal(params["wte"], params["head"])    # untied
    assert abs(float(jnp.std(params["layer_1"]["experts"]["w3"]))
               - 0.02) < 2e-3
    for name in ("input_norm", "post_attention_norm"):
        np.testing.assert_array_equal(params["layer_2"][name]["scale"],
                                      np.ones(64, np.float32))
    assert set(variables["moe"]) == {f"layer_{i}" for i in range(4)}
    for state in variables["moe"].values():
        assert set(state["experts"]) == {"load"}       # no selection bias
        np.testing.assert_array_equal(state["experts"]["load"],
                                      np.zeros(16, np.int32))


def test_o2_keeps_the_norms_and_the_router_float32():
    params = _init(models.smallthinker_tiny())["params"]
    cast = policy.convert_params(params, jnp.bfloat16,
                                 norm_predicate=smallthinker.keep_fp32)
    flat = {jax.tree_util.keystr(path): leaf.dtype for path, leaf in
            jax.tree_util.tree_flatten_with_path(cast)[0]}
    kept = {name for name, dtype in flat.items() if dtype == jnp.float32}
    ends = ("['input_norm']['scale']", "['post_attention_norm']['scale']",
            "['norm_f']['scale']", "['router']")
    assert all(name.endswith(ends) for name in kept)
    assert [sum(name.endswith(end) for name in kept) for end in ends] == [
        4, 4, 1, 4]
    for name in ("['wte']", "['head']", "['layer_1']['experts']['w1']",
                 "['layer_0']['attention']['query']['kernel']"):
        assert flat[name] == jnp.bfloat16, name
    model = models.smallthinker_tiny(dtype=jnp.bfloat16)
    variables = _init(model)
    with pytest.raises(TypeError, match="router's weight arrived as bfloat16"):
        jax.eval_shape(model.apply, {
            "params": policy.convert_params(variables["params"], jnp.bfloat16),
            "moe": variables["moe"]}, _ids())


@pytest.fixture(scope="module")
def against_reference():
    """The tiny model (one period, everything held) and the reference on
    one batch, float32 at full matmul precision."""
    model = models.smallthinker_tiny()
    variables = _init(model)
    ids = _ids()
    x, y = ids[:, :-1], ids[:, 1:]

    def loss(p):
        logits, new = model.apply({"params": p, "moe": variables["moe"]}, x,
                                  mutable=["moe", "intermediates"])
        logp = jax.nn.log_softmax(logits)
        return -jnp.take_along_axis(logp, y[..., None], -1).mean(), new

    with jax.default_matmul_precision("highest"):
        (value, new), grads = jax.jit(jax.value_and_grad(loss, has_aux=True))(
            variables["params"])
    ref = reference.loss_and_grads(variables["params"], x, y, CFG)
    return value, grads, new, ref, variables["params"], (x, y)


def test_forward_loss_and_gradients_equal_the_reference(against_reference):
    value, grads, _, (ref_value, ref_grads, _), *_ = against_reference
    np.testing.assert_allclose(value, ref_value, rtol=2e-6)
    flat = lambda t: jax.tree_util.tree_flatten_with_path(t)[0]
    assert [p for p, _ in flat(grads)] == [p for p, _ in flat(ref_grads)]
    for (path, got), (_, want) in zip(flat(grads), flat(ref_grads)):
        err = float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))
        assert err < 2e-4, (jax.tree_util.keystr(path), err)


def test_routing_counters_and_the_routers_input(against_reference):
    _, _, new, (_, _, routing), params, (x, _) = against_reference
    assert set(routing) == set(new["moe"]) == {f"layer_{i}" for i in range(4)}
    for name, seen in routing.items():
        np.testing.assert_array_equal(new["moe"][name]["experts"]["load"],
                                      seen["counts"])
        seen_here = new["intermediates"][name]["experts"]
        (sel,), (read,) = seen_here["selected"], seen_here["router_in"]
        np.testing.assert_array_equal(np.sort(sel, -1),
                                      np.sort(seen["selected"], -1))
        assert seen["counts"].sum() == BATCH * SEQ * 4
        # the router read the attention's normed input: the reference's
        # router on it selects what the model selected
        np.testing.assert_array_equal(
            reference.select(read, params[name]["experts"]["router"], CFG),
            sel)
    first = new["intermediates"]["layer_0"]["experts"]["router_in"][0]
    h = params["wte"][x]
    np.testing.assert_allclose(first, h * jax.lax.rsqrt(
        (h * h).mean(-1, keepdims=True) + 1e-6), rtol=1e-5, atol=1e-6)


def test_by_layer_equals_the_whole_reference(against_reference):
    *_, (ref_value, ref_grads, routing), params, (x, y) = against_reference
    value, grads, by_layer = reference.loss_and_grads_by_layer(params, x, y,
                                                               CFG)
    np.testing.assert_allclose(value, ref_value, rtol=1e-6)
    for got, want in zip(jax.tree_util.tree_leaves(grads),
                         jax.tree_util.tree_leaves(ref_grads)):
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-7)
    for name in routing:
        np.testing.assert_array_equal(by_layer[name]["selected"],
                                      routing[name]["selected"])


def _attention_outputs(model, params, ids):
    """Each layer's attention output, ``{layer: [B, T, D]}``."""
    _, seen = model.apply({"params": params, "moe": _init(model)["moe"]}, ids,
                          capture_intermediates=lambda mdl, _: isinstance(
                              mdl, smallthinker.Attention),
                          mutable=["intermediates", "moe"])
    return {name: seen["intermediates"][name]["attention"]["__call__"][0]
            for name in seen["intermediates"] if name.startswith("layer_")}


def test_a_key_past_the_window_reaches_the_global_layer_alone():
    """A token further back than the window moves nothing in a window
    layer's attention at the last position, and moves the global layer's;
    so does every layer's below it (the residual stream carries it)."""
    model = models.smallthinker_tiny(sliding_window_layout=(0, 1),
                                     rope_layout=(0, 1))
    params = _init(model)["params"]
    ids = _ids(batch=1, seq=SEQ)
    other = ids.at[0, 5].set(ids[0, 5] % 1000 + 7)     # 42 > 16 back
    # the window layer's own attention, on one input: its keys at 5 differ
    # with the token there, its output at 47 does not
    attn = smallthinker.Attention(num_heads=4, num_kv_heads=2, head_dim=16,
                                  window=16, rope_theta=1.5e6)
    u = jax.random.normal(jax.random.PRNGKey(2), (1, SEQ, 64))
    w = attn.init(jax.random.PRNGKey(3), u)
    moved = u.at[0, 5].add(1.0)
    near = u.at[0, 40].add(1.0)                         # 7 back: in the band
    out = lambda v: attn.apply(w, v)[0, -1]
    np.testing.assert_array_equal(out(u), out(moved))
    assert float(jnp.abs(out(u) - out(near)).max()) > 1e-4
    glob = smallthinker.Attention(num_heads=4, num_kv_heads=2, head_dim=16)
    g = glob.init(jax.random.PRNGKey(3), u)
    assert float(jnp.abs(glob.apply(g, u)[0, -1]
                         - glob.apply(g, moved)[0, -1]).max()) > 1e-4
    # in the model: the global layer's attention at the last position moves
    a, b = (_attention_outputs(model, params, i) for i in (ids, other))
    assert float(jnp.abs(a["layer_0"][0, -1] - b["layer_0"][0, -1]).max()) > 0


def test_rope_theta_moves_the_window_layers_only():
    model = models.smallthinker_tiny()
    params = _init(model)["params"]
    ids = _ids(batch=1, seq=SEQ)
    a = _attention_outputs(model, params, ids)
    b = _attention_outputs(model.clone(rope_theta=1e4), params, ids)
    np.testing.assert_array_equal(a["layer_0"], b["layer_0"])
    for name in ("layer_1", "layer_2", "layer_3"):
        assert float(jnp.abs(a[name] - b[name]).max()) > 1e-4, name


def _o2_step():
    model = models.smallthinker_tiny(dtype=jnp.bfloat16, experts_held=4,
                                     expert_offset=4)
    variables = _init(model)

    def loss_fn(p, model_state, batch):
        x, y = batch
        logits, new = model.apply({"params": p, "moe": model_state}, x,
                                  mutable=["moe"])
        assert logits.dtype == jnp.float32
        return jnp.mean(softmax_cross_entropy_loss(
            logits.reshape(-1, logits.shape[-1]), y.reshape(-1))), new["moe"]

    init_fn, step_fn = training.make_train_step(
        loss_fn, training.adam(3e-3, weight_decay=0.1), opt_level="O2",
        loss_scale="dynamic", norm_predicate=smallthinker.keep_fp32,
        has_model_state=True)
    ids = _ids()
    return (jax.jit(step_fn), init_fn(variables["params"], variables["moe"]),
            (ids[:, :-1], ids[:, 1:]))


o2_step = pytest.fixture(scope="module")(_o2_step)


def test_o2_step_is_finite_the_loss_falls_and_the_load_is_kept(o2_step):
    step, state, batch = o2_step
    losses = []
    for _ in range(4):
        state, metrics = step(state, batch)
        losses.append(float(metrics["loss"]))
    assert np.isfinite(losses).all() and losses[-1] < losses[0]
    for name, s in state.model_state.items():
        load = np.asarray(s["experts"]["load"])
        assert load.dtype == np.int32 and load.sum() == BATCH * SEQ * 4, name
    assert state.params["layer_1"]["experts"]["router"].dtype == jnp.float32


def test_the_scopes_reach_the_compiled_step(o2_step):
    step, state, batch = o2_step
    compiled = step.lower(state, batch).compile().as_text()
    under = "/jvp(apex.forward)/SmallThinker/"
    assert f"{under}layer_0/attention/apex.attn.full/" in compiled
    assert f"{under}layer_1/attention/apex.attn.window/" in compiled
    assert f"{under}layer_1/attention/apex.rope/" in compiled
    assert "layer_0/attention/apex.attn.window/" not in compiled
    assert "layer_0/attention/apex.rope/" not in compiled
    assert "layer_2/attention/apex.attn.full/" not in compiled
    for scope in moe.MOE_SCOPES[1:]:
        assert f"{under}layer_3/experts/apex.moe/{scope}/" in compiled, scope
    assert any("transpose(jvp(apex.forward))" in line
               and "/apex.attn.window/" in line
               for line in compiled.splitlines())


def test_the_four_expert_shares_of_a_layer_add_up_to_the_uncut_layer():
    """Four chips each hold 4 of a layer's 16 experts; attention, the norms
    and the router are whole on each.  The shares' expert parts, with the
    residual stream and attention counted once, add up to the uncut
    reference layer."""
    attention = dict(num_heads=4, num_kv_heads=2, head_dim=16, window=16,
                     rope_theta=1.5e6)
    full = dict(width=32, num_experts=16, experts_held=16, top_k=4)
    h = jax.random.normal(jax.random.PRNGKey(5), (2, SEQ, 64))
    layer = lambda experts: smallthinker.SmallThinkerLayer(attention,
                                                           experts, 1e-6)
    variables = layer(full).init(jax.random.PRNGKey(6), h)
    p, state = variables["params"], variables["moe"]
    with jax.default_matmul_precision("highest"):
        want, (sel, counts) = reference.layer(p, h, CFG, (True, True))
        parts = []
        for offset in (0, 4, 8, 12):
            share = jax.tree_util.tree_map(lambda a: a, p)
            for name in ("w1", "w2", "w3"):
                share["experts"][name] = p["experts"][name][offset:offset + 4]
            out, new = layer(dict(full, experts_held=4, expert_offset=offset)
                             ).apply({"params": share, "moe": state}, h,
                                     mutable=["moe"])
            parts.append(out)
            np.testing.assert_array_equal(new["moe"]["experts"]["load"],
                                          counts)
        eps = CFG["rms_norm_eps"]
        n1 = reference._rms_norm(h, p["input_norm"]["scale"], eps)
        stream = h + reference._attention(n1, p["attention"], CFG, True, True)
    got = sum(parts) - 3 * stream
    np.testing.assert_allclose(got, want, rtol=2e-5,
                               atol=2e-5 * float(jnp.abs(want).max()))
    # one share alone is not the layer
    assert float(jnp.abs(parts[0] - want).max()) > 1e-3
