"""``models.NemotronH``: layers by letter (a mixer or a feed-forward part
alone), what is held as constructor arguments, what amp O2 keeps float32, the
model's state (correction bias, load counts, rows computed), the model
against the plain reference in float32 (forward, loss, gradients, routing),
the O2 train step at a tiny size, the scopes in the compiled step, and the
test that ties the share to the model: the 8 head shares of a Mamba-2 layer,
the 8 of the attention layer and the 64 expert shares of a latent layer, with
the shared expert counted once, add up to what the uncut reference gives.
(The example's tiny preset runs in ``tests/test_examples_smoke.py``, beside
the other models'.)"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu import models, training
from apex_tpu.amp import policy
from apex_tpu.contrib.xentropy import softmax_cross_entropy_loss
from apex_tpu.models import nemotron_h
from apex_tpu.ops import moe
from benchmark.reference import nemotron_h as reference
from test_moe import plain_route, routing_ops

BATCH, SEQ = 2, 33
#: what the reference reads of a configuration, at the tiny preset's sizes
CFG = dict(norm_eps=1e-5, num_experts_per_tok=4, norm_topk_prob=True,
           routed_scaling_factor=5.0, expert_offset=0, mamba_head_dim=16,
           ssm_state_size=16)


def _ids(batch=BATCH, seq=SEQ + 1, vocab=1024):
    return jax.random.randint(jax.random.PRNGKey(1), (batch, seq), 1, vocab)


def _init(model, seed=0):
    return model.init(jax.random.PRNGKey(seed), jnp.zeros((1, 8), jnp.int32))


def _count(tree):
    return sum(a.size for a in jax.tree_util.tree_leaves(tree))


def test_the_default_is_the_published_model_and_the_cut_is_its_share():
    model = models.NemotronH()
    assert len(model.pattern) == 88
    assert [model.pattern.count(c) for c in "ME*"] == [40, 40, 8]
    assert model.pattern[:11] == "MEMEMEM*EME"
    assert (model.hidden_size, model.vocab_size, model.eps) == (
        4096, 131072, 1e-5)
    assert (model.mamba_heads, model.mamba_head_dim, model.mamba_state,
            model.mamba_groups, model.mamba_conv, model.mamba_chunk) == (
                128, 64, 128, 8, 4, 128)
    assert (model.num_heads, model.num_kv_heads, model.head_dim) == (32, 2, 128)
    assert (model.latent_size, model.moe_dim, model.shared_dim,
            model.num_experts, model.experts_held, model.top_k,
            model.routed_scaling_factor) == (1024, 2688, 5376, 512, 512, 22,
                                             5.0)
    # the benchmark's cut: one period, one of 8 tensor-parallel ranks, 8 of
    # 512 experts, 1/8 of the vocabulary
    cut = models.NemotronH(pattern=model.pattern[:11], mamba_heads=16,
                           mamba_groups=1, num_heads=4, num_kv_heads=1,
                           experts_held=8, vocab_size=16384)
    shapes = jax.eval_shape(lambda: _init(cut))
    p = shapes["params"]
    assert set(p["layer_0"]) == {"norm", "mamba"}
    assert set(p["layer_1"]) == {"norm", "experts"}
    assert set(p["layer_7"]) == {"norm", "attention"}
    assert p["layer_0"]["mamba"]["in_proj"]["kernel"].shape == (4096, 2320)
    assert _count(p["layer_0"]) == (4096 * 2320 + 1024 * 4096 + 4 * 1280
                                    + 1280 + 3 * 16 + 1024 + 4096)
    assert _count(p["layer_7"]) == 2 * 4096 * 512 + 2 * 4096 * 128 + 4096
    experts = p["layer_1"]["experts"]
    assert experts["w1"].shape == (8, 1024, 2688)
    assert experts["w2"].shape == (8, 2688, 1024)
    assert experts["router"].shape == (4096, 512)
    assert experts["shared_w1"].shape == (4096, 5376)
    assert _count(experts) == (4096 * 512 + 2 * 4096 * 1024
                               + 2 * 4096 * 5376 + 8 * 2 * 1024 * 2688)
    assert p["wte"].shape == p["head"].shape == (16384, 4096)
    assert _count(p) == 700_862_960
    assert set(shapes["moe"]) == {"layer_1", "layer_3", "layer_5", "layer_8",
                                  "layer_10"}
    state = shapes["moe"]["layer_3"]["experts"]
    assert set(state) == {"correction_bias", "load", "rows_computed"}
    assert (state["correction_bias"].shape, state["correction_bias"].dtype
            ) == ((512,), jnp.float32)
    assert (state["load"].shape, state["load"].dtype) == ((512,), jnp.int32)
    assert (state["rows_computed"].shape, state["rows_computed"].dtype) == (
        (), jnp.int32)
    with pytest.raises(ValueError, match="unknown layer kind"):
        _init(models.nemotron_h_tiny(pattern="M-"))


def test_initialisation_is_the_one_on_file():
    variables = _init(models.nemotron_h_tiny())
    params = variables["params"]
    assert abs(float(jnp.std(params["wte"])) - 0.02) < 2e-3
    assert abs(float(jnp.std(params["head"])) - 0.02) < 2e-3
    assert not np.array_equal(params["wte"], params["head"])    # untied
    assert abs(float(jnp.std(params["layer_1"]["experts"]["w1"])) - 0.02) < 2e-3
    mamba = params["layer_0"]["mamba"]
    np.testing.assert_allclose(mamba["A_log"], np.log(np.arange(1, 9)),
                               rtol=1e-6)
    np.testing.assert_array_equal(mamba["D"], np.ones(8, np.float32))
    step = np.asarray(jax.nn.softplus(mamba["dt_bias"]))
    assert (step >= 1e-3 * 0.999).all() and (step <= 1e-1 * 1.001).all()
    for state in variables["moe"].values():
        np.testing.assert_array_equal(state["experts"]["correction_bias"],
                                      np.zeros(16, np.float32))
        np.testing.assert_array_equal(state["experts"]["load"],
                                      np.zeros(16, np.int32))


def test_o2_keeps_decays_norms_and_the_router_float32():
    params = _init(models.nemotron_h_tiny())["params"]
    cast = policy.convert_params(params, jnp.bfloat16,
                                 norm_predicate=nemotron_h.keep_fp32)
    flat = {jax.tree_util.keystr(path): leaf.dtype for path, leaf in
            jax.tree_util.tree_flatten_with_path(cast)[0]}
    kept = {name for name, dtype in flat.items() if dtype == jnp.float32}
    ends = ("['scale']", "['A_log']", "['dt_bias']", "['D']", "['router']")
    assert all(name.endswith(ends) for name in kept)
    assert [sum(name.endswith(end) for name in kept) for end in ends] == [
        11 + 5 + 1, 5, 5, 5, 5]
    for name in ("['wte']", "['head']", "['layer_1']['experts']['w1']",
                 "['layer_1']['experts']['latent_down']",
                 "['layer_1']['experts']['shared_w2']",
                 "['layer_0']['mamba']['conv_kernel']",
                 "['layer_7']['attention']['query']['kernel']"):
        assert flat[name] == jnp.bfloat16, name
    # without the predicate the router is rounded, and the layer says so
    model = models.nemotron_h_tiny(dtype=jnp.bfloat16, pattern="E")
    variables = _init(model)
    with pytest.raises(TypeError, match="router's weight arrived as bfloat16"):
        jax.eval_shape(model.apply, {
            "params": policy.convert_params(variables["params"], jnp.bfloat16),
            "moe": variables["moe"]}, _ids())


@pytest.fixture(scope="module")
def against_reference():
    """The tiny model (eleven layers, two groups, everything held) and the
    reference on one batch, float32 at full matmul precision."""
    model = models.nemotron_h_tiny()
    variables = _init(model)
    ids = _ids()
    x, y = ids[:, :-1], ids[:, 1:]
    # a bias that is not zero: the reference has to read it from the state
    state = variables["moe"]
    for i, name in enumerate(state):
        state[name]["experts"]["correction_bias"] = 0.05 * jax.random.normal(
            jax.random.PRNGKey(i), (16,))

    def loss(p):
        logits, new = model.apply({"params": p, "moe": state}, x,
                                  mutable=["moe", "intermediates"])
        logp = jax.nn.log_softmax(logits)
        return -jnp.take_along_axis(logp, y[..., None], -1).mean(), new

    with jax.default_matmul_precision("highest"):
        (value, new), grads = jax.jit(jax.value_and_grad(loss, has_aux=True))(
            variables["params"])
    ref = reference.loss_and_grads(variables["params"], x, y, CFG, state)
    return value, grads, new, ref, variables["params"], (x, y), state


def test_forward_loss_and_gradients_equal_the_reference(against_reference):
    value, grads, _, (ref_value, ref_grads, _), *_ = against_reference
    np.testing.assert_allclose(value, ref_value, rtol=2e-6)
    flat = lambda t: jax.tree_util.tree_flatten_with_path(t)[0]
    assert [p for p, _ in flat(grads)] == [p for p, _ in flat(ref_grads)]
    for (path, got), (_, want) in zip(flat(grads), flat(ref_grads)):
        err = float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))
        assert err < 2e-4, (jax.tree_util.keystr(path), err)


def test_routing_and_counters_equal_the_reference(against_reference):
    _, _, new, (_, _, routing), *_ = against_reference
    assert set(routing) == set(new["moe"]) == {
        "layer_1", "layer_3", "layer_5", "layer_8", "layer_10"}
    for name, seen in routing.items():
        state = new["moe"][name]["experts"]
        np.testing.assert_array_equal(state["load"], seen["counts"])
        (sel,) = new["intermediates"][name]["experts"]["selected"]
        np.testing.assert_array_equal(np.sort(sel, -1),
                                      np.sort(seen["selected"], -1))
        assert seen["counts"].sum() == BATCH * SEQ * 4
        # everything is held: 264 rows, one wave of them
        assert int(state["rows_computed"]) == BATCH * SEQ * 4


def test_by_layer_equals_the_whole_reference(against_reference):
    *_, (ref_value, ref_grads, routing), params, (x, y), state = (
        against_reference)
    value, grads, by_layer = reference.loss_and_grads_by_layer(
        params, x, y, CFG, state)
    np.testing.assert_allclose(value, ref_value, rtol=1e-6)
    for got, want in zip(jax.tree_util.tree_leaves(grads),
                         jax.tree_util.tree_leaves(ref_grads)):
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-7)
    for name in routing:
        np.testing.assert_array_equal(by_layer[name]["selected"],
                                      routing[name]["selected"])


def _o2_step():
    model = models.nemotron_h_tiny(dtype=jnp.bfloat16, experts_held=4,
                                   expert_offset=2)
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
        loss_scale="dynamic", norm_predicate=nemotron_h.keep_fp32,
        has_model_state=True)
    ids = _ids()
    state = variables["moe"]
    for i, name in enumerate(state):
        state[name]["experts"]["correction_bias"] = 0.05 * jax.random.normal(
            jax.random.PRNGKey(i), (16,))
    return (jax.jit(step_fn), init_fn(variables["params"], state),
            (ids[:, :-1], ids[:, 1:]))


o2_step = pytest.fixture(scope="module")(_o2_step)


def test_o2_step_is_finite_the_loss_falls_and_the_state_is_kept(o2_step):
    step, state, batch = o2_step
    bias0 = {name: np.asarray(s["experts"]["correction_bias"])
             for name, s in state.model_state.items()}
    losses = []
    for _ in range(6):
        state, metrics = step(state, batch)
        assert not bool(metrics["overflow"])
        losses.append(float(metrics["loss"]))
        for s in state.model_state.values():
            load = np.asarray(s["experts"]["load"])
            assert load.dtype == np.int32 and load.sum() == BATCH * SEQ * 4
            held = int(load[2:6].sum())
            # 264 pairs are one wave: the rows computed are all of them
            # whenever a row is held
            assert int(s["experts"]["rows_computed"]) == (
                BATCH * SEQ * 4 if held else 0)
    assert np.isfinite(losses).all() and losses[-1] < losses[0] - 0.1
    assert all(np.isfinite(np.asarray(leaf, np.float32)).all()
               for leaf in jax.tree_util.tree_leaves(state.params))
    for name, s in state.model_state.items():
        np.testing.assert_array_equal(s["experts"]["correction_bias"],
                                      bias0[name])
    assert "correction_bias" not in str(
        jax.tree_util.tree_structure(state.params))


def test_the_scopes_reach_the_compiled_step_and_the_waves_are_loops(o2_step):
    step, state, batch = o2_step
    compiled = step.lower(state, batch).compile().as_text()
    assert moe.LATENT_SCOPES == ("apex.moe.latent", "apex.moe.shared")
    under = "/jvp(apex.forward)/NemotronH/"
    for scope in moe.MOE_SCOPES[1:] + moe.LATENT_SCOPES:
        # the expert chain and the sum back to the tokens run inside the
        # waves' loop: apex.moe/while/body/apex.moe.experts/
        inside = ("while/body/" if scope in ("apex.moe.experts",
                                             "apex.moe.combine") else "")
        assert (f"{under}layer_1/experts/apex.moe/{inside}{scope}/"
                in compiled), scope
        assert any("transpose(jvp(apex.forward))" in line
                   and f"/apex.moe/{inside}{scope}/" in line
                   for line in compiled.splitlines()), scope
    assert f"{under}layer_0/mamba/apex.ssm/apex.ssm.scan/" in compiled
    assert "/layer_0/experts/" not in compiled
    assert "/layer_1/mamba/" not in compiled
    assert "/layer_7/attention/" in compiled
    # the waves: a loop forward, one recomputed and one backward, a layer
    loops = [line for line in compiled.splitlines()
             if " while(" in line and "/apex.moe/" in line]
    assert any(under + "layer_1/" in line for line in loops)
    assert any("transpose(jvp(apex.forward))" in line for line in loops)


def _loss_of_a_share(**kw):
    """The tiny model holding 4 of its 16 experts: ``(loss, new state)`` as
    a function of the parameters, and the parameters."""
    model = models.nemotron_h_tiny(experts_held=4, expert_offset=2, **kw)
    variables = _init(model)
    ids = _ids()

    def loss(p):
        logits, new = model.apply({"params": p, "moe": variables["moe"]},
                                  ids[:, :-1], mutable=["moe"])
        return -jnp.take_along_axis(jax.nn.log_softmax(logits),
                                    ids[:, 1:, None], -1).mean(), new["moe"]
    return loss, variables["params"]


@pytest.mark.parametrize("route", ["rule", "plain"])
def test_a_differentiated_step_routes_once_a_layer(route, monkeypatch):
    """In the gradient of the loss every one of the five latent layers
    selects once, sorts once and makes its scores once, with the router's
    two gradients behind them; with a plain function for ``route`` the
    recomputed forward makes the scores and selects again, as every step did
    before the route's results were kept."""
    if route == "plain":
        monkeypatch.setattr(moe, "route", plain_route)
    loss, params = _loss_of_a_share()
    again = 2 if route == "plain" else 1
    assert routing_ops(jax.grad(lambda p: loss(p)[0]), params,
                       tokens=BATCH * SEQ, experts=16) == {
        "top_k": 5 * again, "sort": 5, "scores": 5 * again,
        "score_gradients": 10}


def test_the_checkpoints_keep_inputs_the_routed_result_and_the_route():
    """What the eleven checkpoints save: every layer's input, of a latent
    layer the routed result ``[B, T, latent]`` and, under ``ROUTED``, six
    arrays of the (token, slot) pairs' size at most: no score matrix, no
    array with the router's 16 experts as an axis."""
    from jax._src.ad_checkpoint import saved_residuals
    loss, params = _loss_of_a_share()
    kept = [(aval, why) for aval, why in saved_residuals(
        lambda p: loss(p)[0], params) if "argument" not in why]
    shapes = [aval.shape for aval, _ in kept]
    assert not any(16 in shape for shape in shapes), shapes
    pairs = (BATCH * SEQ, 4)
    routed = [(aval, why) for aval, why in kept
              if aval.shape in (pairs, (BATCH * SEQ * 4,), (4,))]
    assert all("apex_tpu/ops/moe.py" in why for _, why in routed)
    # sel, the selected scores, the weights and held; order; group_sizes
    assert [sum(aval.shape == shape for aval, _ in routed)
            for shape in (pairs, (BATCH * SEQ * 4,), (4,))] == [20, 5, 5]
    assert any(moe.ROUTED in why for _, why in routed)
    rest = [shape for shape in shapes
            if shape not in (pairs, (BATCH * SEQ * 4,), (4,))]
    # [batch, tokens, ..] of the layers and of the loss, the last norm's
    # scale; [batch * tokens, ..] of the head, which multiplies over
    # flattened tokens
    assert all(len(shape) >= 3 or shape[0] == BATCH * SEQ
               for shape in rest), rest
    assert rest.count((BATCH, SEQ, 32)) == 5            # MIXED
    assert rest.count((BATCH, SEQ, 64)) >= 11           # the layers' inputs


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
def test_loss_gradients_and_state_are_the_plain_routes_bit_for_bit(
        dtype, monkeypatch):
    """``route``'s own rule under checkpoints that keep its results, and the
    plain function under autodiff, which routes twice, as every step did
    before: loss, every gradient and the model's state are the same bits.
    Op by op: inside one compiled program XLA's fusions choose the last
    digits, and two programs of one formula differ there."""
    loss, params = _loss_of_a_share(pattern="ME*E", dtype=dtype)

    def op_by_op():
        with jax.disable_jit():
            return jax.value_and_grad(loss, has_aux=True)(params)
    ours = op_by_op()
    monkeypatch.setattr(moe, "route", plain_route)
    for got, want in zip(jax.tree_util.tree_leaves(ours),
                         jax.tree_util.tree_leaves(op_by_op()), strict=True):
        np.testing.assert_array_equal(got, want)
    (_, state), grads = ours
    assert all(int(s["experts"]["load"][2:6].sum()) for s in state.values())
    assert all(float(jnp.abs(layer["experts"]["router"]).max()) > 0
               for layer in (grads["layer_1"], grads["layer_3"]))


def test_a_share_of_every_layer_is_a_constructor_argument():
    whole = models.nemotron_h_tiny()
    share = models.nemotron_h_tiny(mamba_heads=4, mamba_groups=1, num_heads=2,
                                   num_kv_heads=1, experts_held=2,
                                   expert_offset=4, vocab_size=128)
    p, q = (jax.eval_shape(lambda m=m: _init(m))["params"]
            for m in (whole, share))
    assert p["layer_0"]["mamba"]["A_log"].shape == (8,)
    assert q["layer_0"]["mamba"]["A_log"].shape == (4,)
    assert q["layer_0"]["mamba"]["conv_kernel"].shape == (4, 4 * 16 + 2 * 16)
    assert q["layer_7"]["attention"]["query"]["kernel"].shape == (64, 2, 16)
    assert q["layer_7"]["attention"]["key"]["kernel"].shape == (64, 1, 16)
    assert q["layer_1"]["experts"]["w1"].shape == (2, 32, 48)
    # whole on every chip: the router, the latent projections, the shared
    # expert, the norms
    for leaf in ("router", "latent_down", "latent_up", "shared_w1",
                 "shared_w2"):
        assert (q["layer_1"]["experts"][leaf].shape
                == p["layer_1"]["experts"][leaf].shape), leaf
    assert q["wte"].shape == q["head"].shape == (128, 64)


# ---------------------------------------------------------------------------
# The shares add up to the uncut layer.  Small sizes of the published
# proportions: 8 groups with their heads, 8 ranks of query heads over 2 KV
# heads, 64 expert-parallel ranks.

HID, RANKS = 32, 8
M_PART = dict(num_heads=16, head_dim=8, state_size=8, n_groups=8,
              conv_width=4, chunk_size=16)
A_PART = dict(num_heads=16, num_kv_heads=2, head_dim=8, sm_scale=8 ** -0.5)
E_PART = dict(latent_size=16, width=24, shared_width=40, num_experts=64,
              experts_held=64, expert_offset=0, top_k=6,
              norm_topk_prob=True, routed_scaling_factor=5.0)
SHARE_CFG = dict(norm_eps=1e-5, num_experts_per_tok=6, norm_topk_prob=True,
                 routed_scaling_factor=5.0, expert_offset=0, mamba_head_dim=8,
                 ssm_state_size=8)


def _layer(kind, part):
    return nemotron_h.NemotronLayer(kind, part, 1e-5, jnp.float32)


def _hidden():
    return jax.random.normal(jax.random.PRNGKey(3), (2, 40, HID))


def _whole(kind, part):
    """``(parameters, state, hidden, what the uncut reference gives)``."""
    h = _hidden()
    variables = _layer(kind, part).init(jax.random.PRNGKey(2), h)
    params = jax.tree_util.tree_map(
        lambda a: a + 0.1 * jax.random.normal(jax.random.PRNGKey(a.size),
                                              a.shape), variables["params"])
    with jax.default_matmul_precision("highest"):
        bias = (jnp.zeros((64,)) if kind == "E" else None)
        want, _ = reference.layer(params, h, bias, SHARE_CFG)
    return params, variables.get("moe"), h, want


def _apply(kind, part, params, h, state=None):
    variables = {"params": params}
    if state is not None:
        variables["moe"] = state
    with jax.default_matmul_precision("highest"):
        return _layer(kind, part).apply(variables, h)


def _columns(groups):
    """For tensor-parallel rank ``r`` of ``RANKS``: its heads, its channels
    of ``d_inner``, and its columns of ``[z | x | B | C | dt]`` and of the
    conv's ``[x | B | C]``."""
    h, p, n = M_PART["num_heads"], M_PART["head_dim"], M_PART["state_size"]
    d_inner, per = h * p, h // RANKS

    def of(r):
        heads = np.arange(r * per, (r + 1) * per)
        channels = np.arange(r * per * p, (r + 1) * per * p)
        state = np.arange(r * n, (r + 1) * n)
        conv = np.concatenate([channels, d_inner + state,
                               d_inner + groups * n + state])
        proj = np.concatenate([channels, d_inner + conv,
                               2 * d_inner + 2 * groups * n + heads])
        return heads, channels, conv, proj
    return of


def test_the_eight_head_shares_of_a_mamba_layer_add_up():
    params, _, h, want = _whole("M", M_PART)
    mamba, of = params["mamba"], _columns(M_PART["n_groups"])
    part = dict(M_PART, num_heads=M_PART["num_heads"] // RANKS, n_groups=1)
    total = h
    for r in range(RANKS):
        heads, channels, conv, proj = of(r)
        share = {"norm": params["norm"], "mamba": {
            "in_proj": {"kernel": mamba["in_proj"]["kernel"][:, proj]},
            "conv_kernel": mamba["conv_kernel"][:, conv],
            "conv_bias": mamba["conv_bias"][conv],
            "dt_bias": mamba["dt_bias"][heads], "A_log": mamba["A_log"][heads],
            "D": mamba["D"][heads],
            "norm": {"scale": mamba["norm"]["scale"][channels]},
            "out_proj": {"kernel": mamba["out_proj"]["kernel"][channels]}}}
        total = total + (_apply("M", part, share, h) - h)
    np.testing.assert_allclose(total, want, atol=2e-5, rtol=2e-5)
    # the uncut program layer, grouped norm and all, is the reference too
    np.testing.assert_allclose(_apply("M", M_PART, params, h), want,
                               atol=2e-5, rtol=2e-5)


def test_the_eight_head_shares_of_the_attention_layer_add_up():
    params, _, h, want = _whole("*", A_PART)
    att = params["attention"]
    per = A_PART["num_heads"] // RANKS
    ranks_a_kv = RANKS // A_PART["num_kv_heads"]
    part = dict(A_PART, num_heads=per, num_kv_heads=1)
    total = h
    for r in range(RANKS):
        q, kv = slice(r * per, (r + 1) * per), slice(r // ranks_a_kv,
                                                     r // ranks_a_kv + 1)
        share = {"norm": params["norm"], "attention": {
            "query": {"kernel": att["query"]["kernel"][:, q]},
            "key": {"kernel": att["key"]["kernel"][:, kv]},
            "value": {"kernel": att["value"]["kernel"][:, kv]},
            "out": {"kernel": att["out"]["kernel"][q]}}}
        total = total + (_apply("*", part, share, h) - h)
    np.testing.assert_allclose(total, want, atol=2e-5, rtol=2e-5)


def test_the_64_expert_shares_add_up_with_the_shared_expert_counted_once():
    params, state, h, want = _whole("E", E_PART)
    experts = params["experts"]
    with jax.default_matmul_precision("highest"):
        u = reference._rms_norm(h, params["norm"]["scale"], 1e-5)
        shared = reference._relu2(u @ experts["shared_w1"]) @ experts[
            "shared_w2"]
    total, rows = h + shared, 0
    for r in range(64):
        share = {"norm": params["norm"], "experts": dict(
            experts, w1=experts["w1"][r:r + 1], w2=experts["w2"][r:r + 1])}
        part = dict(E_PART, experts_held=1, expert_offset=r)
        out, new = nemotron_h.NemotronLayer("E", part, 1e-5, jnp.float32
                                            ).apply(
            {"params": share, "moe": state}, h, mutable=["moe"])
        # every chip computes the shared expert: counted once, above
        total = total + (out - h - shared)
        rows += int(new["moe"]["experts"]["load"][r])
    assert rows == 2 * 40 * 6       # every pair is some share's
    np.testing.assert_allclose(total, want, atol=5e-5, rtol=5e-5)
    np.testing.assert_allclose(_apply("E", E_PART, params, h, state), want,
                               atol=5e-5, rtol=5e-5)
