"""``models.Lfm2Moe``: the two lists (mixer and feed-forward by position), what
amp O2 keeps float32, the model's state (selection bias and load counts), and
the O2 train step at a tiny size: finite, the loss falls, the load counts of a
step sum to ``tokens x k``, the bias receives no update, and the new scopes
reach the compiled module under ``apex.forward`` and its transpose; with a
block small enough for the tiny size, the expert layer's row passes are loops
in the compiled step and the layer says how far they went.  The comparison
with the plain reference is in ``tests/benchmark``."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu import models, training
from apex_tpu.amp import policy
from apex_tpu.contrib.xentropy import softmax_cross_entropy_loss
from apex_tpu.models import lfm2_moe
from apex_tpu.ops import moe
from test_moe import plain_route, routing_ops

BATCH, SEQ = 2, 33


def _ids(batch=BATCH, seq=SEQ + 1, vocab=1024):
    return jax.random.randint(jax.random.PRNGKey(1), (batch, seq), 1, vocab)


def _init(model):
    return model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))


def test_the_default_is_the_published_model():
    model = models.Lfm2Moe()
    kinds = list(model.layer_types)
    assert len(kinds) == 40 and kinds.count("full_attention") == 10
    assert [i for i, k in enumerate(kinds) if k == "full_attention"] == list(
        range(2, 40, 4))
    assert kinds[:2] == ["conv", "conv"] and model.num_dense_layers == 2
    assert (model.hidden_size, model.mlp_dim, model.moe_dim, model.vocab_size
            ) == (2048, 11776, 1536, 65536)
    assert (model.num_experts, model.experts_held, model.top_k) == (64, 64, 4)
    assert (model.num_heads, model.num_kv_heads, model.conv_taps) == (32, 8, 3)
    assert (model.rope_theta, model.eps) == (1e6, 1e-5)
    # the benchmark's cut: one dense layer, one period, 16 experts, 1/4 vocabulary
    cut = models.Lfm2Moe(layer_types=kinds[:1] + kinds[2:6], num_dense_layers=1,
                         experts_held=16, vocab_size=16384)
    shapes = jax.eval_shape(lambda: _init(cut))
    count = lambda tree: sum(a.size for a in jax.tree_util.tree_leaves(tree))
    p = shapes["params"]
    assert count(p["layer_0"]) == 89_139_200        # conv + dense MLP
    assert count(p["layer_1"]) == 161_616_000       # attention + 16 experts
    assert count(p["layer_2"]) == 167_913_472       # conv + 16 experts
    assert count(p["layer_1"]["experts"]) == 16 * 3 * 2048 * 1536 + 2048 * 64
    assert count(p) == 788_052_096
    assert p["layer_1"]["experts"]["w1"].shape == (16, 2048, 1536)
    assert set(shapes["moe"]) == {"layer_1", "layer_2", "layer_3", "layer_4"}
    state = shapes["moe"]["layer_3"]["experts"]
    assert (state["selection_bias"].shape, state["selection_bias"].dtype) == (
        (64,), jnp.float32)
    assert (state["load"].shape, state["load"].dtype) == ((64,), jnp.int32)


def test_mixer_and_feed_forward_follow_their_lists():
    model = models.lfm2_moe_tiny(
        layer_types=("conv", "full_attention", "conv"), num_dense_layers=2)
    variables = _init(model)
    params = variables["params"]
    assert set(params["layer_0"]) == {"operator_norm", "conv", "ffn_norm", "mlp"}
    assert set(params["layer_1"]) == {"operator_norm", "attention", "ffn_norm",
                                      "mlp"}
    assert set(params["layer_2"]) == {"operator_norm", "conv", "ffn_norm",
                                      "experts"}
    assert set(variables["moe"]) == {"layer_2"}
    bad = models.lfm2_moe_tiny(layer_types=("conv", "mamba"))
    with pytest.raises(ValueError, match="unknown layer kind"):
        _init(bad)


def test_initialisation_is_the_one_on_file():
    variables = _init(models.lfm2_moe_tiny())
    params = variables["params"]
    assert abs(float(jnp.std(params["wte"])) - 0.02) < 2e-3
    assert abs(float(jnp.std(params["layer_1"]["experts"]["w1"])) - 0.02) < 2e-3
    taps = params["layer_0"]["conv"]["conv_kernel"]
    assert taps.shape == (3, 64) and float(jnp.abs(taps).max()) <= 3 ** -0.5
    for name in ("q_norm", "k_norm"):
        np.testing.assert_array_equal(params["layer_1"]["attention"][name],
                                      np.ones(16, np.float32))
    for state in variables["moe"].values():
        np.testing.assert_array_equal(state["experts"]["selection_bias"],
                                      np.zeros(8, np.float32))
        np.testing.assert_array_equal(state["experts"]["load"],
                                      np.zeros(8, np.int32))


def test_o2_keeps_norms_and_the_router_float32():
    params = _init(models.lfm2_moe_tiny())["params"]
    cast = policy.convert_params(params, jnp.bfloat16,
                                 norm_predicate=lfm2_moe.keep_fp32)
    flat = {jax.tree_util.keystr(path): leaf.dtype for path, leaf in
            jax.tree_util.tree_flatten_with_path(cast)[0]}
    kept = {name for name, dtype in flat.items() if dtype == jnp.float32}
    assert all(name.endswith(("['scale']", "['q_norm']", "['k_norm']",
                              "['router']")) for name in kept)
    assert sum(name.endswith("['router']") for name in kept) == 4
    assert sum(name.endswith("_norm']") for name in kept) == 2
    assert flat["['wte']"] == flat["['layer_1']['experts']['w1']"] == flat[
        "['layer_0']['conv']['conv_kernel']"] == jnp.bfloat16
    # without the predicate the router is rounded, and the layer says so
    model = models.lfm2_moe_tiny(dtype=jnp.bfloat16)
    variables = _init(model)
    with pytest.raises(TypeError, match="router's weight arrived as bfloat16"
                                        ".*keep_fp32"):
        jax.eval_shape(model.apply, {
            "params": policy.convert_params(variables["params"], jnp.bfloat16),
            "moe": variables["moe"]}, _ids())


def test_a_share_of_the_experts_is_a_constructor_argument():
    """``experts_held`` of ``num_experts`` from ``expert_offset``: the router
    keeps its width, the expert leaves shrink, and the selection is that of
    the model that holds them all."""
    whole = models.lfm2_moe_tiny()
    share = models.lfm2_moe_tiny(experts_held=2, expert_offset=4)
    variables = _init(whole)
    cut = jax.tree_util.tree_map(lambda a: a, variables["params"])
    for name in variables["moe"]:
        for leaf in ("w1", "w3", "w2"):
            cut[name]["experts"][leaf] = cut[name]["experts"][leaf][4:6]
    assert jax.eval_shape(lambda: _init(share))["params"]["layer_1"]["experts"][
        "w1"].shape == (2, 64, 32)
    ids = _ids()[:, :-1]
    _, seen = whole.apply(variables, ids, mutable=["intermediates", "moe"])
    _, seen_cut = share.apply({"params": cut, "moe": variables["moe"]}, ids,
                              mutable=["intermediates", "moe"])
    first = "layer_1"       # deeper layers see another residual stream
    np.testing.assert_array_equal(
        seen["intermediates"][first]["experts"]["selected"][0],
        seen_cut["intermediates"][first]["experts"]["selected"][0])
    np.testing.assert_array_equal(seen["moe"][first]["experts"]["load"],
                                  seen_cut["moe"][first]["experts"]["load"])


def _o2_step():
    model = models.lfm2_moe_tiny(dtype=jnp.bfloat16, experts_held=4,
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
        loss_scale="dynamic", norm_predicate=lfm2_moe.keep_fp32,
        has_model_state=True)
    ids = _ids()
    # a bias that is not zero, to see that no step moves it
    state = variables["moe"]
    for i, name in enumerate(state):
        state[name]["experts"]["selection_bias"] = 0.05 * jax.random.normal(
            jax.random.PRNGKey(i), (8,))
    return (jax.jit(step_fn), init_fn(variables["params"], state),
            (ids[:, :-1], ids[:, 1:]))


o2_step = pytest.fixture(scope="module")(_o2_step)


def test_o2_step_is_finite_the_loss_falls_and_the_state_is_kept(o2_step):
    step, state, batch = o2_step
    bias0 = {name: np.asarray(s["experts"]["selection_bias"])
             for name, s in state.model_state.items()}
    losses, loads = [], []
    for _ in range(6):
        state, metrics = step(state, batch)
        assert not bool(metrics["overflow"])
        losses.append(float(metrics["loss"]))
        loads.append({name: np.asarray(s["experts"]["load"])
                      for name, s in state.model_state.items()})
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0] - 0.1
    assert all(np.isfinite(np.asarray(leaf, np.float32)).all()
               for leaf in jax.tree_util.tree_leaves(state.params))
    # the load counter: every (token, slot) pair of the step, over all 8
    # experts, in each of the four expert layers; it moves as the router trains
    for load in loads:
        assert set(load) == {"layer_1", "layer_2", "layer_3", "layer_4"}
        assert all(c.dtype == np.int32 and c.shape == (8,)
                   and c.sum() == BATCH * SEQ * 4 for c in load.values())
    assert any(not np.array_equal(loads[0][n], loads[-1][n]) for n in loads[0])
    # the selection bias is state, not a parameter: no update reaches it
    for name, s in state.model_state.items():
        np.testing.assert_array_equal(s["experts"]["selection_bias"], bias0[name])
    assert "selection_bias" not in str(jax.tree_util.tree_structure(state.params))


def test_the_new_scopes_reach_the_compiled_step(o2_step):
    step, state, batch = o2_step
    compiled = step.lower(state, batch).compile().as_text()
    assert (lfm2_moe.SCONV_SCOPE, lfm2_moe.ROPE_SCOPE) == ("apex.sconv",
                                                           "apex.rope")
    assert lfm2_moe.MOE_SCOPES is moe.MOE_SCOPES
    under = "/jvp(apex.forward)/Lfm2Moe/"
    for scope in moe.MOE_SCOPES[1:]:
        assert f"{under}layer_1/experts/apex.moe/{scope}/" in compiled, scope
        # backward, the experts' recomputed forward runs beside their rules;
        # the route's results are kept by the layer's checkpoint, so its
        # rule runs and nothing of it is recomputed; the weighted sum back
        # is the layer's last operation, whose forward nothing needs again
        backward = [line for line in compiled.splitlines()
                    if "transpose(jvp(apex.forward))" in line
                    and f"/apex.moe/{scope}/" in line]
        assert any("rematted_computation" not in line for line in backward)
        assert any("rematted_computation" in line for line in backward) == (
            scope == "apex.moe.experts"), scope
    assert f"{under}layer_0/conv/apex.sconv/" in compiled
    assert f"{under}layer_1/attention/apex.rope/" in compiled
    for scope in ("apex.sconv", "apex.rope"):
        assert any("transpose(jvp(apex.forward))" in line and f"/{scope}/" in line
                   for line in compiled.splitlines()), scope
    assert "/layer_0/experts/" not in compiled and "/layer_1/mlp/" not in compiled
    assert "/layer_1/conv/" not in compiled and "/layer_2/attention/" not in compiled


#: 2 x 33 tokens x 4 slots = 264 rows a layer: three blocks of 88
WALKED_BLOCK = 88


def test_the_walked_step_holds_loops_under_the_experts_scope(monkeypatch):
    """The O2 step of the tiny model with a block that its 264 rows fill
    three times: the row passes are ``while`` loops under
    ``apex.moe.experts`` and ``apex.moe.combine``, forward, recomputed and
    backward; with the block as shipped the same step has none."""
    loops = lambda text: [line for line in text.splitlines()
                          if " while(" in line and "/apex.moe/" in line]
    step, state, batch = _o2_step()
    assert not loops(step.lower(state, batch).compile().as_text())
    monkeypatch.setattr(moe, "_ROW_BLOCK", WALKED_BLOCK)
    step, state, batch = _o2_step()
    found = loops(step.lower(state, batch).compile().as_text())
    experts = [line for line in found if "/apex.moe.experts/" in line]
    assert any("/jvp(apex.forward)/Lfm2Moe/layer_1/" in line
               for line in experts)
    assert any("transpose(jvp(apex.forward))" in line
               and "rematted_computation" in line for line in experts)
    assert any("transpose(jvp(apex.forward))" in line
               and "rematted_computation" not in line for line in experts)
    assert any("transpose(jvp(apex.forward))" in line
               and "/apex.moe.combine/" in line for line in found)
    assert not any("/apex.moe.route/" in line for line in found)
    state, metrics = step(state, batch)
    assert np.isfinite(float(metrics["loss"])) and not bool(
        metrics["overflow"])


@pytest.mark.parametrize("block", [WALKED_BLOCK, None],
                         ids=["walked", "as_shipped"])
def test_rows_walked_is_an_intermediate_and_the_state_keeps_its_keys(
        block, monkeypatch):
    if block:
        monkeypatch.setattr(moe, "_ROW_BLOCK", block)
    model = models.lfm2_moe_tiny(experts_held=4, expert_offset=2)
    variables = _init(model)
    ids = _ids()[:, :-1]
    _, seen = model.apply(variables, ids, mutable=["intermediates", "moe"])
    assert set(seen["moe"]) == set(seen["intermediates"]) == {
        "layer_1", "layer_2", "layer_3", "layer_4"}
    bound = BATCH * SEQ * 4
    for name, state in seen["moe"].items():
        assert set(state["experts"]) == {"selection_bias", "load"}
        sown = seen["intermediates"][name]["experts"]
        assert set(sown) == {"selected", "rows_walked"}
        (walked,) = sown["rows_walked"]
        held = int(state["experts"]["load"][2:6].sum())
        assert walked.dtype == jnp.int32 and 0 < held < bound
        assert int(walked) == (-(-held // block) * block if block else bound)
    # nothing is sown, and the state is what it was, for a caller that
    # does not ask
    _, quiet = model.apply(variables, ids, mutable=["moe"])
    assert set(quiet) == {"moe"}


def _share(dtype=jnp.float32):
    """The tiny model holding 4 of its 8 experts, its variables and ids."""
    model = models.lfm2_moe_tiny(experts_held=4, expert_offset=2, dtype=dtype)
    return model, _init(model), _ids()


def _loss_of_a_share(dtype=jnp.float32):
    """``(loss, new state)`` as a function of the parameters."""
    model, variables, ids = _share(dtype)

    def loss(p):
        logits, new = model.apply({"params": p, "moe": variables["moe"]},
                                  ids[:, :-1], mutable=["moe"])
        return -jnp.take_along_axis(jax.nn.log_softmax(logits),
                                    ids[:, 1:, None], -1).mean(), new["moe"]
    return loss, variables["params"]


@pytest.mark.parametrize("route", ["rule", "plain"])
def test_a_differentiated_step_routes_once_a_layer(route, monkeypatch):
    """In the gradient of the loss every one of the four expert layers
    selects once, sorts once and makes its scores once, with the router's
    two gradients behind them; with a plain function for ``route`` the
    recomputed forward makes the scores and selects again, as every step did
    while the checkpoints kept the layer's input only."""
    if route == "plain":
        monkeypatch.setattr(moe, "route", plain_route)
    loss, params = _loss_of_a_share()
    again = 2 if route == "plain" else 1
    assert routing_ops(jax.grad(lambda p: loss(p)[0]), params,
                       tokens=BATCH * SEQ, experts=8) == {
        "top_k": 4 * again, "sort": 4, "scores": 4 * again,
        "score_gradients": 8}


def test_the_checkpoints_keep_inputs_and_the_route():
    """What the five checkpoints save: every layer's input and, of an expert
    layer under ``ROUTED``, seven arrays of the (token, slot) pairs' size at
    most: no score matrix, no array with the router's 8 experts as an
    axis."""
    from jax._src.ad_checkpoint import saved_residuals
    loss, params = _loss_of_a_share()
    kept = [(aval, why) for aval, why in saved_residuals(
        lambda p: loss(p)[0], params) if "argument" not in why]
    shapes = [aval.shape for aval, _ in kept]
    assert not any(8 in shape for shape in shapes), shapes
    pairs, of_pairs = (BATCH * SEQ, 4), ((BATCH * SEQ, 4),
                                         (BATCH * SEQ * 4,), (4,))
    routed = [(aval, why) for aval, why in kept if aval.shape in of_pairs]
    assert all("apex_tpu/ops/moe.py" in why for _, why in routed)
    # sel, the selected scores, the weights, held and pos; order; group_sizes
    assert [sum(aval.shape == shape for aval, _ in routed)
            for shape in of_pairs] == [20, 4, 4]
    assert any(moe.ROUTED in why for _, why in routed)
    rest = [shape for shape in shapes if shape not in of_pairs]
    # [batch, tokens, ..] of the layers and of the loss, the last norm's
    # scale; [batch * tokens, ..] of the head, which multiplies over
    # flattened tokens
    assert all(len(shape) >= 3 or shape[0] == BATCH * SEQ
               for shape in rest), rest
    assert rest.count((BATCH, SEQ, 64)) >= 5            # the layers' inputs


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
def test_loss_gradients_and_state_are_the_plain_routes_bit_for_bit(
        dtype, monkeypatch):
    """``route``'s own rule under checkpoints that keep its results, and the
    plain function under autodiff, which routes twice, as every step did
    before: loss, every gradient and the model's state are the same bits.
    Op by op: inside one compiled program XLA's fusions choose the last
    digits, and two programs of one formula differ there."""
    loss, params = _loss_of_a_share(dtype)

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
    assert all(float(jnp.abs(grads[name]["experts"]["router"]).max()) > 0
               for name in state)
