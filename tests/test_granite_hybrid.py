"""``models.GraniteHybrid``: the layer list, what amp O2 keeps float32, and
the O2 train step at a tiny size (finite, the loss falls, the mixer's scopes
reach the compiled module under ``apex.forward`` and its transpose).  The
comparison with the plain reference is in ``tests/benchmark``."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu import models, training
from apex_tpu.amp import policy
from apex_tpu.contrib.xentropy import softmax_cross_entropy_loss
from apex_tpu.models import granite_hybrid


def _ids(batch=2, seq=33, vocab=1024):
    return jax.random.randint(jax.random.PRNGKey(1), (batch, seq), 1, vocab)


def test_the_default_is_the_published_model():
    model = models.GraniteHybrid()
    kinds = list(model.layer_types)
    assert len(kinds) == 40 and kinds.count("attention") == 4
    assert [i for i, k in enumerate(kinds) if k == "attention"] == [5, 15, 25, 35]
    assert tuple(kinds[:10]) == granite_hybrid.PERIOD
    assert (model.hidden_size, model.mlp_dim, model.vocab_size) == (
        2048, 8192, 100352)
    assert model.mamba_heads * model.mamba_head_dim == 2 * model.hidden_size
    shapes = jax.eval_shape(
        lambda: models.GraniteHybrid(
            layer_types=granite_hybrid.PERIOD, vocab_size=50176).init(
                jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))["params"]
    count = lambda tree: sum(a.size for a in jax.tree_util.tree_leaves(tree))
    assert count(shapes["layer_0"]) == 76_182_976       # a Mamba-2 layer
    assert count(shapes["layer_5"]) == 60_821_504       # the attention layer
    assert count(shapes) == 849_230_784


def test_unknown_layer_kind_is_refused():
    model = models.granite_hybrid_tiny(layer_types=("mamba", "conv"))
    with pytest.raises(ValueError, match="unknown layer kind"):
        model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))


def test_initialisation_is_the_one_on_file():
    params = models.granite_hybrid_tiny().init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]
    mamba = params["layer_0"]["mamba"]
    np.testing.assert_allclose(np.exp(mamba["A_log"]), np.arange(1, 9),
                               rtol=1e-6)
    step = np.asarray(jax.nn.softplus(mamba["dt_bias"]))
    assert (step >= 1e-3 * 0.999).all() and (step <= 1e-1 * 1.001).all()
    np.testing.assert_array_equal(mamba["D"], np.ones(8, np.float32))
    assert abs(float(jnp.std(params["wte"])) - 0.02) < 2e-3
    assert float(jnp.abs(mamba["conv_kernel"]).max()) <= 0.5
    assert "attention" in params["layer_5"] and "mamba" not in params["layer_5"]


def test_o2_keeps_norms_and_the_mixers_scalars_float32():
    params = models.granite_hybrid_tiny().init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]
    cast = policy.convert_params(params, jnp.bfloat16,
                                 norm_predicate=granite_hybrid.keep_fp32)
    flat = {jax.tree_util.keystr(path): leaf.dtype for path, leaf in
            jax.tree_util.tree_flatten_with_path(cast)[0]}
    kept = {name for name, dtype in flat.items() if dtype == jnp.float32}
    assert all(name.endswith(("['scale']", "['A_log']", "['dt_bias']",
                              "['D']")) for name in kept)
    assert sum(name.endswith("['A_log']") for name in kept) == 9
    assert flat["['wte']"] == flat[
        "['layer_0']['mamba']['in_proj']['kernel']"] == jnp.bfloat16


@pytest.fixture(scope="module")
def o2_step():
    model = models.granite_hybrid_tiny(dtype=jnp.bfloat16)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]

    def loss_fn(p, batch):
        x, y = batch
        logits = model.apply({"params": p}, x)
        assert logits.dtype == jnp.float32
        return jnp.mean(softmax_cross_entropy_loss(
            logits.reshape(-1, logits.shape[-1]), y.reshape(-1)))

    init_fn, step_fn = training.make_train_step(
        loss_fn, training.adam(3e-3, weight_decay=0.1), opt_level="O2",
        loss_scale="dynamic", norm_predicate=granite_hybrid.keep_fp32)
    ids = _ids()
    return jax.jit(step_fn), init_fn(params), (ids[:, :-1], ids[:, 1:])


def test_o2_step_is_finite_and_the_loss_falls(o2_step):
    step, state, batch = o2_step
    losses = []
    for _ in range(6):
        state, metrics = step(state, batch)
        assert not bool(metrics["overflow"])
        losses.append(float(metrics["loss"]))
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0] - 0.1
    assert all(np.isfinite(np.asarray(leaf, np.float32)).all()
               for leaf in jax.tree_util.tree_leaves(state.params))


def test_the_mixers_scopes_reach_the_compiled_step(o2_step):
    step, state, batch = o2_step
    compiled = step.lower(state, batch).compile().as_text()
    assert granite_hybrid.SSM_SCOPES == (
        "apex.ssm", "apex.ssm.conv", "apex.ssm.scan", "apex.ssm.norm")
    for scope in granite_hybrid.SSM_SCOPES[1:]:
        assert f"/jvp(apex.forward)/GraniteHybrid/layer_0/mamba/apex.ssm/{scope}/" in compiled
        assert any("transpose(jvp(apex.forward))" in line
                   and f"/apex.ssm/{scope}/" in line
                   and "rematted_computation" in line
                   for line in compiled.splitlines()), scope
    assert "/layer_5/attention/" in compiled
    assert "/layer_5/mamba/" not in compiled


def test_the_backward_rules_keep_the_mixers_scopes(o2_step):
    """The conv and the scan's products have backward rules of their own
    (custom VJPs).  By the benchmark's readers' rule (``scopes_of`` for the
    phase, the innermost ``apex.*`` name of the ``op_name`` for the scope)
    their instructions still count as backward under ``apex.ssm.conv`` and
    ``apex.ssm.scan``: the rules' own, under ``transpose(jvp(apex.forward))``,
    and the forward recomputed for them, under ``rematted_computation``."""
    from benchmark import phase_reduce, scope_reduce

    step, state, batch = o2_step
    hlo = step.lower(state, batch).compile().as_text()
    phase = phase_reduce.scopes_of(hlo)
    seen = {scope: set() for scope in granite_hybrid.SSM_SCOPES[1:3]}
    for name, rest in phase_reduce._INSTRUCTION.findall(hlo):
        m = phase_reduce._OP_NAME.search(rest)
        found = scope_reduce._SCOPE.findall(m.group(1)) if m else []
        if found and found[-1] in seen and phase[name] == "backward":
            assert "transpose(jvp(apex.forward))" in m.group(1)
            seen[found[-1]].add("recomputed" if "rematted_computation"
                                in m.group(1) else "rule")
    assert seen == {"apex.ssm.conv": {"rule", "recomputed"},
                    "apex.ssm.scan": {"rule", "recomputed"}}


def test_the_mixer_refuses_its_scalars_in_a_reduced_dtype():
    """An O2 cast without ``keep_fp32`` rounds ``A_log``, ``dt_bias`` and
    ``D`` to bf16: the model fails at the trace, not silently."""
    model = models.granite_hybrid_tiny(dtype=jnp.bfloat16)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    kept = policy.convert_params(params, jnp.bfloat16,
                                 norm_predicate=granite_hybrid.keep_fp32)
    assert jax.eval_shape(model.apply, {"params": kept},
                          _ids()).dtype == jnp.float32
    with pytest.raises(TypeError, match="A_log arrived as bfloat16.*keep_fp32"):
        jax.eval_shape(model.apply,
                       {"params": policy.convert_params(params, jnp.bfloat16)},
                       _ids())
