"""Expert-parallel MoE vs the dense oracle (fwd + grads + drop behavior).

Beyond-parity (reference is DP-only): switch-style top-1 MoE with
all_to_all dispatch over a 4-rank virtual CPU mesh.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from apex_tpu.parallel.expert_parallel import moe_layer
from apex_tpu.parallel.pipeline import stack_stage_params


E = 4          # experts == ep ranks
D = 8
T = 16         # tokens per rank


@pytest.fixture
def ep_mesh():
    return Mesh(np.array(jax.devices("cpu")[:E]), ("ep",))


def _expert_fn(p, h):
    return jnp.tanh(h @ p["w"]) @ p["v"]


def _params():
    rng = np.random.RandomState(0)
    router = jnp.asarray(rng.randn(D, E) * 0.5, jnp.float32)
    experts = [{"w": jnp.asarray(rng.randn(D, 2 * D) * 0.3, jnp.float32),
                "v": jnp.asarray(rng.randn(2 * D, D) * 0.3, jnp.float32)}
               for _ in range(E)]
    return router, experts


def _oracle(router, experts, x):
    """Dense per-token computation: every token through its argmax expert,
    scaled by its gate (no capacity drops)."""
    logits = x @ router
    probs = jax.nn.softmax(logits, axis=-1)
    assign = jnp.argmax(probs, axis=-1)
    gate = jnp.max(probs, axis=-1)
    all_out = jnp.stack([_expert_fn(p, x) for p in experts])   # [E, T, D]
    y = all_out[assign, jnp.arange(x.shape[0])]
    return y * gate[:, None]


def _run_moe(mesh, router, experts_stacked, x, capacity_factor):
    def fn(router, ep, x):
        ep = jax.tree_util.tree_map(lambda p: jnp.squeeze(p, 0), ep)
        y, aux = moe_layer(x, router, _expert_fn, ep, axis_name="ep",
                           capacity_factor=capacity_factor)
        # load_balance_loss is already global (pmean'd inside moe_layer);
        # dropped_fraction is per-rank — average it to a global diagnostic.
        # pmean of the replicated loss is the identity, so one map is fine.
        aux = jax.tree_util.tree_map(
            lambda v: jax.lax.pmean(v, "ep"), aux)
        return y, aux

    return jax.jit(shard_map(
        fn, mesh=mesh,
        in_specs=(P(), P("ep"), P("ep")),
        out_specs=(P("ep"), P())))(router, experts_stacked, x)


def test_moe_matches_dense_oracle_when_capacity_suffices(ep_mesh):
    router, experts = _params()
    stacked = stack_stage_params(experts)
    x = jnp.asarray(np.random.RandomState(1).randn(E * T, D), jnp.float32)

    # capacity_factor=E => capacity==tokens_per_rank: nothing can drop.
    y, aux = _run_moe(ep_mesh, router, stacked, x, capacity_factor=E)
    assert float(aux.dropped_fraction) == 0.0
    ref = _oracle(router, experts, x)
    np.testing.assert_allclose(np.asarray(y), np.asarray(ref),
                               atol=1e-5, rtol=1e-5)


def test_moe_drops_overflow_tokens(ep_mesh):
    router, experts = _params()
    stacked = stack_stage_params(experts)
    # All tokens identical -> all route to ONE expert -> heavy overflow at
    # capacity_factor 1 (capacity = T/E).
    x = jnp.ones((E * T, D), jnp.float32)
    y, aux = _run_moe(ep_mesh, router, stacked, x, capacity_factor=1.0)
    assert float(aux.dropped_fraction) > 0.5
    # dropped tokens contribute exactly zero
    kept_rows = np.abs(np.asarray(y)).sum(axis=1) > 0
    assert kept_rows.sum() == round((1 - float(aux.dropped_fraction))
                                    * E * T)


def test_moe_gradients_flow_to_experts_and_router(ep_mesh):
    router, experts = _params()
    stacked = stack_stage_params(experts)
    x = jnp.asarray(np.random.RandomState(2).randn(E * T, D), jnp.float32)

    def loss(router, ep, x):
        ep_local = jax.tree_util.tree_map(lambda p: jnp.squeeze(p, 0), ep)
        y, aux = moe_layer(x, router, _expert_fn, ep_local, axis_name="ep",
                           capacity_factor=float(E))
        # Per-rank losses SUM across ranks through shard_map's transpose,
        # so divide by the rank count to match the dense global mean.
        return jnp.mean(y ** 2) / E

    def run(router, ep, x):
        return jax.grad(loss, argnums=(0, 1))(router, ep, x)

    g_router, g_experts = jax.jit(shard_map(
        run, mesh=ep_mesh,
        in_specs=(P(), P("ep"), P("ep")),
        out_specs=(P(), P("ep"))))(router, stacked, x)

    def loss_dense(router, experts, x):
        return jnp.mean(_oracle(router, experts, x) ** 2)

    r_router, r_experts = jax.grad(loss_dense, argnums=(0, 1))(
        router, experts, x)
    np.testing.assert_allclose(np.asarray(g_router), np.asarray(r_router),
                               atol=1e-4, rtol=1e-4)
    assert float(jnp.linalg.norm(g_router)) > 0
    r_stacked = stack_stage_params(r_experts)
    for a, b in zip(jax.tree_util.tree_leaves(g_experts),
                    jax.tree_util.tree_leaves(r_stacked)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-4, rtol=1e-4)


def test_moe_load_balance_loss_uses_global_means(ep_mesh):
    """Switch aux loss must be E * sum_e f_e * P_e over the GLOBAL batch
    (ADVICE r2): with routing skew across ranks, mean-of-local-products
    differs from the correct product-of-global-means."""
    router, experts = _params()
    stacked = stack_stage_params(experts)
    x = jnp.asarray(np.random.RandomState(7).randn(E * T, D) * 3, jnp.float32)

    _, aux = _run_moe(ep_mesh, router, stacked, x, capacity_factor=E)

    # Oracle on the full (unsharded) batch.
    probs = jax.nn.softmax(x @ router, axis=-1)
    f_g = jnp.mean(jax.nn.one_hot(jnp.argmax(probs, -1), E), axis=0)
    p_g = jnp.mean(probs, axis=0)
    want = float(E * jnp.sum(f_g * p_g))
    got = float(aux.load_balance_loss)
    assert abs(got - want) < 1e-5, (got, want)

    # And the skew is real in this fixture: the per-rank means differ,
    # so a mean-of-local-losses would NOT equal the global formula.
    xs = x.reshape(E, T, D)
    local = []
    for r in range(E):
        pr = jax.nn.softmax(xs[r] @ router, axis=-1)
        fr = jnp.mean(jax.nn.one_hot(jnp.argmax(pr, -1), E), axis=0)
        local.append(float(E * jnp.sum(fr * jnp.mean(pr, axis=0))))
    assert abs(np.mean(local) - want) > 1e-4, (np.mean(local), want)
