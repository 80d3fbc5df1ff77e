"""``ops.moe_layer`` (sigmoid router, top-k with a selection bias, pairs sorted
by expert, grouped products over the experts held, no capacity) against a
dense loop over the experts written here, forward and every gradient: under a
balanced routing, one that sends **every** token to one held expert, one that
sends none to a held expert, and with a bias that changes the selection but
not the weights; the four shares of a layer add up to the uncut layer.  Every
one of those on both paths of the row passes: whole arrays (the bound of 192
rows is within one block) and walked, with a block of 16 rows.  Then the
walked path against the straight one bit for bit, at six loads, with the
blocks it does not reach poisoned, and the rows it says it walked."""

import collections

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu import ops
from apex_tpu.ops import moe

N, D, F, E, K = 48, 16, 12, 8, 4
BLOCK = 16


@pytest.fixture(params=["straight", "walked"])
def path(request, monkeypatch):
    if request.param == "walked":
        monkeypatch.setattr(moe, "_ROW_BLOCK", BLOCK)
    return request.param


#: the six tests of the layer run on both paths of ``_walk_rows``
both_paths = pytest.mark.usefixtures("path")


def dense_loop(x, w_gate, bias, w1, w3, w2, offset):
    """Every held expert applied to every token, times that token's weight
    for it: 0 where the expert was not selected."""
    scores = jax.nn.sigmoid(x @ w_gate)
    _, sel = jax.lax.top_k(scores + bias, K)
    picked = jnp.take_along_axis(scores, sel, -1)
    picked = picked / (picked.sum(-1, keepdims=True) + 1e-6)
    out = jnp.zeros_like(x)
    for i in range(w1.shape[0]):
        mine = (sel == offset + i).astype(x.dtype)
        y = (jax.nn.silu(x @ w1[i]) * (x @ w3[i])) @ w2[i]
        out = out + (picked * mine).sum(-1, keepdims=True) * y
    return out, sel


def _weights(held=E, seed=0):
    k = jax.random.split(jax.random.PRNGKey(seed), 6)
    return dict(x=jax.random.normal(k[0], (N, D)),
                w_gate=jax.random.normal(k[1], (D, E)) * 0.5,
                w1=jax.random.normal(k[2], (held, D, F)) * 0.3,
                w3=jax.random.normal(k[3], (held, D, F)) * 0.3,
                w2=jax.random.normal(k[4], (held, F, D)) * 0.3,
                cot=jax.random.normal(k[5], (N, D)))


def _both(p, bias, offset):
    """(output, gradients, selection, counts) of the layer and of the loop."""
    names = ("x", "w_gate", "w1", "w3", "w2")
    args = [p[n] for n in names]
    ours = lambda *a: ops.moe_layer(a[0], a[1], bias, *a[2:], top_k=K,
                                    expert_offset=offset)
    loop = lambda *a: dense_loop(a[0], a[1], bias, *a[2:], offset)
    out = {}
    for name, f in (("ours", ours), ("loop", loop)):
        y, *rest = f(*args)
        grads = jax.grad(lambda *a: jnp.sum(f(*a)[0] * p["cot"]),
                         argnums=tuple(range(5)))(*args)
        out[name] = (y, dict(zip(names, grads)), rest)
    return out


def _assert_equal(result, zero=()):
    (y, grads, (counts, sel, _)), (want, want_grads, (want_sel,)) = (
        result["ours"], result["loop"])
    np.testing.assert_array_equal(np.sort(sel, -1), np.sort(want_sel, -1))
    np.testing.assert_array_equal(
        counts, np.bincount(np.asarray(sel).ravel(), minlength=E))
    scale = max(float(jnp.abs(want).max()), 1e-6)
    np.testing.assert_allclose(y, want, atol=2e-5 * scale + 1e-7)
    for name, g in grads.items():
        w = want_grads[name]
        assert g.shape == w.shape and g.dtype == w.dtype, name
        np.testing.assert_allclose(
            g, w, atol=3e-5 * max(float(jnp.abs(w).max()), 1e-6) + 1e-7,
            err_msg=name)
        if name in zero:
            assert float(jnp.abs(g).max()) == 0.0, name
    return sel, counts


@both_paths
@pytest.mark.parametrize("held,offset", [(8, 0), (2, 0), (2, 4), (3, 5)])
def test_balanced_routing_equals_the_dense_loop(held, offset):
    sel, counts = _assert_equal(_both(_weights(held), jnp.zeros((E,)), offset))
    assert counts.sum() == N * K and counts.dtype == jnp.int32
    assert sel.shape == (N, K) and len(np.unique(np.asarray(sel))) == E


@both_paths
def test_every_token_to_one_held_expert_and_no_row_is_dropped():
    """One column of the router towers over the rest: all 48 tokens select
    expert 5, which is held, and every one of its rows is computed."""
    p = _weights(held=2)
    p["w_gate"] = p["w_gate"].at[:, 5].set(0.0)
    p["x"] = p["x"].at[:, 0].set(30.0)
    p["w_gate"] = p["w_gate"].at[0, 5].set(1.0)
    sel, counts = _assert_equal(_both(p, jnp.zeros((E,)), offset=4))
    assert counts[5] == N and (np.asarray(sel) == 5).sum() == N
    # the loop's output with expert 5 alone is not zero: rows were computed
    y = ops.moe_layer(p["x"], p["w_gate"], jnp.zeros((E,)), p["w1"], p["w3"],
                      p["w2"], top_k=K, expert_offset=4)[0]
    assert float(jnp.abs(y).min(-1).max()) > 0 and bool(
        (jnp.abs(y).sum(-1) > 0).all())


@both_paths
def test_no_token_to_a_held_expert_gives_zero_and_zero_gradients():
    """The two held experts' columns are far below the rest: no pair is held,
    every group is empty, output and the experts' gradients are exact zeros."""
    p = _weights(held=2)
    p["x"] = p["x"].at[:, 0].set(30.0)
    p["w_gate"] = p["w_gate"].at[0].set(0.0).at[0, 4:6].set(-1.0)
    result = _both(p, jnp.zeros((E,)), offset=4)
    sel, counts = _assert_equal(result, zero=("w1", "w3", "w2", "x", "w_gate"))
    assert counts[4] == counts[5] == 0 and counts.sum() == N * K
    assert float(jnp.abs(result["ours"][0]).max()) == 0.0


@both_paths
def test_a_bias_changes_the_selection_but_not_the_weights():
    """A bias of +-2 on two experts moves them into and out of every token's
    selection; the weights are still the unbiased scores of the selected,
    and no gradient reaches the bias."""
    p, offset = _weights(held=4), 2
    bias = jnp.zeros((E,)).at[3].set(2.0).at[6].set(-2.0)
    sel, counts = _assert_equal(_both(p, bias, offset))
    plain_sel = _both(p, jnp.zeros((E,)), offset)["ours"][2][1]
    assert counts[3] == N and counts[6] == 0
    assert not np.array_equal(np.sort(sel, -1), np.sort(plain_sel, -1))
    _, weights, _ = moe.route(p["x"], p["w_gate"], bias, top_k=K)
    scores = jax.nn.sigmoid(p["x"] @ p["w_gate"])
    picked = jnp.take_along_axis(scores, sel, -1)
    np.testing.assert_allclose(
        weights, picked / (picked.sum(-1, keepdims=True) + 1e-6), rtol=1e-6)
    d_bias = jax.grad(lambda b: jnp.sum(ops.moe_layer(
        p["x"], p["w_gate"], b, p["w1"], p["w3"], p["w2"], top_k=K,
        expert_offset=offset)[0] * p["cot"]))(bias)
    assert float(jnp.abs(d_bias).max()) == 0.0


@both_paths
def test_the_four_shares_add_up_to_the_uncut_layer():
    """Offsets 0, 2, 4, 6 of 8, two experts each: outputs and input gradients
    of the four shares sum to those of the layer that holds all eight."""
    p = _weights(held=E)
    bias = 0.1 * jax.random.normal(jax.random.PRNGKey(3), (E,))

    def share(x, offset, held):
        cut = lambda w: w[offset:offset + held]
        return ops.moe_layer(x, p["w_gate"], bias, cut(p["w1"]), cut(p["w3"]),
                             cut(p["w2"]), top_k=K, expert_offset=offset)
    whole, whole_counts, whole_sel, _ = share(p["x"], 0, E)
    parts = [share(p["x"], offset, 2) for offset in (0, 2, 4, 6)]
    np.testing.assert_allclose(sum(y for y, *_ in parts), whole, atol=2e-5)
    for _, counts, sel, _ in parts:     # every share routes over all eight
        np.testing.assert_array_equal(counts, whole_counts)
        np.testing.assert_array_equal(sel, whole_sel)
    d_x = lambda offset, held: jax.grad(
        lambda x: jnp.sum(share(x, offset, held)[0] * p["cot"]))(p["x"])
    # the router's part of the input gradient is in every share's: the
    # weights of a token's held experts; it adds up like the rest
    np.testing.assert_allclose(sum(d_x(o, 2) for o in (0, 2, 4, 6)),
                               d_x(0, E), atol=5e-5)


@both_paths
def test_leading_axes_dtypes_and_refusals():
    p = _weights(held=2)
    x3 = p["x"].reshape(4, 12, D)
    y, counts, sel, walked = ops.moe_layer(
        x3, p["w_gate"], jnp.zeros((E,)), p["w1"], p["w3"], p["w2"], top_k=K,
        expert_offset=4)
    assert y.shape == x3.shape and sel.shape == (N, K) and counts.shape == (E,)
    assert walked.shape == () and walked.dtype == jnp.int32
    cast = lambda a: a.astype(jnp.bfloat16)
    yb, *_ = ops.moe_layer(cast(x3), p["w_gate"], jnp.zeros((E,)),
                           cast(p["w1"]), cast(p["w3"]), cast(p["w2"]),
                           top_k=K, expert_offset=4)
    assert yb.dtype == jnp.bfloat16
    np.testing.assert_allclose(yb.astype(jnp.float32), y, atol=0.1)
    with pytest.raises(TypeError, match="router's weight arrived as bfloat16"):
        ops.moe_layer(cast(x3), cast(p["w_gate"]), jnp.zeros((E,)), p["w1"],
                      p["w3"], p["w2"], top_k=K)
    with pytest.raises(ValueError, match="not among the router's 8"):
        ops.moe_layer(x3, p["w_gate"], jnp.zeros((E,)), p["w1"], p["w3"],
                      p["w2"], top_k=K, expert_offset=7)
    assert moe.MOE_SCOPES == ("apex.moe", "apex.moe.route", "apex.moe.experts",
                              "apex.moe.combine")


#: name -> (experts held, offset, tokens steered to the one held expert or
#: None for the router's own choice, rows held)
LOADS = {"no_row": (1, 7, 0, 0), "one_row": (1, 7, 1, 1),
         "a_block": (1, 7, BLOCK, BLOCK),
         "a_block_and_one": (1, 7, BLOCK + 1, BLOCK + 1),
         "ragged_middle": (3, 5, None, 71), "every_pair": (E, 0, None, N * K)}


def _steered(held, offset, tokens):
    """The layer's arguments with the first ``tokens`` tokens sent to the one
    held expert and no other token (a feature of +-4 that only that expert's
    column reads, against scores of the others that lie well inside (0, 1))."""
    p = _weights(held)
    if tokens is not None:
        p["x"] = p["x"].at[:, 0].set(
            jnp.where(jnp.arange(N) < tokens, 4., -4.))
        p["w_gate"] = p["w_gate"].at[0].set(0.0).at[0, offset].set(2.0)
    return p


def _jitted(p, offset, dtype=jnp.float32):
    """(y, counts, sel, rows walked) and the five gradients, as one program."""
    cast = lambda n: p[n].astype(dtype)
    args = (cast("x"), p["w_gate"], cast("w1"), cast("w3"), cast("w2"))
    layer = lambda *a: ops.moe_layer(a[0], a[1], jnp.zeros((E,)), *a[2:],
                                     top_k=K, expert_offset=offset)
    loss = lambda *a: jnp.sum(layer(*a)[0].astype(jnp.float32) * p["cot"])
    return jax.jit(lambda *a: (layer(*a), jax.grad(loss, range(5))(*a)))(*args)


def _assert_bitwise(got, want):
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want), strict=True):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("load", LOADS)
def test_walked_equals_straight_bit_for_bit(load, dtype, monkeypatch):
    """Output and the gradients to x, the router and w1, w3, w2 on the walked
    path are those of the straight path to the last bit (the tail is masked
    in both, so every value compared is of a held row), and the layer says
    how far it walked."""
    held, offset, tokens, rows = LOADS[load]
    p = _steered(held, offset, tokens)
    assert moe._ROW_BLOCK > N * K           # as shipped: whole arrays
    (*straight, bound), straight_grads = _jitted(p, offset, dtype)
    assert int(straight[1][offset:offset + held].sum()) == rows
    assert int(bound) == N * K
    monkeypatch.setattr(moe, "_ROW_BLOCK", BLOCK)
    (*walked, n_walked), walked_grads = _jitted(p, offset, dtype)
    assert int(n_walked) == -(-rows // BLOCK) * BLOCK
    _assert_bitwise((walked, walked_grads), (straight, straight_grads))
    if rows:
        assert float(jnp.abs(walked[0].astype(jnp.float32)).max()) > 0


def _poisoned(shape, dtype, after):
    """In place of ``moe._unwritten``: NaN where no block is written."""
    return jnp.full(shape, jnp.nan, dtype)


@pytest.mark.parametrize("load", LOADS)
def test_a_poisoned_tail_changes_nothing(load, monkeypatch):
    """The blocks a walk does not reach hold NaN in place of whatever the
    allocation held: output and gradients are the same bits."""
    held, offset, tokens, _ = LOADS[load]
    p = _steered(held, offset, tokens)
    monkeypatch.setattr(moe, "_ROW_BLOCK", BLOCK)
    clean = _jitted(p, offset)
    monkeypatch.setattr(moe, "_unwritten", _poisoned)
    _assert_bitwise(_jitted(p, offset), clean)
    assert all(bool(jnp.isfinite(a).all())
               for a in jax.tree_util.tree_leaves(clean[1]))


@pytest.mark.parametrize("bound,n_rows", [(48, 48), (48, 33), (48, 16),
                                          (48, 5), (48, 0), (40, 7), (16, 3)])
def test_walk_rows_alone(bound, n_rows, monkeypatch):
    """The helper: results written into arrays of their own hold NaN (the
    test's ``_unwritten``) wherever no block was written; results written in
    place of an argument hold the argument there; a bound within one block,
    or of no whole blocks, is computed whole."""
    monkeypatch.setattr(moe, "_ROW_BLOCK", BLOCK)
    monkeypatch.setattr(moe, "_unwritten", _poisoned)
    a = jnp.arange(bound * 3, dtype=jnp.float32).reshape(bound, 3)
    b = jnp.arange(bound, dtype=jnp.int32)
    fn = lambda a, b: (a * 2 + b[:, None], (b + 1).astype(jnp.float32))
    want_twice, want_plus = fn(a, b)
    whole = bound <= BLOCK or bound % BLOCK
    written = bound if whole else -(-n_rows // BLOCK) * BLOCK
    assert int(moe._rows_walked(jnp.int32(n_rows), bound)) == written
    for in_place in (0, 1):
        twice, plus = jax.jit(lambda n: moe._walk_rows(
            fn, n, a, b, in_place=in_place))(n_rows)
        np.testing.assert_array_equal(twice[:written], want_twice[:written])
        np.testing.assert_array_equal(plus[:written], want_plus[:written])
        assert bool(jnp.isnan(plus[written:]).all())
        if in_place:
            np.testing.assert_array_equal(twice[written:], a[written:])
        else:
            assert bool(jnp.isnan(twice[written:]).all())


# ---------------------------------------------------------------------------
# ``ops.latent_moe_layer``: the router reads one array and the experts
# another, an expert is two products around ``relu(.) ** 2``, and the sorted
# rows run in waves whose number follows the load.

LAT = 10        # the latent width: the experts' input is not the router's


def latent_loop(x, latent, w_gate, bias, w1, w2, offset, scaling=1.0):
    """Every held expert applied to every token's latent row, times that
    token's weight for it: 0 where the expert was not selected."""
    scores = jax.nn.sigmoid(x @ w_gate)
    _, sel = jax.lax.top_k(scores + bias, K)
    picked = jnp.take_along_axis(scores, sel, -1)
    picked = picked / (picked.sum(-1, keepdims=True) + 1e-6) * scaling
    out = jnp.zeros_like(latent)
    for i in range(w1.shape[0]):
        mine = (sel == offset + i).astype(x.dtype)
        y = jnp.square(jax.nn.relu(latent @ w1[i])) @ w2[i]
        out = out + (picked * mine).sum(-1, keepdims=True) * y
    return out, sel


def _latent_weights(held=E, seed=0):
    k = jax.random.split(jax.random.PRNGKey(seed), 6)
    return dict(x=jax.random.normal(k[0], (N, D)),
                latent=jax.random.normal(k[1], (N, LAT)),
                w_gate=jax.random.normal(k[2], (D, E)) * 0.5,
                w1=jax.random.normal(k[3], (held, LAT, F)) * 0.3,
                w2=jax.random.normal(k[4], (held, F, LAT)) * 0.3,
                cot=jax.random.normal(k[5], (N, LAT)))


def _latent_both(p, bias, offset, scaling=1.0):
    names = ("x", "latent", "w_gate", "w1", "w2")
    args = [p[n] for n in names]
    ours = lambda *a: ops.latent_moe_layer(
        a[0], a[1], a[2], bias, *a[3:], top_k=K, expert_offset=offset,
        routed_scaling_factor=scaling)
    loop = lambda *a: latent_loop(a[0], a[1], a[2], bias, *a[3:], offset,
                                  scaling)
    out = {}
    for name, f in (("ours", ours), ("loop", loop)):
        y, *rest = f(*args)
        grads = jax.grad(lambda *a: jnp.sum(f(*a)[0] * p["cot"]),
                         argnums=tuple(range(5)))(*args)
        out[name] = (y, dict(zip(names, grads)), rest)
    return out


#: rows a wave: more than the layer ever holds (one wave, the pairs padded
#: to it), the 192 pairs exactly, and 2, 4 and 12 waves of them
WAVES = [256, 192, 96, 48, 16]


@pytest.fixture(params=WAVES)
def wave(request, monkeypatch):
    monkeypatch.setattr(moe, "_WAVE_ROWS", request.param)
    return request.param


@pytest.mark.parametrize("held,offset", [(8, 0), (2, 0), (2, 4), (3, 5)])
def test_latent_layer_equals_the_dense_loop(held, offset, wave):
    """Forward and every gradient, the router's through the weights among
    them, at every number of waves; with all 8 experts held every pair is
    held here (192 rows: one to twelve waves) and the answer is the dense
    one."""
    result = _latent_both(_latent_weights(held), jnp.zeros((E,)), offset, 2.5)
    (y, _, (counts, sel, (rows_held, computed))) = result["ours"]
    sel, counts = _assert_equal(
        {"ours": result["ours"][:2] + ((counts, sel, None),),
         "loop": result["loop"]})
    held_rows = int(counts[offset:offset + held].sum())
    assert int(rows_held) == held_rows
    assert int(computed) == -(-held_rows // min(wave, N * K)) * min(wave, N * K)
    if held == E:
        assert held_rows == N * K
    assert y.shape == (N, LAT)


def test_latent_router_reads_x_and_the_experts_read_latent(wave):
    """Changing the experts' input leaves selection and counts alone;
    changing the router's input changes them and leaves an expert's own
    product alone."""
    p = _latent_weights(held=4)
    f = lambda x, latent: ops.latent_moe_layer(
        x, latent, p["w_gate"], jnp.zeros((E,)), p["w1"], p["w2"], top_k=K)
    y, counts, sel, _ = f(p["x"], p["latent"])
    y2, counts2, sel2, _ = f(p["x"], 2 * p["latent"])
    np.testing.assert_array_equal(sel, sel2)
    np.testing.assert_array_equal(counts, counts2)
    # relu(2 l W1)^2 W2 = 4 relu(l W1)^2 W2
    np.testing.assert_allclose(y2, 4 * y, rtol=1e-5, atol=1e-6)
    _, counts3, sel3, _ = f(p["x"][::-1], p["latent"])
    np.testing.assert_array_equal(sel3, np.asarray(sel)[::-1])
    with pytest.raises(ValueError, match="not the same tokens"):
        f(p["x"][:5], p["latent"])


@pytest.mark.parametrize("load", [0, 1, 15, 16, 17, 48])
def test_latent_loads_under_at_and_over_one_wave(load, monkeypatch):
    """``load`` tokens select the one held expert (a wave is 16 rows: none,
    under, at and over one wave, and three whole waves): the rows held, the
    rows the waves went over, and the dense answer with its gradients."""
    monkeypatch.setattr(moe, "_WAVE_ROWS", 16)
    p = _latent_weights(held=1)
    # expert 5's score is 1 for the first ``load`` tokens and 0 elsewhere
    p["w_gate"] = p["w_gate"].at[:, 5].set(0.0).at[0, 5].set(1.0)
    p["x"] = p["x"].at[:, 0].set(jnp.where(jnp.arange(N) < load, 40.0, -40.0))
    result = _latent_both(p, jnp.zeros((E,)), offset=5)
    y, _, (counts, sel, (rows_held, computed)) = result["ours"]
    _assert_equal({"ours": result["ours"][:2] + ((counts, sel, None),),
                   "loop": result["loop"]})
    assert int(counts[5]) == load == int(rows_held)
    assert int(computed) == -(-load // 16) * 16
    assert bool((jnp.abs(y[load:]).sum(-1) == 0).all())
    if load:
        assert bool((jnp.abs(y[:load]).sum(-1) > 0).all())


def test_latent_shares_add_up_and_bf16_runs(wave):
    """The four shares of two experts add up to the uncut layer; in bf16
    with a float32 router the layer keeps the latent dtype."""
    p = _latent_weights()
    bias = jnp.zeros((E,))
    whole = ops.latent_moe_layer(p["x"], p["latent"], p["w_gate"], bias,
                                 p["w1"], p["w2"], top_k=K)[0]
    parts = sum(ops.latent_moe_layer(
        p["x"], p["latent"], p["w_gate"], bias, p["w1"][i:i + 2],
        p["w2"][i:i + 2], top_k=K, expert_offset=i)[0] for i in range(0, E, 2))
    np.testing.assert_allclose(parts, whole, atol=2e-5)
    half = lambda a: a.astype(jnp.bfloat16)
    y = ops.latent_moe_layer(half(p["x"]), half(p["latent"]), p["w_gate"],
                             bias, half(p["w1"]), half(p["w2"]), top_k=K)[0]
    assert y.dtype == jnp.bfloat16
    np.testing.assert_allclose(y.astype(jnp.float32), whole, atol=0.1,
                               rtol=0.1)
    with pytest.raises(TypeError, match="float32"):
        ops.latent_moe_layer(p["x"], p["latent"], half(p["w_gate"]), bias,
                             p["w1"], p["w2"], top_k=K)
    with pytest.raises(ValueError, match="not among the router's"):
        ops.latent_moe_layer(p["x"], p["latent"], p["w_gate"], bias,
                             p["w1"][:2], p["w2"][:2], top_k=K,
                             expert_offset=7)


@pytest.mark.parametrize("experts,top_k", [(64, 4), (256, 8)])
def test_route_reads_the_selected_scores_by_mask(experts, top_k):
    """``route`` reads the selected scores through a mask: the weights and
    the gradient to the router that a gather of them gives, bit for bit one
    way, and no gather in the lowered form."""
    k = jax.random.split(jax.random.PRNGKey(7), 3)
    x = jax.random.normal(k[0], (40, D))
    w_gate = jax.random.normal(k[1], (D, experts)) * 0.5
    cot = jax.random.normal(k[2], (40, top_k))
    route = lambda w: moe.route(x, w, jnp.zeros((experts,)), top_k=top_k,
                                scaling=5.0)

    def gathered(w):
        scores = jax.nn.sigmoid(jnp.dot(x, w, precision="highest"))
        picked = jnp.take_along_axis(scores, route(w)[0], axis=-1)
        return picked / (picked.sum(-1, keepdims=True) + 1e-6) * 5.0

    np.testing.assert_array_equal(route(w_gate)[1], gathered(w_gate))
    grad = lambda f: jax.grad(lambda w: jnp.sum(f(w) * cot))(w_gate)
    np.testing.assert_allclose(grad(lambda w: route(w)[1]), grad(gathered),
                               rtol=1e-6, atol=1e-7)
    assert "gather" not in jax.jit(lambda w: route(w)[1]).lower(
        w_gate).as_text()
    assert "gather" in jax.jit(gathered).lower(w_gate).as_text()


# ---------------------------------------------------------------------------
# ``route``'s own backward rule, and what a checkpoint around a layer keeps
# of the route under ``moe.ROUTED``.

def routing_ops(f, *args, tokens, experts):
    """How often the program ``f(*args)`` selects (``top_k``), sorts, and
    multiplies at ``Precision.HIGHEST`` into (``scores``) or out of
    (``score_gradients``) a ``[tokens, experts]`` array, over its jaxpr and
    every jaxpr inside it: two of the last are a router's backward."""
    from jax._src import core

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            yield eqn
            for inner in core.jaxprs_in_params(eqn.params):
                yield from walk(inner)
    seen = collections.Counter()
    highest = (jax.lax.Precision.HIGHEST,) * 2
    for eqn in walk(jax.make_jaxpr(f)(*args).jaxpr):
        if eqn.primitive.name in ("top_k", "sort"):
            seen[eqn.primitive.name] += 1
        elif (eqn.primitive.name == "dot_general"
              and eqn.params["precision"] == highest):
            if eqn.outvars[0].aval.shape == (tokens, experts):
                seen["scores"] += 1
            elif (tokens, experts) in [v.aval.shape for v in eqn.invars]:
                seen["score_gradients"] += 1
    return dict(seen)


def plain_route(x, w_gate, bias, *, top_k, norm_topk_prob=True, scaling=1.0):
    """The formula under autodiff, which keeps the ``[N, E]`` scores: the
    oracle of ``route``'s rule, and what the benchmark's controls put in
    ``moe.route``'s place."""
    scores = jax.nn.sigmoid(jnp.dot(x.astype(jnp.float32), w_gate,
                                    precision=jax.lax.Precision.HIGHEST))
    _, sel = jax.lax.top_k(jax.lax.stop_gradient(scores) + bias, top_k)
    experts = jnp.arange(w_gate.shape[1], dtype=sel.dtype)
    weights = jnp.where(sel[..., None] == experts, scores[:, None, :],
                        0).sum(-1)
    if norm_topk_prob:
        weights = weights / (weights.sum(-1, keepdims=True) + 1e-6)
    counts = (sel[..., None] == experts).sum((0, 1), dtype=jnp.int32)
    return sel, weights * scaling, counts


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("norm_topk_prob", [True, False],
                         ids=["normalised", "unnormalised"])
@pytest.mark.parametrize("top_k,experts", [(4, 64), (22, 512), (1, 8)])
def test_the_route_rule_equals_autodiff_bit_for_bit(top_k, experts,
                                                    norm_topk_prob, dtype):
    """Selection, weights, counts and the gradients to ``x`` and ``w_gate``
    of the rule that keeps ``[N, k]`` selected scores are those of autodiff
    through the ``[N, E]`` score matrix, to the last bit, op by op and as one
    program."""
    k = jax.random.split(jax.random.PRNGKey(top_k), 4)
    x = jax.random.normal(k[0], (96, 2 * D)).astype(dtype)
    w_gate = jax.random.normal(k[1], (2 * D, experts)) * 0.5
    bias = 0.3 * jax.random.normal(k[2], (experts,))
    cot = jax.random.normal(k[3], (96, top_k))

    def run(route, jit):
        f = lambda x, w: route(x, w, bias, top_k=top_k, scaling=5.0,
                               norm_topk_prob=norm_topk_prob)
        both = lambda x, w: (f(x, w), jax.grad(
            lambda *a: jnp.sum(f(*a)[1] * cot), (0, 1))(x, w))
        return (jax.jit(both) if jit else both)(x, w_gate)
    for jit in (False, True):
        got, want = run(moe.route, jit), run(plain_route, jit)
        _assert_bitwise(got, want)
        (sel, _, counts), (d_x, d_w) = got
        assert d_x.dtype == dtype and d_w.dtype == jnp.float32
        assert float(jnp.abs(d_w).max()) > 0
        assert not np.array_equal(
            sel, plain_route(x, w_gate, 0 * bias, top_k=top_k)[0])
        assert int(counts.sum()) == 96 * top_k
    d_bias = jax.grad(lambda b: jnp.sum(moe.route(
        x, w_gate, b, top_k=top_k)[1] * cot))(bias)
    assert d_bias.shape == bias.shape and float(jnp.abs(d_bias).max()) == 0.0
    with pytest.raises(TypeError, match="router's weight arrived as bfloat16"):
        moe.route(x, w_gate.astype(jnp.bfloat16), bias, top_k=top_k)
    with pytest.raises(TypeError, match="selection bias as bfloat16"):
        moe.route(x, w_gate, bias.astype(jnp.bfloat16), top_k=top_k)


def _layer_under_test(layer):
    """``(f, arguments)``: the layer's output for a bias that is not zero,
    two of eight experts held, as a function of what a gradient reaches."""
    bias = jnp.zeros((E,)).at[3].set(.2).at[6].set(-.2)
    if layer == "moe_layer":
        p, names = _weights(held=2), ("x", "w_gate", "w1", "w3", "w2")
        f = lambda *a: ops.moe_layer(a[0], a[1], bias, *a[2:], top_k=K,
                                     expert_offset=2)[0]
    else:
        p, names = _latent_weights(held=2), ("x", "latent", "w_gate", "w1",
                                             "w2")
        f = lambda *a: ops.latent_moe_layer(a[0], a[1], a[2], bias, *a[3:],
                                            top_k=K, expert_offset=2,
                                            routed_scaling_factor=2.5)[0]
    return f, [p[n] for n in names], p["cot"]


@pytest.mark.parametrize("route", ["rule", "plain"])
@pytest.mark.parametrize("layer", ["moe_layer", "latent_moe_layer"])
def test_a_checkpoint_that_keeps_routed_gives_the_same_gradients(
        layer, route, monkeypatch):
    """Under ``save_only_these_names(ROUTED)`` the layers give the gradients
    they give without a checkpoint, with ``route``'s own rule and with a
    plain function in its place, as the benchmark's controls replace it.
    The checkpoint's backward sorts nothing again, and with the rule selects
    nothing again (autodiff of the plain function reads the score matrix,
    which is not kept, and so routes twice)."""
    if route == "plain":
        monkeypatch.setattr(moe, "route", plain_route)
    f, args, cot = _layer_under_test(layer)
    kept = jax.checkpoint(f, policy=(
        jax.checkpoint_policies.save_only_these_names(moe.ROUTED)))
    grad = lambda f: jax.jit(jax.grad(lambda *a: jnp.sum(f(*a) * cot),
                                      tuple(range(len(args)))))
    for got, want in zip(grad(kept)(*args), grad(f)(*args), strict=True):
        # two programs: the last digits are the compiler's to choose
        scale = float(jnp.abs(want).max())
        assert scale > 0
        np.testing.assert_allclose(got, want, atol=2e-6 * scale)
    count = lambda f: routing_ops(grad(f), *args, tokens=N, experts=E)
    twice = {"top_k": 2, "sort": 2, "scores": 2, "score_gradients": 2}
    assert count(kept) == (
        {"top_k": 1, "sort": 1, "scores": 1, "score_gradients": 2}
        if route == "rule" else dict(twice, sort=1))
    # a checkpoint without the name: as before
    assert count(jax.checkpoint(f)) == twice


@pytest.mark.parametrize("layer", ["moe_layer", "latent_moe_layer"])
def test_what_a_layer_keeps_of_the_route_has_no_axis_of_experts(layer):
    """The residuals of a checkpoint that keeps ``ROUTED``: the arguments
    and arrays of the pairs' size at most, none ``[.., E]``."""
    from jax._src.ad_checkpoint import saved_residuals
    f, args, cot = _layer_under_test(layer)
    kept = jax.checkpoint(f, policy=(
        jax.checkpoint_policies.save_only_these_names(moe.ROUTED)))
    made = [aval for aval, why in saved_residuals(
        lambda *a: jnp.sum(kept(*a) * cot), *args)
        if "argument" not in why and "constant" not in why]
    assert made and all(aval.size <= N * K and E not in aval.shape[1:]
                        for aval in made), made
    shapes = {(aval.shape, str(aval.dtype)) for aval in made}
    assert shapes == {((N, K), "int32"), ((N, K), "float32"), ((N, K), "bool"),
                      ((N * K,), "int32"), ((2,), "int32")}
    # sel, the selected scores, the weights, held, order, group_sizes, and
    # the SwiGLU layer's pos
    assert len(made) == (7 if layer == "moe_layer" else 6)


# ---------------------------------------------------------------------------
# The softmax router, ReGLU experts and a router input apart from the
# experts' (``score="softmax"``, ``activation="relu"``, ``router_in``), and
# the defaults' program as it was before they came.

def plain_softmax_route(x, w_gate, *, top_k, scaling=1.0):
    """The formula under autodiff: the softmax of the selected logits, which
    is the softmax over all of them renormalised over the ``top_k``."""
    logits = jnp.dot(x.astype(jnp.float32), w_gate,
                     precision=jax.lax.Precision.HIGHEST)
    _, sel = jax.lax.top_k(jax.lax.stop_gradient(logits), top_k)
    whole = jax.nn.softmax(logits, axis=-1)
    picked = jnp.take_along_axis(whole, sel, axis=-1)
    return sel, picked / picked.sum(-1, keepdims=True) * scaling


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("top_k,experts", [(6, 64), (1, 8), (8, 8)])
def test_the_softmax_route_against_its_formula_both_ways(top_k, experts,
                                                         dtype):
    """Selection, weights and counts of ``score="softmax"`` are the top-k
    of the logits and the renormalised softmax over all of them; its rule,
    which keeps ``[N, k]`` selected logits, gives autodiff's gradients to
    ``x`` and ``w_gate`` through the whole ``[N, E]`` softmax."""
    k = jax.random.split(jax.random.PRNGKey(top_k), 3)
    x = jax.random.normal(k[0], (96, 2 * D)).astype(dtype)
    w_gate = jax.random.normal(k[1], (2 * D, experts)) * 0.5
    cot = jax.random.normal(k[2], (96, top_k))
    ours = lambda x, w: moe.route(x, w, None, top_k=top_k, scaling=2.0,
                                  score="softmax")
    plain = lambda x, w: plain_softmax_route(x, w, top_k=top_k, scaling=2.0)
    sel, weights, counts = ours(x, w_gate)
    want_sel, want = plain(x, w_gate)
    np.testing.assert_array_equal(sel, want_sel)
    np.testing.assert_allclose(weights, want, rtol=2e-6, atol=1e-7)
    np.testing.assert_allclose(weights.sum(-1), 2.0, rtol=1e-6)
    np.testing.assert_array_equal(
        counts, np.bincount(np.asarray(sel).ravel(), minlength=experts))
    grad = lambda f: jax.grad(lambda *a: jnp.sum(f(*a)[1] * cot), (0, 1))(
        x, w_gate)
    for got, w in zip(grad(ours), grad(plain), strict=True):
        assert got.dtype == w.dtype
        # one expert a token, or every one: the weights are constants and
        # the true gradient is zero, which autodiff reads to rounding
        scale = float(jnp.abs(w.astype(jnp.float32)).max())
        assert scale > 1e-3 or top_k in (1, experts)
        np.testing.assert_allclose(got.astype(jnp.float32),
                                   w.astype(jnp.float32),
                                   atol=2e-6 * scale + 1e-6, rtol=1e-2
                                   if dtype == jnp.bfloat16 else 1e-5)
    # what the backward reads of the route: the k selected logits a token
    assert routing_ops(jax.grad(lambda w: jnp.sum(ours(x, w)[1] * cot)),
                       w_gate, tokens=96, experts=experts) == {
        "top_k": 1, "scores": 1, "score_gradients": 2}
    with pytest.raises(ValueError, match="no selection bias"):
        moe.route(x, w_gate, jnp.zeros((experts,)), top_k=top_k,
                  score="softmax")
    with pytest.raises(ValueError, match="no selection bias"):
        moe.route(x, w_gate, None, top_k=top_k, score="softmax",
                  norm_topk_prob=False)
    with pytest.raises(ValueError, match="score 'tanh'"):
        moe.route(x, w_gate, None, top_k=top_k, score="tanh")


def softmax_relu_loop(x, u, w_gate, w1, w3, w2, offset):
    """The ReGLU layer under the softmax router that reads ``u``, as a dense
    loop over the held experts."""
    sel, picked = plain_softmax_route(u, w_gate, top_k=K)
    out = jnp.zeros_like(x)
    for i in range(w1.shape[0]):
        mine = (sel == offset + i).astype(x.dtype)
        y = (jax.nn.relu(x @ w1[i]) * (x @ w3[i])) @ w2[i]
        out = out + (picked * mine).sum(-1, keepdims=True) * y
    return out, sel


@both_paths
@pytest.mark.parametrize("held,offset", [(8, 0), (3, 5)])
def test_relu_experts_under_a_softmax_router_that_reads_its_own_input(
        held, offset):
    """``activation="relu"``: ``relu(gate) * up``, forward and backward, on
    both paths of the row passes; ``router_in``: the router reads it and the
    experts read ``x``, and each gets its own gradient."""
    p = _weights(held)
    u = jax.random.normal(jax.random.PRNGKey(11), (N, D))
    names = ("x", "u", "w_gate", "w1", "w3", "w2")
    args = [p["x"], u, p["w_gate"], p["w1"], p["w3"], p["w2"]]
    ours = lambda x, u, g, *w: ops.moe_layer(
        x, g, None, *w, top_k=K, expert_offset=offset, score="softmax",
        activation="relu", router_in=u)[::2]
    loop = lambda x, u, g, *w: softmax_relu_loop(x, u, g, *w, offset)
    got, want = ours(*args), loop(*args)
    np.testing.assert_array_equal(np.sort(got[1], -1), np.sort(want[1], -1))
    scale = float(jnp.abs(want[0]).max())
    np.testing.assert_allclose(got[0], want[0], atol=2e-5 * scale)
    grads = lambda f: jax.grad(lambda *a: jnp.sum(f(*a)[0] * p["cot"]),
                               tuple(range(6)))(*args)
    for name, g, w in zip(names, grads(ours), grads(loop), strict=True):
        scale = float(jnp.abs(w).max())
        assert scale > 0, name
        np.testing.assert_allclose(g, w, atol=3e-5 * scale, err_msg=name)
    # the router's input and the experts' are apart: x reaches the output
    # through the experts alone, u through the weights alone
    with pytest.raises(ValueError, match="not the same tokens"):
        ops.moe_layer(p["x"], p["w_gate"], None, p["w1"], p["w3"], p["w2"],
                      top_k=K, score="softmax", router_in=u[:-1])
    with pytest.raises(ValueError, match="activation 'gelu'"):
        ops.moe_layer(p["x"], p["w_gate"], jnp.zeros((E,)), p["w1"],
                      p["w3"], p["w2"], top_k=K, activation="gelu")


@pytest.mark.parametrize("kind", ["silu", "relu"])
def test_the_gate_both_ways_in_float32_rounded_once(kind):
    """``act(gate) * up`` in float32, rounded once to the rows' dtype, and
    its backward from ``gate`` and ``up`` over the rows walked."""
    k = jax.random.split(jax.random.PRNGKey(3), 3)
    gate, up, d = (jax.random.normal(kk, (64, 24)).astype(jnp.bfloat16)
                   for kk in k)
    act = moe.ACTIVATIONS[kind]
    want = (act(gate.astype(jnp.float32)) * up.astype(jnp.float32)
            ).astype(jnp.bfloat16)
    got, vjp = jax.vjp(lambda g, u: moe._activation(g, u, jnp.int32(64), kind),
                       gate, up)
    np.testing.assert_array_equal(got, want)
    _, want_vjp = jax.vjp(lambda g, u: (act(g.astype(jnp.float32))
                                        * u.astype(jnp.float32)
                                        ).astype(jnp.bfloat16), gate, up)
    for a, b in zip(vjp(d), want_vjp(d), strict=True):
        np.testing.assert_array_equal(a, b)
    if kind == "relu":      # the gradient to the gate is zero where it is
        assert not np.asarray(vjp(d)[0])[np.asarray(gate) < 0].any()


#: sha256 of the lowered value-and-gradient of the two layers at their
#: defaults (sigmoid router, SiLU, the router reading ``x``) under a
#: checkpoint that keeps ``ROUTED``, as PR 36's ``ops/moe.py`` lowered them
_DEFAULTS_LOWERED = {
    "moe_layer":
        "55bfc0af68833da4a55ca3ad639797616e20ee76a0a9afc451dc891bb090e1b8",
    "latent_moe_layer":
        "77e54db0e51cfdfc012584fd13541cbaf044588b46fab2ccc378348d9a167704"}


def _lowered_defaults(layer):
    import hashlib
    n, d, f, e, g, k = 64, 16, 24, 8, 4, 2
    keys = jax.random.split(jax.random.PRNGKey(0), 8)
    x = jax.random.normal(keys[0], (n, d), jnp.bfloat16)
    w_gate = jax.random.normal(keys[1], (d, e)) * .3
    bias = jnp.zeros((e,))
    w = lambda kk, *s: jax.random.normal(kk, s, jnp.bfloat16)
    if layer == "moe_layer":
        args = (x, w_gate, w(keys[2], g, d, f), w(keys[3], g, d, f),
                w(keys[4], g, f, d))
        body = lambda x, wg, *ws: ops.moe_layer(x, wg, bias, *ws, top_k=k,
                                                expert_offset=2)[0]
    else:
        args = (x, w(keys[5], n, 8), w_gate, w(keys[6], g, 8, f),
                w(keys[7], g, f, 8))
        body = lambda x, lat, wg, *ws: ops.latent_moe_layer(
            x, lat, wg, bias, *ws, top_k=k, expert_offset=2)[0]
    kept = jax.checkpoint(body, policy=(
        jax.checkpoint_policies.save_only_these_names(moe.ROUTED)))
    loss = lambda *a: kept(*a).astype(jnp.float32).sum()
    text = jax.jit(jax.value_and_grad(loss, tuple(range(5)))).lower(
        *args).as_text()
    return hashlib.sha256(text.encode()).hexdigest()


@pytest.mark.parametrize("layer", ["moe_layer", "latent_moe_layer"])
def test_the_defaults_lower_to_the_program_they_lowered_to_before(
        layer, monkeypatch):
    """The softmax router, ReGLU and ``router_in`` left the defaults'
    program as it was, walked rows and all: the LFM2 and Nemotron cells
    compile what they compiled (the benchmark's controls patch
    ``moe.route`` by name, which the default still calls without
    ``score``)."""
    monkeypatch.setattr(moe, "_ROW_BLOCK", 32)      # 128 rows: walked
    assert _lowered_defaults(layer) == _DEFAULTS_LOWERED[layer]
    monkeypatch.setattr(moe, "route", plain_route)
    f, args, cot = _layer_under_test("moe_layer")
    jax.grad(lambda *a: jnp.sum(f(*a) * cot))(*args)
