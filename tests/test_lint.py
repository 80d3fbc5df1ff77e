"""tools/jaxlint self-tests + the repo-wide clean-lint tier-1 gate.

Two layers, mirroring the linter's contract (docs/jaxlint.md):

1. fixture self-tests — for every rule J001-J016 a known-bad snippet
   must flag and the same snippet with an inline waiver (or the real
   fix) must pass, so a rule that silently stops firing breaks CI
   before it stops protecting the codebase;
2. the repo gate — ``lint_paths(apex_tpu examples tools)``
   must return zero findings forever: introducing an unwaived host
   sync / retrace hazard / fp32 leak fails tier-1, the same way the
   reference relied on pjit's trace-time machinery (SNIPPETS.md [1]).

Pure AST analysis: no accelerator, runs under ``JAX_PLATFORMS=cpu``
with the standard conftest skip logic (not a ``tpu``-marked test).
"""

import os
import subprocess
import sys
import textwrap

import pytest

from tools.jaxlint import lint_paths, lint_source
from tools.jaxlint.cli import main as jaxlint_main

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LINT_TARGETS = [os.path.join(REPO, p)
                for p in ("apex_tpu", "examples", "tools")] \


def _codes(src, path="apex_tpu/fixture.py", driver=None):
    """Rule codes flagged for a snippet (library context by default)."""
    return sorted({f.rule for f in
                   lint_source(textwrap.dedent(src), path, driver=driver)})


# -- J001: host sync in device code -------------------------------------------

def test_j001_flags_host_sync_in_library_code():
    bad = """
    import jax

    def probe(flag):
        return float(jax.device_get(flag))
    """
    assert _codes(bad) == ["J001"]


def test_j001_waiver_with_reason_passes():
    waived = """
    import jax

    def probe(flag):
        return float(jax.device_get(flag))  # jaxlint: disable=J001 -- test fixture
    """
    assert _codes(waived) == []


def test_j001_driver_flags_only_loop_syncs():
    src = """
    import jax
    import jax.numpy as jnp

    for i in range(10):
        x = jnp.ones(3)
        print(float(jax.device_get(x)))
    done = float(jax.device_get(jnp.ones(3)))
    """
    findings = lint_source(textwrap.dedent(src), "examples/demo.py")
    assert [f.rule for f in findings] == ["J001"]
    assert "inside a loop" in findings[0].message


def test_j001_metadata_reads_are_not_syncs():
    ok = """
    import jax.numpy as jnp

    def widths(x):
        y = jnp.ones(3)
        return int(y.shape[0]), int(jnp.size(y))
    """
    assert _codes(ok) == []


def test_j001_loop_target_from_array_iterable_flags():
    """ISSUE-2 extension: iterating a jax array binds device values to
    the loop target, so float()/.item()/np.asarray() on it inside the
    for body is a per-iteration sync (the old tracking only followed
    Assign bindings and missed exactly this)."""
    bad = """
    import jax.numpy as jnp

    losses = jnp.ones(8)
    for l in losses:
        print(float(l))
    """
    findings = lint_source(textwrap.dedent(bad), "examples/demo.py")
    assert [f.rule for f in findings] == ["J001"]
    waived = bad.replace(
        "print(float(l))",
        "print(float(l))  # jaxlint: disable=J001 -- fixture")
    assert _codes(waived, "examples/demo.py") == []


def test_j001_zip_and_while_body_syncs_flag():
    bad_zip = """
    import jax.numpy as jnp
    import numpy as np

    xs = jnp.ones((4, 2))
    ys = jnp.ones(4)
    for x, y in zip(xs, ys):
        np.asarray(x)
    """
    assert _codes(bad_zip, "examples/demo.py") == ["J001"]
    bad_while = """
    import jax.numpy as jnp

    x = jnp.ones(3)
    while True:
        v = x.item()
        break
    """
    # a while-body sync still flags — since ISSUE 11 as the more
    # specific serving-loop rule J012 (reported INSTEAD of J001)
    assert _codes(bad_while, "examples/demo.py") == ["J012"]


def test_j001_scalar_loop_counters_stay_host_values():
    """enumerate over a jax array: the VALUE target is arrayish, the
    counter stays a Python int — float(i) must not flag."""
    src = """
    import jax.numpy as jnp

    losses = jnp.ones(8)
    for i, l in enumerate(losses):
        print(float(i))
    """
    assert _codes(src, "examples/demo.py") == []
    flagged = src.replace("float(i)", "float(l)")
    assert _codes(flagged, "examples/demo.py") == ["J001"]


# -- J002: jit of non-array Python args ---------------------------------------

_J002_BAD = """
import jax

def step(x, training: bool):
    return x

run = jax.jit(step)
"""


def test_j002_flags_unmarked_python_arg():
    assert _codes(_J002_BAD) == ["J002"]


def test_j002_static_argnums_passes():
    assert _codes(_J002_BAD.replace(
        "jax.jit(step)", "jax.jit(step, static_argnums=(1,))")) == []


def test_j002_static_argnames_and_waiver_pass():
    assert _codes(_J002_BAD.replace(
        "jax.jit(step)",
        "jax.jit(step, static_argnames=('training',))")) == []
    assert _codes(_J002_BAD.replace(
        "run = jax.jit(step)",
        "run = jax.jit(step)  # jaxlint: disable=J002 -- fixture")) == []


def test_j002_flags_str_default():
    bad = """
    import jax

    def step(x, mode="train"):
        return x

    run = jax.jit(step)
    """
    assert _codes(bad) == ["J002"]


# -- J003: fp32 leak in bf16 paths --------------------------------------------

_J003_BAD = """
import jax.numpy as jnp

def forward(x, w):
    assert str(w.dtype) == "bfloat16"
    h = x @ w
    wide = h.astype(jnp.float32)
    return wide + 1
"""


def test_j003_flags_uncompensated_fp32_cast():
    assert _codes(_J003_BAD) == ["J003"]


def test_j003_compensating_downcast_passes():
    fixed = _J003_BAD.replace("return wide + 1",
                              "return (wide + 1).astype(x.dtype)")
    assert _codes(fixed) == []


def test_j003_fp32_loss_sink_is_exempt():
    ok = """
    import jax.numpy as jnp

    def loss(x):
        h = x.astype(jnp.bfloat16)
        return jnp.mean(h.astype(jnp.float32))
    """
    # reductions/losses belong in fp32 under amp (the O1 fp32 list)
    assert "J003" not in _codes(ok)


def test_j003_flags_literal_promotion():
    bad = """
    import jax.numpy as jnp

    def scale(x):
        h = x.astype(jnp.bfloat16)
        return h * jnp.float32(2.0)
    """
    assert "J003" in _codes(bad)


# -- J004: retracing hazards --------------------------------------------------

_J004_BAD = """
import jax
import jax.numpy as jnp

step = jax.jit(lambda x, s: x * s)
x = jnp.ones(3)
for i in range(10):
    x = step(x, i)
"""


def test_j004_flags_loop_scalar_into_jit():
    assert _codes(_J004_BAD, "examples/demo.py") == ["J004"]


def test_j004_traced_array_passes():
    fixed = _J004_BAD.replace("step(x, i)", "step(x, jnp.asarray(i))")
    assert _codes(fixed, "examples/demo.py") == []


def test_j004_flags_loop_scalar_as_keyword_arg():
    # keyword args retrace exactly like positional ones (review finding)
    bad = _J004_BAD.replace("lambda x, s: x * s", "lambda x, s=1: x * s") \
                   .replace("step(x, i)", "step(x, s=i)")
    assert _codes(bad, "examples/demo.py") == ["J004"]


def test_j004_flags_jit_inside_loop():
    bad = """
    import jax

    def rebuild(fns, x):
        outs = []
        for fn in fns:
            outs.append(jax.jit(fn)(x))
        return outs
    """
    assert "J004" in _codes(bad)


# -- J005: use-after-donate ---------------------------------------------------

_J005_BAD = """
import jax

step = jax.jit(lambda s, b: s, donate_argnums=(0,))

def run(state, batch):
    out = step(state, batch)
    return state
"""


def test_j005_flags_read_after_donate():
    assert _codes(_J005_BAD) == ["J005"]


def test_j005_rebinding_passes():
    fixed = _J005_BAD.replace("out = step(state, batch)",
                              "state = step(state, batch)") \
                     .replace("return state", "return state  # rebound")
    assert _codes(fixed) == []


def test_j005_flags_same_line_read_in_rebind():
    # `state = f(state)` after donating state: the RHS Load evaluates
    # before the Store even though the Store tokenizes first (review)
    bad = """
    import jax
    import jax.numpy as jnp

    step = jax.jit(lambda s, b: s, donate_argnums=(0,))

    def run(state, extra, batch):
        out = step(state, batch)
        state = jnp.concatenate([state, extra])
        return out, state
    """
    assert "J005" in _codes(bad)


def test_j005_flags_loop_without_rebind():
    bad = """
    import jax

    step = jax.jit(lambda s, b: s, donate_argnums=(0,))

    def run(state, batches):
        for b in batches:
            out = step(state, b)
        return out
    """
    assert "J005" in _codes(bad)


# -- J006: Python control flow on traced values -------------------------------

_J006_BAD = """
import jax
import jax.numpy as jnp

@jax.jit
def clamp(x):
    if jnp.any(x > 0):
        return x
    return -x
"""


def test_j006_flags_branch_on_traced():
    assert _codes(_J006_BAD) == ["J006"]


def test_j006_unjitted_branch_passes():
    # same body outside jit: Python branching on a concrete array is fine
    assert _codes(_J006_BAD.replace("@jax.jit\n", "")) == []


def test_j006_where_passes():
    fixed = """
    import jax
    import jax.numpy as jnp

    @jax.jit
    def clamp(x):
        return jnp.where(jnp.any(x > 0), x, -x)
    """
    assert _codes(fixed) == []


# -- J007: per-step host staging in training loops ----------------------------

_J007_BAD = """
import jax
import numpy as np

for batch in loader:
    x = jax.device_put(batch)
    state = step(state, x)
"""


def test_j007_flags_per_step_device_put_on_batch():
    assert _codes(_J007_BAD, "examples/demo.py") == ["J007"]


def test_j007_flags_per_step_asarray_in_driver():
    bad = """
    import numpy as np

    for images, labels in stream:
        x = np.asarray(images, np.float32)
        state = step(state, x, labels)
    """
    assert _codes(bad, "examples/demo.py") == ["J007"]


def test_j007_asarray_in_library_loop_passes():
    # the asarray half is scoped to DRIVER files: library code
    # legitimately asarray's in serialization / metadata loops
    src = """
    import numpy as np

    def save_all(leaves):
        return [np.asarray(l) for l in leaves]

    def stage(batches):
        out = []
        for b in batches:
            out.append(np.asarray(b))
        return out
    """
    assert _codes(src, "apex_tpu/fixture.py") == []


def test_j007_device_put_flags_in_library_loops_too():
    # device_put is flagged regardless of driver/library: re-staging
    # per step is the same stall wherever it lives
    src = """
    import jax

    def feed(batches):
        for b in batches:
            yield jax.device_put(b)
    """
    assert _codes(src, "apex_tpu/fixture.py") == ["J007"]


def test_j007_waiver_and_loader_staging_pass():
    waived = _J007_BAD.replace(
        "x = jax.device_put(batch)",
        "x = jax.device_put(batch)  # jaxlint: disable=J007 -- fixture")
    assert _codes(waived, "examples/demo.py") == []
    # the FIX: stage once via the loader, iterate device batches
    fixed = """
    import jax
    from apex_tpu.data import PrefetchLoader

    for batch in PrefetchLoader(stream, depth=2, workers=4):
        state = step(state, batch)
    """
    assert _codes(fixed, "examples/demo.py") == []


def test_j007_outside_loop_passes():
    # one-time staging before the loop is the sanctioned pattern
    src = """
    import jax

    window = jax.device_put(host_window)
    for _ in range(10):
        state = step(state, window)
    """
    assert _codes(src, "examples/demo.py") == []


# -- J008: per-leaf host syncs in tree_leaves loops ---------------------------

_J008_BAD = """
import jax
import jax.numpy as jnp

def grad_norms(grads):
    out = []
    for g in jax.tree_util.tree_leaves(grads):
        leaf_norm = jnp.sqrt(jnp.sum(g * g))
        out.append(float(leaf_norm))
    return out
"""


def test_j008_flags_per_leaf_sync_and_not_j001():
    """The ISSUE-4 fixture: float(leaf_norm) inside a loop over
    tree_leaves is the O(leaves)-round-trips sweep — reported as the
    specific J008, not a garden-variety J001."""
    assert _codes(_J008_BAD) == ["J008"]


def test_j008_waiver_with_reason_passes():
    waived = _J008_BAD.replace(
        "out.append(float(leaf_norm))",
        "out.append(float(leaf_norm))  # jaxlint: disable=J008 -- fixture")
    assert _codes(waived) == []


def test_j008_device_side_reduction_is_the_fix():
    fixed = """
    import jax
    import jax.numpy as jnp

    def grad_norms(grads):
        leaves = jax.tree_util.tree_leaves(grads)
        return jnp.stack([jnp.sqrt(jnp.sum(g * g)) for g in leaves])
    """
    assert _codes(fixed) == []


def test_j008_tree_flatten_binding_and_driver_context():
    bad = """
    import jax
    import numpy as np

    leaves, treedef = jax.tree_util.tree_flatten(tree)
    for l in leaves:
        print(np.asarray(l))
    """
    assert _codes(bad, "examples/demo.py") == ["J008"]
    bad_sub = bad.replace(
        "leaves, treedef = jax.tree_util.tree_flatten(tree)",
        "leaves = jax.tree_util.tree_flatten(tree)[0]")
    assert _codes(bad_sub, "examples/demo.py") == ["J008"]


def test_j008_zip_over_leaf_lists_flags():
    bad = """
    import jax

    def drain(a, b):
        for x, y in zip(jax.tree_util.tree_leaves(a),
                        jax.tree_util.tree_leaves(b)):
            jax.device_get(x + y)
    """
    assert _codes(bad) == ["J008"]


def test_j008_leafless_loop_still_plain_j001():
    # an ordinary array loop stays J001 — J008 is only the tree sweep
    src = """
    import jax.numpy as jnp

    losses = jnp.ones(8)
    for l in losses:
        print(float(l))
    """
    assert _codes(src, "examples/demo.py") == ["J001"]


def test_j008_host_boundary_funcs_stay_exempt():
    # serialization materializes per leaf by contract, like J001
    src = """
    import jax
    import numpy as np

    class Opt:
        def state_dict(self):
            out = []
            for l in jax.tree_util.tree_leaves(self.state):
                out.append(np.asarray(l))
            return out
    """
    assert _codes(src) == []


# -- J009: async-dispatch timing lies -----------------------------------------

_J009_BAD = """
import time
import jax

step = jax.jit(lambda s, b: s + b)

def bench(state, batches):
    t0 = time.perf_counter()
    for b in batches:
        state = step(state, b)
    dt = time.perf_counter() - t0
    return dt
"""


def test_j009_flags_unfenced_timing_of_jitted_call():
    """The ISSUE-5 fixture: perf_counter around a jitted loop with no
    sync in the span times ENQUEUE, not compute (the 6x-chip-peak bench
    round-1 failure mode)."""
    assert _codes(_J009_BAD, "examples/demo.py") == ["J009"]


def test_j009_waiver_with_reason_passes():
    waived = _J009_BAD.replace(
        "    dt = time.perf_counter() - t0",
        "    dt = time.perf_counter() - t0  "
        "# jaxlint: disable=J009 -- fixture")
    assert _codes(waived, "examples/demo.py") == []


def test_j009_block_until_ready_fence_passes():
    fixed = _J009_BAD.replace(
        "    dt = time.perf_counter() - t0",
        "    jax.block_until_ready(state)\n"
        "    dt = time.perf_counter() - t0")
    assert _codes(fixed, "examples/demo.py") == []


def test_j009_value_fetch_fence_passes():
    fixed = _J009_BAD.replace(
        "    dt = time.perf_counter() - t0",
        "    _ = float(state[0])\n"
        "    dt = time.perf_counter() - t0")
    assert _codes(fixed, "examples/demo.py") == []


def test_j009_local_sync_helper_counts_as_fence():
    """A call to a module-local helper that syncs internally (bench.py's
    ``_force`` pattern) fences the timing — one-level interprocedural."""
    fixed = """
    import time
    import jax
    import jax.numpy as jnp

    step = jax.jit(lambda s, b: s + b)

    def _force(x):
        return float(jnp.ravel(x)[0])

    def bench(state, batches):
        t0 = time.perf_counter()
        for b in batches:
            state = step(state, b)
        _force(state)
        dt = time.perf_counter() - t0
        return dt
    """
    assert _codes(fixed, "examples/demo.py") == []


def test_j009_needs_a_jitted_call_between_clocks():
    # plain host timing, and a jitted call outside the clock pair, pass
    src = """
    import time
    import jax

    step = jax.jit(lambda s: s)

    def setup(state):
        state = step(state)          # before the first clock read
        t0 = time.perf_counter()
        host_work()
        dt = time.perf_counter() - t0
        return state, dt
    """
    assert _codes(src, "examples/demo.py") == []


# -- J010: cost harvesting inside step loops ----------------------------------

def test_j010_flags_cost_analysis_in_loop():
    """The ISSUE-6 fixture: harvesting XLA costs per loop iteration
    re-traces (and recompiles) every call — harvest once before."""
    bad = """
    import jax

    step = jax.jit(lambda s, b: s + b)

    def sweep(batches):
        for b in batches:
            cost = step.lower(0.0, b).compile().cost_analysis()
            use(cost)
    """
    # .lower / .compile / .cost_analysis all sit on the same chain;
    # codes dedup to one J010 (jax.jit itself is hoisted, so no J004)
    assert _codes(bad) == ["J010"]


def test_j010_flags_lower_of_jitted_name_in_loop():
    bad = """
    import jax

    step = jax.jit(lambda s, b: s + b)

    def probe(batches):
        for b in batches:
            hlo = step.lower(0.0, b)
    """
    assert _codes(bad) == ["J010"]


def test_j010_waiver_with_reason_passes():
    waived = """
    import jax

    step = jax.jit(lambda s, b: s + b)

    def sweep(shapes):
        for b in shapes:
            # jaxlint: disable=J010 -- fixture: deliberate per-shape harvest
            cost = step.lower(0.0, b).compile().cost_analysis()
    """
    assert _codes(waived) == []


def test_j010_harvest_before_loop_passes():
    ok = """
    import jax

    def sweep(fn, b, batches):
        cost = jax.jit(fn).lower(b).compile().cost_analysis()
        for bb in batches:
            use(cost, bb)
    """
    assert _codes(ok) == []


def test_j010_string_lower_and_re_compile_pass():
    """`.lower()` on a string and `re.compile` are not jitted
    computations — the receiver must be demonstrably jitted."""
    ok = """
    import re

    def scan(names):
        for n in names:
            m = re.compile("x").match(n.lower())
    """
    assert _codes(ok) == []


# -- J011: unfused BN/GN + ReLU chains in model bodies (advisory) -------------

def test_j011_nested_bn_relu_flags():
    bad = """
    import flax.linen as nn

    class Net(nn.Module):
        @nn.compact
        def __call__(self, x):
            return nn.relu(nn.BatchNorm(use_running_average=False)(x))
    """
    assert _codes(bad) == ["J011"]


def test_j011_consecutive_statements_flag():
    bad = """
    import flax.linen as nn

    class Net(nn.Module):
        @nn.compact
        def __call__(self, x):
            y = nn.GroupNorm(num_groups=8)(x)
            y = nn.relu(y)
            return y
    """
    assert _codes(bad) == ["J011"]


def test_j011_partial_and_lambda_norm_aliases_flag():
    """The factory idiom model bodies actually use (dcgan's lambda,
    resnet's functools.partial) must not hide the chain."""
    bad = """
    import functools
    import flax.linen as nn

    class Net(nn.Module):
        @nn.compact
        def __call__(self, x, train=True):
            norm = functools.partial(nn.BatchNorm,
                                     use_running_average=not train)
            x = nn.relu(norm(name="bn0")(x))
            lnorm = lambda name: nn.BatchNorm(use_running_average=not train,
                                              name=name)
            x = nn.relu(lnorm("bn1")(x))
            return x
    """
    assert _codes(bad) == ["J011"]


def test_j011_else_branch_chain_flags():
    """The scan covers every statement list, not just .body — an
    else-arm bn->relu chain is the same two sweeps (review regression
    pin)."""
    bad = """
    import flax.linen as nn

    class Net(nn.Module):
        fused: bool = False

        @nn.compact
        def __call__(self, x):
            if self.fused:
                x = x
            else:
                y = nn.BatchNorm(use_running_average=False)(x)
                y = nn.relu(y)
            return y
    """
    assert _codes(bad) == ["J011"]


def test_j011_negatives_pass():
    """leaky_relu has no fused epilogue; an intervening statement breaks
    the chain; non-__call__ bodies are out of scope."""
    ok = """
    import flax.linen as nn

    class Net(nn.Module):
        @nn.compact
        def __call__(self, x):
            x = nn.leaky_relu(nn.BatchNorm(use_running_average=False)(x),
                              0.2)
            y = nn.BatchNorm(use_running_average=False, name="bn2")(x)
            y = y + x
            y = nn.relu(y)
            return y

    def helper(x):
        return nn.relu(nn.BatchNorm(use_running_average=False)(x))
    """
    assert _codes(ok) == []


def test_j011_waiver_with_reason_passes():
    waived = """
    import flax.linen as nn

    class Net(nn.Module):
        @nn.compact
        def __call__(self, x):
            return nn.relu(nn.BatchNorm(use_running_average=False)(x))  # jaxlint: disable=J011 -- fixture: tiny maps below the fusion crossover
    """
    assert _codes(waived) == []


def test_j011_is_advisory_and_cli_exits_zero(tmp_path):
    """Advisory contract: the finding renders as [advisory] and an
    advisory-only file does NOT fail the CLI; mixing in an error-class
    finding still does."""
    from tools.jaxlint.linter import Finding
    src = textwrap.dedent("""
    import flax.linen as nn

    class Net(nn.Module):
        @nn.compact
        def __call__(self, x):
            return nn.relu(nn.BatchNorm(use_running_average=False)(x))
    """)
    findings = lint_source(src, "apex_tpu/fixture.py")
    assert [f.rule for f in findings] == ["J011"]
    assert findings[0].advisory and "[advisory]" in findings[0].render()
    assert not Finding("p", 1, 0, "J001", "m").advisory

    adv = tmp_path / "advisory_only.py"
    adv.write_text(src)
    assert jaxlint_main([str(adv)]) == 0

    mixed = tmp_path / "mixed.py"
    mixed.write_text(src + textwrap.dedent("""
    import jax

    def probe(flag):
        return float(jax.device_get(flag))
    """))
    assert jaxlint_main([str(mixed)]) == 1


# -- J000: waiver hygiene -----------------------------------------------------

def test_j000_waiver_without_reason_flags_and_waives_nothing():
    bad = """
    import jax

    def probe(flag):
        return float(jax.device_get(flag))  # jaxlint: disable=J001
    """
    assert _codes(bad) == ["J000", "J001"]


def test_j000_unknown_rule_code_flags():
    assert "J000" in _codes("x = 1  # jaxlint: disable=J999 -- nope\n")


def test_waiver_covers_following_line():
    # multi-line statements can't carry a trailing comment on line 1
    src = """
    import jax

    def probe(a, b):
        # jaxlint: disable=J001 -- fixture: stacked transfer
        return float(jax.device_get(
            a + b))
    """
    assert _codes(src) == []


def test_file_level_waiver():
    src = """
    # jaxlint: disable-file=J001 -- fixture: host-side module by design
    import jax

    def probe(flag):
        return float(jax.device_get(flag))
    """
    assert _codes(src) == []


def test_trailing_waiver_does_not_bleed_to_next_line():
    # a trailing waiver is scoped to its own line: an unrelated
    # violation added directly below must still flag (review finding)
    src = """
    import jax

    def probe(a, b):
        x = float(jax.device_get(a))  # jaxlint: disable=J001 -- sanctioned
        y = float(jax.device_get(b))
        return x + y
    """
    findings = lint_source(textwrap.dedent(src), "apex_tpu/fixture.py")
    assert [f.rule for f in findings] == ["J001"]
    assert findings[0].line == 6          # the unwaived second sync


def test_j001_flags_sync_on_jitted_step_outputs():
    # tuple-unpacked results of a jitted callable are device arrays:
    # the per-step float(metrics[...]) sync must flag (review finding —
    # the exact bug class this PR scrubbed from examples/lm)
    src = """
    import jax

    step = jax.jit(lambda s, b: (s, {"loss": s}))

    def train(state, batches):
        for b in batches:
            state, metrics = step(state, b)
            print(float(metrics["loss"]))
        return state
    """
    assert "J001" in _codes(src, "examples/demo.py")


def test_j001_metadata_mixed_with_compute_still_flags():
    # .shape appearing INSIDE a device computation is not an exemption
    # (review finding: float(jnp.sum(y) / y.shape[0]) is a real sync)
    bad = """
    import jax.numpy as jnp

    def mean_of(y):
        return float(jnp.sum(y) / y.shape[0])
    """
    assert _codes(bad) == ["J001"]
    ok = """
    import jax.numpy as jnp

    def rows_times_cols(y):
        return int(y.shape[0] * y.shape[1])
    """
    assert _codes(ok) == []


def test_j001_post_fetch_host_values_are_free():
    # the fetch is the one finding; consuming the fetched host value
    # afterwards is plain host arithmetic (review finding)
    src = """
    import jax
    import jax.numpy as jnp

    def drain(flags):
        vals = jax.device_get(jnp.stack(flags))  # jaxlint: disable=J001 -- the one batched transfer
        if bool(vals.any()):
            return [bool(v) for v in vals]
        return []
    """
    assert _codes(src) == []


def test_j005_fires_at_module_scope():
    # drivers donate-and-read at the top level (review finding: the
    # fn-only read-later lookup made J005 a no-op there)
    src = """
    import jax

    step = jax.jit(lambda s, b: s, donate_argnums=(0,))
    state = init()
    out = step(state, batch)
    print(state)
    """
    assert "J005" in _codes(src, "examples/demo.py")


def test_lambda_argument_is_not_arrayish():
    # feeding arrays to a timing harness via a lambda must not mark the
    # harness's host-float result arrayish (tools/attention_sweep idiom)
    src = """
    import jax.numpy as jnp

    def sweep(timer):
        q = jnp.ones(8)
        t = timer(lambda: q * 2) * 1e3
        best = bool(t < 5.0)
        return best
    """
    assert _codes(src) == []


def test_waivers_in_docstrings_are_ignored():
    src = '''
    def doc():
        """Example: x  # jaxlint: disable=J001"""
        return 1
    '''
    assert _codes(src) == []


# -- CLI contract -------------------------------------------------------------

def test_cli_exit_codes(tmp_path, capsys):
    clean = tmp_path / "clean.py"
    clean.write_text("x = 1\n")
    assert jaxlint_main([str(clean)]) == 0
    assert "clean" in capsys.readouterr().out

    dirty = tmp_path / "dirty.py"
    dirty.write_text("import jax\n\n"
                     "def probe(f):\n"
                     "    return float(jax.device_get(f))\n")
    assert jaxlint_main([str(dirty)]) == 1
    out = capsys.readouterr().out
    assert "J001" in out and "finding" in out

    assert jaxlint_main([]) == 2                       # no paths
    assert jaxlint_main([str(tmp_path / "nope.txt")]) == 2
    assert jaxlint_main(["--list-rules"]) == 0
    assert "J004" in capsys.readouterr().out


@pytest.mark.slow
def test_cli_module_entry_point(tmp_path):
    """``python -m tools.jaxlint`` — the exact invocation CI documents."""
    dirty = tmp_path / "dirty.py"
    dirty.write_text("import jax\n\n"
                     "def probe(f):\n"
                     "    return float(jax.device_get(f))\n")
    r = subprocess.run([sys.executable, "-m", "tools.jaxlint", str(dirty)],
                       cwd=REPO, capture_output=True, text=True)
    assert r.returncode == 1 and "J001" in r.stdout
    r = subprocess.run(
        [sys.executable, "-m", "tools.jaxlint", "--list-rules"],
        cwd=REPO, capture_output=True, text=True)
    assert r.returncode == 0


# -- the tier-1 gate ----------------------------------------------------------

def test_repo_is_lint_clean():
    """THE gate: every finding in the package, the examples, the tools,
    and the bench is either fixed or carries a documented waiver.  A new
    unwaived host sync / retrace hazard / fp32 leak fails tier-1 here."""
    findings = lint_paths(LINT_TARGETS)
    assert not findings, (
        f"{len(findings)} jaxlint finding(s) — fix them or waive with "
        f"'# jaxlint: disable=<rule> -- <reason>':\n"
        + "\n".join(f.render() for f in findings))


def test_repo_gate_actually_sees_the_package():
    """Guard the gate itself: the walk must visit the real modules (an
    empty file list would make the gate pass vacuously)."""
    import glob
    n_pkg = len(glob.glob(os.path.join(REPO, "apex_tpu", "**", "*.py"),
                          recursive=True))
    assert n_pkg > 30        # the package has ~40 modules


# -- J012: per-request host syncs in serving contexts (ISSUE 11) --------------

def test_j012_sync_in_while_serving_loop():
    bad = """
    import jax

    def drain(queue, engine):
        while queue:
            out = engine.decode()
            jax.block_until_ready(out)
    """
    assert _codes(bad) == ["J012"]


def test_j012_sync_in_request_handler_function():
    bad = """
    import jax

    def handle_request(engine, prompt):
        logits = engine.prefill(prompt)
        return jax.device_get(logits)
    """
    assert _codes(bad) == ["J012"]
    # handler-segment matching: 'serve'/'request'/'handler' names too
    also = bad.replace("handle_request", "serve_one")
    assert _codes(also) == ["J012"]


def test_j012_replaces_j001_not_added_to_it():
    """J012 is the MORE SPECIFIC rule: in a serving context the sync is
    reported once as J012, never doubled with J001; outside those
    contexts a loop sync stays plain J001."""
    serving = """
    import jax

    def pump(engine):
        while True:
            x = engine.step()
            v = float(jax.device_get(x))
    """
    assert _codes(serving) == ["J012"]
    plain = """
    import jax

    def sweep(items):
        for it in items:
            jax.device_get(it)
    """
    assert _codes(plain) == ["J001"]


def test_j012_waived_response_boundary():
    ok = """
    import numpy as np

    def handle_request(engine, prompt):
        tok = engine.decode(prompt)
        return np.asarray(tok)  # jaxlint: disable=J001,J012 -- the response boundary: sampled tokens must reach the caller
    """
    assert _codes(ok) == []


def test_j012_driver_top_level_handler_not_flagged():
    """Driver scripts keep the in-loop gate: a handler-named function
    syncing once at top level is the legitimate end-of-run read."""
    src = """
    import jax

    def handle_request(engine, p):
        return jax.device_get(engine.run(p))
    """
    assert _codes(src, path="examples/serve.py") == []
    # ...but a while-loop sync in a driver is still per-request
    loop = """
    import jax

    def main(engine, reqs):
        while reqs:
            jax.device_get(engine.step())
    """
    assert _codes(loop, path="examples/serve.py") == ["J012"]


def test_j012_interior_on_segment_stays_j001():
    """`on` marks a handler only as a PREFIX (`on_request`): an interior
    `_on_` (train_on_batch) must stay J001 so existing J001 waivers keep
    covering it."""
    src = """
    import jax

    def train_on_batch(step, state, b):
        state, m = step(state, b)
        return float(jax.device_get(m))
    """
    assert _codes(src) == ["J001"]
    prefixed = src.replace("train_on_batch", "on_request")
    assert _codes(prefixed) == ["J012"]


# -- J013: unsharded parameter staging in multi-device entry points (ISSUE 12)-

def test_j013_flags_bare_device_put_in_mesh_function():
    bad = """
    import jax
    from jax.sharding import Mesh

    def launch(params, batch):
        mesh = Mesh(jax.devices(), ("data",))
        params = jax.device_put(params)
        return mesh
    """
    assert _codes(bad) == ["J013"]


def test_j013_flags_jnp_asarray_of_params_in_mesh_function():
    bad = """
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding

    def setup(weights):
        w = jnp.asarray(weights)
        return NamedSharding
    """
    assert _codes(bad) == ["J013"]


def test_j013_explicit_sharding_passes():
    ok = """
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    def launch(params):
        mesh = Mesh(jax.devices(), ("data",))
        sh = NamedSharding(mesh, P())
        params = jax.device_put(params, sh)
        other = jax.device_put(params, device=sh)
        return params, other
    """
    assert _codes(ok) == []


def test_j013_only_fires_in_multi_device_functions():
    """A bare device_put in single-device code is normal staging — the
    rule needs the mesh marker (Mesh/MeshPlan/shard_map/NamedSharding)
    in the same function."""
    ok = """
    import jax

    def stage(params):
        return jax.device_put(params)
    """
    assert _codes(ok) == []


def test_j013_only_parameter_sized_names_flag():
    """A scalar/batch staged without a sharding is noise, not a finding
    — the name heuristic keeps the rule to parameter-sized arrays."""
    ok = """
    import jax
    from jax.sharding import Mesh

    def launch(flag):
        mesh = Mesh(jax.devices(), ("data",))
        f = jax.device_put(flag)
        return mesh
    """
    assert _codes(ok) == []


def test_j013_is_advisory_and_waivable():
    from tools.jaxlint.linter import Finding

    assert Finding("p", 1, 0, "J013", "m").advisory
    waived = """
    import jax
    from jax.sharding import Mesh

    def launch(params):
        mesh = Mesh(jax.devices(), ("data",))
        params = jax.device_put(params)  # jaxlint: disable=J013 -- single-host tool, placement irrelevant
        return mesh
    """
    assert _codes(waived) == []


# -- J014: per-step recalibration at quantized-matmul call sites (ISSUE 13) ---

def test_j014_flags_inline_absmax_scale():
    bad = """
    import jax.numpy as jnp
    from apex_tpu import quant

    def step_fn(state, batch):
        x = batch["x"]
        return quant.quantized_matmul(
            x, state["w"], x_scale=jnp.max(jnp.abs(x)) / 127.0)
    """
    assert _codes(bad) == ["J014"]


def test_j014_flags_local_assigned_absmax_and_method_form():
    bad = """
    import jax.numpy as jnp
    from apex_tpu import quant

    def step_fn(state, batch):
        x = batch["x"]
        s = jnp.abs(x).max() / 127.0
        return quant.quantized_matmul(x, state["w"], x_scale=s)
    """
    assert _codes(bad) == ["J014"]


def test_j014_frozen_scale_and_w_scale_pass():
    ok = """
    import jax.numpy as jnp
    from apex_tpu import quant

    def step_fn(state, batch, calib):
        x = batch["x"]
        frozen = calib.scales["mlp_up"]
        a = quant.quantized_matmul(x, state["w"], x_scale=frozen)
        # w_scale from the CURRENT weights is the correct recipe —
        # weights are exact at trace time (never J014)
        b = quant.quantized_matmul(
            x, state["w"], x_scale=frozen,
            w_scale=jnp.max(jnp.abs(state["w"]), axis=0) / 127.0)
        return a + b
    """
    assert _codes(ok) == []


def test_j014_only_fires_on_quant_call_sites():
    ok = """
    import jax.numpy as jnp

    def step_fn(x, w):
        # an absmax that is NOT a quantized-matmul scale arg is fine
        norm = jnp.max(jnp.abs(x))
        return some_op(x, scale=jnp.max(jnp.abs(x)))
    """
    assert _codes(ok) == []


def test_j014_nested_helper_names_do_not_leak():
    """A nested helper's local fresh-absmax name must not mark the
    ENCLOSING function's same-named frozen constant as fresh (review:
    ast.walk descended into nested defs); the helper's own call still
    flags in its own scope."""
    ok = """
    import jax.numpy as jnp
    from apex_tpu import quant

    def outer(state, batch, calib):
        def helper(x):
            s = jnp.abs(x).max() / 127.0
            return s
        s = calib.scales["mlp_up"]       # frozen — shares the name only
        return quant.quantized_matmul(batch["x"], state["w"], x_scale=s)
    """
    assert _codes(ok) == []
    bad = """
    import jax.numpy as jnp
    from apex_tpu import quant

    def outer(state, batch, calib):
        def helper(x):
            s = jnp.abs(x).max() / 127.0
            return quant.quantized_matmul(x, state["w"], x_scale=s)
        frozen = calib.scales["mlp_up"]
        a = quant.quantized_matmul(batch["x"], state["w"], x_scale=frozen)
        return a + helper(batch["x"])
    """
    assert _codes(bad) == ["J014"]       # the helper's OWN site, once


def test_j014_resolution_is_binding_order_aware():
    """The LAST assignment before the call site decides freshness
    (review): a name rebound from a fresh absmax to a frozen constant
    resolves frozen — and the reverse order still flags."""
    ok = """
    import jax.numpy as jnp
    from apex_tpu import quant

    def step_fn(state, batch, calib):
        x = batch["x"]
        s = jnp.max(jnp.abs(x)) / 127.0       # used for clipping only
        clipped = jnp.clip(x, -s * 127.0, s * 127.0)
        s = calib.scales["mlp_up"]            # rebound to the constant
        return quant.quantized_matmul(clipped, state["w"], x_scale=s)
    """
    assert _codes(ok) == []
    bad = """
    import jax.numpy as jnp
    from apex_tpu import quant

    def step_fn(state, batch, calib):
        x = batch["x"]
        s = calib.scales["mlp_up"]
        s = jnp.max(jnp.abs(x)) / 127.0       # rebound to FRESH
        return quant.quantized_matmul(x, state["w"], x_scale=s)
    """
    assert _codes(bad) == ["J014"]


def test_j014_is_advisory_and_waivable():
    from tools.jaxlint.linter import Finding

    assert Finding("p", 1, 0, "J014", "m").advisory
    waived = """
    import jax.numpy as jnp
    from apex_tpu import quant

    def step_fn(state, batch):
        x = batch["x"]
        return quant.quantized_matmul(x, state["w"], x_scale=jnp.max(jnp.abs(x)) / 127.0)  # jaxlint: disable=J014 -- sanctioned dynamic-range probe for the calibration sweep
    """
    assert _codes(waived) == []


# -- J015: literal block-size overrides at kernel call sites (ISSUE 14) -------

def test_j015_flags_literal_block_overrides():
    bad = """
    from apex_tpu.ops.flash_attention import flash_attention

    def step_fn(q, k, v):
        return flash_attention(q, k, v, causal=True, block_q=512,
                               block_k=512)
    """
    assert _codes(bad) == ["J015"]


def test_j015_flags_every_tuned_kernel_kwarg():
    bad = """
    from apex_tpu import normalization, quant

    def step_fn(x, w, g, beta, calib):
        a = normalization.fused_layer_norm(x, (768,), row_block=64)
        b = normalization.fused_layer_norm_affine(x, g, beta, (768,),
                                                  row_block=32)
        c = quant.quantized_matmul(x, w, x_scale=calib.s, block_m=128,
                                   block_n=256)
        return a, b, c
    """
    findings = lint_source(textwrap.dedent(bad), "apex_tpu/fixture.py")
    # one finding per call site (dedup is line-scoped, like waivers —
    # block_m/block_n on one call collapse into a single report)
    assert [f.rule for f in findings] == ["J015"] * 3


def test_j015_variables_and_tuned_dispatch_pass():
    ok = """
    from apex_tpu.ops.flash_attention import flash_attention

    def sweep(q, k, v, blk, cfg):
        # a measured variable / config-derived block is the sanctioned
        # escape hatch; defaults dispatch through the tune cache
        a = flash_attention(q, k, v, causal=True, block_q=blk,
                            block_k=cfg["block_k"])
        b = flash_attention(q, k, v, causal=True)
        return a, b
    """
    assert _codes(ok) == []


def test_j015_only_fires_on_tunable_kernels():
    ok = """
    def step_fn(q, k, v):
        # block-ish kwargs on arbitrary functions are not findings
        return my_custom_op(q, k, v, block_q=512, row_block=64)
    """
    assert _codes(ok) == []


def test_j015_is_advisory_and_waivable():
    from tools.jaxlint.linter import Finding

    assert Finding("p", 1, 0, "J015", "m").advisory
    waived = """
    from apex_tpu.ops.flash_attention import flash_attention

    def reference_probe(q, k, v):
        return flash_attention(q, k, v, causal=True, block_q=1024, block_k=1024)  # jaxlint: disable=J015 -- documented reference path: pins the r4 sweep winner as the A/B baseline
    """
    assert _codes(waived) == []


# -- J016: NCHW convolution layouts (ISSUE 18) --------------------------------

def test_j016_flags_missing_dimension_numbers():
    bad = """
    import jax

    def model(x, w):
        # lax's DEFAULT dimension_numbers IS ('NCHW','OIHW','NCHW')
        return jax.lax.conv_general_dilated(x, w, (1, 1), "SAME")
    """
    assert _codes(bad) == ["J016"]


def test_j016_flags_nchw_literal_and_lax_conv():
    bad = """
    import jax
    from jax import lax

    def model(x, w):
        a = lax.conv_general_dilated(
            x, w, (1, 1), "SAME",
            dimension_numbers=("NCHW", "OIHW", "NCHW"))
        b = jax.lax.conv(x, w, (1, 1), "SAME")
        c = lax.conv_with_general_padding(x, w, (1, 1), [(0, 0), (0, 0)],
                                          None, None)
        return a, b, c
    """
    findings = lint_source(textwrap.dedent(bad), "apex_tpu/fixture.py")
    assert [f.rule for f in findings] == ["J016"] * 3


def test_j016_nhwc_and_non_lax_conv_pass():
    ok = """
    import jax
    from jax import lax

    def model(self, x, w, dn):
        # explicit NHWC is the sanctioned spelling
        a = lax.conv_general_dilated(
            x, w, (1, 1), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"))
        # a variable / ConvDimensionNumbers spec is not inspected
        b = jax.lax.conv_general_dilated(x, w, (1, 1), "SAME",
                                         dimension_numbers=dn)
        # the bare leaf 'conv' (module factories, self.conv) never fires
        c = self.conv(x)
        return a, b, c
    """
    assert _codes(ok) == []


def test_j016_is_advisory_and_waivable():
    from tools.jaxlint.linter import Finding

    assert Finding("p", 1, 0, "J016", "m").advisory
    waived = """
    import jax

    def nchw_ab_probe(x, w):
        return jax.lax.conv(x, w, (1, 1), "SAME")  # jaxlint: disable=J016 -- deliberate NCHW side of the layout A/B benchmark
    """
    assert _codes(waived) == []
