"""jaxlint core — AST rules, waiver handling, and the lint engine.

Rules J001–J015 tuned to this codebase's failure modes (the ones that are
invisible to pytest and surface as 10x dispatch-floor regressions on
the chip):

* **J001** host sync in device code: ``jax.device_get`` / ``.item()`` /
  ``.block_until_ready()`` / ``float()/int()/bool()/np.asarray()`` on
  array values.  In library code (``apex_tpu/``) every occurrence is a
  finding unless the enclosing function is on the host-boundary
  allowlist (``state_dict``/``load_state_dict`` — serialization is
  host-side by contract); in driver scripts (``examples/``, ``tools/``,
  ``tests/``) only syncs inside loop bodies are findings
  (a driver legitimately syncs once at the end, but a per-iteration
  sync is the hot-loop stall the ROADMAP's dispatch floors measure).
* **J002** ``jax.jit`` of a function taking non-array Python args
  (bool/str-typed or bool/str-defaulted params) without covering them
  with ``static_argnums``/``static_argnames``.
* **J003** fp32 dtype leaks inside bf16/amp-cast paths: a function that
  touches ``bfloat16`` and casts to ``float32`` without any
  compensating downcast keeps the wide dtype alive downstream; also
  ``jnp.float32(...)`` literal promotion inside arithmetic.
* **J004** retracing hazards: a jitted callable invoked with the loop
  induction variable (a fresh Python scalar per iteration → one
  retrace per iteration), or ``jax.jit`` itself called inside a loop.
* **J005** use-after-donate: a buffer passed at a ``donate_argnums``
  position of a jitted callable and read again afterwards (donated
  buffers are invalidated by XLA aliasing).
* **J006** Python control flow (``if``/``while``) branching on traced
  values inside a jitted function — trace-time concretization errors,
  or worse, silent trace-time specialization.
* **J007** per-step host staging: ``jax.device_put`` / ``np.asarray`` /
  ``jnp.asarray`` applied to batch data (a loop target drawn from a
  host iterable — a loader/stream) inside a loop body.  Host->device
  staging belongs in the input engine
  (:class:`apex_tpu.data.PrefetchLoader` / ``stage_windows``), where it
  overlaps compute, not on the hot loop where it serializes with it
  (ISSUE 3: the input-side twin of the J001 sync stalls).
* **J008** per-leaf host syncs in loops over pytree leaves: a J001-class
  sync (``float()``/``.item()``/``np.asarray``/``device_get``) inside a
  loop whose iterable comes from ``jax.tree_util.tree_leaves`` /
  ``tree_flatten`` — e.g. ``float(leaf_norm)`` per grad leaf.  One sync
  per step caps throughput at a round-trip; one per LEAF multiplies that
  by the model depth (O(leaves) drains per sweep).  Compute the
  reduction on device (``tree_finite`` / ``multi_tensor_l2norm``, one
  reduce per bucket with a ``BucketStore``) and fetch ONE value, or
  stack the per-leaf values into a single transfer (ISSUE 4: the
  tree-sweep twin of the J001 stalls).
* **J009** async-dispatch timing lies: ``time.time()`` /
  ``time.perf_counter()`` read before AND after a call to a jitted
  callable with **no sync in the timed span** — jax dispatch is
  asynchronous, so the elapsed time measures how fast the host can
  *enqueue* the program, not how long the device takes to run it
  (bench round 1 reported 6x chip peak exactly this way).  Fence the
  measurement with ``jax.block_until_ready(out)`` or a value fetch
  (``device_get`` / ``float()`` on an output) before reading the
  second clock; calls to local helpers that sync internally count
  (ISSUE 5: the static twin of the telemetry stream's measured-window
  contract).
* **J010** cost harvesting inside step loops: ``.cost_analysis()`` /
  ``.memory_analysis()``, or ``.lower()``/``.compile()`` of a jitted
  computation, called inside a loop body.  Each ``lower`` re-traces and
  each ``compile`` re-runs the backend — seconds per call on a real
  chip, and none of it is cached across loop iterations.  Costs are
  static per (shapes, dtypes): harvest ONCE before the loop
  (``apex_tpu.prof.roofline.harvest_costs``) and reuse the result
  (ISSUE 6: the static twin of the roofline engine's harvest-at-trace-
  time contract).
* **J012** per-request host syncs in serving contexts: a J001-class
  sync (``device_get``/``.item()``/``block_until_ready``/``float()`` on
  an array) inside a ``while`` loop or inside a request-handler
  function (``handle*``/``serve*``/``on_*``/``*_handler``/
  ``*request*``).  A training loop pays one sync per K-step window; a
  serving loop that syncs PER REQUEST (or per decode step) caps
  throughput at a host round-trip per token — defer the fetch one step
  behind (the ``DeferredMetrics`` pattern) or batch it, and waive ONLY
  the sanctioned response boundary, where sampled tokens must reach the
  host to drive termination/eviction (ISSUE 11: the serving twin of
  the J001/J008 stalls).  Reported INSTEAD of J001 in those contexts.
* **J011** (advisory) unfused BN/GN + ReLU chains in model bodies:
  ``nn.BatchNorm``/``nn.GroupNorm`` applied and immediately followed by
  ``nn.relu`` — nested (``nn.relu(nn.BatchNorm(...)(x))``) or as
  consecutive statements — inside a module ``__call__``.  apex_tpu
  ships a fused epilogue for exactly this chain
  (``normalization.bn_relu_residual``, reachable through
  ``SyncBatchNorm(fuse_relu=True)`` / ``contrib.groupbn.
  BatchNorm2d_NHWC`` / the ResNet norm-factory hook), which collapses
  the two elementwise sweeps into one pass (ISSUE 7).  Advisory
  severity: reported, waivable, and never fails the CLI on its own —
  the chain is correct, just slower than it needs to be.
* **J013** (advisory) unsharded parameter staging in multi-device
  entry points: ``jax.device_put`` with no sharding argument, or
  ``jnp.asarray``, of a parameter-sized array (name matches
  ``param*``/``state``/``weight*``/``master*``/``moment*``/
  ``opt_state``/``grad*``) inside a function that constructs or maps
  a mesh (``Mesh``/``MeshPlan``/``shard_map``/``NamedSharding``/
  ``make_mesh_train_step``).  The bare put lands the array uncommitted
  on one device: the partitioner reshuffles it per sharded call and
  AOT warmup cannot pin the placement — derive it from the plan
  (``plan.named(...)``/``plan.batch_sharding()``) instead (ISSUE 12).
* **J014** (advisory) per-step recalibration at quantized-matmul call
  sites: a ``quantized_matmul``/``quant_matmul`` call whose ``x_scale``
  /``scale`` argument is a freshly computed ``abs().max()`` (inline or
  via a same-function local).  The activation scale is supposed to be a
  FROZEN calibration constant (``apex_tpu.quant.Calibrator`` observe →
  freeze); re-deriving it in the step pays a full extra reduction per
  dispatch and silently changes the numerics the CONVERGENCE_QUANT
  gate certified.  ``w_scale`` is exempt — weights are exact at trace
  time, per-step channel scales are the correct recipe (ISSUE 13).
* **J015** (advisory) literal block-size overrides at Pallas-kernel
  call sites: a tunable kernel exposing block params
  (``flash_attention`` / ``fused_layer_norm`` / ``quantized_matmul``)
  invoked with an integer LITERAL for
  ``block_q``/``block_k``/``block_m``/``block_n``/``row_block``.  The
  literal freezes one sweep's winner for every device kind and shape,
  bypassing the per-device config cache the tune registry dispatches
  through (``python -m apex_tpu.tune``, ISSUE 14) — leave the blocks
  at their defaults (cache-consulted) or pass a measured variable.
  Waive where the literal IS the documented reference path (a sweep
  tool enumerating configs, an A/B probe pinning one side).

Waivers: ``# jaxlint: disable=J001 -- reason`` on the offending line
suppresses the named rule(s) there; ``# jaxlint: disable-file=J004 --
reason`` suppresses for the whole file.  A waiver **must** carry a
``-- reason``; a bare waiver is itself a finding (J000) so sanctioned
violations stay documented rather than silenced.

All analysis is purely syntactic (``ast``) — no imports of the linted
code, so the linter runs in milliseconds under ``JAX_PLATFORMS=cpu``
with no accelerator present.
"""

from __future__ import annotations

import ast
import os
import re
from typing import Dict, List, NamedTuple, Optional, Sequence, Set, Tuple

__all__ = ["Finding", "RULES", "lint_source", "lint_file", "lint_paths"]


RULES: Dict[str, str] = {
    "J000": "malformed waiver (missing '-- reason' or unknown rule code)",
    "J001": "host sync in device code (device_get/.item()/float() on arrays)",
    "J002": "jax.jit with non-array Python args not marked static",
    "J003": "fp32 dtype leak inside a bf16/amp-cast path",
    "J004": "retracing hazard (jitted callable fed varying Python scalars)",
    "J005": "use-after-donate of a donate_argnums buffer",
    "J006": "Python control flow branching on a traced value under jit",
    "J007": "per-step host staging (device_put/asarray on batch data in a "
            "loop; stage in the loader)",
    "J008": "per-leaf host sync in a loop over tree_leaves/tree_flatten "
            "(O(leaves) round-trips; reduce on device or batch into one "
            "transfer)",
    "J009": "wall-clock timing around a jitted call with no sync in the "
            "timed span (async dispatch: the clock measures enqueue, not "
            "compute)",
    "J010": "cost_analysis()/lower()/compile() of a jitted computation "
            "inside a loop (re-traces and recompiles per call; harvest "
            "once before the loop)",
    "J011": "nn.BatchNorm/nn.GroupNorm immediately followed by nn.relu "
            "in a model __call__ (a fused apex_tpu epilogue exists; "
            "advisory)",
    "J012": "per-request host sync in a serving context (device_get/"
            ".item()/block_until_ready in a while-serving loop or a "
            "request-handler function; defer or batch the fetch — waive "
            "only the sanctioned response boundary)",
    "J013": "device_put/jnp.asarray of a parameter-sized array without "
            "an explicit NamedSharding inside a multi-device entry "
            "point (the array lands replicated/on one device and the "
            "partitioner reshuffles it per call; derive the placement "
            "from the MeshPlan; advisory)",
    "J014": "quantized-matmul call site whose scale argument is a "
            "freshly computed abs().max() (recalibration-per-step: the "
            "per-tensor activation scale should come from a FROZEN "
            "apex_tpu.quant calibration, not be re-derived inside the "
            "step; advisory)",
    "J015": "Pallas kernel invoked with a literal block-size override "
            "(block_q/block_k/block_m/block_n/row_block) instead of "
            "dispatching through the tune registry/config cache — the "
            "literal freezes one device's sweep winner for every "
            "device kind (python -m apex_tpu.tune; advisory)",
    "J016": "NCHW convolution layout: lax.conv_general_dilated with "
            "missing or NC*-leading dimension_numbers, or the "
            "always-NCHW lax.conv/lax.conv_with_general_padding "
            "wrappers — TPU-hostile (the feature axis belongs on the "
            "128 lanes; use ('NHWC','HWIO','NHWC'); advisory)",
}

#: Rules reported as advice, not errors: the CLI exits 0 when only
#: advisory findings remain, and ``Finding.advisory`` marks them.
ADVISORY_RULES: Set[str] = {"J011", "J013", "J014", "J015", "J016"}

# Functions whose *contract* is the host boundary: serialization must
# materialize host values, so J001 does not fire inside them.  Everything
# else documents its sanctioned syncs with an inline waiver.
_J001_HOST_BOUNDARY_FUNCS = {"state_dict", "load_state_dict"}

# Path components that mark a file as a host-side driver script (J001
# then only fires inside loop bodies).
_DRIVER_PARTS = {"examples", "tools", "tests", "docker"}
_DRIVER_BASENAMES = {"bench.py", "setup.py", "conftest.py"}

# Function names that mark per-request serving code for J012: a sync
# anywhere in such a function is a per-request round-trip.  Exactly the
# documented contract — ``handle*``/``serve*`` as underscore-delimited
# segments, ``on_*`` as a PREFIX only (``train_on_batch`` must stay
# J001 territory or existing J001 waivers would silently stop
# covering it), plus ``handler``/``request`` substrings.
_HANDLER_NAME_RE = re.compile(
    r"(^|_)(handle|serve|serving)(_|$)|^_?on_|handler|request")


class Finding(NamedTuple):
    path: str
    line: int
    col: int
    rule: str
    message: str

    @property
    def advisory(self) -> bool:
        return self.rule in ADVISORY_RULES

    def render(self) -> str:
        sev = " [advisory]" if self.advisory else ""
        return (f"{self.path}:{self.line}:{self.col}: "
                f"{self.rule}{sev} {self.message}")


# -- waivers ------------------------------------------------------------------

_WAIVER_RE = re.compile(
    r"#\s*jaxlint:\s*(disable|disable-file)\s*=\s*"
    r"([A-Z][0-9]{3}(?:\s*,\s*[A-Z][0-9]{3})*)"
    r"\s*(?:--\s*(\S.*))?")


def _comments(src: str) -> List[Tuple[int, int, str]]:
    """(line, col, text) of every real comment token — waiver directives
    in docstrings or string literals (e.g. this linter's own docs) must
    not parse as waivers."""
    import io
    import tokenize
    out: List[Tuple[int, int, str]] = []
    try:
        for tok in tokenize.generate_tokens(io.StringIO(src).readline):
            if tok.type == tokenize.COMMENT:
                out.append((tok.start[0], tok.start[1], tok.string))
    except (tokenize.TokenError, IndentationError, SyntaxError):
        pass                   # ast.parse already reported the real error
    return out


class _Waivers:
    """Parsed waiver directives for one file."""

    def __init__(self, src: str, path: str):
        self.line_waivers: Dict[int, Set[str]] = {}
        self.file_waivers: Set[str] = set()
        self.errors: List[Finding] = []
        lines = src.splitlines()
        for lineno, col, text in _comments(src):
            m = _WAIVER_RE.search(text)
            if m is None:
                if re.search(r"jaxlint:\s*disable", text):
                    self.errors.append(Finding(
                        path, lineno, col, "J000",
                        "unparseable jaxlint directive"))
                continue
            kind, codes_s, reason = m.groups()
            codes = {c.strip() for c in codes_s.split(",")}
            bad = codes - set(RULES)
            if bad:
                self.errors.append(Finding(
                    path, lineno, col, "J000",
                    f"unknown rule code(s) {sorted(bad)} in waiver"))
                codes -= bad
            if not reason:
                self.errors.append(Finding(
                    path, lineno, col, "J000",
                    "waiver without a '-- reason' (document why the "
                    "violation is sanctioned)"))
                continue        # an undocumented waiver waives nothing
            if kind == "disable-file":
                self.file_waivers |= codes
                continue
            self.line_waivers.setdefault(lineno, set()).update(codes)
            # A comment-ONLY waiver line also covers the line below it —
            # multi-line statements (backslash/paren continuations)
            # cannot carry a trailing comment on their first physical
            # line.  A trailing waiver stays scoped to its own line, so
            # it cannot silently cover an unrelated violation added on
            # the next line (review: the old unconditional line-1 lookup
            # let exactly that slip through the tier-1 gate).
            standalone = lineno <= len(lines) \
                and not lines[lineno - 1][:col].strip()
            if standalone:
                self.line_waivers.setdefault(lineno + 1, set()).update(codes)

    def waived(self, f: Finding) -> bool:
        if f.rule in self.file_waivers:
            return True
        return f.rule in self.line_waivers.get(f.line, set())


# -- small AST helpers --------------------------------------------------------

def _dotted(node: ast.AST) -> Optional[str]:
    """'jax.jit' for Attribute(Name('jax'), 'jit'); None for anything
    not a pure dotted name."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


def _rooted_at(node: ast.AST, roots: Sequence[str]) -> bool:
    d = _dotted(node)
    if d is None:
        return False
    return d.split(".", 1)[0] in roots


# Trace-time metadata: shape/dtype/aval queries are resolved during
# tracing and never touch the device, so float()/int()/bool() of them
# is NOT a sync even when the operand is an array.
_STATIC_METADATA_CALLS = {
    "jnp.size", "jnp.shape", "jnp.ndim", "jnp.result_type", "jnp.dtype",
    "jnp.issubdtype", "np.prod", "numpy.prod", "math.prod", "len",
    "jax.typeof", "jax.eval_shape", "jax.tree_util.tree_structure",
}
_STATIC_METADATA_ATTRS = {"shape", "ndim", "dtype", "itemsize", "weak_type",
                          "vma", "aval"}


def _is_static_metadata(node: ast.AST) -> bool:
    """True when the expression is built ONLY from trace-time metadata
    (shapes, dtypes, avals) — device-free by construction.  Structural,
    not a substring scan: ``float(jnp.sum(y) / y.shape[0])`` is a real
    device round-trip even though ``.shape`` appears inside it (review:
    the old any-subexpression test exempted exactly that idiom)."""
    if isinstance(node, ast.Constant):
        return True
    if isinstance(node, ast.Attribute):
        return node.attr in _STATIC_METADATA_ATTRS
    if isinstance(node, ast.Subscript):
        return _is_static_metadata(node.value)     # x.shape[0]
    if isinstance(node, ast.Call):
        # metadata queries return host ints/dtypes whatever their args
        return _dotted(node.func) in _STATIC_METADATA_CALLS
    if isinstance(node, ast.BinOp):
        return _is_static_metadata(node.left) \
            and _is_static_metadata(node.right)
    if isinstance(node, ast.UnaryOp):
        return _is_static_metadata(node.operand)
    if isinstance(node, ast.Compare):
        return _is_static_metadata(node.left) \
            and all(_is_static_metadata(c) for c in node.comparators)
    if isinstance(node, ast.BoolOp):
        return all(_is_static_metadata(v) for v in node.values)
    if isinstance(node, (ast.Tuple, ast.List)):
        return all(_is_static_metadata(e) for e in node.elts)
    return False


def _is_arrayish(node: ast.AST, local_arrayish: Set[str]) -> bool:
    """Heuristic: does this expression hold a (possibly traced) array?
    True when any subexpression is rooted at jnp/jax/lax, calls
    ``.astype``, or names a local previously bound from such a value.
    Lambda bodies are NOT part of the expression's value (they run
    later, with their own scope) — descending into them mistakes a
    timing harness fed ``lambda q: flash(q)`` for an array value."""
    stack = [node]
    while stack:
        sub = stack.pop()
        if isinstance(sub, ast.Lambda):
            continue
        if isinstance(sub, ast.Name) and sub.id in local_arrayish:
            return True
        if isinstance(sub, ast.Call):
            if _rooted_at(sub.func, ("jnp", "jax", "lax")):
                return True
            if isinstance(sub.func, ast.Attribute) and sub.func.attr in (
                    "astype", "block_until_ready"):
                return True
        if isinstance(sub, ast.Attribute) and _rooted_at(sub, ("jnp", "lax")):
            return True
        stack.extend(ast.iter_child_nodes(sub))
    return False


def _const_ints(node: ast.AST) -> Optional[Set[int]]:
    """Literal int or tuple/list of ints -> set; None when not literal."""
    if isinstance(node, ast.Constant) and isinstance(node.value, int) \
            and not isinstance(node.value, bool):
        return {node.value}
    if isinstance(node, (ast.Tuple, ast.List)):
        out: Set[int] = set()
        for e in node.elts:
            s = _const_ints(e)
            if s is None:
                return None
            out |= s
        return out
    return None


def _const_strs(node: ast.AST) -> Optional[Set[str]]:
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return {node.value}
    if isinstance(node, (ast.Tuple, ast.List)):
        out: Set[str] = set()
        for e in node.elts:
            s = _const_strs(e)
            if s is None:
                return None
            out |= s
        return out
    return None


class _JitSite(NamedTuple):
    """One ``jax.jit`` application found in the module."""
    node: ast.Call                  # the jax.jit(...) call (or decorator)
    target: Optional[str]           # name of the function being jitted
    bound_name: Optional[str]       # name the jitted callable is bound to
    static_argnums: Optional[Set[int]]   # None = non-literal (unknown)
    static_argnames: Optional[Set[str]]
    donate_argnums: Optional[Set[int]]


def _parse_jit_call(call: ast.Call) -> Tuple[Optional[Set[int]],
                                             Optional[Set[str]],
                                             Optional[Set[int]]]:
    nums: Optional[Set[int]] = set()
    names: Optional[Set[str]] = set()
    donate: Optional[Set[int]] = set()
    for kw in call.keywords:
        if kw.arg == "static_argnums":
            nums = _const_ints(kw.value)
        elif kw.arg == "static_argnames":
            names = _const_strs(kw.value)
        elif kw.arg == "donate_argnums":
            donate = _const_ints(kw.value)
        elif kw.arg is None:         # **kwargs: give up on precision
            nums = names = donate = None
    return nums, names, donate


def _is_jax_jit(func: ast.AST) -> bool:
    return _dotted(func) in ("jax.jit", "jit", "pjit", "jax.pjit")


# Calls that fence async dispatch for J009: a device round-trip or an
# explicit block.  ``float()/int()/bool()`` and ``.fetch()``/``.item()``
# are counted generously (regardless of arg arrayishness) — precision
# over recall on the TIMING rule means missing a pathological
# ``float(python_scalar)`` fence, not flagging a correctly fenced loop.
_J009_SYNC_DOTTED = {"jax.device_get", "jax.block_until_ready",
                     "np.asarray", "numpy.asarray", "np.array",
                     "numpy.array"}


def _is_sync_call(call: ast.Call) -> bool:
    if _dotted(call.func) in _J009_SYNC_DOTTED:
        return True
    if isinstance(call.func, ast.Attribute) and call.func.attr in (
            "item", "block_until_ready", "fetch", "last"):
        return True
    if isinstance(call.func, ast.Name) \
            and call.func.id in ("float", "int", "bool") and call.args:
        return True
    return False


# -- module-level scan: jit sites, donated names, function defs ---------------

class _ModuleIndex:
    """Everything the per-scope rules need to know about the module.

    Name bindings (``step = jax.jit(...)``) are tracked per enclosing
    function: two unrelated locals that happen to share a name in
    different functions must not cross-contaminate J004/J005 (``scope``
    below is the enclosing FunctionDef node, or None at module level —
    module-level bindings are visible from every scope)."""

    def __init__(self, tree: ast.Module):
        self.defs: Dict[str, ast.FunctionDef] = {}
        self.jit_sites: List[_JitSite] = []
        # (scope, name) keys; scope None = module level
        self.jitted_names: Set[Tuple[Optional[ast.AST], str]] = set()
        self.jitted_defs: Set[str] = set()            # def names that get traced
        self.donated: Dict[Tuple[Optional[ast.AST], str], Set[int]] = {}
        self._seen_calls: Set[int] = set()
        self._scan_body(tree.body, None)

    def jitted_name(self, scope, name: str) -> bool:
        return (scope, name) in self.jitted_names \
            or (None, name) in self.jitted_names

    def sync_defs(self) -> Set[str]:
        """Names of module-level defs whose body directly syncs — calling
        one (e.g. a local ``_force``/``drain`` helper) fences an
        async-dispatch timing exactly like an inline ``device_get``, so
        J009 treats it as a sync point (one-level interprocedural)."""
        cached = getattr(self, "_sync_defs", None)
        if cached is None:
            cached = set()
            for name, fn in self.defs.items():
                for sub in ast.walk(fn):
                    if isinstance(sub, ast.Call) and _is_sync_call(sub):
                        cached.add(name)
                        break
            self._sync_defs = cached
        return cached

    def donated_argnums(self, scope, name: str) -> Optional[Set[int]]:
        got = self.donated.get((scope, name))
        if got is None:
            got = self.donated.get((None, name))
        return got

    def _scan_body(self, body: Sequence[ast.stmt], scope) -> None:
        for stmt in body:
            self._scan_stmt(stmt, scope)

    def _scan_stmt(self, stmt: ast.stmt, scope) -> None:
        if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef)):
            self.defs.setdefault(stmt.name, stmt)
            self._scan_decorators(stmt, scope)
            self._scan_body(stmt.body, stmt)
            return
        if isinstance(stmt, ast.ClassDef):
            self._scan_body(stmt.body, scope)
            return
        if isinstance(stmt, (ast.Assign, ast.AnnAssign)) \
                and stmt.value is not None \
                and isinstance(stmt.value, ast.Call) \
                and _is_jax_jit(stmt.value.func):
            targets = (stmt.targets if isinstance(stmt, ast.Assign)
                       else [stmt.target])
            bound = None
            if len(targets) == 1 and isinstance(targets[0], ast.Name):
                bound = targets[0].id
            self._add_call_site(stmt.value, bound, scope)
        # bare jax.jit(...) calls in this statement's own expressions
        # (J002 only); skip subtrees owned by nested defs / child
        # statements — they are visited with their own scope.
        skip: Set[int] = set()
        for sub in ast.iter_child_nodes(stmt):
            if isinstance(sub, (ast.stmt, ast.excepthandler)):
                for n in ast.walk(sub):
                    skip.add(id(n))
        for sub in ast.walk(stmt):
            if sub is stmt or id(sub) in skip:
                continue
            if isinstance(sub, (ast.FunctionDef, ast.AsyncFunctionDef,
                                ast.Lambda)):
                for n in ast.walk(sub):
                    skip.add(id(n))
                continue
            if isinstance(sub, ast.Call) and _is_jax_jit(sub.func) \
                    and id(sub) not in self._seen_calls:
                self._add_call_site(sub, None, scope)
        # recurse into child statements (compound stmt bodies)
        for sub in ast.iter_child_nodes(stmt):
            if isinstance(sub, ast.stmt):
                self._scan_stmt(sub, scope)
            elif isinstance(sub, ast.excepthandler):
                self._scan_body(sub.body, scope)

    def _add_call_site(self, call: ast.Call, bound: Optional[str],
                       scope) -> None:
        self._seen_calls.add(id(call))
        target = None
        if call.args:
            a0 = call.args[0]
            if isinstance(a0, ast.Name):
                target = a0.id
            elif isinstance(a0, ast.Call):
                # jax.jit(functools.partial(fn, ...)) — resolve through
                # the partial to the underlying def for J002/J006.
                if _dotted(a0.func) in ("functools.partial", "partial") \
                        and a0.args and isinstance(a0.args[0], ast.Name):
                    target = a0.args[0].id
        nums, names, donate = _parse_jit_call(call)
        self.jit_sites.append(_JitSite(call, target, bound, nums, names,
                                       donate))
        if target:
            self.jitted_defs.add(target)
        if bound:
            self.jitted_names.add((scope, bound))
            if donate:
                self.donated[(scope, bound)] = donate

    def _scan_decorators(self, fn: ast.FunctionDef, scope) -> None:
        for dec in fn.decorator_list:
            site = None
            if _is_jax_jit(dec):                       # @jax.jit
                site = _JitSite(ast.Call(func=dec, args=[], keywords=[]),
                                fn.name, fn.name, set(), set(), set())
            elif isinstance(dec, ast.Call):
                if _is_jax_jit(dec.func):              # @jax.jit(...) (rare)
                    nums, names, donate = _parse_jit_call(dec)
                    site = _JitSite(dec, fn.name, fn.name, nums, names,
                                    donate)
                elif _dotted(dec.func) in ("functools.partial", "partial") \
                        and dec.args and _is_jax_jit(dec.args[0]):
                    # @functools.partial(jax.jit, static_argnums=...)
                    nums, names, donate = _parse_jit_call(dec)
                    site = _JitSite(dec, fn.name, fn.name, nums, names,
                                    donate)
            if site is None:
                continue
            self.jit_sites.append(site)
            self.jitted_defs.add(fn.name)
            self.jitted_names.add((scope, fn.name))
            if site.donate_argnums:
                self.donated[(scope, fn.name)] = site.donate_argnums


# -- J002: jit of non-array Python args ---------------------------------------

_PYTHONISH_ANNOTATIONS = {"bool", "str"}


def _check_j002(idx: _ModuleIndex, path: str) -> List[Finding]:
    out: List[Finding] = []
    for site in idx.jit_sites:
        if site.target is None or site.target not in idx.defs:
            continue
        if site.static_argnums is None or site.static_argnames is None:
            continue                      # non-literal statics: can't verify
        fn = idx.defs[site.target]
        args = list(fn.args.posonlyargs) + list(fn.args.args)
        defaults = list(fn.args.defaults)
        # align defaults with trailing positional args
        dstart = len(args) - len(defaults)
        for i, a in enumerate(args):
            if a.arg in ("self", "cls"):
                continue
            pythonish = None
            if isinstance(a.annotation, ast.Name) \
                    and a.annotation.id in _PYTHONISH_ANNOTATIONS:
                pythonish = a.annotation.id
            d = defaults[i - dstart] if i >= dstart else None
            if d is not None and isinstance(d, ast.Constant) \
                    and type(d.value) in (bool, str):
                pythonish = type(d.value).__name__
            if pythonish is None:
                continue
            if i in site.static_argnums or a.arg in site.static_argnames:
                continue
            out.append(Finding(
                path, site.node.func.lineno, site.node.func.col_offset,
                "J002",
                f"jax.jit of '{site.target}' passes Python {pythonish} "
                f"arg '{a.arg}' (index {i}) without static_argnums/"
                f"static_argnames — it will trace as an array (bool) or "
                f"fail (str); mark it static"))
    return out


# -- J003: fp32 leaks in bf16 paths -------------------------------------------

def _fn_has_bf16(fn: ast.FunctionDef) -> bool:
    for sub in ast.walk(fn):
        if isinstance(sub, ast.Attribute) and sub.attr == "bfloat16":
            return True
        if isinstance(sub, ast.Constant) and sub.value == "bfloat16":
            return True
    return False


def _is_f32_dtype(node: ast.AST) -> bool:
    d = _dotted(node)
    if d in ("jnp.float32", "np.float32", "numpy.float32", "jax.numpy.float32"):
        return True
    return isinstance(node, ast.Constant) and node.value == "float32"


# fp32 casts whose consumer keeps them fp32 *by design* are exempt:
# softmax/log-softmax/losses/norm statistics belong in fp32 under amp
# (the reference's O1 fp32 function list), and a cast feeding a host
# fetch (float()/device_get) dies at the device boundary anyway.
_J003_FP32_SINK_RE = re.compile(
    r"softmax|loss|xent|entropy|logsumexp|norm|mean|var|sum", re.IGNORECASE)
_J003_HOST_SINKS = {"float", "int", "bool", "print"}


def _j003_exempt_nodes(fn: ast.FunctionDef) -> Set[int]:
    """ids of all nodes living under an fp32-sink call."""
    out: Set[int] = set()
    for sub in ast.walk(fn):
        if not isinstance(sub, ast.Call):
            continue
        d = _dotted(sub.func) or ""
        attr = sub.func.attr if isinstance(sub.func, ast.Attribute) else ""
        name = sub.func.id if isinstance(sub.func, ast.Name) else ""
        sink = (_J003_FP32_SINK_RE.search(d or attr or name)
                or name in _J003_HOST_SINKS
                or d in ("jax.device_get", "np.asarray", "numpy.asarray"))
        if sink:
            for n in ast.walk(sub):
                out.add(id(n))
    return out


def _check_j003(tree: ast.Module, path: str) -> List[Finding]:
    out: List[Finding] = []
    for fn in [n for n in ast.walk(tree)
               if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))]:
        if not _fn_has_bf16(fn):
            continue
        exempt = _j003_exempt_nodes(fn)
        upcasts: List[ast.Call] = []
        has_downcast = False
        for sub in ast.walk(fn):
            if not isinstance(sub, ast.Call) or id(sub) in exempt:
                continue
            if isinstance(sub.func, ast.Attribute) and sub.func.attr == "astype":
                dt = sub.args[0] if sub.args else None
                for kw in sub.keywords:
                    if kw.arg == "dtype":
                        dt = kw.value
                if dt is not None and _is_f32_dtype(dt):
                    upcasts.append(sub)
                elif dt is not None:
                    has_downcast = True
            elif _dotted(sub.func) in ("jnp.asarray", "jnp.array"):
                if not sub.args or not _is_arrayish(sub.args[0], set()):
                    continue    # creation from host data, not a cast
                dt = sub.args[1] if len(sub.args) > 1 else None
                for kw in sub.keywords:
                    if kw.arg == "dtype":
                        dt = kw.value
                if dt is not None and _is_f32_dtype(dt):
                    upcasts.append(sub)
                elif dt is not None:
                    has_downcast = True
        if upcasts and not has_downcast:
            for c in upcasts:
                out.append(Finding(
                    path, c.lineno, c.col_offset, "J003",
                    f"fp32 cast in bf16 function '{fn.name}' with no "
                    f"compensating downcast anywhere in the function — "
                    f"the widened dtype leaks to every consumer"))
        # weak-type / literal promotion: jnp.float32(lit) inside arithmetic
        for sub in ast.walk(fn):
            if isinstance(sub, ast.BinOp):
                for side in (sub.left, sub.right):
                    if isinstance(side, ast.Call) \
                            and _dotted(side.func) == "jnp.float32" \
                            and side.args \
                            and isinstance(side.args[0], ast.Constant):
                        out.append(Finding(
                            path, side.lineno, side.col_offset, "J003",
                            f"jnp.float32(literal) inside arithmetic in "
                            f"bf16 function '{fn.name}' promotes the whole "
                            f"expression to fp32 (non-weak dtype); use a "
                            f"plain Python literal (weak type) or cast the "
                            f"result back"))
    return out


# -- J011: unfused BN/GN + ReLU chains in model __call__ bodies ---------------

_J011_NORMS = {"nn.BatchNorm", "nn.GroupNorm", "linen.BatchNorm",
               "linen.GroupNorm", "flax.linen.BatchNorm",
               "flax.linen.GroupNorm"}
_J011_RELUS = {"nn.relu", "jax.nn.relu", "flax.linen.relu"}


def _j011_norm_aliases(fn: ast.FunctionDef) -> Set[str]:
    """Local names bound to a BN/GN factory: ``norm = functools.partial(
    nn.BatchNorm, ...)`` or ``norm = lambda ...: nn.BatchNorm(...)`` —
    the idiom model bodies use to parameterize their norm layers."""
    out: Set[str] = set()
    for stmt in ast.walk(fn):
        if not (isinstance(stmt, ast.Assign) and len(stmt.targets) == 1
                and isinstance(stmt.targets[0], ast.Name)):
            continue
        v = stmt.value
        name = stmt.targets[0].id
        if isinstance(v, ast.Call) \
                and _dotted(v.func) in ("functools.partial", "partial") \
                and v.args and _dotted(v.args[0]) in _J011_NORMS:
            out.add(name)
        elif isinstance(v, ast.Lambda) and isinstance(v.body, ast.Call):
            f = v.body.func
            if _dotted(f) in _J011_NORMS:
                out.add(name)
            elif isinstance(f, ast.Call) and _dotted(f.func) in _J011_NORMS:
                out.add(name)
    return out


def _j011_is_norm_apply(node: ast.AST, aliases: Set[str]) -> bool:
    """``nn.BatchNorm(...)(x)`` / ``norm_alias(...)(x)`` /
    ``norm_alias(x)`` — a BN/GN module applied to activations."""
    if not isinstance(node, ast.Call):
        return False
    f = node.func
    if isinstance(f, ast.Call):             # ctor-then-apply
        if _dotted(f.func) in _J011_NORMS:
            return True
        if isinstance(f.func, ast.Name) and f.func.id in aliases:
            return True
    if isinstance(f, ast.Name) and f.id in aliases:
        return True
    return False


def _check_j011(tree: ast.Module, path: str) -> List[Finding]:
    findings: List[Finding] = []

    def _report(node: ast.AST, how: str) -> None:
        findings.append(Finding(
            path, node.lineno, node.col_offset, "J011",
            f"BatchNorm/GroupNorm {how} nn.relu in a model __call__ — "
            f"apex_tpu ships a fused epilogue for this exact chain "
            f"(normalization.bn_relu_residual via SyncBatchNorm("
            f"fuse_relu=True) / contrib.groupbn.BatchNorm2d_NHWC / the "
            f"ResNet norm-factory hook): one elementwise pass instead "
            f"of two"))

    for fn in ast.walk(tree):
        if not (isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef))
                and fn.name == "__call__"):
            continue
        aliases = _j011_norm_aliases(fn)
        # nested form: nn.relu(<bn apply>)
        for node in ast.walk(fn):
            if isinstance(node, ast.Call) \
                    and _dotted(node.func) in _J011_RELUS \
                    and node.args \
                    and _j011_is_norm_apply(node.args[0], aliases):
                _report(node, "wrapped directly in")
        # consecutive-statement form: v = <bn apply>; v = nn.relu(v) —
        # across EVERY statement list (if/else arms, loop bodies, try/
        # except/finally), not just .body: an else-branch chain is the
        # same two sweeps.
        stmt_lists = []
        for holder in ast.walk(fn):
            for field in ("body", "orelse", "finalbody"):
                body = getattr(holder, field, None)
                if isinstance(body, list) and body \
                        and isinstance(body[0], ast.stmt):
                    stmt_lists.append(body)
        for body in stmt_lists:
            for prev, nxt in zip(body, body[1:]):
                if not (isinstance(prev, ast.Assign)
                        and len(prev.targets) == 1
                        and isinstance(prev.targets[0], ast.Name)
                        and _j011_is_norm_apply(prev.value, aliases)):
                    continue
                tgt = prev.targets[0].id
                if not (isinstance(nxt, ast.Assign)
                        and isinstance(nxt.value, ast.Call)
                        and _dotted(nxt.value.func) in _J011_RELUS
                        and nxt.value.args
                        and isinstance(nxt.value.args[0], ast.Name)
                        and nxt.value.args[0].id == tgt):
                    continue
                _report(nxt.value, "immediately followed by")
    return findings


# -- J013: unsharded parameter staging in multi-device entry points -----------

#: a function that touches any of these is a "multi-device entry
#: point": it constructs or maps over a mesh, so every array it stages
#: has a RIGHT placement the partitioner cannot infer from a bare put.
_J013_MESH_MARKERS = {"Mesh", "MeshPlan", "shard_map", "NamedSharding",
                      "make_mesh_train_step", "make_mesh"}

#: names that look parameter-sized (the arrays whose silent
#: replication costs HBM and a reshuffle; a scalar metric staged
#: without a sharding is noise, not a finding)
_J013_PARAMISH_RE = re.compile(
    r"(^|_)(params?|state|weights?|masters?|moments?|opt_state|grads?)"
    r"(_|$|\d)", re.IGNORECASE)

_J013_ASARRAY = {"jnp.asarray", "jax.numpy.asarray",
                 "jnp.array", "jax.numpy.array"}


def _j013_is_mesh_fn(fn: ast.AST) -> bool:
    for node in ast.walk(fn):
        name = _dotted(node) if isinstance(node, (ast.Name,
                                                  ast.Attribute)) else None
        if name and name.split(".")[-1] in _J013_MESH_MARKERS:
            return True
    return False


def _j013_paramish(node: ast.AST) -> bool:
    if isinstance(node, ast.Name):
        return bool(_J013_PARAMISH_RE.search(node.id))
    if isinstance(node, ast.Attribute):
        return bool(_J013_PARAMISH_RE.search(node.attr)) \
            or _j013_paramish(node.value)
    return False


def _check_j013(tree: ast.Module, path: str) -> List[Finding]:
    findings: List[Finding] = []
    for fn in ast.walk(tree):
        if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        if not _j013_is_mesh_fn(fn):
            continue
        for node in ast.walk(fn):
            if not isinstance(node, ast.Call):
                continue
            name = _dotted(node.func)
            if name and name.split(".")[-1] == "device_put":
                # an explicit second arg / device= / sharding kwarg IS
                # the placement — only the bare single-arg put flags
                explicit = (len(node.args) >= 2
                            or any(k.arg in ("device", "sharding", "dst")
                                   for k in node.keywords))
                if not explicit and node.args \
                        and _j013_paramish(node.args[0]):
                    findings.append(Finding(
                        path, node.lineno, node.col_offset, "J013",
                        "device_put of a parameter-sized array with no "
                        "sharding inside a multi-device entry point — "
                        "it lands on one device (or replicated) and "
                        "every sharded call reshuffles it; pass the "
                        "NamedSharding the mesh plan derives "
                        "(plan.named(...)/plan.batch_sharding())"))
            elif name in _J013_ASARRAY:
                if node.args and _j013_paramish(node.args[0]):
                    findings.append(Finding(
                        path, node.lineno, node.col_offset, "J013",
                        "jnp.asarray of a parameter-sized array inside "
                        "a multi-device entry point stages it "
                        "uncommitted on the default device — "
                        "device_put with the plan-derived NamedSharding "
                        "instead, so warmup and restore pin the "
                        "placement"))
    return findings


# -- J014: per-step recalibration at quantized-matmul call sites --------------

#: call names that take a calibrated scale (the apex_tpu.quant surface
#: plus the obvious user spellings)
_J014_QUANT_CALLS = {"quantized_matmul", "quant_matmul",
                     "quantized_matmul_ref"}

#: keyword arguments that carry an ACTIVATION scale.  ``w_scale`` is
#: deliberately absent: weights are exact at trace time, so deriving
#: their per-channel scale in-step is the correct recipe.
_J014_SCALE_KWARGS = {"x_scale", "scale"}

_J014_ABS_NAMES = {"abs", "absolute"}
_J014_MAX_NAMES = {"max", "amax", "nanmax"}


def _j014_call_leaf(call: ast.Call) -> Optional[str]:
    """The trailing name of a call: ``jnp.abs`` -> ``abs``, and the
    method form ``expr.max()`` -> ``max`` (an Attribute on a non-name
    value has no dotted spelling but its attr still identifies it)."""
    name = _dotted(call.func)
    if name:
        return name.split(".")[-1]
    if isinstance(call.func, ast.Attribute):
        return call.func.attr
    return None


def _j014_contains_abs(node: ast.AST) -> bool:
    for sub in ast.walk(node):
        if isinstance(sub, ast.Call) \
                and _j014_call_leaf(sub) in _J014_ABS_NAMES:
            return True
    return False


def _j014_is_fresh_absmax(node: ast.AST) -> bool:
    """True when ``node`` computes an absmax inline: ``jnp.max(jnp.abs(
    x))`` / ``jnp.abs(x).max()`` / ``abs(x).max()`` — the per-step
    recalibration shape.  A frozen float, an attribute read
    (``calib.scales[...]``) or a plain name resolves False here; names
    assigned from an absmax in the SAME function are resolved by the
    caller."""
    for sub in ast.walk(node):
        if not isinstance(sub, ast.Call):
            continue
        if _j014_call_leaf(sub) in _J014_MAX_NAMES \
                and _j014_contains_abs(sub):
            return True
    return False


def _j014_scope_walk(fn):
    """``ast.walk`` limited to ``fn``'s OWN scope: nested function defs
    are their own J014 scopes, so a helper's local ``s = abs(x).max()``
    must not mark the enclosing function's ``s`` (a frozen calibration
    constant) as fresh.  Lambdas cannot contain assignments, so their
    bodies stay included (call-site coverage, no name pollution)."""
    stack = list(ast.iter_child_nodes(fn))
    while stack:
        node = stack.pop()
        yield node
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        stack.extend(ast.iter_child_nodes(node))


def _check_j014(tree: ast.Module, path: str) -> List[Finding]:
    findings: List[Finding] = []
    for fn in ast.walk(tree):
        if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        # one-level local resolution (the J009 pattern): a name assigned
        # from a fresh absmax in this function is as fresh as the
        # expression itself.  Binding-order aware: what matters is the
        # LAST assignment to the name before the call site, so
        # ``s = abs(x).max()/127; s = calib.scales[k]`` resolves frozen
        bindings: Dict[str, List[Tuple[int, bool]]] = {}
        for node in _j014_scope_walk(fn):
            if isinstance(node, ast.Assign):
                fresh_val = _j014_is_fresh_absmax(node.value)
                for tgt in node.targets:
                    if isinstance(tgt, ast.Name):
                        bindings.setdefault(tgt.id, []).append(
                            (node.lineno, fresh_val))

        def _name_fresh_at(name: str, lineno: int) -> bool:
            prior = [b for b in bindings.get(name, ())
                     if b[0] <= lineno]
            return bool(prior) and max(prior)[1]

        for node in _j014_scope_walk(fn):
            if not isinstance(node, ast.Call):
                continue
            name = _dotted(node.func)
            if not name or name.split(".")[-1] not in _J014_QUANT_CALLS:
                continue
            for kw in node.keywords:
                if kw.arg not in _J014_SCALE_KWARGS:
                    continue
                fresh = _j014_is_fresh_absmax(kw.value) or (
                    isinstance(kw.value, ast.Name)
                    and _name_fresh_at(kw.value.id, node.lineno))
                if fresh:
                    findings.append(Finding(
                        path, node.lineno, node.col_offset, "J014",
                        f"{kw.arg}= is a freshly computed abs().max() — "
                        f"per-step recalibration re-derives the int8 "
                        f"range every dispatch (an extra full reduction "
                        f"over the activations) and unpins the "
                        f"numerics the convergence gate certified; "
                        f"freeze scales once via apex_tpu.quant."
                        f"Calibrator and pass the calibrated constant"))
    return findings


# -- J015: literal block-size overrides at tunable-kernel call sites ----------

#: call-name leaves of the registered tunable kernels that EXPOSE a
#: block override (xentropy is cache-tuned too but its public function
#: takes no block kwarg, so no literal can appear at a working call
#: site — listing it would document a parameter that does not exist)
_J015_KERNEL_CALLS = {"flash_attention", "fused_layer_norm",
                      "fused_layer_norm_affine", "quantized_matmul"}
#: the tuned block-size parameters across the kernel family
_J015_BLOCK_KWARGS = {"block_q", "block_k", "block_m", "block_n",
                      "row_block"}


def _check_j015(tree: ast.Module, path: str) -> List[Finding]:
    findings: List[Finding] = []
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        name = _dotted(node.func)
        if not name or name.split(".")[-1] not in _J015_KERNEL_CALLS:
            continue
        for kw in node.keywords:
            if kw.arg not in _J015_BLOCK_KWARGS:
                continue
            if isinstance(kw.value, ast.Constant) \
                    and isinstance(kw.value.value, int) \
                    and not isinstance(kw.value.value, bool):
                findings.append(Finding(
                    path, node.lineno, node.col_offset, "J015",
                    f"{kw.arg}={kw.value.value} is a literal block-size "
                    f"override — it freezes one sweep's winner for every "
                    f"device kind and shape; leave the blocks at their "
                    f"defaults so the tune config cache decides per "
                    f"device (python -m apex_tpu.tune), or pass a "
                    f"measured variable"))
    return findings


# -- J016: NCHW convolution layouts -------------------------------------------

#: always-NCHW lax convenience wrappers (no dimension_numbers knob);
#: matched by the FULL dotted suffix ``lax.<name>`` — the bare leaf
#: ``conv`` is far too common (``self.conv(...)`` factories) to match
_J016_LAX_NCHW_CALLS = {"conv", "conv_with_general_padding"}


def _j016_spec_is_nchw(value: ast.expr) -> Optional[bool]:
    """True/False when ``dimension_numbers=`` is a literal spec we can
    read (tuple/list of strings: NC* -> True, else False); None when it
    is a variable / ConvDimensionNumbers expression (not inspected)."""
    if isinstance(value, (ast.Tuple, ast.List)) and value.elts:
        first = value.elts[0]
        if isinstance(first, ast.Constant) and isinstance(first.value, str):
            return first.value.upper().startswith("NC")
    return None


def _check_j016(tree: ast.Module, path: str) -> List[Finding]:
    findings: List[Finding] = []
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        name = _dotted(node.func)
        if not name:
            continue
        parts = name.split(".")
        leaf = parts[-1]
        if leaf == "conv_general_dilated":
            dims = None
            for kw in node.keywords:
                if kw.arg == "dimension_numbers":
                    dims = kw.value
            if dims is None and len(node.args) >= 6:
                dims = node.args[5]
            if dims is None:
                findings.append(Finding(
                    path, node.lineno, node.col_offset, "J016",
                    "conv_general_dilated without dimension_numbers= — "
                    "the lax default IS NCHW ('NCHW','OIHW','NCHW'), a "
                    "TPU-hostile layout that transposes around every "
                    "conv; spell ('NHWC','HWIO','NHWC') explicitly"))
            elif _j016_spec_is_nchw(dims):
                findings.append(Finding(
                    path, node.lineno, node.col_offset, "J016",
                    "NCHW dimension_numbers at a conv call site — TPUs "
                    "tile the feature axis onto the 128 lanes, so NCHW "
                    "pays a transpose either side of every conv; use "
                    "('NHWC','HWIO','NHWC')"))
        elif (leaf in _J016_LAX_NCHW_CALLS and len(parts) >= 2
              and parts[-2] == "lax"):
            findings.append(Finding(
                path, node.lineno, node.col_offset, "J016",
                f"lax.{leaf} is the always-NCHW convenience wrapper — "
                f"it has no layout knob and lands the TPU-hostile "
                f"('NCHW','OIHW','NCHW') spec; call "
                f"conv_general_dilated with ('NHWC','HWIO','NHWC') or "
                f"use flax.linen.Conv"))
    return findings


# -- per-scope walker: J001, J004, J005, J006 ---------------------------------

class _ScopeWalker:
    """Walks one scope (module body or one function body, excluding
    nested defs which become their own scopes) tracking loop depth and
    which locals hold arrays."""

    def __init__(self, idx: _ModuleIndex, path: str, driver: bool,
                 findings: List[Finding]):
        self.idx = idx
        self.path = path
        self.driver = driver
        self.findings = findings

    def lint_module(self, tree: ast.Module) -> None:
        self._scope(tree.body, fn=None)
        for node in ast.walk(tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                self._scope(node.body, fn=node)

    # .. scope machinery ......................................................

    def _scope(self, body: List[ast.stmt], fn) -> None:
        self.fn = fn
        self.body = body
        self.fn_name = fn.name if fn is not None else "<module>"
        # Locals known to hold arrays.  Parameters are deliberately NOT
        # assumed arrayish: ``float(eps)`` on a Python-scalar parameter is
        # the dominant idiom and would drown real syncs in false
        # positives; precision over recall.
        self.arrayish: Set[str] = set()
        # Loop targets drawn from NON-array host iterables (a loader /
        # batch stream): per-step device_put/asarray on these is the
        # J007 host-staging-in-the-hot-loop finding.
        self.batch_vars: Set[str] = set()
        # Locals bound from tree_leaves/tree_flatten results: loops over
        # them are PER-LEAF sweeps, where a sync is J008 (O(leaves)
        # round-trips), not a garden-variety J001.
        self.leafish: Set[str] = set()
        self.jit_scoped = (fn is not None
                           and fn.name in self.idx.jitted_defs)
        # Request-handler scope for J012: syncs anywhere in a function
        # whose NAME marks it as per-request serving code are
        # per-request round-trips, loop or not.
        self.handler_fn = bool(_HANDLER_NAME_RE.search(self.fn_name))
        # J009 collection: clock reads, jitted-call sites, and sync
        # points seen in this scope (line-ordered pairing happens in
        # _finish_j009 once the whole scope is walked).
        self._j009_clocks: List[Tuple[int, int]] = []
        self._j009_jits: List[Tuple[int, str]] = []
        self._j009_syncs: List[int] = []
        self._stmts(body, loop_depth=0, loop_vars=frozenset(),
                    leaf_loop=False)
        self._finish_j009()

    def _stmts(self, body: List[ast.stmt], loop_depth: int,
               loop_vars: frozenset, leaf_loop: bool,
               in_while: bool = False) -> None:
        for stmt in body:
            self._stmt(stmt, loop_depth, loop_vars, leaf_loop, in_while)

    def _stmt(self, stmt: ast.stmt, loop_depth: int,
              loop_vars: frozenset, leaf_loop: bool,
              in_while: bool = False) -> None:
        if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.ClassDef)):
            return                      # nested defs are separate scopes
        if isinstance(stmt, ast.Assign):
            self._track_arrayish(stmt)
            self._track_leafish(stmt)
            self._check_j005_stmt(stmt, loop_depth)
        elif isinstance(stmt, ast.Expr):
            self._check_j005_stmt(stmt, loop_depth)
        # expression-level checks on this statement's own expressions
        self._exprs(stmt, loop_depth, loop_vars, leaf_loop, in_while)
        # recurse into compound statements
        if isinstance(stmt, (ast.For, ast.AsyncFor)):
            new_vars = loop_vars | self._scalar_loop_vars(stmt)
            # Loop targets drawn from an arrayish iterable hold arrays:
            # ``for loss in losses: float(loss)`` is a per-iteration
            # device round-trip exactly like ``float(losses[i])`` — the
            # J001 extension of ISSUE 2 (the old tracking only followed
            # Assign bindings, so iteration syncs in for/while bodies
            # passed the sweep).  Scalar counters (range/enumerate) are
            # excluded; zip over mixed iterables over-approximates, per
            # the waiver contract.
            in_leaf_loop = leaf_loop or self._is_leaves_expr(stmt.iter)
            if in_leaf_loop or _is_arrayish(stmt.iter, self.arrayish):
                for n in ast.walk(stmt.target):
                    if isinstance(n, ast.Name) and n.id not in new_vars:
                        self.arrayish.add(n.id)
            else:
                # Non-array iterable (a loader / host batch stream):
                # its non-scalar targets are host BATCH data — J007
                # territory when device_put/asarray'd per step.
                for n in ast.walk(stmt.target):
                    if isinstance(n, ast.Name) and n.id not in new_vars:
                        self.batch_vars.add(n.id)
            self._stmts(stmt.body, loop_depth + 1, new_vars, in_leaf_loop,
                        in_while)
            self._stmts(stmt.orelse, loop_depth, loop_vars, leaf_loop,
                        in_while)
        elif isinstance(stmt, ast.While):
            self._stmts(stmt.body, loop_depth + 1, loop_vars, leaf_loop,
                        True)
            self._stmts(stmt.orelse, loop_depth, loop_vars, leaf_loop,
                        in_while)
        elif isinstance(stmt, ast.If):
            self._check_j006(stmt)
            self._stmts(stmt.body, loop_depth, loop_vars, leaf_loop,
                        in_while)
            self._stmts(stmt.orelse, loop_depth, loop_vars, leaf_loop,
                        in_while)
        elif isinstance(stmt, (ast.With, ast.AsyncWith)):
            self._stmts(stmt.body, loop_depth, loop_vars, leaf_loop,
                        in_while)
        elif isinstance(stmt, ast.Try):
            self._stmts(stmt.body, loop_depth, loop_vars, leaf_loop,
                        in_while)
            for h in stmt.handlers:
                self._stmts(h.body, loop_depth, loop_vars, leaf_loop,
                            in_while)
            self._stmts(stmt.orelse, loop_depth, loop_vars, leaf_loop,
                        in_while)
            self._stmts(stmt.finalbody, loop_depth, loop_vars, leaf_loop,
                        in_while)

    @staticmethod
    def _scalar_loop_vars(stmt) -> frozenset:
        """Loop targets that are definitely fresh Python scalars per
        iteration: ``for i in range(...)`` (all targets) and the counter
        of ``for i, x in enumerate(...)``.  Iterating arrays/leaves binds
        traced values, which retrace nothing — only scalar counters feed
        J004."""
        it = stmt.iter
        if not isinstance(it, ast.Call):
            return frozenset()
        d = _dotted(it.func)
        if d == "range":
            return frozenset(n.id for n in ast.walk(stmt.target)
                             if isinstance(n, ast.Name))
        if d == "enumerate" and isinstance(stmt.target, ast.Tuple) \
                and stmt.target.elts \
                and isinstance(stmt.target.elts[0], ast.Name):
            return frozenset({stmt.target.elts[0].id})
        return frozenset()

    # tree-leaves iterables feeding J008 (per-leaf sync sweeps)
    _TREE_LEAVES_CALLS = ("jax.tree_util.tree_leaves", "jax.tree_leaves",
                          "tree_leaves", "jax.tree.leaves",
                          "tree_util.tree_leaves")
    _TREE_FLATTEN_CALLS = ("jax.tree_util.tree_flatten", "jax.tree_flatten",
                           "tree_flatten", "jax.tree.flatten",
                           "tree_util.tree_flatten")

    def _is_leaves_expr(self, node: ast.AST) -> bool:
        """Does this expression yield the leaf list of a pytree?
        ``tree_leaves(...)``, ``tree_flatten(...)[0]``, or a local bound
        from either."""
        if isinstance(node, ast.Call) \
                and _dotted(node.func) in self._TREE_LEAVES_CALLS:
            return True
        if isinstance(node, ast.Subscript) \
                and isinstance(node.value, ast.Call) \
                and _dotted(node.value.func) in self._TREE_FLATTEN_CALLS:
            sl = node.slice
            return isinstance(sl, ast.Constant) and sl.value == 0
        if isinstance(node, ast.Name) and node.id in self.leafish:
            return True
        # zip(leaves_a, leaves_b, ...): per-leaf lockstep sweep
        if isinstance(node, ast.Call) and _dotted(node.func) == "zip":
            return any(self._is_leaves_expr(a) for a in node.args)
        return False

    def _track_leafish(self, stmt: ast.Assign) -> None:
        if len(stmt.targets) != 1:
            return
        t, v = stmt.targets[0], stmt.value
        if isinstance(t, ast.Name):
            if self._is_leaves_expr(v):
                self.leafish.add(t.id)
            else:
                self.leafish.discard(t.id)
            return
        # ``leaves, treedef = tree_flatten(tree)``
        if isinstance(t, ast.Tuple) and t.elts \
                and isinstance(t.elts[0], ast.Name) \
                and isinstance(v, ast.Call) \
                and _dotted(v.func) in self._TREE_FLATTEN_CALLS:
            self.leafish.add(t.elts[0].id)
            return
        for n in ast.walk(t):
            if isinstance(n, ast.Name):
                self.leafish.discard(n.id)

    def _track_arrayish(self, stmt: ast.Assign) -> None:
        # Results of a known-jitted callable are device arrays too —
        # ``state, metrics = step(state, b)`` then ``float(metrics[...])``
        # is the per-step sync this PR scrubbed from examples/lm (review:
        # the old tracking missed both the jitted call and tuple targets).
        v = stmt.value
        value_arrayish = _is_arrayish(v, self.arrayish) or (
            isinstance(v, ast.Call) and isinstance(v.func, ast.Name)
            and self.idx.jitted_name(self.fn, v.func.id))
        # A host fetch PRODUCES a host value: after
        # ``vals = jax.device_get(...)`` every later bool(vals)/float()
        # is plain host arithmetic, not another sync (review: the fetch
        # itself is the one finding; post-fetch consumers are noise).
        if isinstance(v, ast.Call) and (
                _dotted(v.func) in ("jax.device_get", "np.asarray",
                                    "numpy.asarray", "np.array",
                                    "numpy.array")
                or (isinstance(v.func, ast.Name)
                    and v.func.id in ("float", "int", "bool"))):
            value_arrayish = False
        if len(stmt.targets) != 1:
            return
        target = stmt.targets[0]
        if isinstance(target, ast.Name):
            names = [target.id]
        elif isinstance(target, (ast.Tuple, ast.List)):
            names = [n.id for e in target.elts for n in ast.walk(e)
                     if isinstance(n, ast.Name)]
        else:
            return
        for name in names:
            if value_arrayish:
                self.arrayish.add(name)
            else:
                self.arrayish.discard(name)

    def _exprs(self, stmt: ast.stmt, loop_depth: int,
               loop_vars: frozenset, leaf_loop: bool,
               in_while: bool = False) -> None:
        # own expressions only (not nested statements/defs)
        for expr in ast.iter_child_nodes(stmt):
            if isinstance(expr, (ast.stmt, ast.FunctionDef)):
                continue
            if isinstance(expr, ast.expr):
                for sub in ast.walk(expr):
                    if isinstance(sub, ast.Call):
                        self._check_j001_call(sub, loop_depth, leaf_loop,
                                              in_while)
                        self._check_j004_call(sub, loop_depth, loop_vars)
                        self._check_j007_call(sub, loop_depth)
                        self._check_j010_call(sub, loop_depth)
                        self._collect_j009(sub)
        # While tests live on the stmt itself
        if isinstance(stmt, ast.While):
            self._check_j006(stmt)

    # .. J001 / J008 / J012 ...................................................

    def _check_j001_call(self, call: ast.Call, loop_depth: int,
                         leaf_loop: bool = False,
                         in_while: bool = False) -> None:
        sync: Optional[str] = None
        d = _dotted(call.func)
        if d in ("jax.device_get", "jax.block_until_ready"):
            sync = d
        elif isinstance(call.func, ast.Attribute) and call.func.attr in (
                "item", "block_until_ready") and not call.args:
            sync = f".{call.func.attr}()"
        elif isinstance(call.func, ast.Name) \
                and call.func.id in ("float", "int", "bool") \
                and len(call.args) == 1 \
                and _is_arrayish(call.args[0], self.arrayish) \
                and not _is_static_metadata(call.args[0]):
            sync = f"{call.func.id}()"
        elif d in ("np.asarray", "numpy.asarray", "np.array", "numpy.array") \
                and call.args and _is_arrayish(call.args[0], self.arrayish) \
                and not _is_static_metadata(call.args[0]):
            sync = d
        if sync is None:
            return
        if self.fn_name in _J001_HOST_BOUNDARY_FUNCS:
            return
        if leaf_loop:
            # The per-LEAF sweep variant (ISSUE 4): O(leaves) round-trips
            # per sweep, the multiplied form of the J001 stall.  More
            # specific rule, reported INSTEAD of J001.
            self.findings.append(Finding(
                self.path, call.lineno, call.col_offset, "J008",
                f"per-leaf host sync {sync} in a loop over pytree leaves "
                f"— O(leaves) device round-trips per sweep; reduce on "
                f"device (tree_finite / multi_tensor_l2norm, one reduce "
                f"per bucket with a BucketStore) and fetch ONE value, or "
                f"stack the per-leaf values into a single transfer"))
            return
        if self.driver and loop_depth == 0:
            return
        if in_while or self.handler_fn:
            # The serving variant (ISSUE 11): a while-serving loop or a
            # request-handler function syncs PER REQUEST / per decode
            # step — reported INSTEAD of J001 (more specific rule, same
            # replacement contract as J008).
            where = ("in a while-serving loop" if in_while else
                     f"in request-handler '{self.fn_name}'")
            self.findings.append(Finding(
                self.path, call.lineno, call.col_offset, "J012",
                f"per-request host sync {sync} {where} — every request "
                f"(or decode step) pays a device round-trip; defer the "
                f"fetch one step behind or batch it, and waive only the "
                f"sanctioned response boundary"))
            return
        where = ("inside a loop" if loop_depth else
                 f"in library function '{self.fn_name}'")
        self.findings.append(Finding(
            self.path, call.lineno, call.col_offset, "J001",
            f"host sync {sync} {where} — blocks dispatch until the device "
            f"round-trip completes; keep the value on device or waive with "
            f"a reason"))

    # .. J007 .................................................................

    _J007_STAGING_CALLS = ("jax.device_put", "np.asarray", "numpy.asarray",
                           "jnp.asarray", "np.array", "numpy.array")

    def _check_j007_call(self, call: ast.Call, loop_depth: int) -> None:
        if loop_depth == 0 or not call.args:
            return
        d = _dotted(call.func)
        if d not in self._J007_STAGING_CALLS:
            return
        if d != "jax.device_put" and not self.driver:
            # The asarray-family half targets TRAINING loops (driver
            # scripts): library code legitimately asarray's inside
            # serialization / per-leaf metadata loops, and its real
            # sync hazards are J001's (arrayish) business.
            return
        arg = call.args[0]
        names = {n.id for n in ast.walk(arg) if isinstance(n, ast.Name)}
        hit = bool(names & self.batch_vars)
        if d == "jax.device_put" and not hit:
            # Re-staging values that are already device arrays is the
            # same per-step stall, whatever name they travel under.
            hit = _is_arrayish(arg, self.arrayish)
        if not hit:
            return
        self.findings.append(Finding(
            self.path, call.lineno, call.col_offset, "J007",
            f"per-step host staging {d} on batch data inside a loop — "
            f"host->device staging belongs in the input engine "
            f"(PrefetchLoader / stage_windows device=...), where it "
            f"overlaps compute instead of serializing with each step"))

    # .. J010 .................................................................

    # Compile-triggering analysis entry points.  The bare attr names fire
    # anywhere in a loop; ``lower``/``compile`` only when the receiver is
    # demonstrably a jitted computation (``jax.jit(f).lower(...)``, a
    # known-jitted name, or a ``.lower(...)`` chain) — ``s.lower()`` on a
    # string and ``re.compile`` must not flag.
    _J010_HARVEST_ATTRS = ("cost_analysis", "memory_analysis")

    def _check_j010_call(self, call: ast.Call, loop_depth: int) -> None:
        if loop_depth == 0:
            return
        f = call.func
        if not isinstance(f, ast.Attribute):
            return
        if f.attr in self._J010_HARVEST_ATTRS:
            what = f".{f.attr}()"
        elif f.attr in ("lower", "compile"):
            recv = f.value
            jitted_recv = (
                (isinstance(recv, ast.Call)
                 and (_is_jax_jit(recv.func)
                      or (isinstance(recv.func, ast.Attribute)
                          and recv.func.attr == "lower")))
                or (isinstance(recv, ast.Name)
                    and self.idx.jitted_name(self.fn, recv.id)))
            if not jitted_recv:
                return
            what = f".{f.attr}()"
        else:
            return
        self.findings.append(Finding(
            self.path, call.lineno, call.col_offset, "J010",
            f"{what} inside a loop — every call re-traces (and "
            f"`.compile()` re-runs the backend, seconds per call on a "
            f"real chip); costs are static per (shapes, dtypes), so "
            f"harvest ONCE before the loop "
            f"(apex_tpu.prof.roofline.harvest_costs) and reuse the "
            f"result"))

    # .. J009 .................................................................

    _J009_CLOCK_CALLS = ("time.time", "time.perf_counter",
                         "time.monotonic", "perf_counter", "monotonic",
                         "timeit.default_timer", "default_timer")

    def _collect_j009(self, call: ast.Call) -> None:
        """Classify one call for the scope-level timing analysis: a
        clock read, a sync point (inline or via a local helper that
        syncs), or a call to a known-jitted callable."""
        if _dotted(call.func) in self._J009_CLOCK_CALLS:
            self._j009_clocks.append((call.lineno, call.col_offset))
            return
        if _is_sync_call(call) or (
                isinstance(call.func, ast.Name)
                and call.func.id in self.idx.sync_defs()):
            self._j009_syncs.append(call.lineno)
            return
        if isinstance(call.func, ast.Name) \
                and self.idx.jitted_name(self.fn, call.func.id):
            self._j009_jits.append((call.lineno, call.func.id))

    def _finish_j009(self) -> None:
        """Pair clock reads around jitted calls: a jitted call between
        two clock reads with no sync inside the span means the elapsed
        time measures ENQUEUE, not compute (async dispatch).  Reported
        at the closing clock read; one finding per scope."""
        if len(self._j009_clocks) < 2 or not self._j009_jits:
            return
        clocks = sorted(self._j009_clocks)
        syncs = sorted(self._j009_syncs)
        for j_line, j_name in sorted(self._j009_jits):
            before = [c for c in clocks if c[0] < j_line]
            after = [c for c in clocks if c[0] > j_line]
            if not before or not after:
                continue
            t_open, t_close = before[-1], after[0]
            if any(t_open[0] < s <= t_close[0] for s in syncs):
                continue
            self.findings.append(Finding(
                self.path, t_close[0], t_close[1], "J009",
                f"wall-clock timing around jitted '{j_name}' with no "
                f"block_until_ready/device_get/value fetch in the timed "
                f"span — jax dispatch is async, so this elapsed time "
                f"measures how fast the host ENQUEUED the program, not "
                f"how long the device ran it; fence with "
                f"jax.block_until_ready(out) or fetch a value before "
                f"reading the second clock"))
            return

    # .. J004 .................................................................

    def _check_j004_call(self, call: ast.Call, loop_depth: int,
                         loop_vars: frozenset) -> None:
        if loop_depth == 0:
            return
        if _is_jax_jit(call.func):
            self.findings.append(Finding(
                self.path, call.lineno, call.col_offset, "J004",
                "jax.jit called inside a loop — a fresh jitted callable "
                "per iteration retraces (and re-compiles) every time; "
                "hoist the jit out of the loop"))
            return
        if not (isinstance(call.func, ast.Name)
                and self.idx.jitted_name(self.fn, call.func.id)):
            return
        # keyword args retrace exactly like positional ones (review:
        # ``step(x, s=i)`` was invisible to the positional-only scan)
        for arg in list(call.args) + [kw.value for kw in call.keywords]:
            if isinstance(arg, ast.Name) and arg.id in loop_vars:
                bad = arg.id
            elif isinstance(arg, (ast.BinOp, ast.UnaryOp)) \
                    and not any(isinstance(s, (ast.Call, ast.Subscript))
                                for s in ast.walk(arg)) \
                    and any(isinstance(s, ast.Name) and s.id in loop_vars
                            for s in ast.walk(arg)):
                bad = ast.unparse(arg)
            else:
                continue
            self.findings.append(Finding(
                self.path, call.lineno, call.col_offset, "J004",
                f"jitted '{call.func.id}' called with loop-varying Python "
                f"scalar '{bad}' — every new value retraces; pass it as a "
                f"traced array (jnp.asarray) or mark it static if it takes "
                f"few values"))

    # .. J005 .................................................................

    def _check_j005_stmt(self, stmt: ast.stmt, loop_depth: int) -> None:
        if isinstance(stmt, ast.Assign):
            call = stmt.value if isinstance(stmt.value, ast.Call) else None
            targets: Set[str] = set()
            for t in stmt.targets:
                for n in ast.walk(t):
                    if isinstance(n, ast.Name):
                        targets.add(n.id)
        elif isinstance(stmt, ast.Expr):
            call = stmt.value if isinstance(stmt.value, ast.Call) else None
            targets = set()
        else:
            return
        if call is None or not isinstance(call.func, ast.Name):
            return
        donate = self.idx.donated_argnums(self.fn, call.func.id)
        if not donate:
            return
        for i in donate:
            if i >= len(call.args) or not isinstance(call.args[i], ast.Name):
                continue
            name = call.args[i].id
            if name in targets:
                continue                      # rebound by this statement: ok
            if loop_depth > 0:
                self.findings.append(Finding(
                    self.path, call.lineno, call.col_offset, "J005",
                    f"'{name}' is donated to '{call.func.id}' "
                    f"(donate_argnums={i}) inside a loop without being "
                    f"rebound — the next iteration re-donates a "
                    f"deleted buffer"))
                continue
            if self._read_later(name, call.lineno):
                self.findings.append(Finding(
                    self.path, call.lineno, call.col_offset, "J005",
                    f"'{name}' is donated to '{call.func.id}' "
                    f"(donate_argnums={i}) but read again later in "
                    f"'{self.fn_name}' — donated buffers are invalidated"))

    def _read_later(self, name: str, after_line: int) -> bool:
        # self.body covers module scope too — drivers donate-and-read at
        # the top level under no function at all (review: the old
        # fn-only lookup made J005 a no-op exactly there).
        occurrences: List[Tuple[int, int, bool]] = []
        for stmt in self.body:
            for sub in ast.walk(stmt):
                if isinstance(sub, ast.Name) and sub.id == name \
                        and sub.lineno > after_line:
                    occurrences.append((sub.lineno, sub.col_offset,
                                        isinstance(sub.ctx, ast.Load)))
        if not occurrences:
            return False
        occurrences.sort()
        # ANY Load on the earliest later line is a read: in
        # ``state = f(state)`` the RHS Load evaluates before the Store
        # even though the Store tokenizes first (review: sorting by
        # column let the col-0 Store mask the same-line read).
        first_line = occurrences[0][0]
        return any(is_load for line, _c, is_load in occurrences
                   if line == first_line)

    # .. J006 .................................................................

    def _check_j006(self, stmt) -> None:
        if not self.jit_scoped:
            return
        test = stmt.test
        traced = None
        for sub in ast.walk(test):
            if isinstance(sub, ast.Call):
                if _rooted_at(sub.func, ("jnp", "lax")):
                    traced = ast.unparse(sub.func)
                    break
                if isinstance(sub.func, ast.Attribute) and sub.func.attr in (
                        "any", "all", "item"):
                    traced = f".{sub.func.attr}()"
                    break
        if traced is None:
            return
        kw = "while" if isinstance(stmt, ast.While) else "if"
        self.findings.append(Finding(
            self.path, stmt.lineno, stmt.col_offset, "J006",
            f"Python '{kw}' branches on traced value ({traced}) inside "
            f"jitted '{self.fn_name}' — use jnp.where/lax.cond; Python "
            f"control flow executes at trace time, not per step"))


# -- engine -------------------------------------------------------------------

def _is_driver_path(path: str) -> bool:
    parts = os.path.normpath(path).split(os.sep)
    return bool(set(parts) & _DRIVER_PARTS) \
        or os.path.basename(path) in _DRIVER_BASENAMES


def lint_source(src: str, path: str = "<string>",
                driver: Optional[bool] = None) -> List[Finding]:
    """Lint one source string; returns unwaived findings (plus J000 for
    malformed waivers).  ``driver`` overrides path-based classification."""
    if driver is None:
        driver = _is_driver_path(path)
    try:
        tree = ast.parse(src)
    except SyntaxError as e:
        return [Finding(path, e.lineno or 0, e.offset or 0, "J000",
                        f"syntax error: {e.msg}")]
    waivers = _Waivers(src, path)
    findings: List[Finding] = []
    idx = _ModuleIndex(tree)
    findings += _check_j002(idx, path)
    findings += _check_j003(tree, path)
    findings += _check_j011(tree, path)
    findings += _check_j013(tree, path)
    findings += _check_j014(tree, path)
    findings += _check_j015(tree, path)
    findings += _check_j016(tree, path)
    _ScopeWalker(idx, path, driver, findings).lint_module(tree)
    kept = [f for f in findings if not waivers.waived(f)]
    kept += waivers.errors
    # Dedup: nested defs are walked by their enclosing function too
    # (J003), and one expression can contain several sync calls
    # (``float(jax.device_get(x))``) — since waivers are line-scoped,
    # one J001 report per line is enough.
    seen: Set[tuple] = set()
    unique = []
    for f in sorted(kept, key=lambda f: (f.line, f.col, f.rule)):
        k = ((f.line, f.rule) if f.rule in ("J001", "J008")
             else (f.line, f.col, f.rule))
        if k in seen:
            continue
        seen.add(k)
        unique.append(f)
    return unique


def lint_file(path: str, driver: Optional[bool] = None) -> List[Finding]:
    with open(path, encoding="utf-8") as f:
        src = f.read()
    return lint_source(src, path, driver=driver)


_SKIP_DIRS = {"__pycache__", ".git", "build", "csrc", "node_modules",
              ".claude"}


def lint_paths(paths: Sequence[str]) -> List[Finding]:
    """Lint files and directory trees; returns all findings sorted by
    (path, line)."""
    files: List[str] = []
    for p in paths:
        if os.path.isdir(p):
            for dirpath, dirnames, filenames in os.walk(p):
                dirnames[:] = sorted(d for d in dirnames
                                     if d not in _SKIP_DIRS)
                files += [os.path.join(dirpath, f) for f in sorted(filenames)
                          if f.endswith(".py")]
        elif p.endswith(".py"):
            files.append(p)
        else:
            raise FileNotFoundError(f"not a directory or .py file: {p!r}")
    out: List[Finding] = []
    for f in files:
        out += lint_file(f)
    out.sort(key=lambda x: (x.path, x.line, x.col, x.rule))
    return out
