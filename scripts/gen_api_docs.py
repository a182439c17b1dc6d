#!/usr/bin/env python
"""Generate the per-symbol API reference into doc/api/ (SURVEY.md §2d's
Doxygen role, stdlib-only).

Walks every module under ``dmlc_core_tpu``, emits one markdown file per
module (module docstring, then each public symbol's signature +
docstring; classes include their public methods), plus an index.

CI contract (wired into scripts/ci.sh): any symbol exported via a
module's ``__all__`` that lacks a docstring FAILS the run — the API
surface a module declares is the surface it must document.  Symbols
that are merely public-by-convention are documented when possible but
not enforced.

Usage:
    python scripts/gen_api_docs.py          # write doc/api/, enforce
    python scripts/gen_api_docs.py --check  # enforce only, write nothing
"""

import importlib
import inspect
import os
import pkgutil
import re
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from dmlc_core_tpu.utils import force_cpu_devices  # noqa: E402

force_cpu_devices(1)   # never let a doc build touch (or hang on) real TPUs

import dmlc_core_tpu  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
OUT_DIR = os.path.join(ROOT, "doc", "api")
KNOB_DOC = os.path.join(ROOT, "doc", "configuration.md")

#: display order + headings for the knob-table groups
_KNOB_GROUPS = [
    ("runtime", "Runtime & debugging"),
    ("observability", "Observability"),
    ("gbt", "GBT engines"),
    ("compile-cache", "Compile cache"),
    ("io", "IO"),
    ("resilience", "Resilience & fault injection"),
    ("serve", "Serving"),
    ("fleet", "Fleet serving"),
    ("tenancy", "Multi-tenant serving"),
    ("stream", "Streaming / online learning"),
    ("distributed", "Distributed launch ABI"),
    ("launch", "Multi-host launch"),
    ("ps", "Parameter server"),
    ("misc", "Miscellaneous"),
]


def _fmt_default(v) -> str:
    if v == "":
        return "*(unset)*"
    return f"`{v!r}`" if isinstance(v, str) else f"`{v}`"


def render_knob_doc() -> str:
    """doc/configuration.md from base/knobs.py — the generated-doc side
    of the knob contract (dmlcheck's knob-registry/knob-doc passes are
    the enforcement side; see doc/static_analysis.md)."""
    from dmlc_core_tpu.base import knobs

    out = [
        "# Configuration: the `DMLC_*` environment knobs",
        "",
        "**Generated by `scripts/gen_api_docs.py` from "
        "`dmlc_core_tpu/base/knobs.py` — do not edit by hand.**  CI "
        "regenerates this page and fails when the committed copy is "
        "stale, and `scripts/dmlcheck.py` fails on any `DMLC_*` literal "
        "in code that is missing from the registry (see "
        "[static analysis](static_analysis.md)).",
        "",
        "The *default* column is what the reading call site applies "
        "when the variable is unset; boolean-ish knobs accept "
        "`0`/`1`.",
        "",
        "A model's hyperparameters are not knobs: they are fields of "
        "its `Parameter` (`HistGBTParam`: `max_depth`, `learning_rate`, "
        "`min_child_weight`, ... and, since PR 56, the growth policy "
        "`grow_policy` = `depthwise` | `lossguide` and the leaf budget "
        "`max_leaves`, where `max_depth=0` under `lossguide` means no "
        "depth cap; since PR 58 `feature_types`, a list of `\"q\"` / "
        "`\"c\"`, `max_cat_to_onehot` and `max_cat_threshold` for "
        "categorical columns), given to the constructor, saved with the model "
        "and listed in the [API pages](api/README.md).",
    ]
    by_group = {}
    for k in knobs.all_knobs():
        by_group.setdefault(k.group, []).append(k)
    for group, title in _KNOB_GROUPS:
        entries = by_group.pop(group, [])
        if not entries:
            continue
        out += ["", f"## {title}", "",
                "| knob | default | meaning |", "|---|---|---|"]
        for k in entries:
            out.append(f"| `{k.name}` | {_fmt_default(k.default)} "
                       f"| {k.doc} |")
    if by_group:
        raise SystemExit(f"knob groups missing from _KNOB_GROUPS: "
                         f"{sorted(by_group)}")
    out += ["", f"*{len(knobs.names())} knobs declared.*", ""]
    return "\n".join(out)


def _iter_modules():
    yield "dmlc_core_tpu", dmlc_core_tpu
    prefix = dmlc_core_tpu.__name__ + "."
    for info in pkgutil.walk_packages(dmlc_core_tpu.__path__, prefix):
        leaf = info.name.rsplit(".", 1)[-1]
        if leaf.startswith("_"):
            continue
        yield info.name, importlib.import_module(info.name)


def _public_symbols(mod):
    """(name, obj, enforced) for the module's documented surface."""
    declared = getattr(mod, "__all__", None)
    if declared is not None:
        for name in declared:
            yield name, getattr(mod, name), True
        return
    for name, obj in sorted(vars(mod).items()):
        if name.startswith("_"):
            continue
        if not (inspect.isclass(obj) or inspect.isfunction(obj)):
            continue
        if getattr(obj, "__module__", None) != mod.__name__:
            continue   # re-exports are documented where they are defined
        yield name, obj, False


def _signature(obj):
    try:
        sig = str(inspect.signature(obj))
    except (ValueError, TypeError):
        return "(...)"
    # default-value reprs of library sentinels embed process-specific
    # memory addresses (e.g. flax's `_Sentinel object at 0x7f...`) —
    # strip them or the staleness gate flaps on every run
    return re.sub(r" at 0x[0-9a-fA-F]+", "", sig)


def _doc_block(obj):
    doc = inspect.getdoc(obj)
    return doc.strip() if doc else ""


def _render_symbol(name, obj, out, missing, enforced, qualifier=""):
    title = f"{qualifier}{name}"
    doc = _doc_block(obj)
    if inspect.isclass(obj):
        out.append(f"### class `{title}{_signature(obj)}`\n")
        if doc:
            out.append(doc + "\n")
        elif enforced:
            missing.append(title)
        for mname, mobj in sorted(vars(obj).items()):
            if mname.startswith("_") and mname != "__init__":
                continue
            if not (inspect.isfunction(mobj)
                    or isinstance(mobj, (classmethod, staticmethod,
                                         property))):
                continue
            raw = mobj
            if isinstance(mobj, (classmethod, staticmethod)):
                raw = mobj.__func__
            if isinstance(mobj, property):
                mdoc = _doc_block(mobj)
                out.append(f"- **`{mname}`** *(property)* — "
                           f"{mdoc.splitlines()[0] if mdoc else ''}\n")
                continue
            mdoc = _doc_block(raw)
            first = mdoc.splitlines()[0] if mdoc else ""
            out.append(f"- **`{mname}{_signature(raw)}`** — {first}\n")
    elif inspect.isfunction(obj) or inspect.isbuiltin(obj):
        out.append(f"### `{title}{_signature(obj)}`\n")
        if doc:
            out.append(doc + "\n")
        elif enforced:
            missing.append(title)
    else:
        out.append(f"### `{title}`\n")
        if doc and doc != _doc_block(type(obj)):
            out.append(doc + "\n")
        out.append(f"*constant of type `{type(obj).__name__}`*\n")


def main() -> int:
    check_only = "--check" in sys.argv
    missing = []
    index = []
    pages = {}
    for modname, mod in sorted(_iter_modules()):
        out = [f"# `{modname}`\n"]
        mdoc = _doc_block(mod)
        if mdoc:
            out.append(mdoc + "\n")
        n_syms = 0
        for name, obj, enforced in _public_symbols(mod):
            _render_symbol(name, obj, out, missing, enforced,
                           qualifier=f"{modname}.")
            n_syms += 1
        if n_syms == 0 and not mdoc:
            continue
        fname = modname.replace(".", "_") + ".md"
        pages[fname] = "\n".join(out) + "\n"
        first = mdoc.splitlines()[0] if mdoc else ""
        index.append(f"- [`{modname}`]({fname}) — {first}")

    if not check_only:
        os.makedirs(OUT_DIR, exist_ok=True)
        for old in os.listdir(OUT_DIR):
            if old.endswith(".md"):
                os.remove(os.path.join(OUT_DIR, old))
        for fname, text in pages.items():
            with open(os.path.join(OUT_DIR, fname), "w") as f:
                f.write(text)
        with open(os.path.join(OUT_DIR, "README.md"), "w") as f:
            f.write("# API reference\n\nGenerated by "
                    "`scripts/gen_api_docs.py` (run it after changing any "
                    "public surface; CI regenerates and fails on "
                    "undocumented `__all__` exports).\n\n"
                    + "\n".join(index) + "\n")
        print(f"gen_api_docs: wrote {len(pages)} module pages to doc/api/")
        with open(KNOB_DOC, "w") as f:
            f.write(render_knob_doc())
        print("gen_api_docs: wrote doc/configuration.md "
              "(knob table from base/knobs.py)")

    if missing:
        print("gen_api_docs: MISSING DOCSTRINGS on __all__ exports:",
              file=sys.stderr)
        for m in missing:
            print(f"  - {m}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
