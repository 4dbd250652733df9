"""Generate the per-module API reference (docs/api/*.md).

The reference ships a Sphinx site (``/root/reference/docs/source/*.rst`` —
amp, optimizers, parallel, layernorm, fp16_utils pages built from
docstrings); this repo's equivalent is a docstring-driven markdown tree so
the docs never drift from the code: every public module under ``apex_tpu``
gets one page listing its public classes/functions with signatures and
docstrings (which already carry the reference file:line citations).

Run: ``python docs/generate_api.py`` (writes docs/api/, about four
seconds). The pages are built, not committed (``.gitignore``), so they
cannot fall behind the code.
"""

from __future__ import annotations

import importlib
import inspect
import os
import pkgutil
import re
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
OUT = os.path.join(ROOT, "docs", "api")

# pages grouped to mirror the reference's Sphinx toctree (amp, optimizers,
# parallel, layernorm/normalization, fp16_utils) plus the TPU-native
# additions the reference has no page for
REF_PAGE = {
    "apex_tpu.amp": "amp.rst",
    "apex_tpu.fp16_utils": "fp16_utils.rst",
    "apex_tpu.optimizers": "optimizers.rst",
    "apex_tpu.normalization": "layernorm.rst",
    "apex_tpu.parallel": "parallel.rst",
}


def public_symbols(mod):
    names = getattr(mod, "__all__", None)
    if names is None:
        names = [n for n, obj in vars(mod).items()
                 if not n.startswith("_")
                 and getattr(obj, "__module__", None) == mod.__name__
                 and (inspect.isclass(obj) or inspect.isfunction(obj))]
    out = []
    for n in names:
        try:
            obj = getattr(mod, n, None)
        except Exception:  # lazy __getattr__ may raise ImportError, which
            continue       # getattr's default does not suppress
        if obj is not None and (inspect.isclass(obj)
                                or inspect.isfunction(obj)):
            out.append((n, obj))
    return out


def _mask_addresses(text: str) -> str:
    # object-repr defaults (flax _Sentinel, bound functions) stringify
    # with the process's heap address — mask it or every regeneration
    # dirties unrelated pages and buries real API changes in churn
    return re.sub(r" at 0x[0-9a-fA-F]+", " at 0x...", text)


def signature_of(obj) -> str:
    try:
        sig = str(inspect.signature(obj))
    except (ValueError, TypeError):
        return "(...)"
    return _mask_addresses(sig)


def render_module(modname: str) -> str | None:
    try:
        mod = importlib.import_module(modname)
    except Exception:  # unimportable here (e.g. newer-jax-only module on a
        return None    # stock-jax box) — keep the existing page instead
    syms = public_symbols(mod)
    doc = inspect.getdoc(mod) or ""
    if not syms and not doc:
        return None
    lines = [f"# `{modname}`", ""]
    if modname in REF_PAGE:
        lines += [f"*Reference Sphinx page: `docs/source/{REF_PAGE[modname]}`*",
                  ""]
    if doc:
        lines += [doc, ""]
    for name, obj in syms:
        kind = "class" if inspect.isclass(obj) else "def"
        lines += [f"## `{kind} {name}{signature_of(obj)}`", ""]
        odoc = inspect.getdoc(obj)
        if odoc:
            lines += [_mask_addresses(odoc), ""]
        if inspect.isclass(obj):
            for mname, meth in sorted(vars(obj).items()):
                if mname.startswith("_") and mname != "__call__":
                    continue
                if not (inspect.isfunction(meth)
                        or isinstance(meth, (classmethod, staticmethod))):
                    continue
                fn = meth.__func__ if isinstance(
                    meth, (classmethod, staticmethod)) else meth
                lines += [f"### `{name}.{mname}{signature_of(fn)}`", ""]
                mdoc = inspect.getdoc(fn)
                if mdoc:
                    lines += [_mask_addresses(mdoc), ""]
    return "\n".join(lines) + "\n"


def _first_prose_line(text: str) -> str:
    for line in text.splitlines():
        if line and not line.startswith("#") and not line.startswith("*"):
            return line.strip()
    return ""


def _module_exists(modname: str) -> bool:
    """Whether the module's source file exists, WITHOUT importing it (an
    import may fail here precisely for the modules whose pages we keep).
    Used to drop pages of renamed/deleted modules."""
    rel = os.path.join(ROOT, *modname.split("."))
    return (os.path.isfile(rel + ".py")
            or os.path.isfile(os.path.join(rel, "__init__.py")))


def main() -> None:
    """Regenerate every page this interpreter can import; pages for modules
    that fail to import here (e.g. mesh modules needing a newer jax than a
    doc-building box carries) are left as previously generated, so a
    degraded environment can still ADD pages without destroying the rest;
    pages whose module source no longer exists (rename/delete) are removed.
    The index is rebuilt from every page present."""
    os.makedirs(OUT, exist_ok=True)
    for f in os.listdir(OUT):
        if (f.endswith(".md") and f != "index.md"
                and not _module_exists(f[:-3])):
            os.remove(os.path.join(OUT, f))
    import apex_tpu

    modules = ["apex_tpu"]
    for info in pkgutil.walk_packages(apex_tpu.__path__, "apex_tpu."):
        base = info.name.rsplit(".", 1)[-1]
        if base.startswith("_") and base != "__init__":
            continue
        modules.append(info.name)

    rendered = 0
    for modname in sorted(set(modules)):
        text = render_module(modname)
        if text is None:
            continue
        with open(os.path.join(OUT, f"{modname}.md"), "w") as f:
            f.write(text)
        rendered += 1

    index = ["# apex_tpu API reference", "",
             "Generated by `docs/generate_api.py` from the live docstrings "
             "(every entry cites its reference counterpart file:line where "
             "one exists). Reference Sphinx pages map as:", ""]
    for mod, page in REF_PAGE.items():
        index.append(f"- `{page}` → [`{mod}`]({mod}.md)")
    index += ["", "## Modules", ""]
    pages = sorted(f for f in os.listdir(OUT)
                   if f.endswith(".md") and f != "index.md")
    for page in pages:
        modname = page[:-3]
        with open(os.path.join(OUT, page)) as f:
            first = _first_prose_line(f.read())
        index.append(f"- [`{modname}`]({modname}.md) — {first}")
    with open(os.path.join(OUT, "index.md"), "w") as f:
        f.write("\n".join(index) + "\n")
    print(f"re-rendered {rendered} pages; indexed {len(pages)} in {OUT}")


if __name__ == "__main__":
    main()
