"""jax-api: every ``jax.*`` attribute chain must resolve against the
installed jax.

The defect class this rule exists for shipped in the seed:
``hydragnn_tpu/parallel/graphshard.py`` called a top-level ``jax.*``
name the then-installed jax kept under ``jax.experimental`` — breaking
every graph-sharding test and the giant-graph examples until the first
run hit the AttributeError. jax moves APIs between minor releases
constantly (``jax.ops``, ``jax.tree_util``, experimental promotions
and removals such as ``jax.experimental.enable_x64``), so chains are
resolved against the interpreter's actual jax at lint time, not a
vendored stub.

Mechanics: for each module, import aliases rooted at jax are tracked
(``import jax.numpy as jnp``, ``from jax import lax``, ``from
jax.sharding import PartitionSpec as P``, ...); every Load-context
attribute chain whose base resolves into jax is then checked attribute
by attribute, importing not-yet-imported submodules along the way
(``jax.experimental.checkify`` is a real module even though
``jax.experimental`` does not re-export it). From-import statements of
jax modules are checked the same way. ``getattr(jax, "name", ...)``
probes are invisible to this rule by construction.

When a top-level attribute is missing, the rule probes
``jax.experimental.<name>`` and suggests the relocation if it exists
(``jax.checkify`` -> ``jax.experimental.checkify.checkify``).
"""

from __future__ import annotations

import ast
import importlib
import types
from typing import Dict, Iterable, List, Optional, Tuple

from hydragnn_tpu.analysis.engine import Finding, LintContext, Rule

# dotted chain -> None (resolves) | error message
_RESOLVE_CACHE: Dict[str, Optional[str]] = {}


def installed_jax_version() -> str:
    """For CLI/report headers — never embedded in finding messages
    (fingerprints must survive jax upgrades)."""
    try:
        import jax

        return jax.__version__
    except Exception:  # pragma: no cover - jax is a hard dep here
        return "unknown"


def _import_maybe(name: str):
    try:
        return importlib.import_module(name)
    except Exception:
        return None


def resolve_chain(dotted: str) -> Optional[str]:
    """None when the chain resolves; otherwise the missing prefix plus
    an optional relocation suggestion."""
    if dotted in _RESOLVE_CACHE:
        return _RESOLVE_CACHE[dotted]
    parts = dotted.split(".")
    obj = None
    consumed = 0
    for i in range(len(parts), 0, -1):
        obj = _import_maybe(".".join(parts[:i]))
        if obj is not None:
            consumed = i
            break
    err: Optional[str] = None
    if obj is None:
        err = f"`{parts[0]}` is not importable"
    else:
        for j in range(consumed, len(parts)):
            attr = parts[j]
            nxt = getattr(obj, attr, _MISSING)
            if nxt is _MISSING and isinstance(obj, types.ModuleType):
                nxt = _import_maybe(f"{obj.__name__}.{attr}")
                if nxt is None:
                    nxt = _MISSING
            if nxt is _MISSING:
                missing = ".".join(parts[: j + 1])
                # NOTE: no version string here — the message feeds the
                # baseline fingerprint, which must survive jax upgrades
                err = f"`{missing}` does not exist in the installed jax"
                hint = _relocation_hint(parts[:j], attr)
                if hint:
                    err += f" (did it move? {hint} resolves)"
                break
            obj = nxt
    _RESOLVE_CACHE[dotted] = err
    return err


_MISSING = object()


def _relocation_hint(prefix: List[str], attr: str) -> Optional[str]:
    """Probe the common jax relocation target: an experimental submodule
    exporting an attribute of its own name (checkify, jet, ...)."""
    if prefix != ["jax"]:
        return None
    mod = _import_maybe(f"jax.experimental.{attr}")
    if mod is not None and hasattr(mod, attr):
        return f"jax.experimental.{attr}.{attr}"
    return None


def _attr_chain(node: ast.Attribute) -> Optional[Tuple[str, List[str]]]:
    """(base_name, [attr, ...]) for a pure Name.attr.attr... chain."""
    attrs: List[str] = []
    cur: ast.AST = node
    while isinstance(cur, ast.Attribute):
        attrs.append(cur.attr)
        cur = cur.value
    if isinstance(cur, ast.Name):
        return cur.id, list(reversed(attrs))
    return None


class JaxApiRule(Rule):
    name = "jax-api"
    description = (
        "jax.* attribute chains and from-imports must resolve against "
        "the installed jax"
    )

    def run(self, ctx: LintContext) -> Iterable[Finding]:
        for sf in ctx.py_files:
            if sf.tree is None:
                continue
            yield from self._check_module(sf)

    def _check_module(self, sf) -> Iterable[Finding]:
        aliases: Dict[str, str] = {}  # local name -> jax-rooted dotted path
        reported = set()  # (line, message) dedupe for nested chains

        def report(line: int, err: str):
            if (line, err) not in reported:
                reported.add((line, err))
                yield Finding(self.name, sf.relpath, line, err)

        # pass 1: aliases + import-site checks
        for node in ast.walk(sf.tree):
            if isinstance(node, ast.Import):
                for a in node.names:
                    if a.name == "jax" or a.name.startswith("jax."):
                        local = a.asname or a.name.split(".")[0]
                        aliases[local] = a.name if a.asname else "jax"
                        err = resolve_chain(a.name)
                        if err:
                            yield from report(node.lineno, err)
            elif isinstance(node, ast.ImportFrom) and node.module:
                if node.level or not (
                    node.module == "jax" or node.module.startswith("jax.")
                ):
                    continue
                for a in node.names:
                    if a.name == "*":
                        continue
                    dotted = f"{node.module}.{a.name}"
                    err = resolve_chain(dotted)
                    if err:
                        yield from report(node.lineno, err)
                    else:
                        aliases[a.asname or a.name] = dotted

        if not aliases:
            return

        # pass 2: attribute chains rooted at a jax alias
        for node in ast.walk(sf.tree):
            if not isinstance(node, ast.Attribute):
                continue
            if not isinstance(node.ctx, ast.Load):
                continue  # setting/deleting attrs is not an API read
            chain = _attr_chain(node)
            if chain is None:
                continue
            base, attrs = chain
            root = aliases.get(base)
            if root is None:
                continue
            err = resolve_chain(".".join([root] + attrs))
            if err:
                yield from report(node.lineno, err)
