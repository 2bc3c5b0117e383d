#!/usr/bin/env python
"""Import-layering check for the engine refactor (ADR 0010; ISSUE 10).

The package layering is::

    kernels / data / health / obs / roofline (primitives)
        ^
    core                                    (algorithm pieces, in-core ops)
        ^
    engine                                  (DataPlane protocol + the ONE
        ^                                    driver + the three planes)
    streaming / distributed / core.bwkm     (thin per-engine entry points)
        ^
    api / service / vq / train / launch     (facades and consumers)

Rules enforced here (MODULE-LEVEL imports only — a lazy import inside a
function body is the sanctioned escape hatch for upward references, e.g.
``core.bwkm.fit_incore`` constructing its plane, ``seed_centroids``
resolving the api init registry, the sharded plane's checkpoint hook):

  * ``repro.engine.*`` may import only the primitive layers: ``repro.core``,
    ``repro.kernels``, ``repro.data``, ``repro.distributed.sharding`` (mesh
    topology helpers, not the distributed entry points), ``repro.health``,
    ``repro.obs`` (spans and per-fit counters), ``repro.roofline``, and
    itself. In particular it must NOT import
    ``repro.api`` / ``repro.service`` / ``repro.vq`` / ``repro.streaming`` /
    ``repro.train`` or the ``distributed.dist_*`` entry points — the engines
    sit BELOW every facade.
  * ``repro.core.*`` must not import ``repro.streaming`` /
    ``repro.distributed`` / ``repro.service`` / ``repro.engine`` /
    ``repro.api`` — with the single sanctioned exception of
    ``repro.api.result``, which deliberately imports nothing from ``repro``
    (the baselines return the unified ``FitResult``).

Run: ``python tools/check_layering.py [src-root]`` — exits non-zero and
prints one line per violation. Wired into the CI lint job.
"""

from __future__ import annotations

import ast
import sys
from pathlib import Path

# package prefix -> ("allow", [prefixes]) or ("deny", [prefixes], [exceptions])
RULES: dict[str, tuple] = {
    "repro.engine": (
        "allow",
        [
            "repro.core",
            "repro.kernels",
            "repro.data",
            "repro.distributed.sharding",
            "repro.health",
            "repro.obs",
            "repro.roofline",
            "repro.engine",
        ],
    ),
    "repro.core": (
        "deny",
        [
            "repro.streaming",
            "repro.distributed",
            "repro.service",
            "repro.engine",
            "repro.api",
        ],
        ["repro.api.result"],
    ),
}


def _matches(name: str, prefix: str) -> bool:
    return name == prefix or name.startswith(prefix + ".")


def _module_level_imports(tree: ast.Module):
    """Yield (lineno, imported-module-name) for module-level imports,
    descending into top-level ``if``/``try`` blocks (TYPE_CHECKING guards,
    optional-dependency fallbacks) but never into function/class bodies."""
    stack = list(tree.body)
    while stack:
        node = stack.pop()
        if isinstance(node, ast.Import):
            for alias in node.names:
                yield node.lineno, alias.name
        elif isinstance(node, ast.ImportFrom):
            if node.level:  # relative import: resolve against the package
                continue  # (repo convention is absolute imports; skip)
            base = node.module or ""
            for alias in node.names:
                # `from repro.distributed import sharding` imports the
                # submodule: check the joined name, which the allow rule for
                # repro.distributed.sharding must see.
                yield node.lineno, f"{base}.{alias.name}" if base else alias.name
        elif isinstance(node, (ast.If, ast.Try)):
            stack.extend(ast.iter_child_nodes(node))


def check_module(module: str, tree: ast.Module) -> list[tuple[int, str, str]]:
    """Violations for one module: ``(lineno, imported, rule-description)``."""
    out = []
    for pkg, rule in RULES.items():
        if not _matches(module, pkg):
            continue
        for lineno, name in _module_level_imports(tree):
            if not _matches(name, "repro"):
                continue
            if rule[0] == "allow":
                # `from repro.core import bwkm` yields repro.core.bwkm — a
                # child of an allowed prefix; `import repro` alone is the
                # root and always fine.
                if name == "repro":
                    continue
                if not any(
                    _matches(name, p) or _matches(p, name) for p in rule[1]
                ):
                    out.append(
                        (lineno, name, f"{pkg} may import only {rule[1]}")
                    )
            else:
                _, denied, exceptions = rule
                if any(_matches(name, e) for e in exceptions):
                    continue
                if any(_matches(name, p) for p in denied):
                    out.append(
                        (lineno, name, f"{pkg} must not import {denied}")
                    )
    return out


def check_tree(src_root: Path) -> list[str]:
    """All violations under ``src_root`` (the directory containing repro/)."""
    violations = []
    for py in sorted((src_root / "repro").rglob("*.py")):
        rel = py.relative_to(src_root)
        module = ".".join(rel.with_suffix("").parts)
        if module.endswith(".__init__"):
            module = module[: -len(".__init__")]
        tree = ast.parse(py.read_text(), filename=str(py))
        for lineno, name, why in check_module(module, tree):
            violations.append(f"{rel}:{lineno}: imports {name} — {why}")
    return violations


def main(argv: list[str]) -> int:
    src_root = Path(argv[1]) if len(argv) > 1 else Path(__file__).parent.parent / "src"
    violations = check_tree(src_root)
    for v in violations:
        print(v, file=sys.stderr)
    if violations:
        print(f"{len(violations)} layering violation(s)", file=sys.stderr)
        return 1
    print("layering OK")
    return 0


if __name__ == "__main__":
    raise SystemExit(main(sys.argv))
