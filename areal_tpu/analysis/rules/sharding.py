"""sharding: PartitionSpec axes must exist.

A ``PartitionSpec`` axis-name typo never fails on a single device and
only explodes (or silently replicates, which is worse) on a real mesh —
the configuration the CPU test cluster covers least.

Every string axis in a ``PartitionSpec(...)``/``P(...)`` call must be a
mesh axis declared somewhere in the linted fileset (``Mesh(devs,
(...))`` positionals, ``axis_names=(...)`` kwargs, ``*_AXIS = "name"``
constants, and ``AXIS_ORDER`` tuples) -> error on an unknown axis.
When the fileset declares no axes at all the check is skipped (a lone
snippet can't be validated).
"""

import ast
from typing import Iterable, Set

from areal_tpu.analysis.core import (
    FileContext,
    Finding,
    ProjectContext,
    Rule,
    Severity,
)
from areal_tpu.analysis.rules._util import call_name, string_constants


def _collect_mesh_axes(tree: ast.AST) -> Set[str]:
    axes: Set[str] = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Call):
            name = (call_name(node) or "").split(".")[-1]
            if name == "Mesh" and len(node.args) >= 2:
                axes.update(c.value for c in string_constants(node.args[1]))
            for kw in node.keywords:
                if kw.arg == "axis_names":
                    axes.update(
                        c.value for c in string_constants(kw.value)
                    )
        elif isinstance(node, ast.Assign) and len(node.targets) == 1:
            t = node.targets[0]
            if isinstance(t, ast.Name) and (
                t.id.endswith("_AXIS") or t.id in ("AXIS_ORDER", "AXIS_NAMES")
            ):
                axes.update(c.value for c in string_constants(node.value))
        elif isinstance(node, ast.AnnAssign) and node.value is not None:
            t = node.target
            if isinstance(t, ast.Name) and (
                t.id.endswith("_AXIS") or t.id in ("AXIS_ORDER", "AXIS_NAMES")
            ):
                axes.update(c.value for c in string_constants(node.value))
    return axes


def _spec_aliases(tree: ast.AST) -> Set[str]:
    """Local names PartitionSpec is importable under (default included)."""
    names = {"PartitionSpec"}
    for node in ast.walk(tree):
        if isinstance(node, ast.ImportFrom) and node.module and (
            node.module.startswith("jax.sharding")
            or node.module.startswith("jax.interpreters.pxla")
        ):
            for alias in node.names:
                if alias.name == "PartitionSpec":
                    names.add(alias.asname or alias.name)
    return names


class ShardingRule(Rule):
    name = "sharding"

    def prepare(self, project: ProjectContext) -> None:
        project.mesh_axes = set()
        for ctx in project.files:
            project.mesh_axes |= _collect_mesh_axes(ctx.tree)

    def check(self, ctx: FileContext) -> Iterable[Finding]:
        axes = ctx.project.mesh_axes
        aliases = _spec_aliases(ctx.tree)
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Call):
                continue
            name = call_name(node) or ""
            short = name.split(".")[-1]
            if axes and (name in aliases or short == "PartitionSpec"):
                for arg in node.args:
                    for const in string_constants(arg):
                        if const.value not in axes:
                            yield Finding(
                                "sharding", Severity.ERROR, ctx.path,
                                const.lineno, const.col_offset,
                                f"PartitionSpec axis '{const.value}' is "
                                "not a declared mesh axis (known: "
                                f"{', '.join(sorted(axes))}); on a real "
                                "mesh this fails or silently replicates",
                            )
