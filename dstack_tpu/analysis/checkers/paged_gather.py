"""KVB01: no whole-table gathers of the KV block pool in kv_blocks.py.

Ragged paged attention (workloads/paged_attention.py) exists so that
no attention builder gathers every block a slot owns into a dense
`(max_len, KV, hd)` scratch view before attending —
`jnp.take(pool, block_tables, ...)` — a whole-pool data movement per
dispatch. This checker is the regression guard: inside `workloads/kv_blocks.py`, any `jnp.take` /
`jnp.take_along_axis` / `lax.gather` whose *indices* operand is a whole
block table (a bare name or attribute like `block_tables`, `table_row`,
`tables`) is flagged. The allowed ragged idiom indexes a single table
column or a computed expression (`tables[:, j]`, `jnp.clip(pos // bs,
...)`) — those indices are Subscript/Call nodes, not bare table names,
so they pass.
"""

import ast
from typing import Iterable, Optional

from dstack_tpu.analysis.astutil import FUNC_NODES, call_name, outer_functions
from dstack_tpu.analysis.core import Checker, Finding, Module

# The file the ban applies to (real tree and test fixtures).
SCOPE_SUFFIX = "workloads/kv_blocks.py"

GATHER_FNS = {
    "jax.numpy.take",
    "jax.numpy.take_along_axis",
    "jax.lax.gather",
}


def _terminal_identifier(node: ast.AST) -> Optional[str]:
    """The final name of a bare Name/Attribute chain; None for computed
    expressions (Subscript, Call, BinOp...), which are the allowed forms."""
    if isinstance(node, ast.Attribute):
        return node.attr
    if isinstance(node, ast.Name):
        return node.id
    return None


def _indices_arg(call: ast.Call) -> Optional[ast.AST]:
    if len(call.args) >= 2:
        return call.args[1]
    for kw in call.keywords:
        if kw.arg == "indices":
            return kw.value
    return None


class PagedGatherChecker(Checker):
    codes = ("KVB01",)

    def check(self, module: Module) -> Iterable[Finding]:
        if not module.rel.endswith(SCOPE_SUFFIX):
            return
        for qualname, func in outer_functions(module.tree):
            for node in ast.walk(func):
                if not isinstance(node, ast.Call):
                    continue
                name = call_name(node)
                if name is None:
                    continue
                if module.aliases.canonical(name) not in GATHER_FNS:
                    continue
                idx = _indices_arg(node)
                if idx is None:
                    continue
                ident = _terminal_identifier(idx)
                if ident is None or "table" not in ident.lower():
                    continue
                yield Finding(
                    code="KVB01",
                    message=(
                        f"whole-table gather `{name}(..., {ident})` re-creates"
                        " the dense KV view the ragged path deleted — attend"
                        " via paged_attention.ragged_attention or index a"
                        " single table column instead"
                    ),
                    rel=module.rel,
                    line=node.lineno,
                    col=node.col_offset,
                    symbol=qualname,
                    key=f"take:{ident}",
                )
