"""RL001 — cross-device collective reachable inside a differentiated
function (the PR 2 double-psum gradient-scaling class).

Under ``shard_map(..., check_vma=False)`` the transpose of
``jax.lax.psum`` is *another* ``psum``: a collective inside the function
handed to ``jax.grad``/``jax.value_and_grad`` silently scales every
gradient by the axis size.  Adam's scale-invariance masks the bug from
loss curves — it shipped here once (fixed in PR 2 for
``core/propagation.py`` and ``distributed/pipeline.py``) and recurred in
``core/parallel.py``'s P3 step until this rule surfaced it.

The fixed idiom: compute the *local* loss inside ``loss_fn``, psum loss
/ count / gradients **outside** the differentiated function.  Legitimate
forward-pass sharding primitives (``psum_scatter`` whose transpose is an
exact ``all_gather``) carry justified suppressions at the call site.
"""
from __future__ import annotations

import ast
from typing import Iterable, List

from repro.analysis import astutil
from repro.analysis.engine import Finding, ModuleContext, Rule

GRAD_QUALNAMES = {"jax.grad", "jax.value_and_grad"}
GRAD_BARE = {"grad", "value_and_grad"}
COLLECTIVE_QUALNAMES = {
    "jax.lax.psum", "lax.psum",
    "jax.lax.psum_scatter", "lax.psum_scatter",
}
COLLECTIVE_BARE = {"psum", "psum_scatter"}
PARTIAL_QUALNAMES = {"functools.partial", "partial"}


class PsumInGradRule(Rule):
    """Flag ``jax.lax.psum``/``psum_scatter`` reachable (within the
    module) from any function passed to ``jax.grad``/``value_and_grad``."""

    rule_id = "RL001"
    name = "psum-in-grad"

    def check_module(self, ctx: ModuleContext) -> Iterable[Finding]:
        tree = ctx.tree
        grad_aliases = astutil.imported_aliases(tree, ("jax",), GRAD_BARE)
        coll_aliases = astutil.imported_aliases(
            tree, ("jax.lax", "lax"), COLLECTIVE_BARE)
        index = astutil.FunctionIndex(tree)

        def is_collective(call: ast.Call) -> bool:
            qn = astutil.call_name(call)
            return qn is not None and (qn in COLLECTIVE_QUALNAMES
                                       or qn in coll_aliases)

        findings: List[Finding] = []
        reported = set()
        for node in ast.walk(tree):
            if not isinstance(node, ast.Call):
                continue
            qn = astutil.call_name(node)
            if qn is None or (qn not in GRAD_QUALNAMES
                              and qn not in grad_aliases):
                continue
            if not node.args:
                continue
            for entry in _resolve_entries(node.args[0], index):
                label = getattr(entry, "name", "<lambda>")
                for call, via in index.reachable_calls(entry,
                                                       is_collective):
                    key = (call.lineno, call.col_offset)
                    if key in reported:
                        continue
                    reported.add(key)
                    cn = astutil.call_name(call)
                    findings.append(Finding(
                        self.rule_id, ctx.path, call.lineno,
                        f"`{cn}` is reachable (via `{via}`) from "
                        f"`{label}`, which is differentiated at line "
                        f"{node.lineno}: under shard_map "
                        f"check_vma=False the transpose inserts a "
                        f"second collective, scaling gradients by the "
                        f"axis size (PR 2 double-psum class) — move "
                        f"the collective outside the differentiated "
                        f"function, or suppress with justification if "
                        f"it is a forward-pass sharding primitive"))
        return findings


def _resolve_entries(arg: ast.AST,
                     index: astutil.FunctionIndex) -> List[ast.AST]:
    """Function bodies a grad-call argument can denote: a lambda, a
    same-module def, or ``functools.partial`` of either."""
    if isinstance(arg, ast.Lambda):
        return [arg]
    if isinstance(arg, ast.Name):
        return index.resolve(arg.id)
    if isinstance(arg, ast.Call):
        qn = astutil.call_name(arg)
        if qn in PARTIAL_QUALNAMES and arg.args:
            return _resolve_entries(arg.args[0], index)
    return []
