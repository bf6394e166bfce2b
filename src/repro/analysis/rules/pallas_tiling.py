"""RL004 — Pallas TPU tile-shape hygiene for ``pl.BlockSpec`` /
``pltpu.VMEM`` literals.

TPU vector memory is tiled (8, 128) for float32: the LAST dimension of a
block maps to the 128-wide lane axis and the second-to-last to the
8-deep sublane axis.  A block whose trailing dims ignore that geometry
silently burns VMEM and MXU occupancy on padding — the repo's kernels
size tiles through ``LANE``/``SUBLANE``-aligned helpers
(``kernels/segment_sum.py:_pick_bf``) and assert a working-set budget
(``VMEM_BUDGET``, checked at trace time by ``_assert_vmem``).  This rule
is the *static* half of those dynamic asserts: it folds int literals,
module constants, and un-reassigned parameter defaults, and checks

* last dim: multiple of 128, or an 8-aligned sliver below 128 (the
  ``_pick_bf`` narrow-feature rule); a last dim of 1 pads to a full
  lane-tile (127/128 waste) and is flagged — EXCEPT the codified
  per-row column idiom: a 2-D ``(rows, 1)`` block or scratch with
  sublane-aligned rows, where one scalar per row is inherent.  Two
  uses: the online-softmax running max/denominator scratch
  (``kernels/flash_attention.py``, ``kernels/gat_fused.py``), and the
  per-edge ids, coefficients and masks the aggregation kernels read
  beside a ``(rows, F)`` block (``kernels/segment_sum.py``).  The TPU
  compiler refuses a 1-D ``(rows,)`` block of a 1-D array (XLA tiles it
  by 1024, Mosaic by 128), so the column is the layout it accepts;
* second-to-last dim: multiple of 8 (or 1 for broadcast/leading axes);
* fully-resolved ``pltpu.VMEM`` scratch shapes: byte size within the
  module's ``VMEM_BUDGET`` (default 8 MiB).

Unresolvable dimensions are skipped, never guessed — runtime-computed
tiles stay covered by the in-kernel ``_assert_vmem`` asserts.
"""
from __future__ import annotations

import ast
from typing import Dict, Iterable, List, Optional

from repro.analysis import astutil
from repro.analysis.engine import Finding, ModuleContext, Rule

LANE = 128
SUBLANE = 8
DEFAULT_VMEM_BUDGET = 8 * 2**20

BLOCKSPEC_QUALNAMES = {"pl.BlockSpec", "pallas.BlockSpec", "BlockSpec"}
VMEM_QUALNAMES = {"pltpu.VMEM", "tpu.VMEM", "VMEM"}

#: dtype qualname suffix -> bytes per element (default 4 / float32)
DTYPE_BYTES = {"bfloat16": 2, "float16": 2, "int8": 1, "uint8": 1,
               "float32": 4, "int32": 4, "uint32": 4}


class PallasTilingRule(Rule):
    """Statically check Pallas block/scratch shape literals for TPU
    lane/sublane alignment and the modeled VMEM budget."""

    rule_id = "RL004"
    name = "pallas-tiling"

    def check_module(self, ctx: ModuleContext) -> Iterable[Finding]:
        tree = ctx.tree
        # only modules that actually build Pallas calls pay the walk
        if "BlockSpec" not in ctx.source and "VMEM" not in ctx.source:
            return []
        module_env = astutil.module_int_constants(tree)
        budget = module_env.get("VMEM_BUDGET", DEFAULT_VMEM_BUDGET)
        findings: List[Finding] = []

        for fn in [tree] + [n for n in ast.walk(tree)
                            if isinstance(n, astutil.FunctionNode)]:
            env = dict(module_env)
            if isinstance(fn, astutil.FunctionNode):
                env.update(_param_defaults(fn, module_env))
            for node in ast.walk(fn):
                if not isinstance(node, ast.Call) or not node.args:
                    continue
                qn = astutil.call_name(node)
                if qn in BLOCKSPEC_QUALNAMES:
                    findings.extend(self._check_shape(
                        ctx, node, env, kind="BlockSpec"))
                elif qn in VMEM_QUALNAMES:
                    findings.extend(self._check_shape(
                        ctx, node, env, kind="VMEM", budget=budget))
        return _dedupe(findings)

    def _check_shape(self, ctx: ModuleContext, call: ast.Call,
                     env: Dict[str, int], *, kind: str,
                     budget: Optional[int] = None) -> List[Finding]:
        shape = call.args[0]
        if not isinstance(shape, ast.Tuple) or not shape.elts:
            return []
        dims = [astutil.const_int(e, env) for e in shape.elts]
        out: List[Finding] = []
        last = dims[-1]
        if last is not None:
            if last == 1 and len(dims) > 1:
                # codified exception: a 2-D (rows, 1) column with
                # sublane-aligned rows — one scalar per row (per-edge
                # ids/coefficients/masks, online-softmax running
                # max/denominator).  Misaligned rows and higher-rank
                # blocks with a trailing 1 stay flagged; unresolvable
                # rows are skipped, never guessed (the in-kernel
                # _assert_vmem covers runtime-computed tiles)
                sub0 = dims[-2]
                column = (len(dims) == 2
                          and (sub0 is None or sub0 % SUBLANE == 0))
                if not column:
                    out.append(Finding(
                        self.rule_id, ctx.path, call.lineno,
                        f"{kind} last dim is 1: the lane axis pads to a "
                        f"full {LANE}-wide tile ({LANE - 1}/{LANE} of "
                        f"the block wasted) — widen the tile; the only "
                        f"codified exception is a 2-D per-row column "
                        f"(rows, 1) with {SUBLANE}-aligned rows (edge "
                        f"ids/coefficients, online-softmax state)"))
            elif last > 1 and last % LANE != 0 and not (
                    last < LANE and last % SUBLANE == 0):
                out.append(Finding(
                    self.rule_id, ctx.path, call.lineno,
                    f"{kind} last dim {last} is not {LANE}-lane aligned "
                    f"(nor an {SUBLANE}-aligned sliver below {LANE}): "
                    f"the tile pads to the next lane multiple — size it "
                    f"like kernels/segment_sum.py:_pick_bf"))
        if len(dims) >= 2:
            sub = dims[-2]
            if sub is not None and sub > 1 and sub % SUBLANE != 0:
                out.append(Finding(
                    self.rule_id, ctx.path, call.lineno,
                    f"{kind} second-to-last dim {sub} is not "
                    f"{SUBLANE}-sublane aligned: the tile pads to the "
                    f"next sublane multiple in VMEM"))
        if (kind == "VMEM" and budget is not None
                and all(d is not None for d in dims)):
            width = _dtype_bytes(call)
            nbytes = width
            for d in dims:
                nbytes *= d                          # type: ignore[operator]
            if nbytes > budget:
                out.append(Finding(
                    self.rule_id, ctx.path, call.lineno,
                    f"VMEM scratch {tuple(dims)} is "
                    f"{nbytes / 2**20:.1f} MiB — exceeds the "
                    f"{budget / 2**20:.0f} MiB working-set budget "
                    f"(VMEM_BUDGET); shrink the tile or shard the "
                    f"resident dimension"))
        return out


def _param_defaults(fn: ast.AST, env: Dict[str, int]) -> Dict[str, int]:
    """Int defaults of ``fn``'s parameters, dropped for any parameter the
    body reassigns (``bq = min(bq, Sq)`` invalidates the default)."""
    reassigned = astutil.assigned_names(fn)
    out: Dict[str, int] = {}
    args = fn.args
    pos = args.posonlyargs + args.args
    for arg, default in zip(pos[len(pos) - len(args.defaults):],
                            args.defaults):
        v = astutil.const_int(default, env)
        if v is not None and arg.arg not in reassigned:
            out[arg.arg] = v
    for arg, default in zip(args.kwonlyargs, args.kw_defaults):
        if default is None:
            continue
        v = astutil.const_int(default, env)
        if v is not None and arg.arg not in reassigned:
            out[arg.arg] = v
    return out


def _dtype_bytes(call: ast.Call) -> int:
    if len(call.args) >= 2:
        qn = astutil.qualname(call.args[1]) or ""
        for suffix, width in DTYPE_BYTES.items():
            if qn.endswith(suffix):
                return width
    return 4


def _dedupe(findings: List[Finding]) -> List[Finding]:
    seen = set()
    out = []
    for f in findings:
        key = (f.line, f.message)
        if key not in seen:
            seen.add(key)
            out.append(f)
    return out
