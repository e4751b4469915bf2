"""JLT010 — Pallas kernel invariants.

The histogram megakernel (``ops/histogram.py:_hist_kernel_body``) and
every future Pallas kernel share a handful of invariants that fail
LATE when broken — at trace time on a TPU run, or worse, silently as
a wrong-dtype accumulation. This rule pins them statically:

- **grid/index-map arity**: every ``BlockSpec`` index-map lambda takes
  exactly ``len(grid)`` parameters, and an index map returning a
  literal tuple returns one index per block dimension;
- **spec/shape rank**: the ``out_specs`` block rank equals the
  ``out_shape`` ``ShapeDtypeStruct`` rank (a rank mismatch is a
  guaranteed Mosaic lowering error);
- **call arity**: ``pallas_call(...)(args)`` passes exactly
  ``len(in_specs)`` arrays, and a resolvable kernel function (a name
  or ``functools.partial(name, ...)``) has exactly
  ``in_specs + outputs + scratch_shapes`` ref parameters after the
  partial-bound ones;
- **accumulator dtype**: ``dot``/``dot_general``/``einsum``/``matmul``
  inside a kernel body must pass ``preferred_element_type`` — the
  default accumulates int8×int8 into int8 and bf16×bf16 into bf16,
  which is exactly the quantized-histogram overflow the f32/int32
  accumulator exists to prevent;
- **VMEM tile budget**: a module issuing ``pallas_call`` must carry a
  static budget guard (a ``*VMEM_BUDGET*`` constant or a ``*fits*``
  predicate, the ``_pallas_fits`` idiom) so tile sizes are checked
  against VMEM before dispatch, and literal ``PALLAS_ROW_TILE*``
  constants must be sublane-aligned (multiples of 8).

Kernel bodies are found two ways: resolved from a ``pallas_call``
first argument, or by name (``*kernel_body*`` — the repo convention).
Non-literal shapes/grids are skipped, never guessed.
"""
from __future__ import annotations

import ast
import re
from typing import Iterator, List, Optional, Set

from ..engine import FileContext, Finding
from . import Rule

_KERNEL_NAME = re.compile(r"kernel_body")
_ROW_TILE = re.compile(r"^PALLAS_ROW_TILE")
_BUDGET_NAME = re.compile(r"VMEM_BUDGET")
_FITS_NAME = re.compile(r"fits")
_DOT_OPS = ("dot", "dot_general", "einsum", "matmul")


def _uses_pallas(ctx: FileContext) -> bool:
    return any("pallas" in v for v in ctx._aliases.values())


def _is_pallas_call(ctx, node: ast.AST) -> bool:
    if not isinstance(node, ast.Call):
        return False
    canon = ctx.canonical(node.func) or ""
    return canon.rsplit(".", 1)[-1] == "pallas_call"


def _kw(call: ast.Call, name: str) -> Optional[ast.AST]:
    for kw in call.keywords:
        if kw.arg == name:
            return kw.value
    return None


def _block_specs(node: Optional[ast.AST], ctx) -> List[ast.Call]:
    """The BlockSpec calls of an in_specs/out_specs expression (a bare
    spec, or a literal list/tuple of them)."""
    if node is None:
        return []
    elts = node.elts if isinstance(node, (ast.List, ast.Tuple)) \
        else [node]
    out = []
    for el in elts:
        if isinstance(el, ast.Call):
            canon = ctx.canonical(el.func) or ""
            if canon.rsplit(".", 1)[-1] == "BlockSpec":
                out.append(el)
    return out


def _spec_count(node: Optional[ast.AST]) -> int:
    """How many specs an in_specs expression holds: the elements of a
    literal list/tuple, whatever each is (a BlockSpec call, or a name
    bound to one earlier); 0 where the expression is not literal."""
    if isinstance(node, (ast.List, ast.Tuple)):
        return len(node.elts)
    return 1 if isinstance(node, ast.Call) else 0


def _spec_shape_rank(spec: ast.Call) -> Optional[int]:
    if spec.args and isinstance(spec.args[0], (ast.Tuple, ast.List)):
        return len(spec.args[0].elts)
    return None


def _spec_index_map(spec: ast.Call) -> Optional[ast.Lambda]:
    for cand in list(spec.args[1:2]) + [kw.value for kw in spec.keywords
                                        if kw.arg == "index_map"]:
        if isinstance(cand, ast.Lambda):
            return cand
    return None


class PallasInvariantsRule(Rule):
    id = "JLT010"
    name = "pallas-invariants"
    summary = ("Pallas BlockSpec/grid/kernel-arity mismatch, missing "
               "accumulator dtype, or missing VMEM budget guard")

    def check(self, ctx: FileContext) -> Iterator[Finding]:
        if not _uses_pallas(ctx):
            return iter(())
        out: List[Finding] = []
        calls = [n for n in ast.walk(ctx.tree)
                 if _is_pallas_call(ctx, n)]
        invocations = {id(n.func): n for n in ast.walk(ctx.tree)
                       if isinstance(n, ast.Call)
                       and isinstance(n.func, ast.Call)}
        kernel_names: Set[str] = set()
        for call in calls:
            kernel_names |= self._check_call_site(
                ctx, call, invocations.get(id(call)), out)
        self._check_kernels(ctx, kernel_names, out)
        if calls:
            self._check_budget(ctx, calls[0], out)
        self._check_row_tiles(ctx, out)
        return iter(out)

    # -- one pallas_call site ------------------------------------------
    def _check_call_site(self, ctx, call: ast.Call,
                         invocation: Optional[ast.Call],
                         out) -> Set[str]:
        grid = _kw(call, "grid")
        grid_rank = len(grid.elts) if isinstance(
            grid, (ast.Tuple, ast.List)) else None
        in_specs = _block_specs(_kw(call, "in_specs"), ctx)
        n_in = _spec_count(_kw(call, "in_specs"))
        out_specs = _block_specs(_kw(call, "out_specs"), ctx)
        for spec in in_specs + out_specs:
            rank = _spec_shape_rank(spec)
            lam = _spec_index_map(spec)
            if lam is None:
                continue
            n_lam = len(lam.args.args)
            if grid_rank is not None and n_lam != grid_rank:
                out.append(self.finding(
                    ctx, lam,
                    "BlockSpec index map takes %d parameter(s) but the "
                    "grid has %d dimension(s) — each grid axis feeds "
                    "one index-map argument" % (n_lam, grid_rank)))
            if rank is not None and isinstance(lam.body, ast.Tuple) \
                    and len(lam.body.elts) != rank:
                out.append(self.finding(
                    ctx, lam,
                    "BlockSpec index map returns %d block index(es) "
                    "for a %d-dimensional block shape — one index per "
                    "block dimension" % (len(lam.body.elts), rank)))
        # out_specs rank vs out_shape rank
        out_shape = _kw(call, "out_shape")
        if isinstance(out_shape, ast.Call) and out_shape.args \
                and isinstance(out_shape.args[0],
                               (ast.Tuple, ast.List)) \
                and len(out_specs) == 1:
            want = len(out_shape.args[0].elts)
            got = _spec_shape_rank(out_specs[0])
            if got is not None and got != want:
                out.append(self.finding(
                    ctx, out_specs[0],
                    "out_specs block is rank %d but out_shape is rank "
                    "%d — the output BlockSpec must match the output "
                    "array's rank" % (got, want)))
        # immediate invocation arity: pallas_call(...)(a, b)
        if invocation is not None and n_in:
            n_args = len(invocation.args)
            if not any(isinstance(a, ast.Starred)
                       for a in invocation.args) \
                    and n_args != n_in:
                out.append(self.finding(
                    ctx, invocation,
                    "pallas_call declares %d in_specs but is invoked "
                    "with %d array(s) — every operand needs exactly "
                    "one BlockSpec" % (n_in, n_args)))
        # kernel arity (name or functools.partial(name, bound...))
        names: Set[str] = set()
        if call.args:
            k = call.args[0]
            bound = 0
            if isinstance(k, ast.Call):
                canon = ctx.canonical(k.func) or ""
                if canon.rsplit(".", 1)[-1] == "partial" and k.args \
                        and isinstance(k.args[0], ast.Name):
                    bound = len(k.args) - 1
                    k = k.args[0]
            if isinstance(k, ast.Name):
                names.add(k.id)
                fi = ctx.project.resolve_symbol(ctx, k.id) \
                    if ctx.project else None
                scratch = _kw(call, "scratch_shapes")
                if fi is not None and n_in and isinstance(
                        scratch, (type(None), ast.List, ast.Tuple)):
                    n_out = 1 if len(out_specs) <= 1 else len(out_specs)
                    n_scratch = len(scratch.elts) if scratch else 0
                    n_refs = len(fi.params) - bound
                    want = n_in + n_out + n_scratch
                    if n_refs != want:
                        out.append(self.finding(
                            ctx, call,
                            "kernel %s has %d ref parameter(s) after "
                            "%d partial-bound, but this pallas_call "
                            "supplies %d (in_specs=%d + outputs=%d + "
                            "scratch=%d)"
                            % (fi.qualname, n_refs, bound, want,
                               n_in, n_out, n_scratch)))
        return names

    # -- kernel bodies -------------------------------------------------
    def _check_kernels(self, ctx, kernel_names: Set[str], out) -> None:
        for node in ast.walk(ctx.tree):
            if not isinstance(node, (ast.FunctionDef,
                                     ast.AsyncFunctionDef)):
                continue
            if node.name not in kernel_names \
                    and not _KERNEL_NAME.search(node.name):
                continue
            for sub in ast.walk(node):
                if not isinstance(sub, ast.Call):
                    continue
                canon = ctx.canonical(sub.func) or ""
                if canon.rsplit(".", 1)[-1] not in _DOT_OPS:
                    continue
                if not canon.startswith(("jax.", "jnp.", "jax")):
                    continue
                if _kw(sub, "preferred_element_type") is None:
                    out.append(self.finding(
                        ctx, sub,
                        "%s inside kernel %s without "
                        "preferred_element_type — the default "
                        "accumulates in the input dtype (int8*int8 "
                        "stays int8): pin the accumulator dtype "
                        "explicitly" % (canon.rsplit(".", 1)[-1],
                                        node.name)))

    # -- module VMEM discipline ----------------------------------------
    def _check_budget(self, ctx, first_call: ast.Call, out) -> None:
        has_budget = False
        for node in ctx.tree.body:
            if isinstance(node, ast.Assign):
                for tgt in node.targets:
                    if isinstance(tgt, ast.Name) \
                            and _BUDGET_NAME.search(tgt.id):
                        has_budget = True
            elif isinstance(node, (ast.FunctionDef,
                                   ast.AsyncFunctionDef)) \
                    and _FITS_NAME.search(node.name):
                has_budget = True
        if not has_budget:
            out.append(self.finding(
                ctx, first_call,
                "pallas_call with no static VMEM budget guard in the "
                "module — add a *_VMEM_BUDGET constant and a fits-"
                "style predicate (the _pallas_fits idiom) so tile "
                "sizes are bounded before dispatch, not by a Mosaic "
                "OOM at trace time"))

    def _check_row_tiles(self, ctx, out) -> None:
        for node in ctx.tree.body:
            if not isinstance(node, ast.Assign):
                continue
            for tgt in node.targets:
                if not (isinstance(tgt, ast.Name)
                        and _ROW_TILE.search(tgt.id)):
                    continue
                v = node.value
                if isinstance(v, ast.Constant) \
                        and isinstance(v.value, int) \
                        and (v.value <= 0 or v.value % 8):
                    out.append(self.finding(
                        ctx, node,
                        "%s = %d is not a positive multiple of 8 — "
                        "TPU sublane tiling pads row tiles to 8, so "
                        "a misaligned tile wastes VMEM the budget "
                        "arithmetic does not account for"
                        % (tgt.id, v.value)))
