"""``pallas-contract`` — dimensional + VMEM contracts at pallas_call sites.

Every ``pl.pallas_call`` in ``kernels/`` encodes an implicit contract:

* each BlockSpec's index_map takes one parameter per grid axis (plus
  one per scalar-prefetch operand under ``PrefetchScalarGridSpec``)
  and returns one coordinate per block dimension;
* the number of runtime operands matches ``in_specs`` (plus the
  scalar-prefetch operands, which come first);
* ``out_specs`` and ``out_shape`` agree in arity;
* a block's last two dims form a plane the TPU compiler accepts: it
  requires multiples of (8, 128) or the array's own dims, so a block
  ``(..., 1, X)`` compiles only over an array whose second-minor dim is
  itself 1.  Interpret mode never checks this; the paged-attention
  ``(1, rows, 1, hd)`` and BlockRandK ``(1, bs)`` row blocks passed
  every interpret-mode test and were refused by the compiler.  View the
  array so the block's last two dims are a full plane;
* the per-grid-step VMEM footprint — Σ block-shape bytes over
  in/out specs and scratch — fits the module's own budget: a
  ``*VMEM_BUDGET*`` constant when the module defines one, else the
  ``~N MB VMEM`` comment-contract in its docstring (the dasha_update
  "comfortably inside ~16 MB VMEM" comment becomes an assertion).

Shapes are resolved by a bounded symbolic evaluator: module constants,
parameter defaults (``block_rows=512`` is the contract's representative
tile), simple local assignments, and single-return module-local helper
calls (``_batched_specs``).  A dimension that stays unresolvable makes
the checker *silent on the footprint* for that site — it never guesses
— while the arity checks still apply.
"""
from __future__ import annotations

import ast
import re
from typing import Any, Dict, Iterable, List, Optional, Tuple, Union

from repro.analysis import _astutil
from repro.analysis._astutil import UNKNOWN, is_known, safe_eval
from repro.analysis.engine import Checker, ModuleCtx
from repro.analysis.findings import Finding

_BUDGET_COMMENT_RE = re.compile(
    r"~?\s*(\d+(?:\.\d+)?)\s*MB\s+VMEM", re.IGNORECASE)
DEFAULT_BUDGET_BYTES = 16 << 20

_DTYPE_BYTES = {"float32": 4, "int32": 4, "uint32": 4, "float64": 8,
                "int64": 8, "bfloat16": 2, "float16": 2, "int16": 2,
                "int8": 1, "uint8": 1, "bool": 1, "bool_": 1}


class _BlockSpec:
    def __init__(self, shape: Any, index_map: Optional[ast.AST],
                 node: ast.AST):
        self.shape = shape          # tuple (possibly with UNKNOWN dims)
        self.index_map = index_map  # Lambda / FunctionDef / None
        self.node = node


class _ShapeStruct:
    def __init__(self, shape: Any, dtype: Optional[str], node: ast.AST):
        self.shape = shape
        self.dtype = dtype
        self.node = node


class _VMEMScratch(_ShapeStruct):
    pass


class _GridSpec:
    def __init__(self, grid: Any, num_scalar_prefetch: int,
                 in_specs: Any, out_specs: Any, node: ast.AST):
        self.grid = grid
        self.num_scalar_prefetch = num_scalar_prefetch
        self.in_specs = in_specs
        self.out_specs = out_specs
        self.node = node


def _dtype_bytes(dtype: Optional[str]) -> int:
    if dtype is None:
        return 4
    return _DTYPE_BYTES.get(dtype.rsplit(".", 1)[-1], 4)


class _Resolver:
    """Bounded symbolic evaluation of local names inside one function."""

    MAX_DEPTH = 3

    def __init__(self, mod: ModuleCtx):
        self.mod = mod

    def function_env(self, fn: _astutil.FunctionNode,
                     bound: Optional[Dict[str, Any]] = None,
                     depth: int = 0) -> Dict[str, Any]:
        env: Dict[str, Any] = dict(self.mod.constants)
        env.update(_astutil.param_defaults(fn, self.mod.constants))
        if bound:
            env.update(bound)
        for stmt in ast.walk(fn):
            if isinstance(stmt, ast.Assign) and len(stmt.targets) == 1:
                val = self.resolve(stmt.value, env, depth)
                tgt = stmt.targets[0]
                if isinstance(tgt, ast.Name):
                    env[tgt.id] = val
                elif isinstance(tgt, ast.Tuple) \
                        and isinstance(val, tuple) \
                        and len(val) == len(tgt.elts):
                    for t, v in zip(tgt.elts, val):
                        if isinstance(t, ast.Name):
                            env[t.id] = v
        return env

    def resolve(self, node: ast.AST, env: Dict[str, Any],
                depth: int = 0) -> Any:
        if isinstance(node, ast.Call):
            return self._resolve_call(node, env, depth)
        if isinstance(node, ast.Lambda):
            return node
        # containers recurse through the full resolver (elements may be
        # BlockSpec calls safe_eval cannot see into)
        if isinstance(node, ast.Tuple):
            return tuple(self.resolve(e, env, depth) for e in node.elts)
        if isinstance(node, ast.List):
            return [self.resolve(e, env, depth) for e in node.elts]
        val = safe_eval(node, env)
        if is_known(val):
            return val
        if isinstance(node, ast.Name):
            fn = self.mod.functions.by_qualname.get(node.id)
            if fn is not None:
                return fn
            return env.get(node.id, UNKNOWN)
        if isinstance(node, ast.BinOp) and isinstance(node.op, ast.Mult):
            left = self.resolve(node.left, env, depth)
            right = self.resolve(node.right, env, depth)
            if isinstance(left, list) and isinstance(right, int):
                return left * right
            if isinstance(right, list) and isinstance(left, int):
                return right * left
        return UNKNOWN

    def _resolve_call(self, call: ast.Call, env: Dict[str, Any],
                      depth: int) -> Any:
        name = self.mod.imports.call_name(call)
        if name is None:
            return UNKNOWN
        tail = name.rsplit(".", 1)[-1]
        kwargs = _astutil.keyword_map(call)
        if tail == "BlockSpec":
            shape = (self.resolve(call.args[0], env, depth)
                     if call.args else
                     self.resolve(kwargs.get("block_shape"), env, depth)
                     if "block_shape" in kwargs else UNKNOWN)
            imap_node: Optional[ast.AST] = None
            if len(call.args) > 1:
                imap_node = call.args[1]
            elif "index_map" in kwargs:
                imap_node = kwargs["index_map"]
            imap = (self.resolve(imap_node, env, depth)
                    if imap_node is not None else None)
            if not isinstance(imap, (ast.Lambda, ast.FunctionDef,
                                     ast.AsyncFunctionDef)):
                imap = imap_node if isinstance(imap_node,
                                               ast.Lambda) else None
            return _BlockSpec(shape, imap, call)
        if tail == "ShapeDtypeStruct":
            shape = (self.resolve(call.args[0], env, depth)
                     if call.args else UNKNOWN)
            dtype = None
            dt_node = (call.args[1] if len(call.args) > 1
                       else kwargs.get("dtype"))
            if dt_node is not None:
                dtype = self.mod.imports.canonical(dt_node)
            return _ShapeStruct(shape, dtype, call)
        if tail == "VMEM":
            shape = (self.resolve(call.args[0], env, depth)
                     if call.args else UNKNOWN)
            dtype = (self.mod.imports.canonical(call.args[1])
                     if len(call.args) > 1 else None)
            return _VMEMScratch(shape, dtype, call)
        if tail == "PrefetchScalarGridSpec":
            nsp = (safe_eval(kwargs["num_scalar_prefetch"], env)
                   if "num_scalar_prefetch" in kwargs else 0)
            return _GridSpec(
                grid=(self.resolve(kwargs["grid"], env, depth)
                      if "grid" in kwargs else UNKNOWN),
                num_scalar_prefetch=nsp if is_known(nsp) else 0,
                in_specs=(self.resolve(kwargs["in_specs"], env, depth)
                          if "in_specs" in kwargs else UNKNOWN),
                out_specs=(self.resolve(kwargs["out_specs"], env, depth)
                           if "out_specs" in kwargs else UNKNOWN),
                node=call)
        # module-local helper with a single return of resolvable values
        # (a helper that returns from several branches is not followed:
        # which branch runs depends on values the resolver lacks)
        local_fn = self.mod.functions.by_qualname.get(name) \
            if "." not in name else None
        if local_fn is not None and depth < self.MAX_DEPTH \
                and sum(isinstance(n, ast.Return)
                        for n in ast.walk(local_fn)) == 1:
            bound: Dict[str, Any] = {}
            params = _astutil.param_names(local_fn)
            for pname, arg in zip(params, call.args):
                bound[pname] = self.resolve(arg, env, depth + 1)
            for kname, kval in kwargs.items():
                bound[kname] = self.resolve(kval, env, depth + 1)
            callee_env = self.function_env(local_fn, bound, depth + 1)
            for stmt in local_fn.body:
                if isinstance(stmt, ast.Return) \
                        and stmt.value is not None:
                    return self.resolve(stmt.value, callee_env,
                                        depth + 1)
        return UNKNOWN


def _lambda_params(imap: ast.AST) -> Optional[int]:
    if isinstance(imap, ast.Lambda):
        return len(imap.args.args) + len(imap.args.posonlyargs)
    if isinstance(imap, (ast.FunctionDef, ast.AsyncFunctionDef)):
        return len(imap.args.args) + len(imap.args.posonlyargs)
    return None


def _lambda_return_arity(imap: ast.AST) -> Optional[int]:
    body: Optional[ast.AST] = None
    if isinstance(imap, ast.Lambda):
        body = imap.body
    elif isinstance(imap, (ast.FunctionDef, ast.AsyncFunctionDef)):
        returns = [s for s in ast.walk(imap) if isinstance(s, ast.Return)
                   and s.value is not None]
        if len(returns) != 1:
            return None
        body = returns[0].value
    if isinstance(body, ast.Tuple):
        return len(body.elts)
    if body is not None:
        return 1
    return None


def _as_spec_list(specs: Any) -> Optional[List[Any]]:
    if isinstance(specs, list):
        return specs
    if isinstance(specs, tuple):
        return list(specs)
    if specs is UNKNOWN or specs is None:
        return None
    return [specs]


def module_budget_bytes(mod: ModuleCtx) -> Tuple[int, str]:
    """The module's own VMEM budget: a ``*VMEM_BUDGET*`` constant wins,
    else the ``~N MB VMEM`` comment-contract, else the 16 MB default."""
    for name, val in mod.constants.items():
        if "VMEM_BUDGET" in name and isinstance(val, (int, float)):
            return int(val), name
    m = _BUDGET_COMMENT_RE.search(mod.source)
    if m:
        return int(float(m.group(1)) * (1 << 20)), \
            f"comment-contract '~{m.group(1)} MB VMEM'"
    return DEFAULT_BUDGET_BYTES, "default 16 MB"


class PallasContractChecker(Checker):
    id = "pallas-contract"
    severity = "error"
    description = ("BlockSpec/grid/index-map arity and static VMEM "
                   "footprint vs the module's budget at every "
                   "pl.pallas_call site")

    def check(self, mod: ModuleCtx) -> Iterable[Finding]:
        resolver = _Resolver(mod)
        budget, budget_src = module_budget_bytes(mod)
        for _qn, fn in mod.functions.functions():
            sites = [n for n in ast.walk(fn) if isinstance(n, ast.Call)
                     and self._is_pallas_call(mod, n)
                     and _astutil.enclosing_function(n) is fn]
            if not sites:
                continue
            env = resolver.function_env(fn)
            for call in sites:
                yield from self._check_site(mod, fn, call, env,
                                            resolver, budget,
                                            budget_src)

    @staticmethod
    def _is_pallas_call(mod: ModuleCtx, call: ast.Call) -> bool:
        name = mod.imports.call_name(call)
        return name is not None and name.endswith(".pallas_call")

    def _check_site(self, mod: ModuleCtx, fn: _astutil.FunctionNode,
                    call: ast.Call, env: Dict[str, Any],
                    resolver: _Resolver, budget: int,
                    budget_src: str) -> Iterable[Finding]:
        kwargs = _astutil.keyword_map(call)

        grid: Any = UNKNOWN
        nsp = 0
        in_specs: Any = UNKNOWN
        out_specs: Any = UNKNOWN
        if "grid_spec" in kwargs:
            gs = resolver.resolve(kwargs["grid_spec"], env)
            if isinstance(gs, _GridSpec):
                grid = gs.grid
                nsp = gs.num_scalar_prefetch
                in_specs = gs.in_specs
                out_specs = gs.out_specs
        else:
            if "grid" in kwargs:
                grid = resolver.resolve(kwargs["grid"], env)
            if "in_specs" in kwargs:
                in_specs = resolver.resolve(kwargs["in_specs"], env)
            if "out_specs" in kwargs:
                out_specs = resolver.resolve(kwargs["out_specs"], env)

        grid_arity: Optional[int] = None
        if isinstance(grid, tuple):
            grid_arity = len(grid)
        elif isinstance(grid, int):
            grid_arity = 1

        in_list = _as_spec_list(in_specs)
        out_list = _as_spec_list(out_specs)

        # 1/2: index-map parameter count and return arity per BlockSpec
        for spec in (in_list or []) + (out_list or []):
            if not isinstance(spec, _BlockSpec):
                continue
            if spec.index_map is not None and grid_arity is not None:
                nparams = _lambda_params(spec.index_map)
                want = grid_arity + nsp
                if nparams is not None and nparams != want:
                    yield mod.finding(
                        self.id, self.severity, spec.node,
                        f"index_map takes {nparams} parameter(s) but "
                        f"the grid has {grid_arity} axis(es)"
                        + (f" + {nsp} scalar-prefetch operand(s)"
                           if nsp else "")
                        + f" = {want} expected")
            if isinstance(spec.shape, tuple) and len(spec.shape) >= 2 \
                    and spec.shape[-2] == 1 and spec.shape[-1] != 1:
                yield mod.finding(
                    self.id, self.severity, spec.node,
                    f"block {_fmt_shape(spec.shape)} has 1 in its "
                    "second-minor dim: the TPU compiler needs the last "
                    "two block dims to be multiples of (8, 128) or the "
                    "array's own dims, so this compiles only over an "
                    "array whose second-minor dim is 1 — view the array "
                    "so the block's last two dims are a full plane")
            if spec.index_map is not None \
                    and isinstance(spec.shape, tuple):
                ret = _lambda_return_arity(spec.index_map)
                if ret is not None and ret != len(spec.shape):
                    yield mod.finding(
                        self.id, self.severity, spec.node,
                        f"index_map returns {ret} coordinate(s) for a "
                        f"{len(spec.shape)}-dim block "
                        f"{_fmt_shape(spec.shape)}")

        # 3: operand count at the immediate call
        outer = _astutil.parent(call)
        if isinstance(outer, ast.Call) and outer.func is call \
                and in_list is not None \
                and not any(isinstance(a, ast.Starred)
                            for a in outer.args):
            n_args = len(outer.args)
            want = len(in_list) + nsp
            if n_args != want:
                yield mod.finding(
                    self.id, self.severity, outer,
                    f"pallas_call receives {n_args} operand(s) but "
                    f"declares {len(in_list)} in_spec(s)"
                    + (f" + {nsp} scalar-prefetch" if nsp else ""))

        # 4: out_specs vs out_shape arity
        out_shape = (resolver.resolve(kwargs["out_shape"], env)
                     if "out_shape" in kwargs else UNKNOWN)
        shape_list = _as_spec_list(out_shape)
        if out_list is not None and shape_list is not None \
                and len(out_list) != len(shape_list):
            yield mod.finding(
                self.id, self.severity, call,
                f"out_specs has {len(out_list)} spec(s) but out_shape "
                f"has {len(shape_list)} result(s)")

        # 5: static VMEM footprint vs the module budget
        scratch = (resolver.resolve(kwargs["scratch_shapes"], env)
                   if "scratch_shapes" in kwargs else [])
        scratch_list = _as_spec_list(scratch) or []
        total = 0
        resolvable = True
        for spec in (in_list or []) + (out_list or []) + scratch_list:
            if isinstance(spec, (_BlockSpec, _ShapeStruct)):
                shape = spec.shape
                dtype = getattr(spec, "dtype", None)
            else:
                resolvable = False
                break
            if not isinstance(shape, tuple) \
                    or not all(isinstance(d, int) for d in shape):
                resolvable = False
                break
            n = 1
            for d in shape:
                n *= d
            total += n * _dtype_bytes(dtype)
        if resolvable and (in_list or out_list) and total > budget:
            yield mod.finding(
                self.id, self.severity, call,
                f"per-grid-step VMEM footprint {total} bytes "
                f"(~{total / (1 << 20):.2f} MB) exceeds the module "
                f"budget {budget} bytes ({budget_src})")


def _fmt_shape(shape: tuple) -> str:
    return "(" + ", ".join(
        str(d) if is_known(d) else "?" for d in shape) + ")"
