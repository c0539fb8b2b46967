"""Static Pallas kernel resource checker.

Walks each kernel module's ``pl.pallas_call`` **BlockSpecs symbolically**
(no JAX import, no execution): the block shapes are AST expressions over
the block-size parameters (``block_e``, ``block_s``, ...), so for any
concrete assignment of those parameters the checker can

* bound the **VMEM working set** per grid step — Σ over operand/output
  blocks of their bytes once padded to whole (sublane, lane) tiles (a
  ``(1, BE)`` event row fills 8 sublanes), plus each kernel's declared
  in-kernel scratch term (the one-hot / DP-front tiles the body
  materializes, the same formulas ``pick_blocks`` budgets against).
  Operands placed in SMEM (``memory_space=...SMEM``, the dicing window)
  are counted apart and never tiled;
* flag **tile misalignment** against the MXU/VPU tiling rules — lane
  (minor) dimension a multiple of 128, sublane a multiple of 8/16/32 for
  4/2/1-byte dtypes.  Whole-array broadcast operands (constant index maps,
  like the align kernel's ``(S, 1)`` cost columns) are exempt; under-sized
  power-of-two tiles (``(1, BE)`` rows, small variant blocks) are
  *warnings* — Mosaic pads them — while oversized unaligned tiles are hard
  errors.

:func:`validate_blocks` is the assertion layer ``pick_blocks`` calls: it
raises :class:`KernelResourceError` when a block assignment breaks the
VMEM limit or a hard alignment rule.  :func:`build_report` evaluates every
kernel at representative operating points for the committed
``BENCH_analysis.json`` artifact.
"""

from __future__ import annotations

import ast
import dataclasses
import functools
import math
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Tuple

__all__ = [
    "KernelResourceError",
    "KERNEL_TABLE",
    "analyze_kernel",
    "estimate_call",
    "validate_blocks",
    "build_report",
]

#: per-chip VMEM (v5e); the hard ceiling validate_blocks asserts against
VMEM_LIMIT_BYTES = 16 << 20
#: the soft budget pick_blocks tunes toward (headroom for double buffering)
VMEM_BUDGET_BYTES = 8 << 20

DTYPE_BYTES = {
    "float32": 4, "int32": 4, "uint32": 4,
    "bfloat16": 2, "float16": 2, "int16": 2,
    "int8": 1, "uint8": 1, "bool": 1, "bool_": 1,
    "float64": 8, "int64": 8,
}
#: minimum sublane multiple by dtype width (TPU packs narrow dtypes deeper)
MIN_SUBLANE = {4: 8, 2: 16, 1: 32, 8: 8}
LANE = 128


class KernelResourceError(RuntimeError):
    """A block assignment violates the VMEM bound or a hard tiling rule."""


# ---------------------------------------------------------------------------
# Kernel registry: where each kernel lives, operand dtypes per call site
# (not recoverable from BlockSpecs), and the in-kernel scratch formula the
# body materializes beyond its declared blocks.
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class CallSpec:
    name: str
    in_dtypes: Tuple[str, ...]
    scratch: str  # bytes, symbolic in the same env as the block shapes


@dataclasses.dataclass(frozen=True)
class KernelSpec:
    rel: str  # kernel module, relative to the repro package root
    calls: Tuple[CallSpec, ...]


KERNEL_TABLE: Dict[str, KernelSpec] = {
    "dfg_count": KernelSpec(
        rel="kernels/dfg_count/kernel.py",
        calls=(
            # the (BS, BE) and (BD, BE) int8 one-hots feed the MXU
            # contraction into the resident (BS, BD) int32 output tile
            CallSpec("plain", ("int32", "int32"),
                     "block_e * (block_s + block_d)"),
            CallSpec("diced",
                     ("int32", "int32", "int32", "int32", "int32"),
                     "block_e * (block_s + block_d)"),
        ),
    ),
    "segment_count": KernelSpec(
        rel="kernels/segment_count/kernel.py",
        calls=(
            # one (BS, BN) f32 one-hot tile
            CallSpec("main", ("int32",), "4 * block_n * block_s"),
        ),
    ),
    "align_dp": KernelSpec(
        rel="kernels/align_dp/kernel.py",
        calls=(
            # DP front (VMEM scratch) + one-hot + gathered M column, each
            # (S, BV) f32
            CallSpec("main",
                     ("int32", "int32", "float32", "float32", "float32"),
                     "3 * 4 * block_v * s"),
        ),
    ),
}


def _pkg_root() -> Path:
    return Path(__file__).resolve().parent.parent


# ---------------------------------------------------------------------------
# AST extraction
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class SpecShape:
    dims: Tuple[str, ...]  # symbolic dim expressions (unparsed AST)
    const_index_map: bool  # whole-array broadcast operand
    smem: bool = False  # scalar memory: no VMEM bytes, no tiling


@dataclasses.dataclass(frozen=True)
class CallSite:
    in_specs: Tuple[SpecShape, ...]
    out_specs: Tuple[SpecShape, ...]
    out_dtype: str
    lineno: int


def _is_blockspec(node: ast.AST) -> bool:
    return (
        isinstance(node, ast.Call)
        and isinstance(node.func, ast.Attribute)
        and node.func.attr == "BlockSpec"
    )


def _spec_shape(call: ast.Call) -> SpecShape:
    if not call.args or not isinstance(call.args[0], (ast.Tuple, ast.List)):
        raise KernelResourceError(
            f"BlockSpec at line {call.lineno} has no literal shape tuple"
        )
    dims = tuple(ast.unparse(e) for e in call.args[0].elts)
    const_map = False
    if len(call.args) > 1 and isinstance(call.args[1], ast.Lambda):
        body = call.args[1].body
        if isinstance(body, (ast.Tuple, ast.List)):
            const_map = all(isinstance(e, ast.Constant) for e in body.elts)
        else:
            const_map = isinstance(body, ast.Constant)
    space = next(
        (k.value for k in call.keywords if k.arg == "memory_space"), None
    )
    smem = isinstance(space, ast.Attribute) and space.attr == "SMEM"
    return SpecShape(dims=dims, const_index_map=const_map, smem=smem)


def _resolve_spec(node: ast.AST, symbols: Dict[str, ast.Call]) -> ast.Call:
    if isinstance(node, ast.Name) and node.id in symbols:
        return symbols[node.id]
    if _is_blockspec(node):
        return node
    raise KernelResourceError(
        f"cannot resolve BlockSpec reference {ast.unparse(node)!r}"
    )


@functools.lru_cache(maxsize=None)
def analyze_kernel(path: str) -> Tuple[CallSite, ...]:
    """All ``pl.pallas_call`` sites in ``path``, in order of appearance,
    with their block shapes extracted symbolically."""
    tree = ast.parse(Path(path).read_text(), filename=str(path))
    sites: List[CallSite] = []
    for fn in [n for n in tree.body if isinstance(n, ast.FunctionDef)]:
        symbols: Dict[str, ast.Call] = {}  # BlockSpecs
        calls: Dict[str, ast.Call] = {}  # any call, for a named out_shape
        for node in ast.walk(fn):
            if isinstance(node, ast.Assign) and isinstance(node.value, ast.Call):
                for t in node.targets:
                    if isinstance(t, ast.Name):
                        calls[t.id] = node.value
                        if _is_blockspec(node.value):
                            symbols[t.id] = node.value
        for node in ast.walk(fn):
            if not (
                isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)
                and node.func.attr == "pallas_call"
            ):
                continue
            kwargs = {k.arg: k.value for k in node.keywords if k.arg}
            raw_in = kwargs.get("in_specs")
            raw_out = kwargs.get("out_specs")
            if raw_in is None or raw_out is None:
                continue
            in_elts = (
                raw_in.elts if isinstance(raw_in, (ast.Tuple, ast.List))
                else [raw_in]
            )
            out_elts = (
                raw_out.elts if isinstance(raw_out, (ast.Tuple, ast.List))
                else [raw_out]
            )
            out_dtype = "float32"
            shape = kwargs.get("out_shape")
            if isinstance(shape, ast.Name):
                shape = calls.get(shape.id)
            if (
                isinstance(shape, ast.Call)
                and len(shape.args) > 1
                and isinstance(shape.args[1], ast.Attribute)
            ):
                out_dtype = shape.args[1].attr
            sites.append(CallSite(
                in_specs=tuple(
                    _spec_shape(_resolve_spec(e, symbols)) for e in in_elts
                ),
                out_specs=tuple(
                    _spec_shape(_resolve_spec(e, symbols)) for e in out_elts
                ),
                out_dtype=out_dtype,
                lineno=node.lineno,
            ))
    sites.sort(key=lambda s: s.lineno)
    return tuple(sites)


# ---------------------------------------------------------------------------
# Symbolic evaluation
# ---------------------------------------------------------------------------


def _eval(node: ast.AST, env: Dict[str, int]) -> int:
    if isinstance(node, ast.Expression):
        return _eval(node.body, env)
    if isinstance(node, ast.Constant) and isinstance(node.value, (int, float)):
        return int(node.value)
    if isinstance(node, ast.Name):
        if node.id not in env:
            raise KernelResourceError(
                f"unresolved symbol {node.id!r}; pass it in the env"
            )
        return int(env[node.id])
    if isinstance(node, ast.BinOp):
        lhs, rhs = _eval(node.left, env), _eval(node.right, env)
        if isinstance(node.op, ast.Add):
            return lhs + rhs
        if isinstance(node.op, ast.Sub):
            return lhs - rhs
        if isinstance(node.op, ast.Mult):
            return lhs * rhs
        if isinstance(node.op, ast.FloorDiv):
            return lhs // rhs
        if isinstance(node.op, ast.Mod):
            return lhs % rhs
        if isinstance(node.op, ast.Pow):
            return lhs ** rhs
    if isinstance(node, ast.UnaryOp) and isinstance(node.op, ast.USub):
        return -_eval(node.operand, env)
    raise KernelResourceError(f"cannot evaluate {ast.unparse(node)!r}")


def _eval_expr(expr: str, env: Dict[str, int]) -> int:
    return _eval(ast.parse(expr, mode="eval"), env)


def _check_tiling(
    label: str, dims: Sequence[int], dtype: str
) -> Tuple[List[str], List[str]]:
    """(errors, warnings) for one evaluated block shape."""
    errors: List[str] = []
    warnings: List[str] = []
    if not dims:
        return errors, warnings
    itemsize = DTYPE_BYTES.get(dtype, 4)
    min_sub = MIN_SUBLANE.get(itemsize, 8)
    lane = dims[-1]
    if lane % LANE != 0:
        if lane < LANE and lane > 0 and (lane & (lane - 1)) == 0:
            warnings.append(
                f"{label}: lane dim {lane} < {LANE} — Mosaic pads the tile "
                f"({lane}/{LANE} lanes used)"
            )
        else:
            errors.append(
                f"{label}: lane dim {lane} is not a multiple of {LANE}"
            )
    if len(dims) >= 2:
        sub = dims[-2]
        if sub % min_sub != 0:
            if sub < min_sub and sub > 0 and (sub & (sub - 1)) == 0:
                warnings.append(
                    f"{label}: sublane dim {sub} < {min_sub} ({dtype}) — "
                    "Mosaic pads the tile"
                )
            else:
                errors.append(
                    f"{label}: sublane dim {sub} is not a multiple of "
                    f"{min_sub} ({dtype})"
                )
    return errors, warnings


def _tile_bytes(dims: Sequence[int], dtype: str) -> int:
    """Bytes of a VMEM block padded to whole (sublane, lane) tiles."""
    itemsize = DTYPE_BYTES.get(dtype, 4)
    dims = list(dims)
    if dims:
        dims[-1] = -(-dims[-1] // LANE) * LANE
    if len(dims) >= 2:
        sub = MIN_SUBLANE.get(itemsize, 8)
        dims[-2] = -(-dims[-2] // sub) * sub
    n = itemsize
    for d in dims:
        n *= d
    return n


def estimate_call(
    kernel_name: str,
    call_index: int,
    env: Dict[str, int],
    *,
    pkg_root: Optional[Path] = None,
) -> Dict[str, object]:
    """VMEM bound + tiling findings for one pallas_call under ``env``."""
    spec = KERNEL_TABLE[kernel_name]
    root = pkg_root or _pkg_root()
    sites = analyze_kernel(str(root / spec.rel))
    if len(sites) != len(spec.calls):
        raise KernelResourceError(
            f"{kernel_name}: expected {len(spec.calls)} pallas_call sites "
            f"in {spec.rel}, found {len(sites)}"
        )
    site = sites[call_index]
    call = spec.calls[call_index]
    if len(site.in_specs) != len(call.in_dtypes):
        raise KernelResourceError(
            f"{kernel_name}/{call.name}: {len(site.in_specs)} in_specs but "
            f"{len(call.in_dtypes)} declared operand dtypes"
        )

    operands = []
    errors: List[str] = []
    warnings: List[str] = []
    total = 0
    smem = 0
    for i, (s, dtype) in enumerate(zip(site.in_specs, call.in_dtypes)):
        dims = [_eval_expr(d, env) for d in s.dims]
        if s.smem:
            nbytes = DTYPE_BYTES.get(dtype, 4) * math.prod(dims)
            smem += nbytes
            operands.append({
                "operand": f"in[{i}]", "block": dims, "dtype": dtype,
                "bytes": nbytes, "memory_space": "smem",
            })
            continue
        nbytes = _tile_bytes(dims, dtype)
        total += nbytes
        operands.append({
            "operand": f"in[{i}]", "block": dims, "dtype": dtype,
            "bytes": nbytes,
        })
        if not s.const_index_map:  # broadcast operands live padded once
            e, w = _check_tiling(f"{call.name} in[{i}]", dims, dtype)
            errors += e
            warnings += w
    for i, s in enumerate(site.out_specs):
        dims = [_eval_expr(d, env) for d in s.dims]
        nbytes = _tile_bytes(dims, site.out_dtype)
        total += nbytes
        operands.append({
            "operand": f"out[{i}]", "block": dims, "dtype": site.out_dtype,
            "bytes": nbytes,
        })
        if not s.const_index_map:
            e, w = _check_tiling(f"{call.name} out[{i}]", dims, site.out_dtype)
            errors += e
            warnings += w
    scratch = _eval_expr(call.scratch, env)
    total += scratch
    return {
        "call": call.name,
        "env": dict(sorted(env.items())),
        "operands": operands,
        "scratch_bytes": scratch,
        "smem_bytes": smem,
        "vmem_bytes": total,
        "errors": errors,
        "warnings": warnings,
    }


# ---------------------------------------------------------------------------
# The assertion layer pick_blocks calls
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=256)
def _validate_cached(
    kernel_name: str, env_items: Tuple[Tuple[str, int], ...],
    vmem_limit_bytes: int,
) -> Tuple[Dict[str, object], ...]:
    env = dict(env_items)
    spec = KERNEL_TABLE[kernel_name]
    reports = []
    for idx in range(len(spec.calls)):
        rep = estimate_call(kernel_name, idx, env)
        if rep["errors"]:
            raise KernelResourceError(
                f"{kernel_name}/{rep['call']}: " + "; ".join(rep["errors"])
            )
        if rep["vmem_bytes"] > vmem_limit_bytes:
            raise KernelResourceError(
                f"{kernel_name}/{rep['call']}: VMEM bound "
                f"{rep['vmem_bytes']} B exceeds the {vmem_limit_bytes} B "
                f"limit for blocks {rep['env']}"
            )
        reports.append(rep)
    return tuple(reports)


def validate_blocks(
    kernel_name: str,
    *,
    vmem_limit_bytes: int = VMEM_LIMIT_BYTES,
    **env: int,
) -> Tuple[Dict[str, object], ...]:
    """Assert a concrete block assignment is resourceable; returns the
    per-call reports.  Raises :class:`KernelResourceError` on a VMEM-limit
    overrun or a hard tile-misalignment."""
    return _validate_cached(
        kernel_name, tuple(sorted(env.items())), int(vmem_limit_bytes)
    )


# ---------------------------------------------------------------------------
# Committed report (BENCH_analysis.json)
# ---------------------------------------------------------------------------


def _scenario_envs(kernel_name: str) -> List[Tuple[str, Dict[str, int]]]:
    """Representative operating points, using each kernel's own
    ``pick_blocks`` so the report describes what actually runs."""
    if kernel_name == "dfg_count":
        from repro.kernels.dfg_count.ops import pick_blocks

        out = []
        for a in (64, 600, 3000):
            be, bs, bd = pick_blocks(a)
            out.append((
                f"A={a}", {"block_e": be, "block_s": bs, "block_d": bd}
            ))
        return out
    if kernel_name == "segment_count":
        from repro.kernels.segment_count.ops import pick_blocks

        out = []
        for s in (256, 4096):
            bn, bs = pick_blocks(s)
            out.append((f"S={s}", {"block_n": bn, "block_s": bs}))
        return out
    if kernel_name == "align_dp":
        from repro.kernels.align_dp.ops import _pad_lane, pick_blocks

        out = []
        for v, l, s in ((50, 40, 30), (1000, 600, 400)):
            out.append((
                f"V={v},L={l},S={s}",
                {
                    "block_v": pick_blocks(v),
                    "lp": _pad_lane(l),
                    "s": _pad_lane(s),
                },
            ))
        return out
    raise KeyError(kernel_name)


def build_report() -> Dict[str, object]:
    """Per-kernel VMEM bounds at representative operating points — the
    committed ``BENCH_analysis.json`` artifact (deterministic: no
    timestamps, no host state)."""
    kernels: Dict[str, object] = {}
    for name, spec in sorted(KERNEL_TABLE.items()):
        scenarios = []
        for label, env in _scenario_envs(name):
            calls = [
                estimate_call(name, idx, env)
                for idx in range(len(spec.calls))
            ]
            scenarios.append({
                "name": label,
                "calls": calls,
                "max_vmem_bytes": max(c["vmem_bytes"] for c in calls),
            })
        kernels[name] = {
            "source": f"src/repro/{spec.rel}",
            "scenarios": scenarios,
        }
    return {
        "generated_by": "python -m repro.analysis --kernel-report",
        "vmem_limit_bytes": VMEM_LIMIT_BYTES,
        "vmem_budget_bytes": VMEM_BUDGET_BYTES,
        "kernels": kernels,
    }
