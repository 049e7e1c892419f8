"""Pallas VMEM budget + tiling alignment pass.

For each ``pl.pallas_call`` in a kernel module, statically evaluate the
BlockSpec block shapes (straight-line abstract interpretation of the enclosing
function, seeded by a per-package *profile* of representative dimensions) and
estimate per-grid-step VMEM residency:

    bytes(spec) = prod(padded block dims) × dtype size × buffering
    buffering   = 2 if the index map varies with the grid (double-buffered DMA)
                  1 if the map is constant (block stays resident)

Padding models the physical VMEM tile: the last dim is padded to a multiple of
128 (lane), the second-to-last to the dtype sublane requirement (4-byte: 8,
2-byte: 16, 1-byte: 32).

Rules:

* ``vmem-budget`` — the per-step total exceeds the 16 MiB VMEM budget;
* ``vmem-misaligned`` — a block dim is neither a multiple of its lane/sublane
  requirement, nor full-span (block dim == array dim — the compiler pads the
  whole array once), nor 1;
* SMEM blocks (``memory_space=pltpu.SMEM``) hold scalars, not VMEM: they
  count 0 bytes but are still held to the tiling rule, as Mosaic holds them;
* ``vmem-uneval`` — a block shape could not be evaluated (the profile is
  missing a symbol).  Unevaluated specs would silently undercount residency,
  so they are findings, not skips.

``--vmem-report`` renders the per-kernel table from the same machinery.
"""

from __future__ import annotations

import ast
import dataclasses
from pathlib import Path

from .findings import Finding

VMEM_LIMIT = 16 * 1024 * 1024

DTYPE_INFO = {  # name -> (bytes, sublane requirement)
    "float32": (4, 8), "int32": (4, 8), "uint32": (4, 8),
    "bfloat16": (2, 16), "float16": (2, 16),
    "int8": (1, 32), "uint8": (1, 32),
}


@dataclasses.dataclass
class KernelProfile:
    """Representative dims + operand dtypes/shapes for one kernel variant."""
    variant: str
    env: dict[str, int]
    dtypes: list[str]               # per BlockSpec, in_specs then out_specs
    arrays: list[tuple[int, ...]]   # full array shapes, same order


# One profile list per kernels/<package>.  Dims mirror the shipped defaults
# (d=768 embeddings, 100k-doc corpus, k=10 retrieval) — the shapes every
# benchmark and ci_gate run actually compiles.
DEFAULT_PROFILES: dict[str, list[KernelProfile]] = {
    "binary_ip": [KernelProfile(
        "default", {"d": 768, "n_words": 24},
        ["int8", "uint32", "int32"],
        [(256, 768), (4096, 24), (256, 4096)],
    )],
    "int8_ip": [KernelProfile(
        "default", {"d": 768},
        ["bfloat16", "uint8", "float32"],
        [(256, 768), (4096, 768), (256, 4096)],
    )],
    "fused_quantize": [KernelProfile(
        "default", {"d": 768, "d_out": 128},
        ["float32", "float32", "float32", "float32", "float32", "float32",
         "uint8"],
        [(4096, 768), (768,), (768, 128), (128,), (128,), (128,),
         (4096, 128)],
    )],
    "topk_blocks": [KernelProfile(
        "default", {"k": 10, "n_d": 102400, "n_blocks": 100},
        ["float32", "float32", "int32"],
        [(256, 102400), (256, 12800), (256, 12800)],
    )],
    "ivf_fused": [
        # in_specs (qe, dense corrections, list chunk, id rows), out_specs
        # (vals, ids); lists of 2048 rows are one chunk
        KernelProfile(
            "float", {"dq": 768, "w": 768, "lc": 2048, "nlist": 1024,
                      "q_pad": 64, "id_rows": 8, "k": 10},
            ["float32", "float32", "float32", "int32", "float32", "int32"],
            [(64, 768), (64, 1024), (1024, 2048, 768), (1024, 2048),
             (64, 128), (64, 128)],
        ),
        KernelProfile(
            "onebit", {"dq": 768, "w": 24, "lc": 2048, "nlist": 1024,
                       "q_pad": 64, "id_rows": 8, "k": 10},
            ["int8", "float32", "uint32", "int32", "float32", "int32"],
            [(64, 768), (64, 1024), (1024, 2048, 24), (1024, 2048),
             (64, 128), (64, 128)],
        ),
        # the paper's IVF 200/100 at 2.1M docs: lists of 13,248 rows laid out
        # to 13,312, 2,048 a step
        KernelProfile(
            "int8", {"dq": 128, "w": 128, "lc": 2048, "nlist": 200,
                     "q_pad": 64, "id_rows": 8, "k": 10},
            ["bfloat16", "float32", "uint8", "int32", "float32", "int32"],
            [(64, 128), (64, 200), (200, 13312, 128), (200, 13312),
             (64, 128), (64, 128)],
        ),
    ],
}


# --- tiny straight-line evaluator ------------------------------------------

def _eval(node: ast.expr, env: dict[str, int]) -> int | None:
    if isinstance(node, ast.Constant) and isinstance(node.value, int):
        return node.value
    if isinstance(node, ast.Attribute) and node.attr == "squeezed":
        return 1  # ``pl.squeezed``: a unit block axis the kernel never sees
    if isinstance(node, ast.Name):
        return env.get(node.id)
    if isinstance(node, ast.UnaryOp) and isinstance(node.op, ast.USub):
        v = _eval(node.operand, env)
        return None if v is None else -v
    if isinstance(node, ast.BinOp):
        a, b = _eval(node.left, env), _eval(node.right, env)
        if a is None or b is None:
            return None
        if isinstance(node.op, ast.Add):
            return a + b
        if isinstance(node.op, ast.Sub):
            return a - b
        if isinstance(node.op, ast.Mult):
            return a * b
        if isinstance(node.op, (ast.FloorDiv, ast.Div)):
            return a // b if b else None
        if isinstance(node.op, ast.Mod):
            return a % b if b else None
        return None
    if isinstance(node, ast.Call):
        fname = (node.func.attr if isinstance(node.func, ast.Attribute)
                 else node.func.id if isinstance(node.func, ast.Name) else "")
        vals = [_eval(a, env) for a in node.args]
        if any(v is None for v in vals):
            return None
        if fname == "cdiv" and len(vals) == 2 and vals[1]:
            return -(-vals[0] // vals[1])
        if fname == "min":
            return min(vals)
        if fname == "max":
            return max(vals)
    return None


def _iter_stmts(body: list[ast.stmt]):
    for stmt in body:
        yield stmt
        for attr in ("body", "orelse", "finalbody"):
            sub = getattr(stmt, attr, None)
            if isinstance(sub, list) and sub and isinstance(sub[0], ast.stmt):
                yield from _iter_stmts(sub)


def _build_env(fn: ast.FunctionDef, profile_env: dict[str, int]) -> dict[str, int]:
    env: dict[str, int] = {}
    # signature defaults (block_q=128, ...)
    args = fn.args
    pos = args.posonlyargs + args.args
    for arg, default in zip(pos[len(pos) - len(args.defaults):], args.defaults):
        if isinstance(default, ast.Constant) and isinstance(default.value, int):
            env[arg.arg] = default.value
    for arg, default in zip(args.kwonlyargs, args.kw_defaults):
        if (default is not None and isinstance(default, ast.Constant)
                and isinstance(default.value, int)):
            env[arg.arg] = default.value
    env.update(profile_env)
    # straight-line assignments
    for stmt in _iter_stmts(fn.body):
        if isinstance(stmt, ast.Assign) and len(stmt.targets) == 1:
            t = stmt.targets[0]
            if isinstance(t, ast.Name):
                v = _eval(stmt.value, env)
                if v is not None:
                    env[t.id] = v
    return env


# --- BlockSpec extraction ---------------------------------------------------

@dataclasses.dataclass
class SpecEstimate:
    label: str                      # "in[0]" / "out[1]"
    shape: tuple[int, ...] | None
    dtype: str
    varies: bool
    bytes: int                      # 0 if shape is None
    align_errors: list[str]


def _call_named(node: ast.expr, name: str) -> bool:
    return (isinstance(node, ast.Call)
            and (node.func.attr if isinstance(node.func, ast.Attribute)
                 else getattr(node.func, "id", "")) == name)


def _index_map_varies(spec_call: ast.Call) -> bool:
    lam = None
    if len(spec_call.args) > 1 and isinstance(spec_call.args[1], ast.Lambda):
        lam = spec_call.args[1]
    for kw in spec_call.keywords:
        if kw.arg == "index_map" and isinstance(kw.value, ast.Lambda):
            lam = kw.value
    if lam is None:
        return True  # identity map: block index == grid index → varies
    body = lam.body
    elts = body.elts if isinstance(body, ast.Tuple) else [body]
    return any(not isinstance(e, ast.Constant) for e in elts)


def _in_smem(spec_call: ast.Call) -> bool:
    return any(kw.arg == "memory_space" and isinstance(kw.value, ast.Attribute)
               and kw.value.attr == "SMEM" for kw in spec_call.keywords)


def _spec_shape(spec_call: ast.Call, env: dict[str, int]) -> tuple[int, ...] | None:
    if not spec_call.args:
        return None
    shp = spec_call.args[0]
    if not isinstance(shp, ast.Tuple):
        return None
    dims = [_eval(e, env) for e in shp.elts]
    if any(d is None for d in dims):
        return None
    return tuple(dims)


def _collect_specs(call: ast.Call, fn: ast.FunctionDef) -> tuple[list[ast.Call], list[ast.Call]]:
    """Return (in_spec calls, out_spec calls) for a pallas_call."""
    kwargs = {kw.arg: kw.value for kw in call.keywords}
    src = kwargs
    gs = kwargs.get("grid_spec")
    if isinstance(gs, ast.Name):
        for stmt in _iter_stmts(fn.body):
            if (isinstance(stmt, ast.Assign) and len(stmt.targets) == 1
                    and isinstance(stmt.targets[0], ast.Name)
                    and stmt.targets[0].id == gs.id
                    and isinstance(stmt.value, ast.Call)):
                src = {kw.arg: kw.value for kw in stmt.value.keywords}
                break
    elif isinstance(gs, ast.Call):
        src = {kw.arg: kw.value for kw in gs.keywords}

    def specs_of(node: ast.expr | None) -> list[ast.Call]:
        if node is None:
            return []
        if isinstance(node, (ast.List, ast.Tuple)):
            return [e for e in node.elts if _call_named(e, "BlockSpec")]
        if _call_named(node, "BlockSpec"):
            return [node]
        return []

    return specs_of(src.get("in_specs")), specs_of(src.get("out_specs"))


def _padded_bytes(shape: tuple[int, ...], dtype: str) -> int:
    size, sublane = DTYPE_INFO.get(dtype, (4, 8))
    dims = list(shape)
    if len(dims) >= 1:
        dims[-1] = -(-dims[-1] // 128) * 128
    if len(dims) >= 2:
        dims[-2] = -(-dims[-2] // sublane) * sublane
    total = size
    for d in dims:
        total *= max(d, 1)
    return total


def _alignment_errors(shape: tuple[int, ...], dtype: str,
                      array: tuple[int, ...] | None) -> list[str]:
    size, sublane = DTYPE_INFO.get(dtype, (4, 8))
    errs = []

    def full_span(axis_from_end: int) -> bool:
        if array is None or len(array) != len(shape):
            return False
        return shape[-axis_from_end] == array[-axis_from_end]

    if len(shape) >= 1:
        last = shape[-1]
        if last % 128 != 0 and last != 1 and not full_span(1):
            errs.append(f"lane:{last}: last dim {last} not a multiple of "
                        f"128 (lane)")
    if len(shape) >= 2:
        sub = shape[-2]
        if sub % sublane != 0 and sub != 1 and not full_span(2):
            errs.append(f"sublane:{sub}: dim {sub} not a multiple of "
                        f"{sublane} ({dtype} sublane)")
    return errs


@dataclasses.dataclass
class KernelEstimate:
    package: str
    variant: str
    path: str
    line: int
    specs: list[SpecEstimate]
    uneval: int

    @property
    def total_bytes(self) -> int:
        return sum(s.bytes for s in self.specs)

    @property
    def ok(self) -> bool:
        return (self.total_bytes <= VMEM_LIMIT and self.uneval == 0
                and not any(s.align_errors for s in self.specs))


def estimate_file(tree: ast.Module, relpath: str,
                  profiles: list[KernelProfile]) -> list[KernelEstimate]:
    package = _package_of(relpath) or Path(relpath).stem
    out: list[KernelEstimate] = []
    for fn in ast.walk(tree):
        if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        calls = [n for n in ast.walk(fn) if _call_named(n, "pallas_call")]
        for call in calls:
            in_specs, out_specs = _collect_specs(call, fn)
            all_specs = ([("in", i, s) for i, s in enumerate(in_specs)]
                         + [("out", i, s) for i, s in enumerate(out_specs)])
            for prof in profiles:
                env = _build_env(fn, prof.env)
                ests: list[SpecEstimate] = []
                uneval = 0
                for idx, (side, i, spec) in enumerate(all_specs):
                    dtype = prof.dtypes[idx] if idx < len(prof.dtypes) else "float32"
                    array = prof.arrays[idx] if idx < len(prof.arrays) else None
                    shape = _spec_shape(spec, env)
                    varies = _index_map_varies(spec)
                    if shape is None:
                        uneval += 1
                        ests.append(SpecEstimate(f"{side}[{i}]", None, dtype,
                                                 varies, 0, []))
                        continue
                    nbytes = (0 if _in_smem(spec) else
                              _padded_bytes(shape, dtype) * (2 if varies else 1))
                    ests.append(SpecEstimate(
                        f"{side}[{i}]", shape, dtype, varies, nbytes,
                        _alignment_errors(shape, dtype, array)))
                out.append(KernelEstimate(package, prof.variant, relpath,
                                          call.lineno, ests, uneval))
    return out


def _package_of(relpath: str) -> str | None:
    parts = Path(relpath).parts
    if "kernels" in parts:
        i = parts.index("kernels")
        if i + 1 < len(parts) - 1:
            return parts[i + 1]
    return None


def profiles_for(relpath: str) -> list[KernelProfile] | None:
    pkg = _package_of(relpath)
    if pkg is None or not relpath.endswith("kernel.py"):
        return None
    return DEFAULT_PROFILES.get(
        pkg, [KernelProfile("default", {}, [], [])])


def check_vmem(tree: ast.Module, relpath: str,
               profiles: list[KernelProfile] | None = None) -> list[Finding]:
    profs = profiles if profiles is not None else profiles_for(relpath)
    if profs is None:
        return []
    findings: list[Finding] = []
    for est in estimate_file(tree, relpath, profs):
        name = f"{est.package}[{est.variant}]"
        if est.uneval:
            findings.append(Finding(
                rule="vmem-uneval", path=est.path, line=est.line,
                qualname=name, detail=f"{est.uneval} specs",
                message=(f"{est.uneval} BlockSpec shape(s) could not be "
                         f"evaluated — extend the {est.package} profile so the "
                         f"estimate covers every operand"),
            ))
        if est.total_bytes > VMEM_LIMIT:
            findings.append(Finding(
                rule="vmem-budget", path=est.path, line=est.line,
                qualname=name, detail=str(est.total_bytes // (1024 * 1024)),
                message=(f"estimated per-step VMEM {est.total_bytes / 2**20:.1f} "
                         f"MiB exceeds the {VMEM_LIMIT // 2**20} MiB budget"),
            ))
        for s in est.specs:
            for err in s.align_errors:
                tag, _, msg = err.partition(": ")
                findings.append(Finding(
                    rule="vmem-misaligned", path=est.path, line=est.line,
                    qualname=name, detail=f"{s.label}:{tag}",
                    message=f"{s.label} block {s.shape} {s.dtype}: {msg}",
                ))
    return findings


def render_report(estimates: list[KernelEstimate]) -> str:
    lines = [
        f"{'kernel':<24} {'blocks':>6} {'est VMEM':>10} {'limit':>8} status",
        "-" * 60,
    ]
    for est in estimates:
        name = f"{est.package}[{est.variant}]"
        status = "OK" if est.ok else "FAIL"
        if est.uneval:
            status += f" ({est.uneval} uneval)"
        align = sum(len(s.align_errors) for s in est.specs)
        if align:
            status += f" ({align} misaligned)"
        lines.append(
            f"{name:<24} {len(est.specs):>6} "
            f"{est.total_bytes / 2**20:>8.2f}MB {VMEM_LIMIT // 2**20:>6}MB "
            f"{status}")
    return "\n".join(lines)
