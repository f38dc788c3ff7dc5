"""HLO-text and jaxpr analysis: collective inventory, donation
aliases, entry signatures, op budgets.

`cost_analysis()` does not expose collective traffic, so we parse the
compiled (post-SPMD) HLO.  Shapes in the compiled module are already
per-device, so summed operand bytes are per-chip quantities — exactly
what the roofline's collective term wants.  The same parsers back the
``repro.analysis`` rule engine: donation audits read the module
header's ``input_output_alias`` map, collective budgets read the
inventory, host-transfer bans read instruction sites.

Ring-algorithm byte multipliers (bytes actually serialized on links,
per device, group size n):
    all-gather       result_bytes · (n−1)/n
    reduce-scatter   operand_bytes · (n−1)/n
    all-reduce       2 · operand_bytes · (n−1)/n   (RS + AG)
    all-to-all       operand_bytes · (n−1)/n
    collective-permute  operand_bytes
"""
from __future__ import annotations

import math
import re
from collections import defaultdict


def cost_analysis_dict(ca) -> dict:
    """Normalize ``Compiled.cost_analysis()`` across jax versions.

    jax < 0.4.35 returns a list with one properties-dict per program;
    newer versions return the dict directly (and either may be None when
    the backend provides no analysis).  ``dict(list_of_dicts)`` raises
    ``ValueError: dictionary update sequence element #0 has length 53``,
    which used to error every dry-run on version drift.
    """
    if ca is None:
        return {}
    if isinstance(ca, dict):
        return dict(ca)
    if isinstance(ca, (list, tuple)):
        out: dict = {}
        for entry in ca:
            if entry:
                out.update(entry)
        return out
    return dict(ca)

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
    # low-precision families (one byte unless noted)
    "f8e4m3": 1, "f8e5m2": 1, "f8e4m3fn": 1, "f8e4m3fnuz": 1,
    "f8e4m3b11fnuz": 1, "f8e5m2fnuz": 1, "f8e3m4": 1, "f8e8m0fnu": 1,
    "f4e2m1fn": 0.5, "s4": 0.5, "u4": 0.5,
}

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_OP_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?[\w.\-]+\s*=\s*(\([^)]*\)|\S+)\s+"
    r"(all-reduce-start|all-reduce|all-gather-start|all-gather|"
    r"reduce-scatter|all-to-all|collective-permute-start|"
    r"collective-permute)\b(.*)$")
# iota form: replica_groups=[G,S]<=[...] (G groups of S) or [N]<=[...]
_GROUPS_IOTA_RE = re.compile(r"replica_groups=\[([\d,]+)\]<=\[")


def _shape_bytes(shape_str: str) -> int:
    total = 0.0
    for dtype, dims in _SHAPE_RE.findall(shape_str):
        if dtype not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dtype]
    return int(math.ceil(total))


def _balanced_braces(text: str, start: int) -> str | None:
    """Contents of the brace group opening at ``text[start] == '{'``."""
    if start < 0 or start >= len(text) or text[start] != "{":
        return None
    depth, j = 0, start
    while j < len(text):
        if text[j] == "{":
            depth += 1
        elif text[j] == "}":
            depth -= 1
            if depth == 0:
                return text[start + 1:j]
        j += 1
    return None


def _group_size(rest: str, default: int) -> int:
    """Size of the largest replica group named on a collective line.

    Handles the explicit list form (``replica_groups={{0,1},{2,3,4,5}}``
    → 4, not the first group's 2), the flat single-group form
    (``replica_groups={0,1,2}`` → 3) and both iota forms
    (``[G,S]<=[...]`` → S, ``[N]<=[...]`` → N).  Falls back to
    ``default`` (the world size) when no group annotation is present.
    """
    m = _GROUPS_IOTA_RE.search(rest)
    if m:
        dims = [int(d) for d in m.group(1).split(",") if d]
        if dims:
            return dims[-1]
    key = "replica_groups="
    at = rest.find(key)
    if at >= 0:
        body = _balanced_braces(rest, at + len(key))
        if body is not None:
            groups = re.findall(r"\{([^{}]*)\}", body)
            if groups:  # explicit list of groups
                sizes = [len([t for t in g.split(",") if t.strip()])
                         for g in groups]
                sizes = [s for s in sizes if s > 0]
                if sizes:
                    return max(sizes)
            else:  # one flat group
                ids = [t for t in body.split(",") if t.strip()]
                if ids:
                    return len(ids)
    return default


def collective_inventory(hlo_text: str, *, world_size: int):
    """Per-op-kind collective byte totals (per device).

    Returns dict kind → {"count": int, "bytes": payload-on-link bytes,
    "raw_bytes": tensor bytes}.
    """
    inv = defaultdict(lambda: {"count": 0, "bytes": 0.0, "raw_bytes": 0.0})
    for line in hlo_text.splitlines():
        m = _OP_RE.match(line)
        if not m:
            continue
        shape_str, kind, rest = m.groups()
        kind = kind.replace("-start", "")
        size = _shape_bytes(shape_str)
        n = _group_size(rest, world_size)
        frac = (n - 1) / n if n > 1 else 0.0
        if kind == "all-reduce":
            moved = 2.0 * size * frac
        elif kind == "all-gather":
            moved = size * frac
        elif kind == "reduce-scatter":
            moved = size * frac
        elif kind == "all-to-all":
            moved = size * frac
        else:  # collective-permute
            moved = float(size)
        inv[kind]["count"] += 1
        inv[kind]["bytes"] += moved
        inv[kind]["raw_bytes"] += float(size)
    return dict(inv)


def total_collective_bytes(hlo_text: str, *, world_size: int) -> float:
    inv = collective_inventory(hlo_text, world_size=world_size)
    return sum(v["bytes"] for v in inv.values())


def count_op(hlo_text: str, opname: str) -> int:
    """Number of ``<opname>(...)`` *instruction sites*.

    Only counts lines of the form ``%name = <shape> <opname>(...)`` —
    bare name mentions inside fusion labels, ``calls=`` references or
    ``metadata={op_name="..."}`` strings do not match.
    """
    pat = re.compile(
        r"=\s*(?:\([^)]*\)|[\w\[\],{}]+)\s+" + re.escape(opname) + r"\(")
    return sum(1 for line in hlo_text.splitlines() if pat.search(line))


_ALIAS_PAIR_RE = re.compile(
    r"\{([\d\s,]*)\}:\s*\((\d+),\s*\{([\d\s,]*)\}\s*(?:,\s*([\w-]+))?\)")


def parse_input_output_aliases(hlo_text: str) -> list:
    """Donation/aliasing map from the module header.

    Parses ``input_output_alias={ {0}: (0, {}, may-alias), ... }`` into
    a list of ``{"output_index", "param_number", "param_index",
    "kind"}`` dicts.  Empty when the module declares no aliasing (e.g.
    a jit without donated arguments).
    """
    key = "input_output_alias="
    at = hlo_text.find(key)
    if at < 0:
        return []
    body = _balanced_braces(hlo_text, at + len(key))
    if body is None:
        return []
    out = []
    for m in _ALIAS_PAIR_RE.finditer(body):
        out.append({
            "output_index": tuple(
                int(t) for t in m.group(1).split(",") if t.strip()),
            "param_number": int(m.group(2)),
            "param_index": tuple(
                int(t) for t in m.group(3).split(",") if t.strip()),
            "kind": m.group(4) or "may-alias",
        })
    return out


_PARAM_RE = re.compile(r"([%\w.\-]+)\s*:\s*(\w+)\[([\d,]*)\]")


def entry_parameters(hlo_text: str) -> list:
    """``[(name, dtype, shape)]`` of the ENTRY computation's parameters.

    Shapes are per-device in a post-SPMD module, so together with
    :func:`parse_input_output_aliases` this answers "which state
    buffers does the compiled round update in place".
    """
    for line in hlo_text.splitlines():
        ls = line.strip()
        if not ls.startswith("ENTRY "):
            continue
        head = ls.split(" -> ")[0]
        lp = head.find("(")
        if lp < 0:
            return []
        sig = head[lp + 1:]
        if sig.endswith(")"):
            sig = sig[:-1]
        return [
            (name, dtype, tuple(int(d) for d in dims.split(",") if d))
            for name, dtype, dims in _PARAM_RE.findall(sig)
        ]
    return []


#: numpy dtype name → HLO dtype token (for matching state leaves
#: against entry-parameter shapes).
NUMPY_TO_HLO_DTYPE = {
    "bool": "pred", "int8": "s8", "uint8": "u8", "int16": "s16",
    "uint16": "u16", "bfloat16": "bf16", "float16": "f16",
    "int32": "s32", "uint32": "u32", "float32": "f32", "int64": "s64",
    "uint64": "u64", "float64": "f64", "complex64": "c64",
    "complex128": "c128",
}


def count_dtype_refs(hlo_text: str, dtype: str = "f64") -> int:
    """Occurrences of ``dtype[...]`` shapes anywhere in the module."""
    return len(re.findall(rf"\b{re.escape(dtype)}\[", hlo_text))


#: HLO opcodes that move data across the host boundary.
HOST_TRANSFER_OPS = ("infeed", "outfeed", "send", "recv")


def count_host_transfer_ops(hlo_text: str) -> int:
    """Host-boundary instruction sites: infeed/outfeed/send/recv plus
    python-callback custom-calls."""
    n = sum(count_op(hlo_text, op) for op in HOST_TRANSFER_OPS)
    n += len(re.findall(r'custom_call_target="[^"]*callback[^"]*"',
                        hlo_text))
    return n


def jaxpr_eqn_counts(jaxpr) -> dict:
    """Primitive-name → count over a jaxpr, recursing into sub-jaxprs.

    Accepts a ``ClosedJaxpr`` (what ``jax.make_jaxpr`` returns) or a raw
    ``Jaxpr``.  Descends into every jaxpr-valued equation param (pjit
    bodies, scan/while/cond branches, custom-call wrappers) so kernels
    wrapped in nested ``jax.jit`` are still counted — this is what the
    fused-round op-count assertions use (one Pallas ``pallas_call`` per
    fused pass, no duplicated elementwise sweeps).
    """
    from collections import Counter

    counts: Counter = Counter()

    def visit_param(v):
        if hasattr(v, "eqns"):  # Jaxpr
            visit(v)
        elif hasattr(v, "jaxpr"):  # ClosedJaxpr
            visit(v.jaxpr)
        elif isinstance(v, (list, tuple)):
            for item in v:
                visit_param(item)

    def visit(jx):
        for eqn in jx.eqns:
            counts[eqn.primitive.name] += 1
            for v in eqn.params.values():
                visit_param(v)

    visit(jaxpr.jaxpr if hasattr(jaxpr, "jaxpr") else jaxpr)
    return dict(counts)


def jaxpr_pallas_kernel_names(jaxpr) -> dict:
    """Kernel-function-name → count over every ``pallas_call`` equation.

    Recurses like :func:`jaxpr_eqn_counts`; the name is the one the TPU
    lowering gives the kernel: the equation's ``name`` param when the
    caller set one, else the kernel body's python function name (e.g.
    ``_kernel3`` / ``_fused_gss3``) from the kernel jaxpr's debug info,
    so rules can budget *which* kernels a round launches, not just how
    many.  Unnamed pallas calls count under ``"<unknown>"``.
    """
    from collections import Counter

    counts: Counter = Counter()

    def visit_param(v):
        if hasattr(v, "eqns"):
            visit(v)
        elif hasattr(v, "jaxpr"):
            visit(v.jaxpr)
        elif isinstance(v, (list, tuple)):
            for item in v:
                visit_param(item)

    def visit(jx):
        for eqn in jx.eqns:
            if eqn.primitive.name == "pallas_call":
                debug = getattr(eqn.params.get("jaxpr"), "debug_info", None)
                name = (eqn.params.get("name")
                        or getattr(debug, "func_name", None))
                counts[name or "<unknown>"] += 1
            for v in eqn.params.values():
                visit_param(v)

    visit(jaxpr.jaxpr if hasattr(jaxpr, "jaxpr") else jaxpr)
    return dict(counts)


def jaxpr_dtypes(jaxpr) -> set:
    """Set of output dtype names over all equations (recursive).

    The static half of the no-f64 rule: a stray ``float64`` promotion
    (x64 mode, a numpy scalar leaking in) shows up in the jaxpr long
    before the compiled module.
    """
    dtypes: set = set()

    def visit_param(v):
        if hasattr(v, "eqns"):
            visit(v)
        elif hasattr(v, "jaxpr"):
            visit(v.jaxpr)
        elif isinstance(v, (list, tuple)):
            for item in v:
                visit_param(item)

    def visit(jx):
        for eqn in jx.eqns:
            for ov in eqn.outvars:
                dt = getattr(ov.aval, "dtype", None)
                if dt is not None:
                    dtypes.add(str(dt))
            for v in eqn.params.values():
                visit_param(v)

    visit(jaxpr.jaxpr if hasattr(jaxpr, "jaxpr") else jaxpr)
    return dtypes


def toplevel_elementwise_shapes(jaxpr, prims=("add", "sub", "mul")) -> list:
    """Output shapes of top-level elementwise eqns (no sub-jaxpr
    descent, but pjit bodies are inlined one level).

    Used to assert the flat round has no separate full-width λ/z/center
    HBM sweeps outside the fused kernel: any surviving top-level
    add/sub over the whole (N, D) state shows up here.
    """
    shapes = []

    def visit(jx, depth):
        for eqn in jx.eqns:
            if eqn.primitive.name in prims:
                shapes.extend(tuple(ov.aval.shape) for ov in eqn.outvars)
            elif eqn.primitive.name in ("jit", "pjit", "closed_call") \
                    and depth < 1:
                for v in eqn.params.values():
                    if hasattr(v, "jaxpr"):
                        visit(v.jaxpr, depth + 1)

    visit(jaxpr.jaxpr if hasattr(jaxpr, "jaxpr") else jaxpr, 0)
    return shapes
