"""HLO-text analysis: collective-traffic accounting + roofline terms.

``cost_analysis()`` gives FLOPs and HBM bytes but NOT collective traffic —
we recover it by scanning the (post-SPMD-partitioning) HLO for
all-gather / all-reduce / reduce-scatter / all-to-all / collective-permute
ops and summing their operand sizes (per instructions in the brief).
"""
from __future__ import annotations

import dataclasses
import re
import warnings
from typing import Dict

from repro.common.hardware import V5E, Chip

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16, "s4": 1, "u4": 1, "f8e4m3fn": 1, "f8e5m2": 1,
}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")

# e.g.  bf16[16,1024,512]{2,1,0}   or   f32[] (scalar)
_SHAPE_RE = re.compile(r"\b([a-z]+[0-9]+(?:e[0-9a-z]+)?|pred)\[([0-9,]*)\]")

_warned_dtypes: set = set()


def _shape_bytes(dtype: str, dims: str) -> int:
    if dtype not in _DTYPE_BYTES and dtype not in _warned_dtypes:
        _warned_dtypes.add(dtype)
        warnings.warn(
            f"hlo_analysis: unknown HLO element type {dtype!r}; assuming "
            "4 bytes/element — add it to _DTYPE_BYTES for exact accounting",
            stacklevel=3)
    n = 1
    if dims:
        for d in dims.split(","):
            n *= int(d)
    return n * _DTYPE_BYTES.get(dtype, 4)


def _collective_result_bytes(result_str: str, *, async_start: bool) -> int:
    """Traffic bytes of one collective's result string.

    Sync collectives (and variadic tuple results) sum every tuple element.
    An async ``-start`` returns a tuple carrying BOTH the operand alias and
    the destination buffer (plus context scalars on some backends); summing
    it would double-count the pair, so only the largest element — the
    destination a device receives — is charged, and the matching ``-done``
    (a read of that same buffer) is charged nothing by the callers.
    """
    sizes = [_shape_bytes(d, dims) for d, dims in _SHAPE_RE.findall(result_str)]
    if not sizes:
        return 0
    return max(sizes) if async_start and len(sizes) > 1 else sum(sizes)


_OP_RE = re.compile(
    r"=\s+(.*?)\s+(all-gather|all-reduce|reduce-scatter|all-to-all|"
    r"collective-permute)(-start|-done)?\(")

_COMP_HDR_RE = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*\(.*\)\s*(?:->.*)?\{\s*$")
_WHILE_RE = re.compile(r"while\(.*?condition=%?([\w.\-]+),\s*body=%?([\w.\-]+)")
_CALL_RE = re.compile(r"(?:to_apply|body|condition|calls)=%?([\w.\-]+)")
_CONST_RE = re.compile(r"constant\((\d+)\)")


def _split_computations(hlo_text: str) -> Dict[str, list]:
    """computation name -> list of instruction lines."""
    comps: Dict[str, list] = {}
    cur = None
    for line in hlo_text.splitlines():
        m = _COMP_HDR_RE.match(line.strip())
        if m and ("{" in line):
            cur = m.group(1)
            comps[cur] = []
        elif cur is not None:
            if line.strip() == "}":
                cur = None
            else:
                comps[cur].append(line.strip())
    return comps


def _trip_count(cond_lines: list) -> int:
    """Heuristic trip count of a while condition: the largest integer
    constant compared against (lax.scan conditions are `i < constant(T)`)."""
    best = 1
    for line in cond_lines:
        if "compare(" in line or "constant(" in line:
            for c in _CONST_RE.findall(line):
                best = max(best, int(c))
    return best


def collective_bytes(hlo_text: str) -> Dict[str, int]:
    """Per-collective-kind traffic bytes, *loop-aware*.

    Post-SPMD CPU HLO prints operands by name only, so each collective is
    accounted by its result shape(s) (all-reduce: result == operand;
    all-gather: the full gathered tensor a device receives). Collectives
    inside while bodies (lax.scan over layers/chunks) are multiplied by the
    loop trip count, recursively — a flat text scan would undercount a
    40-layer scan by 40x. ``-done`` halves of async pairs are skipped.
    """
    comps = _split_computations(hlo_text)
    entry = None
    for line in hlo_text.splitlines():
        if line.startswith("ENTRY"):
            m = _COMP_HDR_RE.match(line.strip())
            if m:
                entry = m.group(1)
    if entry is None and comps:
        entry = next(iter(comps))

    out = {k: 0 for k in _COLLECTIVES}
    out["n_ops"] = 0

    seen = set()

    def walk(comp: str, mult: int):
        if comp not in comps or (comp, mult) in seen:
            return
        seen.add((comp, mult))
        for line in comps[comp]:
            m = _OP_RE.search(line)
            if m and m.group(3) != "-done":
                kind = m.group(2)
                total = _collective_result_bytes(
                    m.group(1), async_start=m.group(3) == "-start")
                out[kind] += total * mult
                out["n_ops"] += mult
            wm = _WHILE_RE.search(line)
            if wm:
                cond, body = wm.group(1), wm.group(2)
                trips = _trip_count(comps.get(cond, []))
                walk(body, mult * trips)
            elif "fusion(" in line or "call(" in line or "custom-call(" in line:
                for callee in _CALL_RE.findall(line):
                    walk(callee, mult)

    if entry:
        walk(entry, 1)
    out["total"] = sum(out[k] for k in _COLLECTIVES)
    return out


@dataclasses.dataclass
class CollectiveOp:
    """One collective instruction found in a module, loop-aware.

    ``shapes`` lists every (dtype, dims) element of the result (async
    ``-start`` tuples carry both the operand alias and the destination);
    ``bytes`` is the de-duplicated traffic charge of the op."""
    kind: str
    bytes: int
    shapes: list
    mult: int
    computation: str
    line: str


def find_collectives(hlo_text: str) -> list:
    """Structured listing of every collective (``-done`` halves skipped),
    with while-loop multipliers — the walk ``collective_bytes`` totals."""
    comps = _split_computations(hlo_text)
    entry = None
    for line in hlo_text.splitlines():
        if line.startswith("ENTRY"):
            m = _COMP_HDR_RE.match(line.strip())
            if m:
                entry = m.group(1)
    if entry is None and comps:
        entry = next(iter(comps))

    found: list = []
    seen = set()

    def walk(comp: str, mult: int):
        if comp not in comps or (comp, mult) in seen:
            return
        seen.add((comp, mult))
        for line in comps[comp]:
            m = _OP_RE.search(line)
            if m and m.group(3) != "-done":
                shapes = [(d, tuple(int(x) for x in dims.split(",") if x))
                          for d, dims in _SHAPE_RE.findall(m.group(1))]
                found.append(CollectiveOp(
                    kind=m.group(2),
                    bytes=_collective_result_bytes(
                        m.group(1), async_start=m.group(3) == "-start"),
                    shapes=shapes, mult=mult, computation=comp,
                    line=line.strip()))
            wm = _WHILE_RE.search(line)
            if wm:
                trips = _trip_count(comps.get(wm.group(1), []))
                walk(wm.group(2), mult * trips)
            elif "fusion(" in line or "call(" in line or "custom-call(" in line:
                for callee in _CALL_RE.findall(line):
                    walk(callee, mult)

    if entry:
        walk(entry, 1)
    return found


# ---------------------------------------------------------------------------
# Loop-aware full analysis (flops / HBM bytes / collectives)
#
# XLA-CPU's HloCostAnalysis counts while bodies ONCE (verified empirically:
# flops are independent of lax.scan length), which under-counts scan-over-
# layers programs by the trip count. We therefore walk the partitioned HLO
# ourselves, multiplying by while trip counts:
#   * flops: dot ops (2 * numel(result) * prod(contracting dims)) — matmuls
#     dominate every workload here.
#   * hbm bytes: per top-level instruction, result + operand bytes at fusion
#     granularity (fusion internals live in registers/cache, like XLA's own
#     bytes-accessed model).
#   * collectives: result-shape bytes per kind.
# ---------------------------------------------------------------------------

_INSTR_RE = re.compile(r"^(?:ROOT\s+)?%([\w.\-]+)\s*=\s*(.+?)\s+([\w\-]+)\(")
_LHS_C_RE = re.compile(r"lhs_contracting_dims=\{([0-9,]*)\}")
_OPERAND_RE = re.compile(r"%([\w.\-]+)")

_SKIP_BYTES_OPS = {"parameter", "constant", "tuple", "get-tuple-element",
                   "bitcast", "after-all", "iota"}
# Ops that touch only O(result) bytes of their (possibly huge) operands —
# counting the full operand would charge a 500MB buffer to every 2MB slice.
_SLICE_OPS = {"dynamic-slice", "slice", "gather", "dynamic-update-slice",
              "scatter", "pad", "reshape", "broadcast", "transpose", "copy",
              "convert", "reduce"}


def _parse_dims(dims: str):
    return [int(d) for d in dims.split(",")] if dims else []


def _result_bytes(result_str: str) -> int:
    return sum(_shape_bytes(d, dims) for d, dims in _SHAPE_RE.findall(result_str))


def analyze_module(hlo_text: str) -> Dict[str, float]:
    """Loop-aware {flops, hbm_bytes, coll_*} for one partitioned module."""
    comps = _split_computations(hlo_text)
    entry = None
    for line in hlo_text.splitlines():
        if line.startswith("ENTRY"):
            m = _COMP_HDR_RE.match(line.strip())
            if m:
                entry = m.group(1)
    if entry is None and comps:
        entry = next(iter(comps))

    # Symbol tables: comp -> {instr name -> result string}
    symtab: Dict[str, Dict[str, str]] = {}
    for cname, lines in comps.items():
        tab = {}
        for line in lines:
            m = _INSTR_RE.match(line)
            if m:
                tab[m.group(1)] = m.group(2)
        symtab[cname] = tab

    out = {"flops": 0.0, "hbm_bytes": 0.0, "n_dots": 0}
    for k in _COLLECTIVES:
        out[k] = 0
    out["coll_ops"] = 0

    def dot_flops(cname, line, result_str):
        mo = _INSTR_RE.match(line)
        ops = line[mo.end():]
        names = _OPERAND_RE.findall(ops[:ops.find(")")])
        lhs_shape = None
        if names:
            lhs_str = symtab[cname].get(names[0], "")
            shapes = _SHAPE_RE.findall(lhs_str)
            if shapes:
                lhs_shape = _parse_dims(shapes[0][1])
        cm = _LHS_C_RE.search(line)
        cdims = _parse_dims(cm.group(1)) if cm else []
        contracted = 1
        for d in cdims:
            if lhs_shape and d < len(lhs_shape):
                contracted *= lhs_shape[d]
        numel = 1
        shapes = _SHAPE_RE.findall(result_str)
        if shapes:
            for d in _parse_dims(shapes[0][1]):
                numel *= d
        return 2.0 * numel * contracted

    def walk(cname: str, mult: float, *, bytes_level: bool):
        for line in comps.get(cname, []):
            m = _INSTR_RE.match(line)
            if not m:
                continue
            name, result_str, opcode = m.groups()
            base_op = opcode.replace("-start", "").replace("-done", "")
            if base_op in _COLLECTIVES:
                if not opcode.endswith("-done"):
                    out[base_op] += _collective_result_bytes(
                        result_str,
                        async_start=opcode.endswith("-start")) * mult
                    out["coll_ops"] += mult
                continue
            if opcode == "dot":
                out["flops"] += dot_flops(cname, line, result_str) * mult
                out["n_dots"] += mult
            if opcode == "while":
                wm = _WHILE_RE.search(line)
                if wm:
                    trips = _trip_count(comps.get(wm.group(1), []))
                    walk(wm.group(2), mult * trips, bytes_level=bytes_level)
                continue
            if opcode in ("fusion", "call", "conditional", "custom-call",
                          "async-start"):
                for callee in _CALL_RE.findall(line):
                    # fusions: walk for dots/collectives only (their internal
                    # traffic is on-chip); calls: walk fully.
                    walk(callee, mult,
                         bytes_level=(bytes_level and opcode != "fusion"))
                if opcode != "fusion":
                    continue   # call results counted inside the callee
            if bytes_level and opcode not in _SKIP_BYTES_OPS:
                b = _result_bytes(result_str)
                if opcode in ("dynamic-update-slice", "scatter"):
                    # in-place update: touched bytes ~ 2 x the (small) update
                    ops_str = line[m.end():]
                    names = _OPERAND_RE.findall(ops_str[:max(ops_str.find(")"), 0)])
                    op_bytes = [_result_bytes(symtab[cname].get(nm, ""))
                                for nm in names[1:]]
                    op_bytes = [x for x in op_bytes if x > 0]
                    b = 2 * min(op_bytes) if op_bytes else b
                elif opcode == "fusion":
                    # loop-carried in-place fusions (cache writes): an operand
                    # with the result's exact shape aliases it — charge the
                    # update slice (smallest operand), not the full buffer.
                    ops_str = line[m.end():]
                    names = _OPERAND_RE.findall(ops_str[:max(ops_str.find(")"), 0)])
                    shapes = [symtab[cname].get(nm, "") for nm in names]
                    op_bytes = [_result_bytes(s) for s in shapes if s]
                    if any(s.split("{")[0] == result_str.split("{")[0]
                           for s in shapes if s):
                        small = [x for x in op_bytes
                                 if 0 < x < _result_bytes(result_str)]
                        b = 2 * max(small) if small else b
                    else:
                        # fused dynamic-slices read O(result) of big operands:
                        # cap each operand's charge at 4x the result size.
                        cap = 4 * _result_bytes(result_str)
                        b += sum(min(x, cap) for x in op_bytes)
                elif opcode in _SLICE_OPS:
                    b *= 2          # read slice + write result
                else:
                    ops_str = line[m.end():]
                    names = _OPERAND_RE.findall(ops_str[:max(ops_str.find(")"), 0)])
                    for nm in names:
                        src = symtab[cname].get(nm)
                        if src:
                            b += _result_bytes(src)
                out["hbm_bytes"] += b * mult

    if entry:
        walk(entry, 1.0, bytes_level=True)
    out["coll_bytes"] = sum(out[k] for k in _COLLECTIVES)
    return out


@dataclasses.dataclass
class Roofline:
    """Three-term roofline for one compiled (arch × shape × mesh) program.

    cost_analysis() describes the per-device partitioned module, so each
    term is seconds-per-chip — identical to the brief's
    HLO_total / (chips × peak) since HLO_total = chips × HLO_per_device.
    """
    flops: float                 # per-device HLO flops
    hbm_bytes: float             # per-device HLO bytes accessed
    coll_bytes: float            # per-device collective operand bytes
    chip: Chip = V5E

    @property
    def compute_s(self) -> float:
        return self.flops / self.chip.peak_flops_bf16

    @property
    def memory_s(self) -> float:
        return self.hbm_bytes / self.chip.hbm_bandwidth

    @property
    def collective_s(self) -> float:
        # all of the chip's ICI links together
        return self.coll_bytes / self.chip.ici_bandwidth

    @property
    def dominant(self) -> str:
        terms = {"compute": self.compute_s, "memory": self.memory_s,
                 "collective": self.collective_s}
        return max(terms, key=terms.get)

    @property
    def bound_s(self) -> float:
        return max(self.compute_s, self.memory_s, self.collective_s)

    def as_dict(self) -> dict:
        return {
            "flops_per_device": self.flops,
            "hbm_bytes_per_device": self.hbm_bytes,
            "collective_bytes_per_device": self.coll_bytes,
            "compute_s": self.compute_s,
            "memory_s": self.memory_s,
            "collective_s": self.collective_s,
            "dominant": self.dominant,
        }


def model_flops(cfg, n_tokens: int, *, train: bool = False) -> float:
    """MODEL_FLOPS = 6·N·D (dense) / 6·N_active·D (MoE) per the brief.

    N counts *active* parameters (MoE: shared + top_k routed experts only);
    forward-only workloads use 2·N·D."""
    d, L = cfg.d_model, cfg.n_layers
    hd = cfg.hd
    attn = d * (cfg.n_heads * hd) + 2 * d * (cfg.n_kv_heads * hd) \
        + (cfg.n_heads * hd) * d
    if cfg.arch == "rwkv":
        layer = 5 * d * d + 2 * d * cfg.d_ff + d * d        # time+channel mix
    else:
        fe = cfg.ffn_hidden
        if cfg.n_experts:
            routed = cfg.top_k * 3 * d * fe
            shared = cfg.n_shared_experts * 3 * d * fe
            dense_res = 3 * d * cfg.d_ff if cfg.dense_residual else 0
            ffn = routed + shared + dense_res
        else:
            ffn = 3 * d * cfg.d_ff
        if cfg.arch == "hybrid":
            ed = cfg.mamba_expand * d
            frac_attn = 1.0 / cfg.attn_every
            mamba = 2 * d * ed + ed * d  # in/out projections
            layer = frac_attn * attn + (1 - frac_attn) * mamba + ffn
        else:
            layer = attn + ffn
    n_active = L * layer + 2 * d * cfg.vocab
    if cfg.arch == "encdec":
        n_active += cfg.n_enc_layers * (attn + 3 * d * cfg.d_ff)
    mult = 6.0 if train else 2.0
    return mult * n_active * n_tokens
