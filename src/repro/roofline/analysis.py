"""Roofline terms from the compiled dry-run artifact (no hardware needed).

Per the assignment:
    compute term    = HLO_FLOPs / (chips × peak_FLOP/s)
    memory term     = HLO_bytes / (chips × HBM_bw)
    collective term = collective_bytes / (chips × link_bw)

`cost_analysis()` supplies FLOPs/bytes; collective bytes are parsed from the
compiled HLO text by summing the **operand** sizes of every all-gather /
all-reduce / reduce-scatter / all-to-all / collective-permute.

Partitioning note (verified empirically in EXPERIMENTS.md §Dry-run): under
SPMD the compiled module is the single per-device program, so
cost_analysis/HLO numbers are *per-chip*. The roofline denominators below
therefore use per-chip peaks (the assignment's ``chips ×`` denominators with
the matching global numerators — dividing per-chip work by per-chip peak is
the same quantity).
"""
from __future__ import annotations

import dataclasses
import re


@dataclasses.dataclass(frozen=True)
class ChipPeaks:
    flops: float               # bf16 FLOP/s per chip
    hbm_bw: float              # HBM bytes/s per chip
    ici_bw: float              # interconnect bytes/s per link


# Published per-chip peaks, keyed by `jax.Device.device_kind`.
# "TPU v5 lite" (v5e): Google Cloud documentation, "TPU v5e" — 197 TFLOP/s
# bf16, 819 GB/s HBM, 1,600 Gbit/s chip-to-chip interconnect over 4 links.
PEAKS = {"TPU v5 lite": ChipPeaks(flops=197e12, hbm_bw=819e9, ici_bw=50e9)}

# The chip the analytic (dry-run) terms below are projected onto.
TARGET_KIND = "TPU v5 lite"


def peaks(device_kind: str) -> ChipPeaks:
    """Peaks of the chip a time was measured on. A chip that is not in
    `PEAKS` is an error, never a default: a measured time divided by
    another chip's peak is a wrong number."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no published peaks for device kind "
                         f"{device_kind!r}; add them to PEAKS with their "
                         f"source") from None


PEAK_FLOPS = PEAKS[TARGET_KIND].flops
HBM_BW = PEAKS[TARGET_KIND].hbm_bw
ICI_BW = PEAKS[TARGET_KIND].ici_bw

_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute")

_SHAPE_RE = re.compile(r"(?:[a-z]+\d*)\[([\d,]*)\]")
_DTYPE_BYTES = {"f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3": 1,
                "f8e5m2": 1, "s64": 8, "u64": 8, "s32": 4, "u32": 4,
                "s16": 2, "u16": 2, "s8": 1, "u8": 1, "pred": 1, "s4": 0.5,
                "u4": 0.5, "c64": 8, "c128": 16}


def _type_bytes(t: str) -> float:
    """'f32[256,128]{1,0}' → bytes."""
    m = re.match(r"([a-z]+[\d\w]*?)\[([\d,]*)\]", t)
    if not m:
        return 0.0
    dt, dims = m.group(1), m.group(2)
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * _DTYPE_BYTES.get(dt, 4)


_DEF_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*"                 # result name
    r"((?:\([^=]*?\))|(?:\w+\[[^\]]*\](?:\{[^}]*\})?))\s+"  # result type
    r"([\w\-]+)\(")                                          # op name


def _result_bytes(type_str: str) -> float:
    """Bytes of a result type, tuples summed."""
    return sum(_type_bytes(m.group(0)) for m in re.finditer(
        r"[a-z]+[\d\w]*?\[[\d,]*\]", type_str))


def _split_computations(hlo_text: str) -> dict[str, list[str]]:
    """computation name → its instruction lines.

    Header lines start at column 0 and end with '{':
      ``%name (params...) -> type {`` / ``ENTRY %main (...) -> ... {``.
    Signatures contain nested parens (tuple params), so the name is taken as
    the first token rather than regex-parsing the full signature.
    """
    comps: dict[str, list[str]] = {}
    cur: str | None = None
    for line in hlo_text.splitlines():
        if (not line.startswith(" ") and line.rstrip().endswith("{")
                and not line.startswith("HloModule")):
            tok = line.strip()
            if tok.startswith("ENTRY"):
                tok = tok[len("ENTRY"):].strip()
            name = tok.lstrip("%").split("(")[0].split()[0] if tok else ""
            if name:
                cur = name
                comps[cur] = []
                continue
        if line.strip() == "}":
            cur = None
            continue
        if cur is not None:
            comps[cur].append(line)
    return comps


def _loop_multipliers(comps: dict[str, list[str]]) -> dict[str, float]:
    """Effective execution count per computation.

    `lax.scan` compiles to `while(...), condition=%c, body=%b`; the trip
    count is the constant bound in the condition's compare. cost_analysis
    and a naive text scan count loop bodies ONCE — this multiplier map is
    how the roofline corrects collective bytes for scanned layer stacks
    (compose through nesting: a scan inside a scan multiplies).
    """
    # trip count per while-body: find its condition's compare constant
    body_trip: dict[str, float] = {}
    calls: dict[str, list[tuple[str, float]]] = {c: [] for c in comps}
    for cname, lines in comps.items():
        for ln in lines:
            wm = re.search(r"while\(.*?condition=%?([\w.\-]+), "
                           r"body=%?([\w.\-]+)", ln)
            if not wm:
                wm = re.search(r"while\(.*?body=%?([\w.\-]+), "
                               r"condition=%?([\w.\-]+)", ln)
                if wm:
                    body, cond = wm.group(1), wm.group(2)
                else:
                    body = cond = None
            else:
                cond, body = wm.group(1), wm.group(2)
            if body:
                trip = 1.0
                for cl in comps.get(cond, []):
                    cm = re.search(r"constant\((\d+)\)", cl)
                    if cm:
                        trip = max(trip, float(cm.group(1)))
                body_trip[body] = trip
                calls[cname].append((body, trip))
            # non-while computation calls execute once per call site
            for sub in re.finditer(
                    r"(?:to_apply|body|calls|computation)=%?([\w.\-]+)", ln):
                if sub.group(1) != body and sub.group(1) in comps:
                    calls[cname].append((sub.group(1), 1.0))

    mult: dict[str, float] = {}

    def fill(cname: str, m: float):
        mult[cname] = max(mult.get(cname, 0.0), m)
        for child, k in calls.get(cname, []):
            if child != cname:
                fill(child, m * k)

    # entry computations: those never called
    called = {c for lst in calls.values() for c, _ in lst}
    for c in comps:
        if c not in called:
            fill(c, 1.0)
    for c in comps:
        mult.setdefault(c, 1.0)
    return mult


def collective_bytes_from_hlo(hlo_text: str) -> dict[str, float]:
    """Sum **operand** bytes per collective kind from compiled HLO text.

    Compiled/scheduled HLO references operands by name only, so we build a
    symbol table (name → result bytes) first, then resolve each collective's
    operand list. Collectives inside while (scan) bodies are multiplied by
    the loop trip count (`_loop_multipliers`); async -start/-done pairs are
    counted once at -start.
    """
    comps = _split_computations(hlo_text)
    if not comps:  # fallback: treat whole text as one computation
        comps = {"entry": hlo_text.splitlines()}
    mults = _loop_multipliers(comps)

    table: dict[str, float] = {}
    coll: list[tuple[str, str, float]] = []
    for cname, lines in comps.items():
        m_c = mults.get(cname, 1.0)
        for line in lines:
            m = _DEF_RE.match(line)
            if not m:
                continue
            name, rtype, op = m.groups()
            table[name] = _result_bytes(rtype)
            base = op[:-6] if op.endswith("-start") else op
            if base in _COLLECTIVES and not op.endswith("-done"):
                call = line.split("(", 1)[1]
                depth, end = 1, len(call)
                for i, ch in enumerate(call):
                    if ch == "(":
                        depth += 1
                    elif ch == ")":
                        depth -= 1
                        if depth == 0:
                            end = i
                            break
                coll.append((base, call[:end], m_c))

    out: dict[str, float] = {k: 0.0 for k in _COLLECTIVES}
    for kind, args, m_c in coll:
        b = 0.0
        for om in re.finditer(r"%([\w.\-]+)", args):
            b += table.get(om.group(1), 0.0)
        if "%" not in args:
            b += _result_bytes(args)
        out[kind] += b * m_c
    out["total"] = sum(out[k] for k in _COLLECTIVES)
    return out


@dataclasses.dataclass
class RooflineTerms:
    flops: float
    bytes_accessed: float
    collective_bytes: float
    chips: int
    model_flops: float = 0.0    # analytic 6·N·D (or 6·N_active·D)

    @property
    def compute_s(self) -> float:
        return self.flops / PEAK_FLOPS

    @property
    def memory_s(self) -> float:
        return self.bytes_accessed / HBM_BW

    @property
    def collective_s(self) -> float:
        return self.collective_bytes / ICI_BW

    @property
    def dominant(self) -> str:
        terms = {"compute": self.compute_s, "memory": self.memory_s,
                 "collective": self.collective_s}
        return max(terms, key=terms.get)

    @property
    def step_time_s(self) -> float:
        """Roofline step time = max of the three terms (perfect overlap)."""
        return max(self.compute_s, self.memory_s, self.collective_s)

    @property
    def useful_flops_fraction(self) -> float:
        if self.flops == 0:
            return 0.0
        return (self.model_flops / self.chips) / self.flops

    @property
    def roofline_fraction(self) -> float:
        """Useful-compute fraction of the roofline step time (MFU-like)."""
        if self.step_time_s == 0:
            return 0.0
        return (self.model_flops / self.chips / PEAK_FLOPS) \
            / self.step_time_s

    def to_dict(self) -> dict:
        return {
            "flops_per_chip": self.flops,
            "bytes_per_chip": self.bytes_accessed,
            "collective_bytes_per_chip": self.collective_bytes,
            "chips": self.chips,
            "model_flops_global": self.model_flops,
            "compute_s": self.compute_s,
            "memory_s": self.memory_s,
            "collective_s": self.collective_s,
            "dominant": self.dominant,
            "step_time_s": self.step_time_s,
            "useful_flops_fraction": self.useful_flops_fraction,
            "roofline_fraction": self.roofline_fraction,
        }


# ---------------------------------------------------------------------------
# Full text-based HLO cost model (trip-count aware)
# ---------------------------------------------------------------------------

_PARAM_RE = re.compile(r"([\w.\-]+):\s*((?:\([^()]*\))|(?:[a-z]+[\d\w]*?"
                       r"\[[^\]]*\](?:\{[^}]*\})?))")
_SHAPE_DIMS_RE = re.compile(r"[a-z]+[\d\w]*?\[([\d,]*)\]")

# Ops whose operands+results cross the HBM boundary at the top level of a
# scheduled computation. Elementwise/layout ops (add, convert, transpose,
# broadcast, …) are excluded — a TPU compile fuses those into neighbors, and
# counting them would bill the same buffer once per elementwise op. What
# remains is one materialization per fusion/matmul/reduction/scatter-gather
# boundary: the TPU-semantics HBM traffic estimate.
_MEM_OPS = ("fusion", "custom-call", "dot", "convolution", "scatter",
            "gather", "dynamic-slice", "dynamic-update-slice", "copy",
            "reduce", "reduce-window", "sort", "concatenate")


def _dims(type_str: str) -> list[int]:
    m = _SHAPE_DIMS_RE.search(type_str)
    if not m:
        return []
    return [int(d) for d in m.group(1).split(",") if d]


def hlo_costs(hlo_text: str) -> dict[str, float]:
    """FLOPs / HBM bytes / collective bytes from compiled HLO text, with
    while-loop bodies multiplied by their trip counts.

    This replaces `compiled.cost_analysis()` for the roofline because XLA's
    cost analysis visits each computation ONCE — a scanned 40-layer stack
    would be undercounted 40×. Method:
      * flops  — every `dot` line: 2 × prod(result dims) × K, K from the
        lhs operand's contracting dims (per-computation symbol tables built
        from instruction results and header params), × loop multiplier.
      * bytes  — operand+result bytes of top-level memory-moving ops in
        control-flow computations (entry + while bodies); fusion-internal
        computations are excluded (register/VMEM-resident).
      * collective bytes — operand bytes of collective ops × multiplier.
    """
    comps = _split_computations(hlo_text)
    mults = _loop_multipliers(comps)

    # per-computation symbol tables (params from headers need re-parse)
    tables: dict[str, dict[str, float]] = {}
    type_tables: dict[str, dict[str, str]] = {}
    header_params: dict[str, dict[str, str]] = {}
    for line in hlo_text.splitlines():
        if (not line.startswith(" ") and line.rstrip().endswith("{")
                and not line.startswith("HloModule")):
            tok = line.strip()
            if tok.startswith("ENTRY"):
                tok = tok[len("ENTRY"):].strip()
            name = tok.lstrip("%").split("(")[0].split()[0] if tok else ""
            if not name:
                continue
            sig = tok[len(name) + (1 if tok.startswith("%") else 0):]
            header_params[name] = {pm.group(1): pm.group(2)
                                   for pm in _PARAM_RE.finditer(sig)}

    # computations called via calls=/to_apply= are fusion-internal
    internal: set[str] = set()
    for lines in comps.values():
        for ln in lines:
            for m in re.finditer(r"(?:calls|to_apply)=%?([\w.\-]+)", ln):
                internal.add(m.group(1))

    flops = 0.0
    mem_bytes = 0.0
    for cname, lines in comps.items():
        mult = mults.get(cname, 1.0)
        table: dict[str, str] = dict(header_params.get(cname, {}))
        for ln in lines:
            m = _DEF_RE.match(ln)
            if not m:
                continue
            name, rtype, op = m.groups()
            table[name] = rtype
            if op == "dot":
                k = 1.0
                cm = re.search(r"lhs_contracting_dims=\{([\d,]*)\}", ln)
                lhs_m = re.search(r"dot\(%?([\w.\-]+)", ln)
                if cm and lhs_m and lhs_m.group(1) in table:
                    ld = _dims(table[lhs_m.group(1)])
                    for ci in cm.group(1).split(","):
                        if ci and int(ci) < len(ld):
                            k *= ld[int(ci)]
                rd = _dims(rtype)
                n_out = 1.0
                for d in rd:
                    n_out *= d
                flops += 2.0 * n_out * k * mult
            if cname not in internal and any(
                    op == mo or op.startswith(mo + ".") for mo in _MEM_OPS):
                call = ln.split("(", 1)[1] if "(" in ln else ""
                ops_b = [_result_bytes(table[om.group(1)])
                         for om in re.finditer(r"%([\w.\-]+)",
                                               call.split("),")[0])
                         if om.group(1) in table]
                rb = _result_bytes(rtype)
                # Slice-semantics ops move only the slice, not the (possibly
                # giant, aliased-in-place) backing buffer — e.g. per-layer
                # reads/writes against a scan-stacked KV-cache carry.
                if op.startswith("dynamic-slice"):
                    b = 2.0 * rb
                elif op.startswith("dynamic-update-slice"):
                    upd = ops_b[1] if len(ops_b) > 1 else rb
                    b = 2.0 * upd
                elif op.startswith("gather"):
                    b = 2.0 * rb + (ops_b[1] if len(ops_b) > 1 else 0.0)
                elif op.startswith("scatter"):
                    upd = ops_b[-1] if ops_b else rb
                    b = 2.0 * upd + (ops_b[1] if len(ops_b) > 2 else 0.0)
                elif op.startswith("fusion") and rb in ops_b:
                    # In-place update fusion (scan ys-accumulation / cache
                    # write): the result aliases the same-sized operand —
                    # the buffer is NOT re-read/re-written wholesale, only
                    # the updated region moves (≈ the other operands).
                    others = list(ops_b)
                    others.remove(rb)
                    b = sum(others) + (max(others) if others else 0.0)
                else:
                    b = rb + sum(ops_b)
                mem_bytes += b * mult

    coll = collective_bytes_from_hlo(hlo_text)
    return {"flops": flops, "bytes": mem_bytes, **coll}


def analyze_compiled(compiled, chips: int,
                     model_flops: float = 0.0) -> RooflineTerms:
    costs = hlo_costs(compiled.as_text())
    return RooflineTerms(flops=costs["flops"], bytes_accessed=costs["bytes"],
                         collective_bytes=costs["total"], chips=chips,
                         model_flops=model_flops)


def roofline_terms(flops, bytes_accessed, collective_bytes, chips,
                   model_flops=0.0) -> RooflineTerms:
    return RooflineTerms(flops, bytes_accessed, collective_bytes, chips,
                         model_flops)
