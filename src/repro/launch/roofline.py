"""Roofline terms from a compiled dry-run artifact (no real hardware).

  compute term    = HLO_FLOPs / (chips x peak_FLOP/s)
  memory term     = HLO_bytes / (chips x HBM_bw)
  collective term = collective_bytes / (chips x link_bw)

The peaks are those of the analysed device kind (``launch.mesh.HW``); a
kind with no entry there is an error, not a default.

Methodology.  ``compiled.cost_analysis()`` reports per-device numbers but
counts ``while`` bodies ONCE (verified empirically: a scanned L-layer stack
reports 1/L of the flops), so we parse the compiled HLO text ourselves and
walk the computation graph with loop trip counts (parsed from each loop
condition's bound constant):

  - FLOPs: every ``dot`` op contributes 2 * prod(result dims) * prod(lhs
    contracting dims) — matmul flops dominate these workloads; elementwise
    flops are not counted (noted under-count, typically <5%).
  - HBM bytes: per top-level op (fusion boundaries), result + operand buffer
    bytes — the standard post-fusion traffic proxy.
  - collective bytes: result buffers of all-gather / all-reduce /
    reduce-scatter / all-to-all / collective-permute (``-done`` halves of
    async pairs skipped).

All three are per-device, trip-weighted.  MODEL_FLOPS = 6·N·D (train) or
2·N·D (inference) uses active params for MoE.
"""
from __future__ import annotations

import re
from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple

import numpy as np

from .mesh import ChipPeaks, peaks

__all__ = ["RooflineReport", "analyze", "hlo_costs", "model_flops"]

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
}

_COLL_RE = re.compile(
    r"=\s+(.+?)\s+"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(-start|-done)?\(")
_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_OP_RE = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.+?)\s+([\w\-]+)\(")
# first dot operand; commas inside shape brackets / layout braces (older HLO
# dumps print full operand types, e.g. "dot(f32[64,128]{1,0} %a, ...)") don't
# terminate the match.
_DOT_ARGS_RE = re.compile(r"dot\(((?:\[[^\]]*\]|\{[^}]*\}|[^,)\[{])+)")
_CONTRACT_RE = re.compile(r"lhs_contracting_dims=\{([\d,]*)\}")
_COMP_RE = re.compile(r"^(?:ENTRY )?%?([\w.\-]+)(?:\.clone)? \([^)]*\)", re.M)
_WHILE_RE = re.compile(r"while\(.*?condition=%?([\w.\-]+), body=%?([\w.\-]+)")
_CALLEE_RE = re.compile(r"(?:to_apply|body|condition|branch_computations)=\{?%?([\w.\-]+)")
_CONST_RE = re.compile(r"s32\[\]\s+constant\((\d+)\)")

_CONTROL_OPS = {"while", "conditional", "call", "tuple", "get-tuple-element",
                "parameter", "constant", "after-all", "custom-call"}


def _shape_elems_bytes(dt: str, dims: str) -> Tuple[int, int]:
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n, n * _DTYPE_BYTES.get(dt, 0)


def _buffer_bytes(type_str: str) -> int:
    return sum(_shape_elems_bytes(dt, dims)[1]
               for dt, dims in _SHAPE_RE.findall(type_str))


def _split_computations(hlo_text: str) -> Dict[str, str]:
    """name -> body text, by tracking top-level brace blocks."""
    comps: Dict[str, str] = {}
    name, depth, buf = None, 0, []
    for line in hlo_text.splitlines():
        if depth == 0:
            m = _COMP_RE.match(line.strip())
            if m and line.rstrip().endswith("{"):
                name, buf, depth = m.group(1), [line], 1
                if line.strip().startswith("ENTRY"):
                    name = "__entry__"
                continue
        else:
            depth += line.count("{") - line.count("}")
            buf.append(line)
            if depth <= 0:
                comps[name] = "\n".join(buf)
                name, depth, buf = None, 0, []
    return comps


def _trip_count(cond_text: str) -> int:
    """Scan loops lower to ``iv < N``; take the max s32 constant as N."""
    consts = [int(c) for c in _CONST_RE.findall(cond_text)]
    return max(consts) if consts else 1


def _line_costs(line: str, in_fusion: bool,
                symtab: Dict[str, str]) -> Dict[str, float]:
    """Costs contributed by a single HLO instruction line.

    ``symtab`` maps instruction names to their result type strings (operands
    are printed by name only in modern HLO dumps)."""
    out: Dict[str, float] = {}
    cm = _COLL_RE.search(line)
    if cm and cm.group(3) != "-done":
        kind = cm.group(2)
        b = _buffer_bytes(cm.group(1))
        out["collective_bytes"] = b
        out[f"coll:{kind}"] = b
        return out

    m = _OP_RE.match(line)
    if not m:
        return out
    types, op = m.group(2), m.group(3)

    if op == "dot":
        contract = _CONTRACT_RE.search(line)
        result_elems = sum(_shape_elems_bytes(dt, dims)[0]
                           for dt, dims in _SHAPE_RE.findall(types))
        k = 1
        am = _DOT_ARGS_RE.search(line)
        lhs_type = None
        if am:
            tok = am.group(1).strip()
            if "[" in tok:
                lhs_type = tok
            else:
                lhs_type = symtab.get(tok.lstrip("%"))
        if lhs_type and contract:
            shapes = _SHAPE_RE.findall(lhs_type)
            if shapes:
                dimlist = [int(d) for d in shapes[0][1].split(",") if d]
                for ci in contract.group(1).split(","):
                    if ci and int(ci) < len(dimlist):
                        k *= dimlist[int(ci)]
        out["dot_flops"] = 2.0 * result_elems * k

    if not in_fusion and op not in _CONTROL_OPS:
        # post-fusion traffic proxy: result buffers of top-level ops (operand
        # traffic is the producing op's result; counting both would double).
        out["traffic_bytes"] = _buffer_bytes(types)
    return out


def hlo_costs(hlo_text: str) -> Dict[str, float]:
    """Trip-weighted per-device costs from compiled HLO text."""
    comps = _split_computations(hlo_text)
    fusion_comps = {n for n in comps if "fused" in n}

    def direct(name: str) -> Dict[str, float]:
        acc: Dict[str, float] = {}
        in_fusion = name in fusion_comps
        lines = comps[name].splitlines()
        symtab: Dict[str, str] = {}
        for line in lines:
            m = _OP_RE.match(line)
            if m:
                symtab[m.group(1)] = m.group(2)
        for line in lines:
            for k, v in _line_costs(line, in_fusion, symtab).items():
                acc[k] = acc.get(k, 0.0) + v
            # entry parameters = real HBM reads (weights/caches/batch), once
            if name == "__entry__":
                m = _OP_RE.match(line)
                if m and m.group(3) == "parameter":
                    acc["traffic_bytes"] = acc.get("traffic_bytes", 0.0) \
                        + _buffer_bytes(m.group(2))
        return acc

    cache: Dict[str, Dict[str, float]] = {}

    def total(name: str, seen=()) -> Dict[str, float]:
        if name in cache:
            return cache[name]
        if name not in comps or name in seen:
            return {}
        text = comps[name]
        acc = direct(name)
        handled = set()
        for m in _WHILE_RE.finditer(text):
            cond, body = m.group(1), m.group(2)
            trips = _trip_count(comps.get(cond, ""))
            for k, v in total(body, seen + (name,)).items():
                acc[k] = acc.get(k, 0.0) + v * trips
            handled.update({cond, body})
        for m in _CALLEE_RE.finditer(text):
            callee = m.group(1)
            if callee in handled or callee not in comps:
                continue
            for k, v in total(callee, seen + (name,)).items():
                acc[k] = acc.get(k, 0.0) + v
            handled.add(callee)
        cache[name] = acc
        return acc

    entry = "__entry__" if "__entry__" in comps else (next(iter(comps)) if comps else "")
    return total(entry) if entry else {}


def model_flops(n_params_active: int, n_tokens: int, kind: str) -> float:
    """6·N·D for a train step (fwd+bwd); 2·N·D for inference-only steps."""
    mult = 6.0 if kind == "train" else 2.0
    return mult * n_params_active * n_tokens


@dataclass
class RooflineReport:
    arch: str
    shape: str
    mesh: str
    chips: int
    device_kind: str             # key into launch.mesh.HW
    # per-device, trip-weighted, from the HLO walk
    device_flops: float          # dot flops
    device_bytes: float          # traffic proxy
    collective_bytes: float
    collectives_by_kind: Dict[str, int]
    # raw cost_analysis (loop bodies counted once — for reference only)
    ca_flops_raw: float
    ca_bytes_raw: float
    # memory_analysis (per device)
    arg_bytes: int
    temp_bytes: int
    output_bytes: int
    # model-level
    model_flops_total: float
    n_tokens: int

    @property
    def peaks(self) -> ChipPeaks:
        return peaks(self.device_kind)

    @property
    def compute_s(self) -> float:
        return self.device_flops / self.peaks.flops_bf16

    @property
    def memory_s(self) -> float:
        return self.device_bytes / self.peaks.hbm_bw

    @property
    def collective_s(self) -> float:
        return self.collective_bytes / self.peaks.ici_bw

    @property
    def dominant(self) -> str:
        terms = {"compute": self.compute_s, "memory": self.memory_s,
                 "collective": self.collective_s}
        return max(terms, key=terms.get)

    @property
    def useful_flops_ratio(self) -> float:
        """MODEL_FLOPS / global HLO flops — catches remat/redundancy waste."""
        total_hlo = self.device_flops * self.chips
        return self.model_flops_total / total_hlo if total_hlo else 0.0

    @property
    def hbm_per_device_gib(self) -> float:
        return (self.arg_bytes + self.temp_bytes) / 2**30

    @property
    def step_time_s(self) -> float:
        """No-overlap roofline estimate: max of the three terms."""
        return max(self.compute_s, self.memory_s, self.collective_s)

    def to_dict(self) -> Dict[str, Any]:
        return {
            "arch": self.arch, "shape": self.shape, "mesh": self.mesh,
            "chips": self.chips, "device_kind": self.device_kind,
            "device_flops": self.device_flops,
            "device_bytes": self.device_bytes,
            "collective_bytes": self.collective_bytes,
            "collectives_by_kind": self.collectives_by_kind,
            "ca_flops_raw": self.ca_flops_raw, "ca_bytes_raw": self.ca_bytes_raw,
            "arg_bytes": self.arg_bytes, "temp_bytes": self.temp_bytes,
            "output_bytes": self.output_bytes,
            "model_flops_total": self.model_flops_total,
            "n_tokens": self.n_tokens,
            "compute_s": self.compute_s, "memory_s": self.memory_s,
            "collective_s": self.collective_s, "dominant": self.dominant,
            "useful_flops_ratio": self.useful_flops_ratio,
            "hbm_per_device_gib": self.hbm_per_device_gib,
            "step_time_s": self.step_time_s,
        }


def analyze(
    arch: str, shape_name: str, mesh_name: str, chips: int,
    compiled, n_params_active: int, n_tokens: int, kind: str,
    device_kind: str, hlo_text: Optional[str] = None,
) -> RooflineReport:
    """``device_kind`` names the chip the program was compiled for; its
    peaks must be in ``launch.mesh.HW``."""
    peaks(device_kind)  # fail before the HLO walk, not when a term is read
    ca = compiled.cost_analysis() or {}
    ma = compiled.memory_analysis()
    text = hlo_text if hlo_text is not None else compiled.as_text()
    costs = hlo_costs(text)
    by_kind = {k.split(":", 1)[1]: int(v) for k, v in costs.items()
               if k.startswith("coll:")}
    return RooflineReport(
        arch=arch, shape=shape_name, mesh=mesh_name, chips=chips,
        device_kind=device_kind, device_flops=float(costs.get("dot_flops", 0.0)),
        device_bytes=float(costs.get("traffic_bytes", 0.0)),
        collective_bytes=float(costs.get("collective_bytes", 0.0)),
        collectives_by_kind=by_kind,
        ca_flops_raw=float(ca.get("flops", 0.0)),
        ca_bytes_raw=float(ca.get("bytes accessed", 0.0)),
        arg_bytes=int(getattr(ma, "argument_size_in_bytes", 0)),
        temp_bytes=int(getattr(ma, "temp_size_in_bytes", 0)),
        output_bytes=int(getattr(ma, "output_size_in_bytes", 0)),
        model_flops_total=model_flops(n_params_active, n_tokens, kind),
        n_tokens=n_tokens,
    )
