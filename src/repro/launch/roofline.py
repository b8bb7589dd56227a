"""Roofline-term extraction from compiled dry-run artifacts.

Three terms per (arch × shape × mesh), in seconds:

    compute    = HLO_FLOPs / (chips × 197e12 FLOP/s)         (bf16 MXU)
    memory     = HLO_bytes / (chips × 819e9 B/s)             (HBM)
    collective = Σ collective_bytes / (chips × 50e9 B/s)     (ICI per link)

HLO_FLOPs / bytes come from ``compiled.cost_analysis()``. Collective bytes
are NOT in cost_analysis — we parse the optimized HLO text and sum operand
sizes of all-gather / all-reduce / reduce-scatter / all-to-all /
collective-permute ops (output-shape bytes; a per-chip lower bound for ring
algorithms is (n-1)/n of that, which we fold into the constant).

Also reported: MODEL_FLOPS = 6·N·D (dense) or 6·N_active·D (MoE) and the
useful-compute ratio MODEL_FLOPS / HLO_FLOPs (catches remat/dispatch waste).
"""

from __future__ import annotations

import dataclasses
import re

import numpy as np

PEAK_FLOPS = 197e12      # bf16 per chip (v5e)
HBM_BW = 819e9           # bytes/s per chip
ICI_BW = 50e9            # bytes/s per link

_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2,
    "f16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
    "c64": 8, "c128": 16, "e4m3": 1, "e5m2": 1, "f8e4m3fn": 1, "f8e5m2": 1,
}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def _shape_bytes(shape_str: str) -> int:
    """'bf16[8,128]' → bytes. Tuples handled by caller via findall."""
    total = 0
    for dt, dims in _SHAPE_RE.findall(shape_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def cost_analysis_dict(compiled) -> dict:
    """``compiled.cost_analysis()`` as a flat dict ({} when XLA reports
    nothing)."""
    return compiled.cost_analysis() or {}


def collective_bytes(hlo_text: str) -> dict[str, int]:
    """Sum output bytes of every collective op in (optimized) HLO text."""
    out: dict[str, int] = {c: 0 for c in _COLLECTIVES}
    for line in hlo_text.splitlines():
        s = line.strip()
        # result-shape = op-name(...); match ops like:
        #   %ar = bf16[1024,512]{1,0} all-reduce(...), replica_groups=...
        m = re.match(r"%?[\w.\-]+\s*=\s*(\([^)]*\)|[\w\[\],{}\s]+?)\s*"
                     r"(all-gather-start|all-gather|all-reduce-start|all-reduce|"
                     r"reduce-scatter|all-to-all|collective-permute-start|"
                     r"collective-permute)\(", s)
        if not m:
            continue
        shape_str, op = m.group(1), m.group(2)
        op = op.replace("-start", "")
        out[op] += _shape_bytes(shape_str)
    return out


@dataclasses.dataclass
class Roofline:
    arch: str
    cell: str
    mesh: str
    chips: int
    hlo_flops: float
    hlo_bytes: float
    coll_bytes: dict[str, int]
    model_flops: float

    # cost_analysis() is evaluated on the SPMD-partitioned per-device module
    # (verified empirically: a (2048³) matmul sharded 16 ways reports 1/16 of
    # the global FLOPs), so the terms below are per-chip — no ÷chips.
    @property
    def t_compute(self) -> float:
        return self.hlo_flops / PEAK_FLOPS

    @property
    def t_memory(self) -> float:
        return self.hlo_bytes / HBM_BW

    @property
    def t_collective(self) -> float:
        return sum(self.coll_bytes.values()) / ICI_BW

    @property
    def bottleneck(self) -> str:
        terms = {"compute": self.t_compute, "memory": self.t_memory,
                 "collective": self.t_collective}
        return max(terms, key=terms.get)

    @property
    def useful_ratio(self) -> float:
        """MODEL_FLOPS (global) vs compiled FLOPs (per-device × chips)."""
        total = self.hlo_flops * self.chips
        return self.model_flops / total if total else 0.0

    @property
    def roofline_fraction(self) -> float:
        """compute-term / max-term: 1.0 = perfectly compute-bound."""
        t = max(self.t_compute, self.t_memory, self.t_collective)
        return self.t_compute / t if t else 0.0

    def to_dict(self) -> dict:
        return {
            "arch": self.arch, "cell": self.cell, "mesh": self.mesh,
            "chips": self.chips,
            "hlo_flops": self.hlo_flops, "hlo_bytes": self.hlo_bytes,
            "coll_bytes": self.coll_bytes,
            "model_flops": self.model_flops,
            "t_compute_s": self.t_compute, "t_memory_s": self.t_memory,
            "t_collective_s": self.t_collective,
            "bottleneck": self.bottleneck,
            "useful_ratio": self.useful_ratio,
            "roofline_fraction": self.roofline_fraction,
        }


def model_flops(cfg, cell) -> float:
    """6·N·D with N = active params (MoE counts top-k experts only); decode
    cells use D = global_batch tokens (one step)."""
    from repro.launch.steps import abstract_params

    params = abstract_params(cfg)
    total = 0
    expert_extra = 0
    for path, leaf in _iter_paths(params):
        n = int(np.prod(leaf.shape))
        total += n
        if "moe/w_" in path:
            expert_extra += n
    if cfg.num_experts:
        active = total - expert_extra + expert_extra * (
            cfg.num_experts_per_tok / cfg.num_experts)
    else:
        active = total
    if cell.kind == "train":
        tokens = cell.global_batch * cell.seq_len
        return 6.0 * active * tokens
    if cell.kind == "prefill":
        tokens = cell.global_batch * cell.seq_len
        return 2.0 * active * tokens
    return 2.0 * active * cell.global_batch  # decode: one token per sequence


def _iter_paths(tree, prefix=""):
    if isinstance(tree, dict):
        for k, v in tree.items():
            yield from _iter_paths(v, f"{prefix}/{k}" if prefix else k)
    elif isinstance(tree, (list, tuple)):
        for i, v in enumerate(tree):
            yield from _iter_paths(v, f"{prefix}/{i}")
    else:
        yield prefix, tree


def build_roofline(cfg, cell, mesh_name: str, chips: int, cost: dict,
                   hlo_text: str) -> Roofline:
    return Roofline(
        arch=cfg.name,
        cell=cell.name,
        mesh=mesh_name,
        chips=chips,
        hlo_flops=float(cost.get("flops", 0.0)),
        hlo_bytes=float(cost.get("bytes accessed", 0.0)),
        coll_bytes=collective_bytes(hlo_text),
        model_flops=model_flops(cfg, cell),
    )


def format_table(rows: list[dict]) -> str:
    hdr = (f"{'arch':<16}{'cell':<13}{'mesh':<10}{'t_comp(ms)':>11}"
           f"{'t_mem(ms)':>11}{'t_coll(ms)':>11}{'bound':>11}"
           f"{'useful':>8}{'roofl%':>8}")
    lines = [hdr, "-" * len(hdr)]
    for r in rows:
        lines.append(
            f"{r['arch']:<16}{r['cell']:<13}{r['mesh']:<10}"
            f"{r['t_compute_s']*1e3:>11.3f}{r['t_memory_s']*1e3:>11.3f}"
            f"{r['t_collective_s']*1e3:>11.3f}{r['bottleneck']:>11}"
            f"{r['useful_ratio']:>8.3f}{r['roofline_fraction']*100:>8.1f}")
    return "\n".join(lines)
