"""Roofline analysis of a compiled program (assignment §Roofline).

Three terms per (arch × shape × mesh), all derived WITHOUT hardware:

  compute    = HLO_FLOPs(per device)      / peak_FLOPs
  memory     = HLO_bytes(per device)      / HBM_bw
  collective = collective_bytes(per dev)  / link_bw

Sources: ``compiled.cost_analysis()`` for FLOPs/bytes (JAX reports the
per-device partitioned module); collective bytes are NOT in cost_analysis,
so we parse the optimized HLO text and sum the output-shape bytes of every
all-gather / all-reduce / reduce-scatter / all-to-all / collective-permute
(all-reduce counted twice: reduce-scatter + all-gather equivalent).

Collectives are additionally classified intra- vs inter-pod by inspecting
``source_target_pairs`` / ``replica_groups`` against the pod boundary —
this is what lets EXPERIMENTS.md verify the paper's topology-aware claim
(SwiftFusion keeps the high-volume Ring traffic inside the pod).
"""
from __future__ import annotations

import dataclasses
import re

# TPU v5e-class hardware constants (assignment-provided)
PEAK_FLOPS = 197e12  # bf16 FLOP/s per chip
HBM_BW = 819e9  # B/s per chip
LINK_BW = 50e9  # B/s per ICI link

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1, "c64": 8, "c128": 16,
}

_COLLECTIVE_RE = re.compile(
    r"^\s*\S+\s*=\s*(?:\([^)]*\)|(\w+)\[([\d,]*)\][^ ]*)\s*"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\(",
)
_TUPLE_RE = re.compile(
    r"^\s*\S+\s*=\s*\((.*?)\)\s*"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\(",
)
_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_PAIRS_RE = re.compile(r"source_target_pairs=\{((?:\{\d+,\d+\},?)*)\}")
_GROUPS_RE = re.compile(r"replica_groups=\{((?:\{[\d,]+\},?)*)\}")


def _shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * _DTYPE_BYTES.get(dtype, 4)


def _crosses_pod(line: str, pod_size: int) -> bool:
    m = _PAIRS_RE.search(line)
    if m:
        for pair in re.findall(r"\{(\d+),(\d+)\}", "{" + m.group(1) + "}"):
            a, b = int(pair[0]), int(pair[1])
            if a // pod_size != b // pod_size:
                return True
        return False
    m = _GROUPS_RE.search(line)
    if m:
        for grp in re.findall(r"\{([\d,]+)\}", "{" + m.group(1) + "}"):
            ranks = [int(r) for r in grp.split(",")]
            if len({r // pod_size for r in ranks}) > 1:
                return True
        return False
    return False


@dataclasses.dataclass
class CollectiveStats:
    bytes_total: int = 0
    bytes_inter_pod: int = 0
    counts: dict = dataclasses.field(default_factory=dict)

    def add(self, kind: str, nbytes: int, inter: bool) -> None:
        self.bytes_total += nbytes
        if inter:
            self.bytes_inter_pod += nbytes
        key = kind + ("/inter" if inter else "/intra")
        self.counts[key] = self.counts.get(key, 0) + 1


def parse_collectives(hlo_text: str, pod_size: int = 1 << 30) -> CollectiveStats:
    """Sum per-device collective bytes from partitioned optimized HLO."""
    stats = CollectiveStats()
    for line in hlo_text.splitlines():
        if "-start(" not in line and not re.search(
            r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)\(",
            line,
        ):
            continue
        if "-done(" in line or "-done " in line:
            continue
        m = _COLLECTIVE_RE.match(line)
        kind = None
        nbytes = 0
        if m and m.group(1):
            kind = m.group(3)
            nbytes = _shape_bytes(m.group(1), m.group(2))
        else:
            mt = _TUPLE_RE.match(line)
            if mt:
                kind = mt.group(2)
                # tuple shapes (async start ops): count the largest element
                # (the payload buffer), not control scalars
                sizes = [_shape_bytes(d, s) for d, s in _SHAPE_RE.findall(mt.group(1))]
                nbytes = max(sizes) if sizes else 0
        if not kind:
            continue
        if kind == "all-reduce":
            nbytes *= 2  # RS + AG equivalent wire traffic
        stats.add(kind, nbytes, _crosses_pod(line, pod_size))
    return stats


@dataclasses.dataclass
class Roofline:
    flops_per_device: float
    bytes_per_device: float
    collective_bytes: float
    collective_inter_pod: float
    t_compute: float
    t_memory: float
    t_collective: float
    bottleneck: str
    model_flops: float  # 6·N·D (dense) or 6·N_active·D
    useful_ratio: float  # MODEL_FLOPS / (HLO_FLOPs × chips)

    def as_dict(self):
        return dataclasses.asdict(self)


def analyze(cost: dict, hlo_text: str, *, chips: int, pod_size: int,
            model_flops: float) -> Roofline:
    coll = parse_collectives(hlo_text, pod_size)
    return analyze_from_terms(
        flops=float(cost.get("flops", 0.0)),
        byts=float(cost.get("bytes accessed", 0.0)),
        coll_bytes=float(coll.bytes_total),
        coll_inter=float(coll.bytes_inter_pod),
        chips=chips,
        model_flops=model_flops,
    )


def analyze_from_terms(*, flops: float, byts: float, coll_bytes: float,
                       coll_inter: float, chips: int,
                       model_flops: float) -> Roofline:
    t_c = flops / PEAK_FLOPS
    t_m = byts / HBM_BW
    t_x = coll_bytes / LINK_BW
    terms = {"compute": t_c, "memory": t_m, "collective": t_x}
    bottleneck = max(terms, key=terms.get)
    total_hlo_flops = flops * chips
    return Roofline(
        flops_per_device=flops,
        bytes_per_device=byts,
        collective_bytes=coll_bytes,
        collective_inter_pod=coll_inter,
        t_compute=t_c,
        t_memory=t_m,
        t_collective=t_x,
        bottleneck=bottleneck,
        model_flops=model_flops,
        useful_ratio=(model_flops / total_hlo_flops) if total_hlo_flops else 0.0,
    )
