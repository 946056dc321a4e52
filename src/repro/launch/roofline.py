"""Roofline analysis from compiled (dry-run) artifacts — no hardware needed.

Three terms per (arch x shape x mesh), in seconds:

    compute    = HLO_FLOPs_per_device / peak_FLOP/s_per_chip
    memory     = HLO_bytes_per_device / HBM_bandwidth_per_chip
    collective = ring-model wire bytes per device / ICI link bandwidth

`cost_analysis()` reports per-partition FLOPs/bytes (post-SPMD HLO), so the
spec's "/ chips" division is already applied. Collective bytes are NOT in
cost_analysis: we parse the post-optimization HLO text, sum the result sizes
of every all-gather / all-reduce / reduce-scatter / all-to-all /
collective-permute, resolve each op's replica-group size, and apply ring
transfer factors (AR: 2S(G-1)/G; AG/A2A: S(G-1)/G; RS: operand (G-1)/G;
permute: S).

Hardware peaks come from `PEAKS`, keyed by the `device_kind` JAX reports;
a chip that is not in the table is an error, not a default.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Dict, Tuple


@dataclasses.dataclass(frozen=True)
class ChipPeaks:
    flops: float         # bf16 FLOP/s per chip
    hbm_bw: float        # HBM bytes/s per chip
    ici_bw: float        # interconnect bytes/s per link
    source: str


PEAKS: Dict[str, ChipPeaks] = {
    "TPU v5 lite": ChipPeaks(
        flops=197e12, hbm_bw=819e9, ici_bw=50e9,
        source="Google Cloud documentation, 'TPU v5e': 197 TFLOP/s bf16, "
               "16 GB HBM at 819 GB/s, 1,600 Gbit/s interchip interconnect "
               "over 4 links (50 GB/s per link)"),
}


def peaks_for(device_kind: str) -> ChipPeaks:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no peaks for device kind {device_kind!r}; known: "
                         f"{sorted(PEAKS)}") from None

_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2,
    "s32": 4, "u32": 4, "s64": 8, "u64": 8, "f8e4m3fn": 1, "f8e5m2": 1,
    "bf16": 2, "f16": 2, "f32": 4, "f64": 8, "c64": 8, "c128": 16,
}

_COLL_RE = re.compile(
    r"=\s*(?P<type>\([^)]*\)|[a-z0-9]+\[[0-9,]*\](?:\{[^}]*\})?)\s*"
    r"(?P<op>all-reduce|all-gather|reduce-scatter|all-to-all|"
    r"collective-permute)(?P<start>-start)?\(")

_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")
_GROUPS_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_GROUPS_BRACE_RE = re.compile(r"replica_groups=\{\{([0-9, ]+)\}")
_PAIRS_RE = re.compile(r"source_target_pairs=\{")


def _shape_bytes(type_str: str) -> int:
    total = 0
    for dtype, dims in _SHAPE_RE.findall(type_str):
        if dtype not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d.strip():
                n *= int(d)
        total += n * _DTYPE_BYTES[dtype]
    return total


def _group_size(line: str, default: int) -> int:
    m = _GROUPS_RE.search(line)
    if m:
        return int(m.group(1))
    m = _GROUPS_BRACE_RE.search(line)
    if m:
        return len([x for x in m.group(1).split(",") if x.strip()])
    return default


@dataclasses.dataclass
class CollectiveStats:
    count: int = 0
    result_bytes: int = 0
    wire_bytes: float = 0.0    # ring-model, per device

    def to_json(self):
        return dataclasses.asdict(self)


def parse_collectives(hlo_text: str, n_devices: int
                      ) -> Dict[str, CollectiveStats]:
    """Sum collective op sizes from post-optimization (per-partition) HLO."""
    out: Dict[str, CollectiveStats] = {}
    for line in hlo_text.splitlines():
        m = _COLL_RE.search(line)
        if m is None:
            continue
        op = m.group("op")
        rb = _shape_bytes(m.group("type"))
        g = _group_size(line, n_devices)
        if op == "all-reduce":
            wire = 2.0 * rb * (g - 1) / max(g, 1)
        elif op == "all-gather":
            wire = rb * (g - 1) / max(g, 1)
        elif op == "reduce-scatter":
            wire = rb * (g - 1)           # operand = result * g
        elif op == "all-to-all":
            wire = rb * (g - 1) / max(g, 1)
        else:                             # collective-permute
            wire = float(rb)
        st = out.setdefault(op, CollectiveStats())
        st.count += 1
        st.result_bytes += rb
        st.wire_bytes += wire
    return out


@dataclasses.dataclass
class Roofline:
    arch: str
    shape: str
    mesh: str
    n_devices: int
    flops_per_device: float
    bytes_per_device: float
    wire_bytes_per_device: float
    collectives: Dict[str, Dict]
    t_compute: float
    t_memory: float
    t_collective: float
    bottleneck: str
    model_flops_global: float
    model_flops_ratio: float          # model_flops / (hlo_flops * chips)
    memory_stats: Dict
    variant: str = "baseline"

    def to_json(self) -> Dict:
        return dataclasses.asdict(self)

    def summary(self) -> str:
        return (f"{self.arch:26s} {self.shape:12s} {self.mesh:9s} "
                f"C={self.t_compute * 1e3:9.3f}ms "
                f"M={self.t_memory * 1e3:9.3f}ms "
                f"X={self.t_collective * 1e3:9.3f}ms "
                f"-> {self.bottleneck:10s} useful={self.model_flops_ratio:6.1%}")


def model_flops(cfg, shape) -> float:
    """MODEL_FLOPS: 6·N·D train, 2·N·D prefill, 2·N·B decode (N = active)."""
    n_active = cfg.active_param_count()
    if shape.kind == "train":
        return 6.0 * n_active * shape.global_batch * shape.seq_len
    if shape.kind == "prefill":
        return 2.0 * n_active * shape.global_batch * shape.seq_len
    return 2.0 * n_active * shape.global_batch      # one token per sequence


def extract_costs(compiled, n_devices: int
                  ) -> Tuple[float, float, Dict[str, CollectiveStats]]:
    """(flops, bytes, collectives) for ONE compiled module (per-partition).

    NOTE: XLA cost analysis counts a while-loop body ONCE regardless of trip
    count, so for scan-over-layers models these raw numbers undercount —
    use `extrapolate_costs` with reduced-depth clones (see dryrun.py).
    """
    ca = compiled.cost_analysis()
    if isinstance(ca, list):
        ca = ca[0]
    flops = float(ca.get("flops", 0.0))
    byts = float(ca.get("bytes accessed", 0.0))
    colls = parse_collectives(compiled.as_text(), n_devices)
    return flops, byts, colls


def extrapolate_costs(costs_g2, costs_g4, g2: int, g4: int, g_full: int
                      ) -> Tuple[float, float, Dict[str, CollectiveStats]]:
    """Linear depth extrapolation: cost(G) = base + G * per_group.

    Scan-over-layer-groups models are exactly depth-linear (homogeneous
    groups), so two reduced-depth compiles (g2 < g4 groups) recover both the
    loop-invariant base (embed/unembed/optimizer tails) and the per-group
    slope that XLA's while-loop cost analysis drops.
    """
    f2, b2, c2 = costs_g2
    f4, b4, c4 = costs_g4
    span = g4 - g2
    extra = g_full - g2
    flops = f2 + (f4 - f2) / span * extra
    byts = b2 + (b4 - b2) / span * extra
    colls: Dict[str, CollectiveStats] = {}
    for kind in set(c2) | set(c4):
        a = c2.get(kind, CollectiveStats())
        b = c4.get(kind, CollectiveStats())
        colls[kind] = CollectiveStats(
            count=int(round(a.count + (b.count - a.count) / span * extra)),
            result_bytes=int(a.result_bytes
                             + (b.result_bytes - a.result_bytes) / span * extra),
            wire_bytes=a.wire_bytes + (b.wire_bytes - a.wire_bytes) / span * extra)
    return flops, byts, colls


def analyze(compiled, cfg, shape, mesh_name: str, n_devices: int,
            device_kind: str, variant: str = "baseline", costs=None,
            memory_compiled=None) -> Roofline:
    peaks = peaks_for(device_kind)
    flops, byts, colls = (costs if costs is not None
                          else extract_costs(compiled, n_devices))
    wire = sum(c.wire_bytes for c in colls.values())
    t_c = flops / peaks.flops
    t_m = byts / peaks.hbm_bw
    t_x = wire / peaks.ici_bw
    bottleneck = max((("compute", t_c), ("memory", t_m),
                      ("collective", t_x)), key=lambda kv: kv[1])[0]
    mf = model_flops(cfg, shape)
    ratio = mf / max(flops * n_devices, 1.0)
    try:
        ma = (memory_compiled or compiled).memory_analysis()
        mem = {"argument_bytes": int(ma.argument_size_in_bytes),
               "output_bytes": int(ma.output_size_in_bytes),
               "temp_bytes": int(ma.temp_size_in_bytes),
               "alias_bytes": int(ma.alias_size_in_bytes)}
    except Exception as e:  # noqa: BLE001 — backend-dependent
        mem = {"error": str(e)}
    return Roofline(
        arch=cfg.name, shape=shape.name, mesh=mesh_name, n_devices=n_devices,
        flops_per_device=flops, bytes_per_device=byts,
        wire_bytes_per_device=wire,
        collectives={k: v.to_json() for k, v in colls.items()},
        t_compute=t_c, t_memory=t_m, t_collective=t_x, bottleneck=bottleneck,
        model_flops_global=mf, model_flops_ratio=ratio, memory_stats=mem,
        variant=variant)
