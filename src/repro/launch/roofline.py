"""Roofline-term derivation from compiled XLA artifacts (no real hardware).

Per (arch x shape x mesh) cell:
    compute term    = HLO_FLOPs_per_device / peak_FLOPs_per_chip
    memory term     = HLO_bytes_per_device / HBM_bw
    collective term = collective_wire_bytes_per_device / link_bw

Sources: ``compiled.cost_analysis()`` reports per-device (per-partition)
FLOPs and bytes; collective bytes are parsed from the post-SPMD optimized
HLO (``compiled.as_text()``) since cost_analysis excludes them.  Wire-byte
factors use ring-algorithm costs: all-reduce moves ~2x the buffer over the
slowest link, all-gather / reduce-scatter ~1x, all-to-all ~1x,
collective-permute 1x.

Hardware constants come from ``PEAKS``, one row per ``Device.device_kind``;
a device kind missing from it is an error, never a default.

MODEL_FLOPS sanity ratio: 6*N*D (train) / 2*N*D (prefill) / 2*N*B (decode),
with N_active for MoE — the fraction of compiled compute that is "useful"
(catches remat recompute, dispatch overheads, padded heads/vocab waste).
"""
from __future__ import annotations

import dataclasses
import re
from typing import Dict, Optional


@dataclasses.dataclass(frozen=True)
class ChipPeaks:
    """Published per-chip peaks."""
    bf16_flops: float            # FLOP/s
    hbm_bytes_per_s: float
    link_bytes_per_s: float      # one ICI link


# Source: Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 16 GB
# HBM at 819 GB/s, 1,600 Gbit/s of chip-to-chip interconnect over 4 links
# (50 GB/s per link).
PEAKS = {
    "TPU v5 lite": ChipPeaks(bf16_flops=197e12, hbm_bytes_per_s=819e9,
                             link_bytes_per_s=50e9),
}


def chip_peaks(device_kind: str) -> ChipPeaks:
    """The ``PEAKS`` row for ``device_kind`` (``jax.Device.device_kind``)."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no published peaks for device kind "
                         f"{device_kind!r}; known: {sorted(PEAKS)}") from None


_DTYPE_BYTES = {
    "f64": 8, "s64": 8, "u64": 8,
    "f32": 4, "s32": 4, "u32": 4,
    "bf16": 2, "f16": 2, "s16": 2, "u16": 2,
    "f8e4m3fn": 1, "f8e5m2": 1, "s8": 1, "u8": 1, "pred": 1,
    "s4": 0.5, "u4": 0.5,
}

_COLL_RE = re.compile(
    r"(\(?[a-z0-9,\[\]{}() ]*?\)?)\s+"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\(", re.IGNORECASE)

_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")

_WIRE_FACTOR = {
    "all-reduce": 2.0,
    "all-gather": 1.0,
    "reduce-scatter": 1.0,
    "all-to-all": 1.0,
    "collective-permute": 1.0,
}


def _shape_bytes(shape_str: str) -> float:
    """Sum bytes over every 'dtype[dims]' group in a (possibly tuple) shape."""
    total = 0.0
    for dt, dims in _SHAPE_RE.findall(shape_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                if d:
                    n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def parse_collectives(hlo_text: str) -> Dict[str, float]:
    """Per-op-kind result bytes (per device) from optimized HLO text."""
    out: Dict[str, float] = {}
    for line in hlo_text.splitlines():
        line = line.strip()
        if "-done" in line.split("=")[-1][:60]:
            continue  # async *-done repeats the shape of the *-start
        m = re.search(
            r"=\s+(\(?.*?\)?)\s+"
            r"(all-reduce|all-gather|reduce-scatter|all-to-all|"
            r"collective-permute)(?:-start)?\(", line)
        if not m:
            continue
        shape_str, kind = m.group(1), m.group(2).lower()
        b = _shape_bytes(shape_str)
        out[kind] = out.get(kind, 0.0) + b
    return out


@dataclasses.dataclass
class RooflineTerms:
    flops: float
    bytes_accessed: float
    collective_bytes: float          # result bytes, per device
    collective_wire_bytes: float     # ring-cost wire bytes
    compute_s: float
    memory_s: float
    collective_s: float
    dominant: str
    model_flops_per_device: Optional[float] = None
    useful_ratio: Optional[float] = None

    def as_dict(self):
        return dataclasses.asdict(self)


def derive(cost_analysis: dict, hlo_text: str, device_kind: str,
           model_flops_per_device: Optional[float] = None,
           hlo_analysis: Optional[dict] = None) -> RooflineTerms:
    """Roofline terms against the peaks of ``device_kind``.

    Prefer the trip-count-aware analyzer (repro.launch.hlo_analysis);
    XLA's cost_analysis counts while bodies once and is kept only as a
    cross-reference."""
    peaks = chip_peaks(device_kind)
    if hlo_analysis is None:
        from repro.launch import hlo_analysis as ha
        hlo_analysis = ha.analyze(hlo_text)
    flops = float(hlo_analysis["flops"])
    bytes_accessed = float(hlo_analysis["bytes"])
    colls = hlo_analysis["collectives"]
    coll_bytes = sum(colls.values())
    wire = float(hlo_analysis["collective_wire_bytes"])
    compute_s = flops / peaks.bf16_flops
    memory_s = bytes_accessed / peaks.hbm_bytes_per_s
    coll_s = wire / peaks.link_bytes_per_s
    terms = {"compute": compute_s, "memory": memory_s, "collective": coll_s}
    dominant = max(terms, key=terms.get)
    ratio = (model_flops_per_device / flops
             if model_flops_per_device and flops else None)
    return RooflineTerms(
        flops=flops, bytes_accessed=bytes_accessed,
        collective_bytes=coll_bytes, collective_wire_bytes=wire,
        compute_s=compute_s, memory_s=memory_s, collective_s=coll_s,
        dominant=dominant, model_flops_per_device=model_flops_per_device,
        useful_ratio=ratio)


# --------------------------------------------------------------------------- #
# MODEL_FLOPS estimation
# --------------------------------------------------------------------------- #
def count_params(abstract_params) -> int:
    import jax
    return int(sum(x.size for x in jax.tree_util.tree_leaves(abstract_params)))


def active_params(cfg, abstract_params) -> int:
    """N_active: for MoE, experts count at top_k / n_experts utilization."""
    import jax
    total = 0
    flat = jax.tree_util.tree_leaves_with_path(abstract_params)
    for path, leaf in flat:
        keys = [getattr(p, "key", getattr(p, "name", "")) for p in path]
        frac = 1.0
        if cfg.family == "moe_lm" and any(
                str(k).startswith("e_") for k in keys):
            frac = cfg.top_k / max(cfg.n_experts, 1)
        total += leaf.size * frac
    return int(total)


def _attention_flops(cfg, kind: str, B: int, S: int) -> float:
    """Quadratic attention term missing from 6*N*D (PaLM-appendix style).

    fwd = 4 * B * S^2 * (H*hd) / 2 (causal); train multiplies by 4
    (fwd + 2x bwd + remat re-fwd); decode reads S keys for 1 query."""
    H = getattr(cfg, "padded_heads", 0) or 0
    hd = cfg.head_dim or 0
    if H == 0 or hd == 0:
        return 0.0
    if cfg.family == "hybrid":
        # only 1-in-3 layers attend, over a bounded window
        L_attn = cfg.n_layers // 3
        span = min(cfg.attn_window, S)
        per_layer_fwd = 4.0 * B * S * span * H * hd / 2.0
    elif cfg.family == "encdec":
        L_attn = cfg.n_enc_layers + 2 * cfg.n_dec_layers
        per_layer_fwd = 4.0 * B * S * S * H * hd / 2.0
    elif cfg.family in ("ssm",):
        return 0.0
    else:
        L_attn = cfg.n_layers
        per_layer_fwd = 4.0 * B * S * S * H * hd / 2.0
    if kind == "train":
        return 4.0 * L_attn * per_layer_fwd
    if kind == "prefill":
        return L_attn * per_layer_fwd
    # decode: one query over the full cache
    return L_attn * 4.0 * B * S * H * hd


def model_flops(cfg, abstract_params, kind: str, global_batch: int,
                seq_len: int, n_devices: int) -> float:
    n_act = active_params(cfg, abstract_params)
    if kind == "train":
        total = 6.0 * n_act * global_batch * seq_len
    elif kind == "prefill":
        total = 2.0 * n_act * global_batch * seq_len
    else:  # decode: one token per sequence
        total = 2.0 * n_act * global_batch
    total += _attention_flops(cfg, kind, global_batch, seq_len)
    return total / n_devices
