"""What every cell shares: its files, the device check, compile counting,
the metric readers and the result line.

A cell is ``bench/workloads/<cell>.json``; it names its configuration
``bench/configs/<config>.json`` and its driver ``bench/drivers/<driver>.py``.
Each metric of ``BENCHMARK.json`` is read by ``bench/metrics/<metric>.py``.
Nothing here knows a cell, a configuration or a metric by name.
"""
from __future__ import annotations

import importlib.util
import json
import math
import sys
from pathlib import Path
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def _read_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_benchmark() -> dict:
    return _read_json(ROOT / "BENCHMARK.json")


def load_cell(name: str) -> dict:
    """The cell's workload file with its configuration under ``"cfg"``."""
    path = BENCH / "workloads" / f"{name}.json"
    if not path.is_file():
        raise SystemExit(f"no workload file {path}")
    cell = _read_json(path)
    cell["name"] = name
    cell["cfg"] = _read_json(BENCH / "configs" / f"{cell['config']}.json")
    return cell


def traffic_params(cell: dict, smoke: bool) -> dict:
    """The cell's traffic, with its rehearsal sizes laid over it when
    ``smoke`` (nested groups merged key by key)."""
    t = dict(cell["traffic"])
    if smoke:
        for k, v in cell["smoke"].items():
            t[k] = dict(t[k], **v) if isinstance(v, dict) and k in t else v
    return t


def model_numbers(cell: dict, smoke: bool) -> dict:
    """The configuration's model numbers (rehearsal ones with ``smoke``)."""
    cfg = cell["cfg"]
    return dict(cfg["model"], **cfg["smoke"]["model"]) if smoke \
        else dict(cfg["model"])


def model_config(cell: dict, smoke: bool):
    """The program's ``ModelConfig``: the registry entry the configuration
    names, with its overrides (the registry's rehearsal entry and the
    rehearsal overrides with ``smoke``)."""
    import dataclasses
    from repro.configs import get_config, get_smoke_config
    cfg = cell["cfg"]
    if smoke:
        return dataclasses.replace(get_smoke_config(cfg["registry"]),
                                   **cfg["smoke"].get("overrides", {}))
    return dataclasses.replace(get_config(cfg["registry"]),
                               **cfg.get("overrides", {}))


@jax.jit
def _norms(tree):
    return jax.tree_util.tree_map(
        lambda v: jnp.linalg.norm(v.astype(jnp.float32).ravel()), tree)


def leaf_norms(tree) -> Dict[str, float]:
    """``{path: norm}`` of every leaf of a pytree of arrays, in float32."""
    flat, _ = jax.tree_util.tree_flatten_with_path(_norms(tree))
    return {jax.tree_util.keystr(p): float(n) for p, n in flat}


def load_module(path: Path):
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def cell_metrics(bench: dict, cell: str, trace: bool) -> List[dict]:
    """The metric entries of ``BENCHMARK.json`` this run reports: the
    cell's end-to-end metrics without tracing, its per-layer ones with."""
    group = bench["per_layer"] if trace else bench["end_to_end"]
    return [m for m in group if cell in m.get("workloads", [cell])]


def read_metrics(entries: List[dict], record: dict) -> Dict[str, dict]:
    """Run each metric's reader on the run record; a reader that finds
    nothing to read returns None and its metric is left out."""
    out = {}
    for m in entries:
        reader = load_module(BENCH / "metrics" / f"{m['name']}.py")
        value = reader.read(record)
        if value is None:
            continue
        value = float(value)
        if not math.isfinite(value):
            raise ValueError(f"metric {m['name']} read {value}")
        out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


# ---------------------------------------------------------------------- #
# device
# ---------------------------------------------------------------------- #
def require_chips(n: int, allow_cpu: bool = False):
    """The devices of this run; exits non-zero without ``n`` TPU chips."""
    devices = jax.devices()
    if allow_cpu:
        return devices[:1]
    if devices[0].platform != "tpu":
        print(f"bench: no TPU (JAX sees {devices[0].platform}); no result",
              file=sys.stderr)
        raise SystemExit(2)
    if len(devices) < n:
        print(f"bench: the cell needs {n} chips, JAX sees {len(devices)}",
              file=sys.stderr)
        raise SystemExit(2)
    return devices[:n]


def memory_peak(devices) -> Optional[int]:
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


def device_info(devices, peak: Optional[int]) -> dict:
    d = devices[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices), "memory_peak_bytes": peak}


class CompileLog:
    """Backend-compile seconds, compiles and persistent-cache hits, from
    JAX's monitoring events, read as differences between snapshots."""

    def __init__(self):
        from jax import monitoring
        self.seconds, self.compiles, self.cache_hits = 0.0, 0, 0
        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs
            self.compiles += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def snapshot(self):
        return self.seconds, self.compiles, self.cache_hits

    def since(self, snap) -> dict:
        s, c, h = snap
        return {"compile_s": self.seconds - s, "compiles": self.compiles - c,
                "cache_hits": self.cache_hits - h}


class BackendRecord:
    """Every ``repro.quant.backend.get_impl`` resolution made while active:
    the dispatcher falls back to ``ref`` silently when a backend lacks a
    format, which would take the kernels off the measured path."""

    def __init__(self):
        from repro.quant import backend as qb
        self._qb, self._orig, self.calls = qb, qb.get_impl, []

    def __enter__(self):
        def recording(op, fmt, backend=None):
            impl, actual = self._orig(op, fmt, backend)
            self.calls.append((op, fmt, self._qb.resolve_backend(backend),
                               actual))
            return impl, actual
        self._qb.get_impl = recording
        return self

    def __exit__(self, *exc):
        self._qb.get_impl = self._orig

    def fallbacks(self) -> int:
        return sum(1 for c in self.calls if c[2] != c[3])


# ---------------------------------------------------------------------- #
# small statistics
# ---------------------------------------------------------------------- #
def nearest_rank(values, q: float) -> float:
    """The ``q`` quantile (0..1) by nearest rank: the smallest value with at
    least a ``q`` share of the values at or below it.  +inf counts as the
    largest value."""
    vals = sorted(values)
    if not vals:
        raise ValueError("quantile of no values")
    k = max(1, math.ceil(q * len(vals)))
    return vals[k - 1]


def worst_leaf_gap(prog: Dict[str, float], ref: Dict[str, float],
                   keep: Optional[List[str]] = None) -> float:
    """Largest ``|prog - ref|`` of per-leaf norms, against the larger of
    the reference's norm of that leaf and its median leaf norm."""
    names = keep if keep is not None else sorted(ref)
    med = sorted(ref.values())[len(ref) // 2]
    return max(abs(prog[n] - ref[n]) / max(ref[n], med, 1e-30)
               for n in names)
