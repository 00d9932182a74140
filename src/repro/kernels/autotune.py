"""Block-size autotuner for the Pallas kernels, roofline-driven.

The kernel wrappers historically hard-coded tile sizes (``block_n=256``,
``block_t=512``, ...) — fine for one shape, wrong for the rest: a 100-row
router tile padded to 256 wastes 60% of the MXU issue slots, and a short
KV cache swept with 512-wide tiles pays a whole extra grid step of
launch overhead. This module picks the tile per ``(kernel, dtype, dims)``
instead:

1. **Analytic pass** — every candidate is scored against the TPU v5e
   roofline (compute at ``PEAK_FLOPS``, traffic at ``HBM_BW``) including
   the padding waste its grid would execute and a fixed per-grid-step
   launch overhead. This is deterministic, instant, and what the serving
   engine uses.
2. **Measured pass (optional)** — :func:`tune` times each candidate with
   a caller-supplied closure (see ``benchmarks/kernels_bench.py``) and
   overrides the analytic choice. Interpret-mode wall times measure the
   Python emulator, so measurement is only meaningful on a real TPU,
   where the kernels are compiled; the benches use it to produce the
   published tuning tables.

Choices land in a process-level cache and can be persisted/loaded as
JSON (``save_cache`` / ``load_cache``) so a tuned table ships with a
deployment.

This module also owns the v5e hardware constants; ``benchmarks/roofline``
imports them from here so src/ never depends on benchmarks/.
"""
from __future__ import annotations

import json
import math
from pathlib import Path
from typing import Callable, Dict, Iterable, Optional, Tuple

# TPU v5e hardware constants (per chip), from the assignment brief
PEAK_FLOPS = 197e12          # bf16 FLOP/s
HBM_BW = 819e9               # bytes/s
ICI_BW = 50e9                # bytes/s per link

# fixed cost charged per grid step (dispatch + pipeline bubble), seconds.
# Order-of-magnitude for a v5e scalar-core grid iteration; its only role
# is to stop the analytic model from always preferring the tiniest tile.
GRID_STEP_OVERHEAD_S = 1e-6

CANDIDATES: Dict[str, Dict[str, Tuple[int, ...]]] = {
    "router_topk": {"block_n": (32, 64, 128, 256, 512)},
    "decode_attention": {"block_t": (128, 256, 512, 1024)},
    "expert_ffn": {"block_c": (32, 64, 128, 256),
                   "block_f": (128, 256, 512)},
    "grouped_moe": {"block_f": (128, 256, 512)},
}

_CACHE: Dict[tuple, Dict[str, int]] = {}


def _bytes_of(dtype) -> int:
    try:
        return int(dtype.itemsize)            # np / jnp dtypes
    except AttributeError:
        return 2 if "16" in str(dtype) else 4


def _ceil_div(a: int, b: int) -> int:
    return -(-a // b)


def analytic_time_s(kernel: str, knobs: Dict[str, int],
                    dims: Dict[str, int], itemsize: int = 4) -> float:
    """Roofline estimate of one kernel invocation under ``knobs``.

    Each grid step is charged max(compute, traffic) on the PADDED tile
    (the waste a bad tile size actually executes) plus the fixed step
    overhead. Weight operands with a constant index map are charged once
    (they stay resident across the sequential grid).
    """
    if kernel == "router_topk":
        N, D, E = dims["N"], dims["D"], dims["E"]
        bn = min(knobs["block_n"], max(N, 1))
        steps = _ceil_div(N, bn)
        flops = 2.0 * bn * D * E
        byts = bn * (D + 2 * dims.get("k", 1)) * itemsize
        per = max(flops / PEAK_FLOPS, byts / HBM_BW) + GRID_STEP_OVERHEAD_S
        return steps * per + D * E * itemsize / HBM_BW
    if kernel == "decode_attention":
        B, H, T = dims["B"], dims["H"], dims["T"]
        G, D = dims.get("G", 1), dims["D"]
        bt = min(knobs["block_t"], max(T, 1))
        steps = _ceil_div(T, bt)
        byts = 2.0 * bt * D * itemsize               # K + V tile
        flops = 2.0 * 2 * G * bt * D
        per = max(flops / PEAK_FLOPS, byts / HBM_BW) + GRID_STEP_OVERHEAD_S
        return B * H * steps * per
    if kernel in ("expert_ffn", "grouped_moe"):
        D, F = dims["D"], dims["F"]
        rows = dims.get("rows", dims.get("C", 1) * dims.get("E", 1))
        bc = min(knobs.get("block_c", dims.get("block_rows", 8)),
                 max(rows, 1))
        bf = min(knobs["block_f"], max(F, 1))
        row_steps = _ceil_div(rows, bc)
        f_steps = _ceil_div(F, bf)
        flops = 2.0 * 3 * bc * D * bf
        byts = (bc * D + 2 * D * bf + bf * D) * itemsize
        per = max(flops / PEAK_FLOPS, byts / HBM_BW) + GRID_STEP_OVERHEAD_S
        return row_steps * f_steps * per
    raise KeyError(f"unknown kernel {kernel!r}")


def _grid(kernel: str) -> Iterable[Dict[str, int]]:
    knobs = CANDIDATES[kernel]
    names = sorted(knobs)
    combos = [{}]
    for name in names:
        combos = [{**c, name: v} for c in combos for v in knobs[name]]
    return combos


def resolve(kernel: str, dtype, **dims) -> Dict[str, int]:
    """Best knob set for ``(kernel, dtype, dims)`` (analytic, cached)."""
    key = (kernel, str(dtype), tuple(sorted(dims.items())))
    hit = _CACHE.get(key)
    if hit is not None:
        return hit
    itemsize = _bytes_of(dtype)
    best, best_t = None, math.inf
    for knobs in _grid(kernel):
        t = analytic_time_s(kernel, knobs, dims, itemsize)
        if t < best_t:
            best, best_t = knobs, t
    _CACHE[key] = best
    return best


def tune(kernel: str, dtype, dims: Dict[str, int],
         measure_fn: Callable[[Dict[str, int]], float],
         ) -> Dict[str, int]:
    """Measured tuning: time every candidate with ``measure_fn(knobs)``
    (returning seconds) and cache the winner, overriding the analytic
    choice for subsequent :func:`resolve` calls on the same key."""
    key = (kernel, str(dtype), tuple(sorted(dims.items())))
    best, best_t = None, math.inf
    for knobs in _grid(kernel):
        t = measure_fn(knobs)
        if t < best_t:
            best, best_t = knobs, t
    _CACHE[key] = best
    return best


def save_cache(path: str) -> None:
    rows = [{"kernel": k[0], "dtype": k[1], "dims": list(k[2]),
             "knobs": v} for k, v in sorted(_CACHE.items())]
    Path(path).write_text(json.dumps(rows, indent=2))


def load_cache(path: str) -> int:
    rows = json.loads(Path(path).read_text())
    for r in rows:
        key = (r["kernel"], r["dtype"],
               tuple((str(a), int(b)) for a, b in r["dims"]))
        _CACHE[key] = {str(a): int(b) for a, b in r["knobs"].items()}
    return len(rows)


def clear_cache() -> None:
    _CACHE.clear()
