"""Benchmark harness — one entry per paper table/figure plus roofline.

Suites are DISCOVERED, not hardcoded: every module in ``benchmarks/``
exposing a callable ``run()`` registers itself (``common.py``,
``run.py``, and ``roofline.py`` are plumbing and excluded). Prints
``name,us_per_call,derived`` CSV rows. Figures map to the paper:
  fig10_*    expert-selection prediction accuracy   (paper Fig. 10)
  fig11_*    scatter-gather communication designs   (paper Fig. 11)
  fig12_*    ODS vs MIQCP vs random deployment      (paper Fig. 12)
  fig13_*    BO acquisition comparison              (paper Fig. 13)
  fig14_*    overall cost/throughput baselines      (paper Fig. 14)
  overhead_* algorithm overhead                     (paper §V-F)
  kernel_*   Pallas kernel micro-benchmarks
  roofline_* dominant roofline term per arch/shape  (EXPERIMENTS.md §Roofline)

Usage:
    PYTHONPATH=src:. python benchmarks/run.py                # all suites
    PYTHONPATH=src:. python benchmarks/run.py --list         # names only
    PYTHONPATH=src:. python benchmarks/run.py --only fig12_ods
    PYTHONPATH=src:. python benchmarks/run.py --only fig12_ods,serving_bench
"""
from __future__ import annotations

import argparse
import importlib
import pkgutil
import sys
import traceback
from pathlib import Path
from typing import Callable, Dict

# suites that are harness plumbing, not benchmarks
_EXCLUDE = {"common", "run", "roofline"}


def discover_suites() -> Dict[str, Callable[[], None]]:
    """Import every sibling module with a module-level ``run()``."""
    suites: Dict[str, Callable[[], None]] = {}
    for info in sorted(pkgutil.iter_modules([str(Path(__file__).parent)]),
                       key=lambda m: m.name):
        if info.name in _EXCLUDE or info.name.startswith("_"):
            continue
        mod = importlib.import_module(f"benchmarks.{info.name}")
        fn = getattr(mod, "run", None)
        if callable(fn):
            suites[info.name] = fn
    return suites


def roofline_summary() -> None:
    """Roofline summary (reads experiments/dryrun; skip gracefully)."""
    from benchmarks import roofline
    rows = roofline.load_all()
    for r in rows:
        if r["mesh"] == "single":
            dom = r["dominant"]
            print(f"roofline_{r['arch']}_{r['shape']},"
                  f"{r[dom + '_s'] * 1e6:.1f},dominant={dom}")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--only", default="",
                    help="comma-separated suite names to run (default: all)")
    ap.add_argument("--list", action="store_true",
                    help="print discovered suite names and exit")
    args = ap.parse_args(argv)
    from repro.device import enable_compile_cache
    enable_compile_cache()

    suites = discover_suites()
    if args.list:
        for name in suites:
            print(name)
        return
    if args.only:
        wanted = [s.strip() for s in args.only.split(",") if s.strip()]
        unknown = [w for w in wanted if w not in suites]
        if unknown:
            raise SystemExit(
                f"unknown suite(s) {unknown}; available: {sorted(suites)}")
        suites = {name: suites[name] for name in wanted}

    print("name,us_per_call,derived")
    failures = []
    for name, fn in suites.items():
        try:
            fn()
        except Exception:            # noqa: BLE001
            failures.append(name)
            traceback.print_exc()
    if not args.only:
        try:
            roofline_summary()
        except Exception:            # noqa: BLE001
            traceback.print_exc()
    if failures:
        print(f"FAILED suites: {failures}", file=sys.stderr)
        raise SystemExit(1)


if __name__ == "__main__":
    main()
