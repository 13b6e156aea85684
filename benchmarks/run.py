"""Benchmark driver — one module per paper table/figure.

Prints a ``name,us_per_call,derived`` CSV summary at the end (harness
contract); each module also prints its human-readable table.

  serialization_bench   — paper Table 1
  scaling_single_node   — paper Figs. 6 (weak) & 7 (strong)
  scaling_multi_node    — paper Figs. 8 (weak) & 9 (strong)
  trace_analysis        — paper Fig. 10
"""
from __future__ import annotations

import argparse
import sys
import traceback


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma list: serialization,scaling1,scalingN,trace")
    args = ap.parse_args()
    want = set(args.only.split(",")) if args.only else None

    from . import (scaling_multi_node, scaling_single_node,
                   serialization_bench, trace_analysis)
    benches = [
        ("serialization", serialization_bench.run),
        ("scaling1", scaling_single_node.run),
        ("scalingN", scaling_multi_node.run),
        ("trace", trace_analysis.run),
    ]
    rows = []
    failed = False
    for name, fn in benches:
        if want and name not in want:
            continue
        print(f"\n{'=' * 72}\n[{name}]\n{'=' * 72}")
        try:
            rows.extend(fn() or [])
        except Exception:
            failed = True
            traceback.print_exc()
    print("\n# CSV summary")
    print("name,us_per_call,derived")
    for name, us, derived in rows:
        print(f"{name},{us:.2f},{derived}")
    if failed:
        sys.exit(1)


if __name__ == "__main__":
    main()
