"""Benchmark harness — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows.  Run:
  PYTHONPATH=src python -m benchmarks.run [--fast]
"""
from __future__ import annotations

import argparse
import sys


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true", help="skip the trained-model PPL table")
    ap.add_argument("--only", default="")
    args = ap.parse_args()

    from benchmarks import (
        fig4_convergence,
        table1_bitwidth,
        table2_ppl,
        table5_sub4bit,
        table8_ablation,
        table9_universal,
        table10_codeword,
    )

    mods = {
        "table1": table1_bitwidth,
        "fig4": fig4_convergence,
        "table5": table5_sub4bit,
        "table8": table8_ablation,
        "table9": table9_universal,
        "table10": table10_codeword,
        "table2": table2_ppl,
    }
    if args.only:
        mods = {k: v for k, v in mods.items() if k in args.only.split(",")}
    if args.fast:
        mods.pop("table2", None)

    print("name,us_per_call,derived")
    for name, mod in mods.items():
        print(f"# --- {name} ({mod.__doc__.strip().splitlines()[0]}) ---")
        try:
            mod.run(fast=args.fast)
        except Exception as e:  # pragma: no cover
            print(f"{name},0,ERROR:{type(e).__name__}:{e}", file=sys.stdout)
            raise


if __name__ == "__main__":
    main()
