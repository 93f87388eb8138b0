"""Benchmark aggregator — one section per paper table/figure + system perf.

Sections:
  paper_tables    Tables 2 / 3 / 4 (accuracy + communication cost)
  comm_scaling    Table 1 rate claims: cost vs ε and vs k
  engine_sweep    batched engine vs sequential per-instance sweeps
                  (writes BENCH_engine.json at the repo root)
  maxmarg_sweep   batched MAXMARG selector vs the retired host loop
                  (writes BENCH_maxmarg.json at the repo root)
  lower_bound     Appendix A (Ω(1/ε)) and Appendix B (Ω(|D_A|)) constructions
  kernel_bench    data-plane hot-loop timings
  roofline_table  §Roofline terms from the dry-run artifacts (if present)

Prints a final ``name,us_per_call,derived`` CSV block, and exits non-zero
when any section failed (the others still run).
"""

from __future__ import annotations

import os
import sys
import traceback
from typing import List

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks import comm_scaling, engine_sweep, kernel_bench, lower_bound
from benchmarks import maxmarg_sweep, paper_tables, roofline_table


def main() -> int:
    csv: List[str] = []
    failed: List[str] = []
    sections = [
        ("paper tables (2/3/4)", paper_tables.main),
        ("communication scaling (Table 1 rates)", comm_scaling.main),
        ("engine sweep (batched vs sequential)", engine_sweep.main),
        ("maxmarg sweep (batched vs host loop)", maxmarg_sweep.main),
        ("lower bounds (App A/B)", lower_bound.main),
        ("kernel micro-bench", kernel_bench.main),
    ]
    for title, fn in sections:
        print(f"\n{'=' * 72}\n== {title}\n{'=' * 72}")
        try:
            csv += fn() or []
        except Exception:  # noqa: BLE001 — run the rest, fail at the end
            traceback.print_exc()
            csv.append(f"{title},0,ERROR")
            failed.append(title)
    if os.path.exists(roofline_table.RESULTS):
        for mesh in ("single", "multi"):
            print(f"\n{'=' * 72}\n== roofline ({mesh})\n{'=' * 72}")
            try:
                csv += roofline_table.main(mesh) or []
            except Exception:  # noqa: BLE001
                traceback.print_exc()
                failed.append(f"roofline ({mesh})")
    else:
        print("\n(no dryrun.jsonl — run `python -m repro.launch.dryrun` for the "
              "roofline section)")

    print(f"\n{'=' * 72}\n== CSV\n{'=' * 72}")
    print("name,us_per_call,derived")
    for line in csv:
        print(line)
    if failed:
        print(f"\nFAILED sections: {', '.join(failed)}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
