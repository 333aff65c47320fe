"""Benchmark harness entrypoint.

    PYTHONPATH=src python -m benchmarks.run [--only table1,fig1,...]

Sections:
  * paper tables/figures (table1, fig1, fig2, fig3) on synthetic streams,
  * roofline table from the dry-run artifacts (if present).

Speed is measured on the TPU by ``bench/run.py``, not here.
"""
from __future__ import annotations

import argparse
import time
from pathlib import Path


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="")
    args = ap.parse_args(argv)
    only = set(args.only.split(",")) if args.only else None

    from benchmarks import paper_tables, roofline_report

    t0 = time.time()
    lines = []
    if only is None or only & {"table1", "fig1", "fig2", "fig3", "paper"}:
        lines += paper_tables.run_all()
    if only is None or "roofline" in only:
        d = Path("experiments/dryrun")
        if d.exists():
            lines.append("== roofline (fd cost-faithful dry-run artifacts,"
                         " see DESIGN.md §6b) ==")
            rows = roofline_report.load(d, tag="fd")
            lines += roofline_report.fmt_table(rows)
    print("\n".join(lines))
    print(f"\n[benchmarks done in {time.time() - t0:.1f}s]")


if __name__ == "__main__":
    main()
