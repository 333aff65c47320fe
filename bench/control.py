"""Readings that set the limits of ``correct``: the program and the control.

    python3 bench/control.py --workload <cell> --seeds 1,2,3 --seconds <s>

For each seed, one process runs the cell as ``run.py`` does and prints
one JSON line with the numbers its check compared (the program's
readings), and the same numbers with the plain reference computed at
each lower precision put in the program's place (the control: ``high``,
the three-pass bf16 split, for a configuration that states float32 at
``highest``; and one bf16 pass).  The benchmark's own runs never do
this; it needs a TPU like they do.
"""
import argparse
import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path.insert(0, str(ROOT))
    from bench import harness

    cell = harness.load_cell(args.workload)
    import jax

    if jax.devices()[0].platform != "tpu":
        print("control: no TPU; nothing was run", file=sys.stderr)
        return 2
    for seed in (int(s) for s in args.seeds.split(",")):
        out = harness.run(cell, seed, args.seconds,
                          t_start=time.perf_counter(),
                          control=("high", "bf16"))
        print(json.dumps({
            "workload": cell.name, "seed": seed, "correct": out["correct"],
            "program": {k: v["value"] for k, v in out["checks"].items()},
            "control": out["control"], "sample": out["sample"],
            "info": out["info"],
            "metrics": out["metrics"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
