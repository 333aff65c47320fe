"""Benchmark of the served summarizer on a TPU: one run of one cell.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Cells are the ``workloads`` of ``BENCHMARK.json``.  A run builds the
cell's pod, admits its tenants, draws the traffic from the seed, warms up
with one serve round, measures for ``--seconds``, drains, and checks what
the pod read back against the plain reference (``bench/check.py``).
With ``--trace 0`` the result holds the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics from a profiler trace of the window.

The last line of standard output is one JSON object; the numbers the
check compared, each with its limit, are the last lines of standard
error and the last key of that object.  Without a TPU, or with fewer
chips than the cell asks for, the run exits 2 before any work.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def fail(msg: str) -> int:
    print(f"bench: {msg}; nothing was run", file=sys.stderr, flush=True)
    return 2


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0 or args.seconds <= 0:
        return fail("--seed must be >= 0 and --seconds > 0")
    if not (ROOT / "src" / "repro").is_dir():
        return fail(f"the program (src/repro) is not in {ROOT}")

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path.insert(0, str(ROOT))
    from bench import harness

    cell = harness.load_cell(args.workload)
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < cell.chips:
        return fail(f"cell {cell.name} needs {cell.chips} TPU chip(s); JAX "
                    f"found {len(devs)} {devs[0].platform} device(s) "
                    f"({devs[0].device_kind})")

    harness.log(f"[{cell.name}] set-up: {len(devs)} {devs[0].device_kind} "
                f"found at {time.perf_counter() - T_START:.3f} s")
    out = harness.run(cell, args.seed, args.seconds, trace=bool(args.trace),
                      t_start=T_START)
    checks = out.pop("checks")
    print(f"correct {out['correct']}; the numbers compared:",
          file=sys.stderr)
    for name, c in checks.items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    line = {k: out[k] for k in ("correct", "attempted", "failed", "metrics",
                                "device")}
    for k in ("breakdown", "info"):
        if k in out:
            line[k] = out[k]
    line["checks"] = checks
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
