"""Readings that set the limits of ``correct``, on the chip.

    python3 benchmarks/chip/control.py --workload <cell> --seconds <s> \
        --seeds <n,n,...>

Runs the cell once per seed in this one process (a short window; set-up
and the reference as in a benchmark run) and prints, per seed, one JSON
line with the numbers ``correct`` compares for the program (the lower
readings) and, beside them, for the control: the reference in fp8 in the
program's place, and for a training cell the planted fault of half of
each batch left out.  The benchmark's own runs never run this.
"""
import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from harness import runner  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="",
                    help="the seeds that also read the control and faults "
                         "(default: all)")
    args = ap.parse_args()
    ctl = {int(s) for s in args.control_seeds.split(",") if s}
    for seed in (int(s) for s in args.seeds.split(",")):
        try:
            out = runner.run(args.workload, seed, args.seconds, False,
                             control=not ctl or seed in ctl)
        except runner.NoChip as e:
            print(f"control: {e}", file=sys.stderr)
            return 3
        log = out["log"]
        print(json.dumps({
            "seed": seed, "correct": out["correct"],
            "program": {k: v["value"] for k, v in out["checks"].items()},
            **{k: log[k] for k in log if k.startswith(("control", "fault"))},
            "setup_s": out["metrics"].get("setup_s", {}).get("value")},
            default=float), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
