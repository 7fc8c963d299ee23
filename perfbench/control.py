"""Readings that set a cell's correctness limit, on the chip.

    python3 perfbench/control.py --workload <cell> --seeds 1,2,3 --seconds 5 \\
        [--control-seeds 3]

In one process, for each seed, one run of the cell through the harness's
own `run_cell` with a window of `--seconds` at the cell's own load: its
numbers are the lower reading (sound runs of the program). For the first
`--control-seeds` seeds a second run puts the plain reference at
bfloat16 in the program's place: the upper reading (the control). The
benchmark's own runs never run this. Prints one line per reading and a
JSON summary last.
"""
import argparse
import json
import sys
import time

import harness


def main(argv):
    ap = argparse.ArgumentParser(prog="perfbench/control.py")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--control-seeds", type=int, default=3)
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]
    out = {"workload": args.workload, "program": {}, "control": {}}
    runs = [(s, None) for s in seeds] + \
        [(s, "bf16") for s in seeds[:args.control_seeds]]
    for seed, control in runs:
        try:
            res = harness.run_cell(args.workload, seed, args.seconds, False,
                                   process_t0=time.perf_counter(),
                                   control=control)
        except harness.NoAccelerator as e:
            harness.say(f"[control] {e}")
            return 3
        numbers = {k: v["value"] for k, v in res["checks"].items()}
        out["control" if control else "program"][seed] = numbers
        harness.say(f"[control] seed {seed} "
                    f"{'control ' + control if control else 'program'} "
                    f"{json.dumps(numbers)} answers {res['attempted']}")
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
