"""The control run, and the readings that the correctness limit is set
from: for each seed, one window of the cell at its own size and load;
then the int8 control's first choices take the place of the served tokens
of the check's sample and go through the same checks, which must find the
run not correct. Each line gives that verdict, the control's widest logit
gap and the program's own over the same sample, against the float32
reference. All seeds run in one process, so the programs compile once.

    python benchmarks/chip/control.py --workload <cell> --seconds <s> \
        --seeds 1,2,3

Prints one JSON line per seed. The benchmark's own runs never run the
control.
"""
from __future__ import annotations

import argparse
import json

import run


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    a = ap.parse_args()
    for seed in [int(s) for s in a.seeds.split(",")]:
        res = run.run_cell(["--workload", a.workload, "--seed", str(seed),
                            "--seconds", str(a.seconds), "--trace", "0"],
                           control=True)
        ch = res["checks"]
        print(json.dumps({
            "seed": seed, "correct": res["correct"],
            "control_gap": ch["max_logit_gap"]["value"],
            "program_gap": res["program_gap"],
            "tokens": ch["tokens_compared"]["value"],
            "metrics": {k: v["value"] for k, v in res["metrics"].items()},
            "finished_in_window": sum(1 for lg in res["run"].reqs
                                      if lg.done and lg.last <= res["run"].t1),
            "limit": ch["max_logit_gap"]["limit"]}), flush=True)


if __name__ == "__main__":
    main()
