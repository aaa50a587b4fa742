"""Find a cell's knee: serve it at several offered rates in one process
and print one line per rate with what was offered and what was served.

    python benchmarks/chip/sweep.py --workload <cell> --seed <n> \
        --seconds <s> --rates 0.5,1,1.5

The knee is the highest rate at which 90% of the window's requests meet
both limits of the mix and the backlog does not grow (every request due in
the window finishes within the drain). It is found once, when a cell is
defined; the cell then runs at a fixed rate.
"""
from __future__ import annotations

import argparse
import json
import sys

import run


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    a = ap.parse_args()
    for rate in [float(r) for r in a.rates.split(",")]:
        res = run.run_cell(["--workload", a.workload, "--seed", str(a.seed),
                            "--seconds", str(a.seconds), "--trace", "0"],
                           cell_override={"cell": {"rate": rate}})
        r = res["run"]
        slo = r.cell.mix["slo"]
        met = run.load_reader("slo_met_pct")(r)
        out = {"rate": rate, "correct": res["correct"],
               "requests": len(r.reqs),
               "finished": sum(lg.done for lg in r.reqs),
               "finished_in_window": sum(1 for lg in r.reqs if lg.done
                                         and lg.last <= r.t1),
               "slo_met_pct": met, "slo": slo,
               "migration_ms_p90": run.load_reader("migration_ms_p90.burst")(r),
               "flips": r.flips,
               "drain_s": max((lg.last or r.t1) for lg in r.reqs) - r.t1,
               **{k: v["value"] for k, v in res["metrics"].items()}}
        print(json.dumps(out), flush=True)
        print(json.dumps(out), file=sys.stderr, flush=True)


if __name__ == "__main__":
    main()
