"""Read the numbers `correct` compares, and their control, over many seeds.

    python3 benchmark/control.py --workload <name> --seeds 201,202,203 [--control 3]

One process, so that a long set-up is paid once.  For each seed it takes the
cell's first steps through ``Module.fit`` exactly as a run does (``run.py``'s
own set-up; no measured window: training's readings need none), frees the
program, follows them with the plain reference, and prints every number the
harness can compare.  For the first ``--control`` seeds it also puts the
reference in the program's place, computed in the precision one step below
the one the configuration states (``check.control_precision``): those numbers
have to come out over the limits.  A limit is set from what this prints
(PERF.md section 2), never from a guess.  The benchmark's own runs do not
run this.
"""

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import run  # noqa: E402


def numbers(su, program, reference):
    """{number: value} as ``run.compare`` computes them, all of them."""
    rows, _ = run.compare(program, reference, {}, su.cfg["name"], every=True)
    return {what: value for what, value, _ in rows}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--control", type=int, default=3,
                    help="how many of the seeds also run the control")
    ap.add_argument("--manifest",
                    default=os.path.join(run.REPO, "BENCHMARK.json"))
    ap.add_argument("--out", default=None, help="append JSON lines here")
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]
    # one Module, one compiled step, for every seed
    su = run.start(args.manifest, args.workload, seeds[0])
    if su is None:
        return 1
    check = su.cfg["check"]
    for n, seed in enumerate(seeds):
        batches, feed, key = run.seed_inputs(su, seed)
        program = su.job.first_steps(feed, key, check["steps"])
        su.job.release()
        ref = run.follow(su, batches, key)
        row = {"workload": su.cell["name"], "seed": seed,
               "ref_losses": ref["losses"],
               "program": numbers(su, program, ref)}
        del program
        if n < args.control:
            run.log(f"control seed={seed} "
                    f"precision={check['control_precision']}")
            low = run.follow(su, batches, key, check["control_precision"])
            row["control"] = numbers(su, low, ref)
            del low
        del ref
        print(json.dumps(row), flush=True)
        if args.out:
            os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
            with open(args.out, "a") as f:
                f.write(json.dumps(row) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
