#!/usr/bin/env python3
"""The control's readings, which set the upper end of the limit on
`float_rel_err` (bench/limits.json), for one cell at its own size.

The control is the reference computed in bfloat16 (bench/reference.py),
put in the program's place: for each seed it answers the cell's own
queries, each stream's first passes and more than a run compares, and
compares them with the float64 reference as a run compares the
program's answers.  The benchmark's own runs never run it; the program's
readings are the runs' own `checks`.

    python3 bench/control.py --workload stream.power --seeds 1,2,3
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
QUERIES_PER_STREAM = 72  # twelve passes, more than a stream finishes in a window


def readings(tables, mix: dict, seed: int, per_stream: int = QUERIES_PER_STREAM) -> dict:
    from bench import reference, traffic

    ref = reference.Reference(tables)
    ctl = reference.Reference(tables, precision="bfloat16")
    wrong, err = 0, 0.0
    for k in range(mix["streams"]):
        for q, p in traffic.first(mix, seed, k, per_stream):
            w, e = reference.compare(q, ctl.answer(q, p), ref.answer(q, p))
            wrong, err = wrong + w, max(err, e)
    return {"wrong_exact": wrong, "float_rel_err": err}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    args = ap.parse_args(argv)
    sys.path.insert(0, ROOT)
    from bench import datagen, spec

    cell = spec.workload(spec.load(ROOT), args.workload)
    data, mix = spec.config(cell["config"])["data"], spec.traffic(cell["traffic"])
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        tables = datagen.gen_tables(data["generator_sf"], seed, data["row_group_size"])
        out = {"seed": seed, "queries": QUERIES_PER_STREAM * mix["streams"],
               "control": readings(tables, mix, seed),
               "seconds": time.perf_counter() - t0}
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
