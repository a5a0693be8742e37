"""Readings that the correctness limit of a cell is set from.

For each seed: weights from the seed, the cell's traffic for ``--seconds``,
the same sample of finished requests that a benchmark run checks, and the
checks of a benchmark run against the configuration's limit: above all the
widest gap by which a served token's reference logit lies below the
reference's best. For the first ``--control`` seeds it also judges the fp8
control at the same positions (the gap of the token that the control ranks
first) with the same limit, where ``correct`` has to come out false. Prints
one JSON line per seed and a summary line: ``lower`` is the largest program
reading, ``upper`` the smallest control reading.

The cell is named ``<config>.<traffic>`` and need not be in BENCHMARK.json.
Run on a machine with a TPU, from the root of a checkout:

    python3 chipbench/calibrate.py --workload <cell> --seeds 1-12 --control 4 --seconds 15
"""

from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

sys.path[0] = str(Path(__file__).resolve().parents[1])


def seeds_of(text: str) -> list[int]:
    out = []
    for part in text.split(","):
        lo, _, hi = part.partition("-")
        out += list(range(int(lo), int(hi) + 1)) if hi else [int(lo)]
    return out


def reading(cell: dict, seed: int, seconds: float, control: bool, *, model=None, mix=None,
            limit=None):
    """One seed's checks for the program and, with ``control``, for the fp8
    control, each with its ``correct`` against ``limit`` (by default the
    configuration's)."""
    from chipbench import families, harness

    conf = harness.load_config(cell["config"])
    m = dict(model or conf["model"], name=cell["config"])
    mix = mix or harness.load_mix(cell["traffic"])
    engine = harness.build_engine(m, seed)
    harness.warm_up(engine, mix)
    recs, _, _, _ = harness.drive(engine, harness.Load(mix, seed, m["vocab_size"]), seconds,
                                  counters=families.of(m).tick_counters)
    del engine
    gc.collect()
    sample = harness.check_sample(recs, seed)
    limit = conf["check"]["max_logit_gap"] if limit is None else limit
    _, checks, ctl = harness.judge(recs, sample, m, seed, limit, control=control)
    out = {"seed": seed, "requests": len(recs), "checked_requests": len(sample),
           "checked_tokens": checks["checked_tokens"]["value"],
           "program_gap": checks["max_logit_gap"]["value"],
           "program_correct": harness.is_correct(checks)}
    if control:
        out["control_gap"] = ctl["max_logit_gap"]["value"]
        out["control_correct"] = harness.is_correct(ctl)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__,
                                 formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--workload", required=True, help="<config>.<traffic>")
    ap.add_argument("--seeds", required=True, help="e.g. 1-12 or 5,9,2000000001")
    ap.add_argument("--control", type=int, default=4, help="seeds that also judge the control")
    ap.add_argument("--seconds", type=float, default=15.0)
    args = ap.parse_args(argv)

    from chipbench import harness

    cell = harness.cell_named(args.workload)
    sys.path.insert(1, str(harness.ROOT / "src"))
    harness.use_checkout_dirs()
    harness.chip(cell["chips"])
    rows = []
    for i, seed in enumerate(seeds_of(args.seeds)):
        t = time.perf_counter()
        row = reading(cell, seed, args.seconds, i < args.control)
        row["wall_s"] = time.perf_counter() - t
        rows.append(row)
        print(json.dumps(row), flush=True)
    ctl = [r for r in rows if "control_gap" in r]
    print(json.dumps({"workload": args.workload,
                      "lower": max(r["program_gap"] for r in rows),
                      "upper": min(r["control_gap"] for r in ctl) if ctl else None,
                      "program_correct": all(r["program_correct"] for r in rows),
                      "control_never_correct": not any(r["control_correct"] for r in ctl),
                      "seeds": len(rows), "control_seeds": len(ctl)}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
