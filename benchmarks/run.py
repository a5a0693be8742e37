"""Benchmark harness: one registered runner per bench family.

One entrypoint executes every bench (or a ``--only`` subset), prints the
``name,us_per_call,derived`` CSV contract to stdout, and writes each family's
JSON artifact under ``--out``:

  * ``paper_figures`` -> BENCH_paper_figures.json (per-figure headline numbers)
  * ``fleet``         -> BENCH_fleet.json (scalar-vs-vectorized throughput)
  * ``cluster``       -> BENCH_cluster.json (closed-loop client-epochs/s +
                         equilibrium iterations)
  * ``meanfield``     -> BENCH_meanfield.json (million-client diurnal-day
                         throughput + mean-field-vs-exact gated MAPE)
  * ``validate``      -> BENCH_validate.json (fidelity-gate cost + headline MAPE)
  * ``tail``          -> BENCH_tail.json (sojourn-quantile throughput +
                         asymptote-vs-Euler gap + station_pass speedup)
  * ``kernels``       -> BENCH_kernels.json (per-kernel reference latency +
                         validated interpret-mode max-abs error)
  * ``measure``       -> BENCH_measure.json (engine tokens/s, harness
                         requests/s, fit wall time, measured-gate MAPE)
  * ``obs``           -> BENCH_obs.json (tracer-disabled overhead gate,
                         enabled-tracer tokens/s, audit rows/s + re-sum gate)
  * ``plan``          -> BENCH_plan.json (provisioning-solver wall time,
                         equilibrium solves vs grid size, plan picked)

Every BENCH_*.json written by a run gets a ``manifest`` block stamped in
(``repro.obs.run_manifest``: seed-free provenance — git sha, config hash,
package versions; no timestamps) so check_regression can say when a baseline
came from different provenance.

An unknown ``--only`` family is an error (nonzero exit, known families
listed) — CI relies on that exit code, so a typo can never silently run
nothing and upload an empty artifact as green.

The family list is not declared here: ``BENCHES`` derives from the single
experiment registry in ``repro.exp.spec``, so this CLI, ``repro.launch
.reproduce``, and the regression gate can never disagree about what exists.
This entry point keeps its historical flags, CSV contract, and exit codes.

Usage:
  PYTHONPATH=src python -m benchmarks.run --out experiments/bench
  PYTHONPATH=src python -m benchmarks.run --only fleet --only kernels
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path


def run_paper_figures(out_dir: Path) -> dict:
    from . import paper_figures as F

    report = {
        "fig2_mape_pct": F.fig2_workload_characteristics(),
        "fig3_mape_pct": F.fig3_complex_models(),
        "fig4_crossovers_mbps": F.fig4_bandwidth_crossovers(),
        "fig5a_split_mape_pct": F.fig5a_split_processing(),
        "fig5b_offload_wins": F.fig5b_request_rate(),
        "fig5c_crossover_m": F.fig5c_multitenancy(),
        "fig6_strategies": F.fig6_network_adaptation(),
        "fig7_targets": F.fig7_multitenant_adaptation(),
        "model_accuracy": F.model_accuracy_suite(),
    }
    (out_dir / "BENCH_paper_figures.json").write_text(json.dumps(report, indent=2))
    return report


def _family_runner(payload: str):
    """A ``fn(out_dir) -> report`` wrapper over a registry payload, resolved
    lazily so importing this module stays cheap (and so the registry's
    ``benchmarks.run:*`` payloads don't import-cycle at module load)."""
    def run(out_dir: Path) -> dict:
        from repro.exp.runner import resolve_payload

        return resolve_payload(payload)(out_dir)
    return run


def _benches() -> dict:
    from repro.exp.spec import bench_family_specs

    return {family: _family_runner(spec.payload)
            for family, spec in bench_family_specs().items()}


#: family -> runner, derived from the ONE experiment registry
#: (``repro.exp.spec``): a family added there is automatically runnable
#: here, reproducible via ``repro.launch.reproduce``, and checked for
#: registry completeness by tests/test_exp.py
BENCHES = _benches()


def stamp_manifests(out_dir: Path) -> None:
    """Attach the run-provenance manifest to every BENCH_*.json artifact."""
    from repro.obs import run_manifest

    manifest = run_manifest()
    for path in sorted(out_dir.glob("BENCH_*.json")):
        doc = json.loads(path.read_text())
        doc["manifest"] = manifest
        path.write_text(json.dumps(doc, indent=2))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    # families are validated by hand (not argparse choices) so an unknown
    # name exits nonzero with the registry listed — and stays that way as
    # the registry grows, instead of silently running nothing
    ap.add_argument("--only", action="append", metavar="FAMILY",
                    help="run only these bench families (repeatable and/or "
                         "comma-separated; default all; "
                         f"known: {', '.join(sorted(BENCHES))})")
    ap.add_argument("--out", type=Path, default=Path("experiments/bench"),
                    help="directory for JSON artifacts")
    args = ap.parse_args(argv)

    # accept --only a,b alongside repeated --only a --only b; empty segments
    # from stray commas are dropped so "a,,b" and "a," don't become families
    selected = [n.strip() for item in (args.only or [])
                for n in item.split(",") if n.strip()]
    if args.only and not selected:
        print(f"error: --only given but no family names parsed "
              f"(known: {', '.join(sorted(BENCHES))})", file=sys.stderr)
        return 2
    unknown = [n for n in selected if n not in BENCHES]
    if unknown:
        print(f"error: unknown bench famil{'y' if len(unknown) == 1 else 'ies'} "
              f"{', '.join(repr(n) for n in unknown)} "
              f"(known: {', '.join(sorted(BENCHES))})", file=sys.stderr)
        return 2

    names = selected or list(BENCHES)
    args.out.mkdir(parents=True, exist_ok=True)
    print("name,us_per_call,derived")
    for name in names:
        BENCHES[name](args.out)
    stamp_manifests(args.out)
    return 0


if __name__ == "__main__":
    from repro.jaxenv import enable_compilation_cache

    enable_compilation_cache()
    raise SystemExit(main())
