"""The control of ``correct``: the plain reference, computed one step of
precision below what the configuration states, put in the program's place.
It has to come out as NOT correct, or the comparison could not tell a
later PR that lowers the precision from one that does not.

    python3 benchmark/control.py --workload <cell> --seeds 1,2,3

prints, for each seed, every number of the comparison with its limit
(``--parts solver`` lowers the solver alone, the other parts as stated). The
benchmark's own runs never run it; the builder of a benchmark PR runs it
on the chip at the cell's own size, and ``tests/benchmark`` at a size a
test holds. ``--fault half_rows`` (fit cells) instead fits the reference
at full precision on the first half of the training rows: half of the
batch left out, the mean taken over the rest.
"""

import os
import sys

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _ROOT not in sys.path:
    sys.path.insert(0, _ROOT)


def read(manifest, workload: str, seed: int, *, seconds: float, device, peak,
         fault=None, parts=None) -> dict:
    """The comparison's numbers, each with its limit, for one seed of
    ``workload`` with the control (or the fault) in the program's place."""
    from benchmark import compare, harness

    cell = manifest.cell(workload)
    traffic = manifest.traffic(cell["traffic"])
    config = manifest.config(cell["config"])
    run = harness.Run(
        manifest=manifest, cell=cell, config=config, traffic=traffic,
        seed=seed, seconds=seconds, trace=False, device=device, peak=peak,
        phases=harness.Phases(),
    )
    driver = manifest.driver(traffic["kind"])
    if fault == "half_rows":
        produced = driver.control(
            run, compare.HIGHEST, rows=config["n_train"] // 2
        )
    elif fault is None:
        said = compare.stated(config)
        lowered = compare.below(said)
        if parts:  # lower only these parts, the others as stated
            lowered = {p: lowered[p] if p in parts else said[p] for p in said}
        produced = driver.control(run, lowered)
    else:
        raise SystemExit(f"control: no fault {fault!r}")
    compared = driver.check(run, produced)
    return {
        "workload": workload, "seed": seed, "fault": fault, "parts": parts,
        "correct": all(r["value"] <= r["limit"] for r in compared.values()),
        "compared": compared, "all": run.facts["compared_all"],
    }


def main(argv) -> int:
    import argparse
    import json

    from benchmark import harness, program

    ap = argparse.ArgumentParser("benchmark/control.py")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--fault", default=None)
    ap.add_argument(
        "--parts", default=None,
        help="lower only these parts (featurizer,solver,apply); default all",
    )
    args = ap.parse_args(argv)
    manifest = harness.Manifest(_ROOT)
    os.environ.setdefault("JAX_PLATFORMS", "tpu")
    peaks = manifest.peaks()
    device = program.require_tpu(manifest.cell(args.workload)["chips"], peaks)
    seconds = args.seconds or manifest.doc["run_seconds"]
    for seed in (int(s) for s in args.seeds.split(",")):
        print(json.dumps(read(
            manifest, args.workload, seed, seconds=seconds, device=device,
            peak=peaks[device["kind"]], fault=args.fault,
            parts=args.parts.split(",") if args.parts else None,
        )), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1:]))
