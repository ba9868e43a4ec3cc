#!/usr/bin/env python3
"""Read a cell's check numbers over many seeds in one process, for setting
the limits of ``bench/traffic/<traffic>.json``.

    python3 bench/tools/readings.py --workload <cell> --seeds 1,2,3 \\
        [--control] [--fault half_batch] [--out FILE]

For each seed it builds the cell's timed path from the seed as a run does,
drives it as far as the check needs (a training cell's first rounds; a
serving cell's first batches at its own load), and compares it with the
plain reference. ``--control`` also reads the control: the reference
computed in bfloat16, in the program's place. ``--fault`` plants one of
``lib/faults.py``'s faults in the program first. One JSON line per seed
goes to stdout (and to ``--out``). Needs a TPU, like a run.
"""
import argparse
import gc
import json
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--fault", default=None)
    ap.add_argument("--batches", type=int, default=2,
                    help="serving: batches served per seed")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    import jax
    import run as benchrun
    from lib import checks, faults, harness
    harness.use_checkout_cache(jax)
    if jax.devices()[0].platform != "tpu":
        sys.exit("readings: needs a TPU")
    spec = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    cell = {w["name"]: w for w in spec["workloads"]}[args.workload]
    centry = {c["name"]: c for c in spec["configs"]}[cell["config"]]
    cfg = json.loads((BENCH.parent / centry["file"]).read_text())
    tr = json.loads((BENCH / "traffic" / f"{cell['traffic']}.json")
                    .read_text())
    drv = benchrun._load_module(BENCH / "drivers" / f"{tr['driver']}.py")
    ctx = benchrun.Context(cell=cell, config=cfg, traffic=tr, seconds=0.0,
                           trace=False, devices=jax.devices()[:1],
                           meter=harness.CompileMeter(jax),
                           t_start=time.perf_counter(), jax=jax)
    out = open(args.out, "a") if args.out else None
    plant = faults.plant(args.fault) if args.fault else None
    if plant:
        plant.__enter__()
    for seed in [int(s) for s in args.seeds.split(",")]:
        t0 = time.perf_counter()
        row = {"cell": cell["name"], "seed": seed, "fault": args.fault}
        b = drv.build(ctx, seed)
        if tr["driver"] == "pftt_population_round":
            n = tr["check_rounds"]
            rec = drv.first_rounds(b, n)
            streams = b.streams
            del b
            gc.collect()
            ref = drv.reference(cfg, tr, streams, n)
            row["program"] = checks.train_numbers(rec, ref)
            if args.control:
                ctl = drv.reference(cfg, tr, streams, n, control=True)
                row["control"] = checks.train_numbers(ctl, ref)
        else:
            import numpy as np
            served = np.stack([b.serve_batch(i)[1]
                               for i in range(args.batches)])
            streams = b.streams
            del b
            gc.collect()
            B = tr["batch"]
            g = np.random.default_rng([streams["sample"], args.batches])
            ids = g.choice(args.batches * B, size=min(
                tr["check_requests"], args.batches * B), replace=False)
            picks = [(int(i // B), int(i % B)) for i in ids]
            sv = np.stack([served[i, r] for i, r in picks])
            gap, ctl = drv.reference_gaps(cfg, tr, streams, picks, sv,
                                          control=args.control)
            row["program"] = {"logit_gap": gap}
            if args.control:
                row["control"] = {"logit_gap": ctl}
        row["seconds"] = time.perf_counter() - t0
        line = json.dumps(row)
        print(line, flush=True)
        if out:
            out.write(line + "\n")
            out.flush()
    if plant:
        plant.__exit__(None, None, None)


if __name__ == "__main__":
    main()
