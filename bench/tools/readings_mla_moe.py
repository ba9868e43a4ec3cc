#!/usr/bin/env python3
"""Read the latent-attention MoE serving cell's check numbers over many
seeds in one process, for setting the limits of its traffic file.

    python3 bench/tools/readings_mla_moe.py --workload <cell> \\
        --seeds 1,2,3 [--fault expert_dropped] [--out FILE]

For each seed it builds the cell's timed path from the seed as a run does
(``drivers/serve_closed_loop_mla_moe.py::build``), serves one batch, and
checks that batch as a run checks its seeded batch
(``reference_check``): the program's numbers, and the control's, the
reference computed in bfloat16 put in the program's place.  ``--fault``
plants one of ``lib/faults_mla_moe.py``'s faults in the program first.
One JSON line per seed goes to stdout (and to ``--out``).  Needs a TPU,
like a run.
"""
import argparse
import contextlib
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
    ap.add_argument("--fault", default=None)
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    import jax
    import numpy as np
    import run as benchrun
    from lib import faults_mla_moe, harness
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
                           seed=0, trace=False, devices=jax.devices()[:1],
                           meter=harness.CompileMeter(jax),
                           t_start=time.perf_counter(), jax=jax)
    out = open(args.out, "a") if args.out else None
    with (faults_mla_moe.plant(args.fault) if args.fault
          else contextlib.nullcontext()):
        for seed in [int(s) for s in args.seeds.split(",")]:
            t0 = time.perf_counter()
            b = drv.build(ctx, seed)
            logits = []
            _, served, counters = b.serve_batch(0, logits_out=logits)
            rows = drv.batch_rows(counters)
            streams = b.streams
            del b, counters
            gc.collect()
            ref = drv.reference_check(cfg, tr, streams, 0, served,
                                      np.stack(logits, 1), control=True)
            del logits
            row = {"cell": cell["name"], "seed": seed, "fault": args.fault}
            for who, gaps, err, held in (
                    ("program", ref["gaps"], ref["err"], rows),
                    ("control", ref["control_gaps"], ref["control_err"],
                     ref["control_held"])):
                row[who] = {**drv.logit_numbers(gaps, err),
                            "moe_rows_gap": drv.rows_gap(held, ref["held"]),
                            "token_miss_share": float(np.mean(gaps > 0)),
                            "mean_gap": float(np.mean(gaps))}
            row.update({
                "rows": rows.tolist(),
                "held": [int(h) for h in ref["held"]],
                "seconds": time.perf_counter() - t0})
            line = json.dumps(row)
            print(line, flush=True)
            if out:
                out.write(line + "\n")
                out.flush()


if __name__ == "__main__":
    main()
