#!/usr/bin/env python3
"""Run one benchmark cell on the accelerator this process finds.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything a cell needs is found by name from ``BENCHMARK.json``: the
cell's configuration file, its traffic file ``bench/traffic/<traffic>.json``
(which names its driver, ``bench/drivers/<driver>.py``), and one reader per
per-layer metric, ``bench/metrics/<metric>.py``. A new cell, traffic mix,
driver or metric is new files plus an entry in ``BENCHMARK.json``.

The driver builds the system under test from ``src/`` with weights and
inputs drawn from ``--seed``, warms up every shape, measures for
``--seconds``, and checks what the timed path produced against the plain
reference. With ``--trace 0`` the result carries the cell's end-to-end
metrics; with ``--trace 1`` a profiled stretch follows the window and the
result carries the per-layer metrics, ``busy_s``/``window_s`` and a
breakdown. The last line of stdout is one JSON object; the numbers that
decide ``correct`` are the last lines of stderr and the last key of it.
Exits non-zero, printing no result, without a TPU with as many chips as
the cell asks for, or without the program's sources.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
CHECKOUT = BENCH.parent
sys.path.insert(0, str(BENCH))


class Context:
    """What a driver gets: the cell, its configuration and traffic, the
    run's arguments, the devices, the compile meter and the start time."""

    def __init__(self, **kw):
        self.__dict__.update(kw)


def _load_module(path: Path):
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def applies(entry: dict, cell: str, reported: set = None) -> bool:
    """Whether a metric entry belongs in ``cell``'s result: the cells it
    lists, or, without a list, every cell (an end-to-end metric) or every
    cell that reports the end-to-end metric it moves (a per-layer one)."""
    if "workloads" in entry:
        return cell in entry["workloads"]
    return reported is None or entry["moves"] in reported


def per_layer_metrics(spec: dict, cell: str, run: dict) -> dict:
    e2e = {m["name"] for m in spec["end_to_end"] if applies(m, cell)}
    out = {}
    for m in spec["per_layer"]:
        if not applies(m, cell, e2e):
            continue
        reader = _load_module(BENCH / "metrics" / f"{m['name']}.py")
        value = reader.read(run)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()

    spec = json.loads((CHECKOUT / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if args.workload not in cells:
        sys.exit(f"bench: no workload {args.workload!r}; "
                 f"known: {sorted(cells)}")
    cell = cells[args.workload]
    cfg_entry = {c["name"]: c for c in spec["configs"]}[cell["config"]]
    cfg = json.loads((CHECKOUT / cfg_entry["file"]).read_text())
    traffic = json.loads(
        (BENCH / "traffic" / f"{cell['traffic']}.json").read_text())
    if not (CHECKOUT / "src" / "repro").is_dir():
        sys.exit(f"bench: the program's sources ({CHECKOUT / 'src'}) are "
                 f"not in this checkout")
    sys.path.insert(0, str(CHECKOUT / "src"))
    # the TPU runtime would log under /tmp; a run writes only in its checkout
    # and the directories it is given
    os.environ.setdefault("TPU_LOG_DIR", "disabled")

    import jax
    from lib import harness
    harness.use_checkout_cache(jax)
    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(f"bench: no TPU: JAX's first device is "
                 f"{devices[0].platform} ({devices[0].device_kind})")
    if len(devices) < cell["chips"]:
        sys.exit(f"bench: {cell['name']} needs {cell['chips']} chips, "
                 f"found {len(devices)}")
    devices = devices[:cell["chips"]]

    ctx = Context(cell=cell, config=cfg, traffic=traffic, seed=args.seed,
                  seconds=args.seconds, trace=bool(args.trace),
                  devices=devices, meter=harness.CompileMeter(jax),
                  t_start=T_START, jax=jax)
    driver = _load_module(BENCH / "drivers" / f"{traffic['driver']}.py")
    res = driver.run(ctx)
    emit(spec, cell, args, devices, res)


def emit(spec, cell, args, devices, res):
    """Assemble and print the result line (and the checks on stderr)."""
    from lib import checks
    numbers = dict(res["numbers"])
    limits = dict(res["limits"])
    numbers["window_compiles"] = res["window_compiles"]
    limits["window_compiles"] = 0
    correct, rows = checks.verdict(numbers, limits)
    if args.trace:
        metrics = per_layer_metrics(spec, cell["name"], res["run"])
    else:
        metrics = {}
        for m in spec["end_to_end"]:
            if applies(m, cell["name"]):
                metrics[m["name"]] = {"value": float(res["metrics"][m["name"]]),
                                      "unit": m["unit"]}
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices),
              "memory_peak_bytes": int(res["memory_peak_bytes"])}
    out = {"correct": bool(correct), "attempted": int(res["attempted"]),
           "failed": int(res["failed"]), "metrics": metrics,
           "device": device}
    if args.trace:
        tr = res["run"]["trace"]
        device["busy_s"] = tr["busy_s"]
        device["window_s"] = tr["window_s"]
        out["breakdown"] = res["breakdown"]
    out["checks"] = {k: {"value": v, "limit": lim} for k, v, lim in rows}
    for k, v, lim in rows:
        print(f"check {k} {v!r} limit {lim!r} "
              f"{'ok' if v <= lim else 'FAILED'}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
