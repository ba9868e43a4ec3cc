"""Sharded cohort engine: ONE fused federated round on 1 vs 8 devices.

The workload is the PFTT-shaped cohort of ``fl_engine_bench`` (frozen
reduced-roberta base, trainable adapters + head, AdamW, outage weight
vector).  Per cohort size this measures, for a single device and for an
8-way client-sharded mesh (``build_supervised_round(mesh=...)``,
``shard_map`` + psum aggregation — core/cohort.py):

* wall-clock per fused round (AOT-compiled, compile excluded),
* PER-DEVICE peak compiled memory (XLA ``memory_analysis``: temp +
  argument bytes — on the mesh each device only holds its client shard of
  trainables/moments/batches, so this shrinks with the shard count),

and writes ``BENCH_cohort_shard.json``.

Because ``--xla_force_host_platform_device_count`` must be set before jax
imports, each device count runs in a fresh worker subprocess (this module
with ``--worker``); the parent merges rows.  NOTE: 8 forced CPU devices
multiply compile time, and on an oversubscribed host the 8-way wall-clock
is pessimistic — treat the memory column as the scaling signal and the
wall-clock as an upper bound.
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

_MARK = "COHORT_SHARD_ROW "


# ---------------------------------------------------------------------------
# worker: runs under a forced device count, one row per cohort size
# ---------------------------------------------------------------------------


def _worker(cohorts, rounds: int) -> None:
    import jax
    import jax.numpy as jnp

    from repro import trees
    from repro.core.cohort import build_supervised_round
    from repro.sharding import auto_mesh, cohort_sharding

    from benchmarks.fl_engine_bench import _build_workload

    n_dev = len(jax.devices())
    mesh = auto_mesh((n_dev,), ("data",)) if n_dev > 1 else None

    for n_clients in cohorts:
        local_step, pred, states, batches, weights, steps = _build_workload(
            n_clients)
        st_tr = trees.stack([tr for tr, _ in states])
        st_op = trees.stack([op for _, op in states])
        dev_batches = {k: jnp.asarray(v) for k, v in batches.items()}
        w = jnp.asarray(weights)
        cs = None
        if mesh is not None:
            cs = cohort_sharding(mesh, n_clients, ("data",))
            assert cs.n_pad == 0, (n_clients, n_dev)   # clean scaling points
            st_tr, st_op, dev_batches, w = jax.device_put(
                (st_tr, st_op, dev_batches, w), cs.named)
        # donate=False: state reused across timed rounds; AOT-compile so the
        # memory stats and the timed call share one executable
        round_step = build_supervised_round(
            local_step, pred, donate=False, mesh=mesh,
            client_axes=("data",) if mesh is not None else None)
        t0 = time.perf_counter()
        compiled = round_step.lower(st_tr, st_op, dev_batches, w).compile()
        compile_s = time.perf_counter() - t0
        mem = compiled.memory_analysis()
        out = compiled(st_tr, st_op, dev_batches, w)          # warmup
        jax.block_until_ready(out[0])
        t0 = time.perf_counter()
        for _ in range(rounds):
            out = compiled(st_tr, st_op, dev_batches, w)
        jax.block_until_ready(out[0])
        row = {"n_clients": n_clients, "n_devices": n_dev,
               "ms_per_round": (time.perf_counter() - t0) / rounds * 1e3,
               "device_peak_bytes": int(mem.temp_size_in_bytes
                                        + mem.argument_size_in_bytes),
               "temp_bytes": int(mem.temp_size_in_bytes),
               "argument_bytes": int(mem.argument_size_in_bytes),
               "compile_s": compile_s}
        print(_MARK + json.dumps(row), flush=True)


# ---------------------------------------------------------------------------
# parent: one subprocess per device count (XLA_FLAGS must precede jax import)
# ---------------------------------------------------------------------------


def _spawn(n_dev: int, cohorts, rounds: int):
    env = dict(os.environ)
    # forced host devices exist only on the CPU backend, and a child must
    # never contend for an accelerator its parent may hold
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (f"--xla_force_host_platform_device_count={n_dev} "
                        + env.get("XLA_FLAGS", "")).strip()
    env.setdefault("PYTHONPATH", "src")
    proc = subprocess.run(
        [sys.executable, "-m", "benchmarks.cohort_shard_bench", "--worker",
         "--cohorts", ",".join(map(str, cohorts)), "--rounds", str(rounds)],
        capture_output=True, text=True, env=env, timeout=3600)
    rows = [json.loads(line[len(_MARK):]) for line in proc.stdout.splitlines()
            if line.startswith(_MARK)]
    if proc.returncode != 0 or len(rows) != len(cohorts):
        raise RuntimeError(
            f"cohort_shard worker (devices={n_dev}) failed "
            f"rc={proc.returncode}:\n{proc.stderr[-3000:]}")
    return rows


def main(quick: bool = True, out: str = "BENCH_cohort_shard.json"):
    cohorts = (8, 32) if quick else (8, 32, 64)
    rounds = 3 if quick else 10
    per_dev = {n_dev: _spawn(n_dev, cohorts, rounds) for n_dev in (1, 8)}
    results = []
    for i, n in enumerate(cohorts):
        r1, r8 = per_dev[1][i], per_dev[8][i]
        row = {"n_clients": n, "dev1": r1, "dev8": r8,
               "wallclock_speedup_8dev": r1["ms_per_round"]
               / max(r8["ms_per_round"], 1e-9),
               "device_mem_ratio_8dev": r1["device_peak_bytes"]
               / max(r8["device_peak_bytes"], 1)}
        results.append(row)
        print(f"cohort_shard_n{n},{r8['ms_per_round'] * 1e3:.1f},"
              f"1dev={r1['ms_per_round']:.1f}ms "
              f"speedup={row['wallclock_speedup_8dev']:.2f}x "
              f"device_peak {r1['device_peak_bytes']:,}->"
              f"{r8['device_peak_bytes']:,}B "
              f"(x{row['device_mem_ratio_8dev']:.2f})")
    record = {"profile": "quick" if quick else "full",
              "workload": "pftt-shaped adapters+head local SGD, reduced "
                          "roberta d16, batch 2, seq 16, 5 local steps; "
                          "fused round sharded over a (n_dev,) 'data' mesh "
                          "(forced host-platform CPU devices — wall-clock "
                          "is an upper bound, per-device memory is exact)",
              "results": results}
    if out:
        with open(out, "w") as f:
            json.dump(record, f, indent=1)
        print(f"# wrote {out}")
    return record


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--worker", action="store_true")
    ap.add_argument("--cohorts", default="8,32")
    ap.add_argument("--rounds", type=int, default=3)
    args = ap.parse_args()
    cohorts = tuple(int(c) for c in args.cohorts.split(","))
    if args.worker:
        _worker(cohorts, args.rounds)
    else:
        main(quick=not bool(os.environ.get("FULL")))
