#!/usr/bin/env python3
"""Record a small profiler trace on a TPU for the trace reducer's test.

    python3 bench/tools/record_trace.py OUT_DIR

Runs three steps of a decode-sized projection through the program's fused
LoRA kernel (``lora_matmul``, 8 rows, 768 -> 768, rank 8) and a plain
matmul, each step under a ``decode`` annotation and followed by a short
host sleep under ``host-wait``, all inside a ``bench-window`` annotation.
Writes the trace under OUT_DIR, copies the ``.xplane.pb`` to
OUT_DIR/small.xplane.pb, and prints the trace's layout and its reduction.
Exits non-zero without a TPU.
"""
import os
import shutil
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
CHECKOUT = BENCH.parent
sys.path.insert(0, str(CHECKOUT / "src"))
sys.path.insert(0, str(BENCH))


def main():
    out = Path(sys.argv[1]).resolve()
    import jax
    import jax.numpy as jnp
    if jax.devices()[0].platform != "tpu":
        sys.exit("record_trace: needs a TPU")
    from repro.kernels.lora_fused.ops import lora_matmul
    from lib import trace_reduce

    k = jax.random.split(jax.random.PRNGKey(0), 4)
    x = jax.random.normal(k[0], (8, 768), jnp.float32)
    w = jax.random.normal(k[1], (768, 768), jnp.float32) * 0.03
    a = jax.random.normal(k[2], (768, 8), jnp.float32) * 0.03
    b = jax.random.normal(k[3], (8, 768), jnp.float32) * 0.03
    step = jax.jit(lambda x: jnp.tanh(lora_matmul(x, w, a, b, scale=2.0)) @ w)
    step(x).block_until_ready()
    raw = out / "raw"
    jax.profiler.start_trace(str(raw))
    with jax.profiler.TraceAnnotation("bench-window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("decode"):
                x = step(x)
                x.block_until_ready()
            with jax.profiler.TraceAnnotation("host-wait"):
                time.sleep(0.002)
    jax.profiler.stop_trace()
    src = trace_reduce.find_xplane(str(raw))
    dst = out / "small.xplane.pb"
    shutil.copyfile(src, dst)
    print(f"trace {dst} ({os.path.getsize(dst)} bytes)")
    print(trace_reduce.describe(str(dst), n_events=8))
    print(trace_reduce.reduce_trace(str(dst), host_spans=("decode",
                                                          "host-wait"),
                                    kernels=("lora",)))


if __name__ == "__main__":
    main()
