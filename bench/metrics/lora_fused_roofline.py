"""Share of its roofline that the fused LoRA kernel (``kernels/lora_fused``)
reaches, in %: the least time of the traced calls (per call the larger of
FLOPs over peak FLOP/s and bytes over peak bytes/s, from the call shapes
the driver counted, ``lib/flops.py::lora_fused_call``) over the summed
device durations of the kernel's events in the trace. The driver records
which bound limits the calls. Nothing is read where the trace holds none of
the kernel's events or their number differs from the calls counted."""


def read(run):
    tr = run.get("trace")
    kern = run.get("kernel")
    if not tr or not kern:
        return None
    secs = tr["kernel_s"].get(kern["match"], 0.0)
    calls = tr["kernel_calls"].get(kern["match"], 0)
    if secs <= 0 or calls != kern["calls"]:
        return None
    return 100.0 * kern["least_s"] / secs
