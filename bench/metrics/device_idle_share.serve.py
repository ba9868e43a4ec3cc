"""Share of the traced decode batches in which no operation ran on the device, in
%: 1 - busy / window, from the profiler trace (``lib/trace_reduce.py``)."""


def read(run):
    tr = run.get("trace")
    if not tr or tr["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
