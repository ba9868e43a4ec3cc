"""Peak device memory as a share of the chip's HBM, in %: the runtime's
``peak_bytes_in_use`` on the fullest chip, read after the window and the
trace, over the published HBM size (``lib/peaks.py``)."""


def read(run):
    pk = run.get("peaks")
    if not pk or not run.get("memory_peak_bytes"):
        return None
    return 100.0 * run["memory_peak_bytes"] / pk["hbm_bytes"]
