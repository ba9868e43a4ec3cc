"""Model-FLOP utilization of serving, in %: the FLOPs that the window's
prefills and decode steps require (``lib/flops.py``: projections, causal
attention over the cache, LM head for the last prompt position and every
decoded token) over the window's seconds times the chips' bf16 peak."""


def read(run):
    pk = run.get("peaks")
    if not pk or not run.get("flops"):
        return None
    return 100.0 * run["flops"] / (run["window_s"] * run["chips"]
                                   * pk["flops_bf16"])
