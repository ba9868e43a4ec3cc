"""Model-FLOP utilization of the whole round, in %: the FLOPs the window's
rounds require (``lib/flops.py``: training tokens forward and backward to
activations plus LoRA and adapter weight gradients, eval tokens forward)
over the window's seconds times the chips' bf16 peak."""


def read(run):
    pk = run.get("peaks")
    if not pk or not run.get("flops"):
        return None
    return 100.0 * run["flops"] / (run["window_s"] * run["chips"]
                                   * pk["flops_bf16"])
