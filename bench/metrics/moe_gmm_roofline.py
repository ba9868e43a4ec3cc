"""Share of its roofline that the grouped expert matmul (``kernels/moe_gmm``)
reaches, in %: the least time of the traced batches' grouped matmuls (per
expert layer and phase, the larger of FLOPs over peak FLOP/s and bytes
over peak bytes/s, from the rows and expert hits the program counted:
``drivers/serve_closed_loop_mla_moe.py::gmm_least_s``) over the summed
device time of the Pallas kernel events under the ``moe/gmm`` named scope
(``lib/scopes.py``). Nothing is read where the trace holds no such
event."""


def read(run):
    gmm = run.get("gmm")
    sc = run.get("scopes") or {}
    if not gmm or not sc.get("kernel_s"):
        return None
    secs = sc["kernel_s"].get(gmm["scope"], 0.0)
    if secs <= 0:
        return None
    return 100.0 * gmm["least_s"] / secs
