"""Device time of latent attention per traced batch, in ms: the self time
of the ops whose ``tf_op`` lies under the program's ``mla`` named scope
(projections with the client LoRA, the latent cache writes, sequence-form
attention in prefill and absorbed attention in decode), from the profiler
trace (``lib/scopes.py``), over the traced batches."""


def read(run):
    sc = (run.get("scopes") or {}).get("scope_s") or {}
    if not sc.get("mla"):
        return None
    return 1e3 * sc["mla"] / run["traffic"]["trace_batches"]
