"""Device time of the expert layers per traced batch, in ms: the self time
of the ops whose ``tf_op`` lies under the program's ``moe`` named scope
(routing, the grouped matmuls, the combine and the shared experts of every
MoE layer, prefill and decode), from the profiler trace
(``lib/scopes.py``), over the traced batches."""


def read(run):
    sc = (run.get("scopes") or {}).get("scope_s") or {}
    if not sc.get("moe"):
        return None
    return 1e3 * sc["moe"] / run["traffic"]["trace_batches"]
