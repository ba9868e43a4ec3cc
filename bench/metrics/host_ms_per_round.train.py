"""Host time of the round driver per round, in ms: the program's own
``sample``, ``plan``, ``gather``, ``scatter`` and ``ledger`` spans
(``fl/population.py::PopulationRunner.run_round``), summed over the
window's rounds."""

SPANS = ("sample", "plan", "gather", "scatter", "ledger")


def read(run):
    spans = run.get("spans") or {}
    if not run.get("rounds") or "sample" not in spans:
        return None
    return 1e3 * sum(spans.get(s, 0.0) for s in SPANS) / run["rounds"]
