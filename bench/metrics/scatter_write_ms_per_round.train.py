"""The row writes into the store per round, in ms: the program's
``scatter.write`` spans (``fl/population.py::PopulationStore.scatter``, one
per slot: ``dst[ids] = ...`` for each leaf), averaged over the window's
rounds. Inside ``scatter``."""

SPAN = "scatter.write"


def read(run):
    spans = run.get("spans") or {}
    if not run.get("rounds") or SPAN not in spans:
        return None
    return 1e3 * spans[SPAN] / run["rounds"]
