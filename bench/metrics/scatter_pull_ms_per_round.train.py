"""The device-to-host pull of the round's results per round, in ms: the
program's ``scatter.pull`` spans
(``fl/population.py::PopulationStore.scatter``, one per slot: ``np.asarray``
of each result leaf), averaged over the window's rounds. Inside
``scatter``."""

SPAN = "scatter.pull"


def read(run):
    spans = run.get("spans") or {}
    if not run.get("rounds") or SPAN not in spans:
        return None
    return 1e3 * spans[SPAN] / run["rounds"]
