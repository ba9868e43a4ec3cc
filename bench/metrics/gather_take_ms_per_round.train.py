"""The store's row copies per round, in ms: the program's ``gather.take``
spans (``fl/population.py::PopulationStore.gather``, one per slot: the
``np.take`` of the sampled and ghost rows into the slot's staging
buffer), averaged over the window's rounds. Inside ``gather``."""

SPAN = "gather.take"


def read(run):
    spans = run.get("spans") or {}
    if not run.get("rounds") or SPAN not in spans:
        return None
    return 1e3 * spans[SPAN] / run["rounds"]
