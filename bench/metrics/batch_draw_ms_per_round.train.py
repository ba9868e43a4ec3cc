"""The round's batch draw per round, in ms: the program's
``device-step.draw`` span (``fl/population.py::PopulationRunner.run_round``:
the cohort's ``draw_batches``, the ghost rows and the stacker), averaged over
the window's rounds. Inside ``device-step``."""

SPAN = "device-step.draw"


def read(run):
    spans = run.get("spans") or {}
    if not run.get("rounds") or SPAN not in spans:
        return None
    return 1e3 * spans[SPAN] / run["rounds"]
