"""The host's wait on the fused round per round, in ms: the program's
``device-step.wait`` span (``fl/population.py::PopulationRunner.run_round``:
``jax.block_until_ready`` on the round step's outputs), averaged over the
window's rounds. Inside ``device-step``."""

SPAN = "device-step.wait"


def read(run):
    spans = run.get("spans") or {}
    if not run.get("rounds") or SPAN not in spans:
        return None
    return 1e3 * spans[SPAN] / run["rounds"]
