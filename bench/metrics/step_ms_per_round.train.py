"""The fused round's time per round, in ms: the program's ``device-step``
span (batch draw, stacking, the round step and its block) plus the
harness's ``eval`` span (the cohort eval dispatch and its block)."""


def read(run):
    spans = run.get("spans") or {}
    if not run.get("rounds") or "device-step" not in spans:
        return None
    return 1e3 * (spans["device-step"] + spans.get("eval", 0.0)) / run["rounds"]
