"""The control, at a size a test run can hold: the plain reference
computed in bfloat16, put in the program's place, has to come out not
correct under each cell's limits (the configurations state float32, and
bfloat16 is the precision below it). At the cells' own sizes the control
is read on the chip (``bench/tools/readings.py --control``); PERF.md gives
those readings."""

from conftest import tiny_traffic
from lib import checks, weights
import run as benchrun

BENCH = benchrun.BENCH


def _driver(name):
    return benchrun._load_module(BENCH / "drivers" / f"{name}.py")


def test_pftt_control_fails(tiny_roberta):
    drv = _driver("pftt_population_round")
    tr = tiny_traffic("pop1k-k8", population=16, cohort=4, batch=4, seq=16,
                      local_steps=2, eval_rows=4)
    streams = dict(zip(("weights", "clients", "sampler", "channel", "data"),
                       weights.seed_streams(2**31 + 11, 5)))
    ref = drv.reference(tiny_roberta, tr, streams, tr["check_rounds"])
    ctl = drv.reference(tiny_roberta, tr, streams, tr["check_rounds"],
                        control=True)
    nums = checks.train_numbers(ctl, ref)
    assert not checks.verdict(nums, tr["limits"])[0], nums


def test_serve_control_fails():
    """At gpt2-small's own widths (the error grows with depth and width),
    with shorter prompts and as many served positions as a run checks:
    8 requests, 64-token prompts, 64 positions each."""
    import json
    import numpy as np
    cfg = json.loads((BENCH / "configs" / "gpt2-small.json").read_text())
    drv = _driver("serve_closed_loop")
    tr = tiny_traffic("lora8-b8-p512-g64", batch=4, prompt_len=64,
                      gen_tokens=64, check_block=4)
    streams = dict(zip(("weights", "clients", "data", "sample"),
                       weights.seed_streams(2**31 + 13, 4)))
    picks = [(i, r) for i in range(2) for r in range(4)]
    served = np.zeros((8, tr["gen_tokens"]), np.int32)
    _, ctl = drv.reference_gaps(cfg, tr, streams, picks, served,
                                control=True)
    assert ctl > tr["limits"]["logit_gap"], ctl
