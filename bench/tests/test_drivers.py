"""Each driver's loop, window and check at a reduced size on the CPU,
outside the timed path: a sound run comes out correct, and a run with the
timed path broken underneath comes out not correct, once for each fault
the cell can have (the cells run on one chip, so there is no exchange
between chips to leave out). The harness's look for a chip is skipped."""
import time

import jax
import pytest

import run as benchrun
from conftest import tiny_traffic
from lib import checks, faults, harness

PFTT = dict(population=16, cohort=4, batch=4, seq=16, local_steps=2,
            eval_rows=4, max_rounds_per_s=2000)
SERVE = dict(batch=2, prompt_len=16, gen_tokens=6, check_requests=4,
             check_block=2)


def drive(driver, cfg, traffic, seed=2**31 + 7, seconds=0.3):
    ctx = benchrun.Context(
        cell={"name": "test", "chips": 1}, config=cfg, traffic=traffic,
        seed=seed, seconds=seconds, trace=False, devices=jax.devices()[:1],
        meter=harness.CompileMeter(jax), t_start=time.perf_counter(),
        jax=jax)
    mod = benchrun._load_module(benchrun.BENCH / "drivers" / f"{driver}.py")
    return mod.run(ctx)


def correct(res, skip=()):
    limits = {k: v for k, v in res["limits"].items() if k not in skip}
    return checks.verdict(res["numbers"], limits)[0]


def test_pftt_sound_run(tiny_roberta):
    res = drive("pftt_population_round", tiny_roberta,
                tiny_traffic("pop1k-k8", **PFTT))
    assert correct(res), res["numbers"]
    assert res["window_compiles"] == 0
    assert res["metrics"]["train_tokens_per_s"] > 0
    assert res["attempted"] >= 1


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch",
                                   "answer_altered"])
def test_pftt_fault_is_caught(tiny_roberta, fault):
    with faults.plant(fault):
        res = drive("pftt_population_round", tiny_roberta,
                    tiny_traffic("pop1k-k8", **PFTT))
    assert not correct(res), (fault, res["numbers"])


def test_serve_sound_run(tiny_gpt2):
    res = drive("serve_closed_loop", tiny_gpt2,
                tiny_traffic("lora8-b8-p512-g64", **SERVE))
    # the kernel is interpreted on the CPU, so no custom call is compiled
    assert correct(res, skip=("missing_kernel",)), res["numbers"]
    assert res["window_compiles"] == 0
    m = res["metrics"]
    assert m["ttft_p95_ms"] > 0 and m["token_gap_p95_ms"] > 0


def test_serve_fault_token_altered(tiny_gpt2):
    with faults.plant("token_altered"):
        res = drive("serve_closed_loop", tiny_gpt2,
                    tiny_traffic("lora8-b8-p512-g64", **SERVE))
    assert not correct(res, skip=("missing_kernel",)), res["numbers"]


@pytest.mark.parametrize("driver,traffic,cfg", [
    ("pftt_population_round", "pop1k-k8", "tiny_roberta"),
    ("serve_closed_loop", "lora8-b8-p512-g64", "tiny_gpt2")])
def test_unread_traffic_key_is_refused(driver, traffic, cfg, request):
    """A traffic file cannot ask for behaviour its driver does not have."""
    over = PFTT if driver.startswith("pftt") else SERVE
    tr = tiny_traffic(traffic, codec="int8", **over)
    with pytest.raises(ValueError, match="codec"):
        drive(driver, request.getfixturevalue(cfg), tr)
