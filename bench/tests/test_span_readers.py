"""The readers of the round's child spans: a number from a run's window
spans, ``None`` where the program has no such span (a program from before
the spans were split), and a number from the PFTT driver's own run."""
import json

import pytest

import run as benchrun
from test_drivers import PFTT, drive
from conftest import tiny_traffic

READERS = {
    "gather_take_ms_per_round.train": ("gather.take", "gather"),
    "batch_draw_ms_per_round.train": ("device-step.draw", "device-step"),
    "device_wait_ms_per_round.train": ("device-step.wait", "device-step"),
    "scatter_pull_ms_per_round.train": ("scatter.pull", "scatter"),
    "scatter_write_ms_per_round.train": ("scatter.write", "scatter"),
}
PARENT_SPANS = {"round": 9.0, "sample": 0.1, "plan": 0.2, "gather": 4.0,
                "device-step": 2.0, "scatter": 1.0, "ledger": 0.1,
                "eval": 0.5}


def reader(name):
    return benchrun._load_module(benchrun.BENCH / "metrics" / f"{name}.py")


@pytest.mark.parametrize("name", sorted(READERS))
def test_reader_value_from_run(name):
    span = READERS[name][0]
    run = {"rounds": 4, "spans": dict(PARENT_SPANS, **{span: 0.6})}
    assert reader(name).read(run) == pytest.approx(150.0)


@pytest.mark.parametrize("name", sorted(READERS))
def test_reader_none_without_span(name):
    assert reader(name).read({"rounds": 4, "spans": PARENT_SPANS}) is None
    assert reader(name).read({"rounds": 0, "spans": {
        READERS[name][0]: 1.0}}) is None
    assert reader(name).read({"rounds": 4, "spans": None}) is None


def test_readers_listed_for_the_pftt_cells_only():
    spec = json.loads((benchrun.CHECKOUT / "BENCHMARK.json").read_text())
    by = {m["name"]: m for m in spec["per_layer"]}
    pftt = ["pftt-roberta.pop1k-k8", "pftt-roberta.pop1k-k32-fedsgd"]
    for name in READERS:
        assert by[name]["workloads"] == pftt
        assert by[name]["moves"] == "train_tokens_per_s"
    run = {"rounds": 2, "spans": dict(PARENT_SPANS, **{
        s: 0.1 for s, _ in READERS.values()}), "flops": 0.0,
        "window_s": 1.0, "chips": 1, "peaks": None, "trace": None}
    got = benchrun.per_layer_metrics(spec, "serve-gpt2.lora8-b8-p512-g64",
                                     run)
    assert not set(READERS) & set(got)


def test_readers_on_the_pftt_driver(tiny_roberta):
    """The driver's window carries the program's child spans; each reads
    a number no larger than its parent's share of the round."""
    res = drive("pftt_population_round", tiny_roberta,
                tiny_traffic("pop1k-k8", **PFTT))
    run = res["run"]
    for name, (span, parent) in READERS.items():
        value = reader(name).read(run)
        assert value is not None and value > 0, name
        assert value <= 1e3 * run["spans"][parent] / run["rounds"], name
