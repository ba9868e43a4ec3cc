"""The latent-attention MoE serving cell's driver, counts and readers on
the CPU at a reduced size (d 64, 4 heads, kv_lora 32, rope 16, nope 16,
v 16, 8 of 16 experts held, top 4, one shared, 1 dense + 2 MoE layers).

The faults run at d 1024: ``logit_gap``'s limit is in logits of the
published width, whose spread grows as the square root of the hidden size
(about 0.9 at d 2048, 0.16 at d 64), so at d 64 every fault reads small.
``expert_dropped`` moves no served token at this size (its routed part is
one softmax weight over 16 experts against a vocabulary of 512), so it is
read on the chip only (``tools/readings_mla_moe.py``)."""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import BENCH, tiny_traffic
from lib import faults_mla_moe, flops_mla_moe, scopes, trace_reduce
from test_drivers import correct, drive

TRACE = BENCH / "testdata" / "small.xplane.pb"
SERVE = dict(batch=4, prompt_len=16, gen_tokens=6, check_block=2)


@pytest.fixture
def tiny_dsv2():
    cfg = json.loads((BENCH / "configs" / "deepseek-v2-lite.json")
                     .read_text())
    cfg.update(hidden_size=64, num_attention_heads=4, num_key_value_heads=4,
               kv_lora_rank=32, qk_rope_head_dim=16, qk_nope_head_dim=16,
               v_head_dim=16, intermediate_size=96, moe_intermediate_size=32,
               router_experts=16, n_routed_experts=8, num_experts_per_tok=4,
               n_shared_experts=1, num_hidden_layers=3, vocab_size=512)
    return cfg


@pytest.fixture
def wide_dsv2(tiny_dsv2):
    return dict(tiny_dsv2, hidden_size=1024)


def _serve(cfg):
    return drive("serve_closed_loop_mla_moe", cfg,
                 tiny_traffic("lora8-b8-p4096-g64", **SERVE))


def test_mla_moe_sound_run(tiny_dsv2):
    res = _serve(tiny_dsv2)
    # the kernels run on the CPU's paths, so no custom call is compiled
    assert correct(res, skip=("missing_kernel",)), res["numbers"]
    assert res["window_compiles"] == 0
    assert res["numbers"]["moe_dropped"] == 0
    assert res["numbers"]["moe_rows_gap"] == 0
    assert res["numbers"]["logit_error"] < 1e-4
    c = res["run"]["counters"]
    batches = res["run"]["batches"]
    # every token of every step sends its 4 picks somewhere; about half
    # of them land on the 8 held experts of 16
    assert 0 < c["moe.rows"] < batches * 2 * (16 + 5) * 4 * 2
    assert c["moe.experts_hit"] > 0 and c["moe.dropped"] == 0
    assert res["run"]["flops"] > 0


@pytest.mark.parametrize("fault", ["experts_shifted", "rows_dropped"])
def test_mla_moe_fault_is_caught(wide_dsv2, fault):
    with faults_mla_moe.plant(fault):
        res = _serve(wide_dsv2)
    assert not correct(res, skip=("missing_kernel",)), (fault,
                                                        res["numbers"])


def test_mla_moe_control_reads_the_precision(wide_dsv2):
    """The check's numbers on the control, the reference computed in
    bfloat16 put in the program's place: its ``logit_error`` lies orders
    above the program's, which passes every limit.  At this size the
    control reads 0.022-0.035 over seeds, around the limit set at the
    published width; that it fails the limit there is read on the chip
    (``tools/readings_mla_moe.py``: 0.033-0.034 against the program's
    0.019-0.020)."""
    import time

    import run as benchrun
    from lib import checks, harness
    tr = tiny_traffic("lora8-b8-p4096-g64", **SERVE)
    ctx = benchrun.Context(
        cell={"name": "test", "chips": 1}, config=wide_dsv2, traffic=tr,
        seed=2**31 + 7, seconds=0.0, trace=False, devices=jax.devices()[:1],
        meter=harness.CompileMeter(jax), t_start=time.perf_counter(),
        jax=jax)
    drv = benchrun._load_module(BENCH / "drivers" /
                                "serve_closed_loop_mla_moe.py")
    b = drv.build(ctx, ctx.seed)
    logits = []
    _, served, counters = b.serve_batch(0, logits_out=logits)
    rows = drv.batch_rows(counters)
    ref = drv.reference_check(wide_dsv2, tr, b.streams, 0, served,
                              np.stack(logits, 1), control=True)
    program = {**drv.logit_numbers(ref["gaps"], ref["err"]),
               "moe_rows_gap": drv.rows_gap(rows, ref["held"]),
               "moe_dropped": 0.0}
    limits = {k: v for k, v in tr["limits"].items() if k != "missing_kernel"}
    assert checks.verdict(program, limits)[0], program
    control = drv.logit_numbers(ref["control_gaps"], ref["control_err"])
    assert control["logit_error"] > 1e3 * program["logit_error"]


def test_gmm_count_against_jaxpr_walk():
    """The grouped matmul's required FLOPs against the program's jaxpr
    walker (``launch/jaxpr_cost.py``) on a plain per-group product over
    the routed rows only; its bytes against the arrays that product reads
    and writes (the experts hit, each row in and out)."""
    from repro.launch.jaxpr_cost import step_flops
    sizes = [5, 0, 17, 3]
    k, n = 24, 40
    x = jnp.ones((sum(sizes), k))
    w = jnp.ones((len(sizes), k, n))

    def per_group(x, w):
        outs, o = [], 0
        for g, s in enumerate(sizes):
            if s:
                outs.append(x[o:o + s] @ w[g])
            o += s
        return jnp.concatenate(outs)

    hit = sum(1 for s in sizes if s)
    f, nb = flops_mla_moe.gmm_call(sum(sizes), hit, k, n)
    assert step_flops(per_group, x, w) == f
    read = x.nbytes + hit * w[0].nbytes
    assert nb == read + sum(sizes) * n * 4


def test_scope_reader_on_recorded_trace():
    """On a trace recorded on a TPU v5e (three fused-LoRA steps), the ops
    under the kernel's jit scope are the kernel's events, with the time
    and count the trace reducer finds by name."""
    red = trace_reduce.reduce_trace(str(TRACE), kernels=("lora_matmul",))
    got = scopes.scope_times(str(TRACE), ("jit(lora_matmul)", "dot_general",
                                          "moe"))
    assert got["scope_s"]["jit(lora_matmul)"] == pytest.approx(
        red["kernel_s"]["lora_matmul"], abs=1e-12)
    assert got["kernel_calls"]["jit(lora_matmul)"] == 3
    assert got["kernel_s"]["dot_general"] == 0.0
    assert got["scope_s"]["dot_general"] > 0
    assert got["scope_s"]["moe"] == 0.0
    assert scopes.under("jit(f)/while/body/moe/gmm/pallas_call:", "moe/gmm")
    assert scopes.under("jit(f)/moe/route/argsort", "moe")
    assert scopes.under("jit(f)/while/body/closed_call/moe/while/body/"
                        "closed_call/gmm/jit(moe_gmm)/pallas_call:", "moe/gmm")
    assert not scopes.under("jit(f)/moe_x/gmm", "moe")
    assert not scopes.under("jit(f)/gmm/moe", "moe/gmm")


def test_new_readers():
    """The three new per-layer readers on a run record, and nothing read
    where the trace found none of their scope (as on the parent, which
    has no such scope)."""
    from run import _load_module
    rd = {n: _load_module(BENCH / "metrics" / f"{n}.py") for n in (
        "moe_ms_per_batch.serve", "mla_ms_per_batch.serve",
        "moe_gmm_roofline")}
    run = {"traffic": {"trace_batches": 2},
           "scopes": {"scope_s": {"moe": 0.2, "mla": 0.1, "moe/gmm": 0.05},
                      "kernel_s": {"moe/gmm": 0.04}},
           "gmm": {"least_s": 0.01, "scope": "moe/gmm"}}
    assert rd["moe_ms_per_batch.serve"].read(run) == pytest.approx(100.0)
    assert rd["mla_ms_per_batch.serve"].read(run) == pytest.approx(50.0)
    assert rd["moe_gmm_roofline"].read(run) == pytest.approx(25.0)
    empty = {"traffic": {"trace_batches": 2},
             "scopes": {"scope_s": {"moe": 0.0, "mla": 0.0},
                        "kernel_s": {"moe/gmm": 0.0}},
             "gmm": {"least_s": 0.01, "scope": "moe/gmm"}}
    for r in rd.values():
        assert r.read(empty) is None and r.read({}) is None
