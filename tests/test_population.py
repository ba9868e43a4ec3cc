"""Population-scale FL: store gather/scatter, seeded samplers, scenario
traces, and the sampled-cohort round's parity with a standalone cohort.

The contract under test (fl/population.py + wireless/scenarios.py):

* ``ClientSampler`` is one seeded stream — same seed → same cohort
  sequence, and a ``state_dict`` snapshot restored mid-stream reproduces
  the uninterrupted sequence exactly (checkpoint resume).
* ``PopulationStore.gather``/``scatter`` round-trip rows losslessly,
  never touch unsampled rows, and reuse ONE staging buffer per slot
  (steady-state rounds allocate nothing).
* A sampled cohort pushed through the fused robust round body and
  scattered back equals the same clients run as a standalone
  ``n_clients=cohort`` stack, ≤1e-6 (here: bitwise — same program, same
  inputs).
* ``Scenario.realize`` is a pure function of the spec: per-axis draw
  blocks keep class_probs stable when availability/mobility toggle.
* Every leaf the store holds is writable and C-contiguous, whatever the
  layout it was handed (a TPU pull keeps the device's); ``relaid_bytes``
  counts the bytes copied into C order, and a C-ordered numpy input costs
  nothing.
* ``PopulationRunner.run_round`` splits its ``gather``, ``device-step``
  and ``scatter`` spans into the store's and the step's child spans and
  byte counters, each child inside its parent, and the tracer changes
  nothing the store holds.
"""
import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import trees
from repro.fl.population import (ClientSampler, PopulationConfig,
                                 PopulationData, PopulationRunner,
                                 PopulationStore, stacked_client_init)
from repro.obs.trace import SpanTracer
from repro.wireless.scenarios import Scenario

# ---------------------------------------------------------------------------
# ClientSampler: determinism + mid-stream resume
# ---------------------------------------------------------------------------


def test_sampler_same_seed_same_stream():
    a = ClientSampler("uniform", 100, 8, seed=7)
    b = ClientSampler("uniform", 100, 8, seed=7)
    for _ in range(10):
        np.testing.assert_array_equal(a.sample(), b.sample())


def test_sampler_different_seed_differs():
    a = ClientSampler("uniform", 1000, 8, seed=0)
    b = ClientSampler("uniform", 1000, 8, seed=1)
    assert any(not np.array_equal(a.sample(), b.sample()) for _ in range(5))


def test_sampler_cohort_shape_and_uniqueness():
    s = ClientSampler("uniform", 50, 16, seed=0)
    for _ in range(20):
        ids = s.sample()
        assert ids.shape == (16,)
        assert len(np.unique(ids)) == 16
        assert np.all(np.diff(ids) > 0)          # sorted, no repeats
        assert ids.min() >= 0 and ids.max() < 50


def test_sampler_midstream_resume_reproduces_stream():
    """A state_dict taken mid-stream resumes into the SAME uninterrupted
    cohort sequence (the checkpoint/resume contract)."""
    ref = ClientSampler("uniform", 200, 8, seed=3)
    full = [ref.sample() for _ in range(12)]

    first = ClientSampler("uniform", 200, 8, seed=3)
    for _ in range(5):
        first.sample()
    snap = first.state_dict()

    resumed = ClientSampler("uniform", 200, 8, seed=3)
    resumed.load_state_dict(snap)
    for r in range(5, 12):
        np.testing.assert_array_equal(resumed.sample(), full[r])


def test_sampler_state_dict_json_roundtrip():
    import json
    s = ClientSampler("availability", 64, 4, seed=1)
    p = np.linspace(0.1, 1.0, 64)
    s.sample(p)
    snap = json.loads(json.dumps(s.state_dict()))   # sidecar is JSON
    t = ClientSampler("availability", 64, 4, seed=99)
    t.load_state_dict(snap)
    for _ in range(5):
        np.testing.assert_array_equal(s.sample(p), t.sample(p))


def test_availability_sampler_skews_to_reachable():
    s = ClientSampler("availability", 100, 10, seed=0)
    p = np.full(100, 1e-6)
    p[:20] = 1.0            # only the first 20 clients are reachable
    counts = np.zeros(100)
    for _ in range(50):
        counts[s.sample(p)] += 1
    assert counts[:20].sum() > 0.99 * counts.sum()


def test_sampler_unknown_kind_raises():
    with pytest.raises(ValueError):
        ClientSampler("roundrobin", 10, 2)


# ---------------------------------------------------------------------------
# PopulationConfig validation
# ---------------------------------------------------------------------------


def test_population_config_validates():
    PopulationConfig(population=100, cohort_size=8)
    with pytest.raises(ValueError):
        PopulationConfig(population=4, cohort_size=8)
    with pytest.raises(ValueError):
        PopulationConfig(population=10, cohort_size=0)
    with pytest.raises(ValueError):
        PopulationConfig(population=10, cohort_size=2, sampler="magic")
    # availability sampling needs an availability trace to weight by
    with pytest.raises(ValueError):
        PopulationConfig(population=10, cohort_size=2,
                         sampler="availability")
    with pytest.raises(ValueError):
        PopulationConfig(population=10, cohort_size=2,
                         sampler="availability", scenario=Scenario())
    PopulationConfig(population=10, cohort_size=2, sampler="availability",
                     scenario=Scenario(avail="diurnal"))


# ---------------------------------------------------------------------------
# PopulationStore: gather/scatter round-trip, isolation, buffer reuse
# ---------------------------------------------------------------------------


def _toy_store(n, seed=0):
    r = np.random.RandomState(seed)
    tree = {"a": {"w": r.randn(n, 3, 4).astype(np.float32)},
            "b": r.randn(n, 5).astype(np.float32)}
    return PopulationStore({"trainable": tree}), tree


def test_store_gather_scatter_roundtrip():
    store, ref = _toy_store(32)
    ids = np.asarray([3, 7, 11, 30])
    g = store.gather("trainable", ids)
    np.testing.assert_array_equal(g["a"]["w"], ref["a"]["w"][ids])
    np.testing.assert_array_equal(g["b"], ref["b"][ids])
    store.scatter("trainable", ids, jax.tree_util.tree_map(jnp.asarray, g))
    np.testing.assert_array_equal(store.slots["trainable"]["a"]["w"],
                                  ref["a"]["w"])


def test_store_scatter_leaves_unsampled_rows_untouched():
    store, ref = _toy_store(16)
    ids = np.asarray([2, 5])
    new = jax.tree_util.tree_map(
        lambda l: jnp.zeros((2,) + l.shape[1:], l.dtype),
        store.gather("trainable", ids))
    store.scatter("trainable", ids, new)
    mask = np.ones(16, bool)
    mask[ids] = False
    np.testing.assert_array_equal(store.slots["trainable"]["b"][mask],
                                  ref["b"][mask])
    np.testing.assert_array_equal(store.slots["trainable"]["b"][ids], 0.0)


def test_store_gather_ghost_pad_repeats_first_row():
    store, ref = _toy_store(8)
    ids = np.asarray([1, 4])
    g = store.gather("trainable", ids, pad_to=5)
    assert g["b"].shape == (5, 5)
    for ghost in range(2, 5):
        np.testing.assert_array_equal(g["b"][ghost], ref["b"][1])


def test_store_gather_reuses_staging_buffer():
    """Steady-state rounds must not allocate: the second gather refills the
    SAME numpy buffer objects."""
    store, _ = _toy_store(16)
    g1 = store.gather("trainable", np.asarray([0, 1]), pad_to=4)
    g2 = store.gather("trainable", np.asarray([9, 3]), pad_to=4)
    assert g1["b"] is g2["b"]
    assert g1["a"]["w"] is g2["a"]["w"]


def test_store_scatter_copies_out_of_device_buffer():
    """scatter must COPY device results: a zero-copy view of a donated jax
    buffer would dangle once the next round rebinds it."""
    store, _ = _toy_store(4)
    ids = np.asarray([0, 1])
    dev = jax.tree_util.tree_map(jnp.asarray, store.gather("trainable", ids))
    store.scatter("trainable", ids, dev)
    for leaf in jax.tree_util.tree_leaves(store.slots["trainable"]):
        assert leaf.flags.writeable            # host-owned, not a jax view


def test_store_zero_rows():
    store, ref = _toy_store(8)
    store.zero_rows("trainable", [2, 6])
    np.testing.assert_array_equal(store.slots["trainable"]["b"][2], 0.0)
    np.testing.assert_array_equal(store.slots["trainable"]["b"][5],
                                  ref["b"][5])


def test_store_checkpoint_roundtrip():
    store, ref = _toy_store(8)
    tree = store.checkpoint_tree()
    store2, _ = _toy_store(8, seed=1)
    store2.load_checkpoint_tree(tree)
    np.testing.assert_array_equal(store2.slots["trainable"]["b"], ref["b"])
    # restored slots stay writable (np.savez round-trips can return
    # read-only arrays)
    store2.zero_rows("trainable", [0])


def _transposed_tree(n, writable, seed=0):
    """A tree laid out as a TPU pull is: leaf ``a/w`` (n, 3, 4, 8) with its
    last two axes swapped in memory (not C-contiguous), leaf ``b`` a
    C-contiguous (n, 5); both read-only unless ``writable``."""
    r = np.random.RandomState(seed)
    tree = {"a": {"w": np.swapaxes(
                r.randn(n, 3, 8, 4).astype(np.float32), -1, -2)},
            "b": r.randn(n, 5).astype(np.float32)}
    for leaf in jax.tree_util.tree_leaves(tree):
        assert leaf.flags.writeable
        leaf.flags.writeable = writable
    assert not tree["a"]["w"].flags.c_contiguous
    return tree


def _assert_host_c(store, slot="trainable"):
    for leaf in jax.tree_util.tree_leaves(store.slots[slot]):
        assert leaf.flags.c_contiguous and leaf.flags.writeable


@pytest.mark.parametrize("writable", [False, True])
def test_store_lays_out_transposed_source_c_contiguous(writable):
    src = _transposed_tree(10, writable)
    store = PopulationStore({"trainable": src})
    _assert_host_c(store)
    for k, leaf in trees.flatten(src).items():
        np.testing.assert_array_equal(
            trees.flatten(store.slots["trainable"])[k], leaf, err_msg=k)
    # only the non-C leaf was re-laid out; the C leaf, read-only or not,
    # is no re-layout
    assert store.relaid_bytes == src["a"]["w"].nbytes
    if writable:                       # a writable C leaf is kept as is
        assert store.slots["trainable"]["b"] is src["b"]


def test_store_of_c_source_relays_nothing():
    store, ref = _toy_store(8)
    assert store.relaid_bytes == 0
    for k, leaf in trees.flatten(ref).items():
        assert trees.flatten(store.slots["trainable"])[k] is leaf


@pytest.mark.parametrize("writable", [False, True])
def test_store_transposed_source_gather_scatter_bit_exact(writable):
    """On a store built from a transposed source, gather (ghost rows
    included) and scatter equal plain fancy indexing bit for bit."""
    src = _transposed_tree(12, writable)
    store = PopulationStore({"trainable": src})
    ref = jax.tree_util.tree_map(np.array, src)
    ids = np.asarray([9, 2, 6])
    full = np.asarray([9, 2, 6, 9, 9])
    g = store.gather("trainable", ids, pad_to=5)
    for k, leaf in trees.flatten(ref).items():
        np.testing.assert_array_equal(trees.flatten(g)[k], leaf[full],
                                      err_msg=k)
    r = np.random.RandomState(3)
    new = jax.tree_util.tree_map(
        lambda l: r.randn(5, *l.shape[1:]).astype(l.dtype), ref)
    store.scatter("trainable", ids,
                  jax.tree_util.tree_map(jnp.asarray, new))
    for k, leaf in trees.flatten(ref).items():
        leaf[ids] = trees.flatten(new)[k][:3]
        np.testing.assert_array_equal(
            trees.flatten(store.slots["trainable"])[k], leaf, err_msg=k)
    _assert_host_c(store)


def test_store_load_checkpoint_tree_relays_transposed_tree():
    store, _ = _toy_store(10)
    store.tracer = SpanTracer()
    src = _transposed_tree(10, writable=False, seed=4)
    store.load_checkpoint_tree({"trainable": src})
    _assert_host_c(store)
    np.testing.assert_array_equal(store.slots["trainable"]["a"]["w"],
                                  src["a"]["w"])
    assert store.relaid_bytes == src["a"]["w"].nbytes
    assert store.tracer.counts() == {
        "store.relaid_bytes": src["a"]["w"].nbytes}


def test_stacked_client_init_broadcasts_constants():
    keys = jax.vmap(lambda i: jax.random.fold_in(jax.random.PRNGKey(0), i))(
        jnp.arange(6))
    st = stacked_client_init(
        lambda k: {"w": jax.random.normal(k, (3,)),
                   "c": jnp.zeros((2,))}, keys)
    assert st["w"].shape == (6, 3)
    assert st["c"].shape == (6, 2)
    assert len({tuple(np.asarray(st["w"][i])) for i in range(6)}) == 6


# ---------------------------------------------------------------------------
# sampled-cohort round ≡ standalone cohort (the tentpole parity claim)
# ---------------------------------------------------------------------------


def _toy_cohort(n, seed=0):
    from repro.optim import sgd

    def loss_fn(tr, batch):
        return jnp.mean((tr["shared"]["w"].sum() + tr["local"]["v"].sum()
                         - batch["tgt"]) ** 2)

    opt = sgd(1e-2)

    def local_step(tr, op, batch):
        loss, grads = jax.value_and_grad(loss_fn)(tr, batch)
        upd, op = opt.update(grads, op, tr)
        return jax.tree_util.tree_map(lambda p, u: p + u, tr, upd), op, loss

    rng = np.random.RandomState(seed)
    mk = lambda: {"shared": {"w": rng.randn(3).astype(np.float32)},
                  "local": {"v": rng.randn(2).astype(np.float32)}}
    stacked = trees.stack([mk() for _ in range(n)])
    return local_step, opt, stacked, rng


def test_sampled_round_matches_standalone_cohort():
    """Gather K rows from an N-client store, run the fused robust round,
    scatter back — the sampled rows must equal the same K clients run as a
    standalone n_clients=K stack (same compiled program, same inputs: the
    store adds nothing numerically).  ≤1e-6 required; bitwise expected."""
    from repro.core.cohort import build_supervised_round

    N, K = 24, 4
    local_step, opt, stacked, rng = _toy_cohort(N)
    st_op = stacked_client_init(
        lambda k: opt.init({"shared": {"w": jnp.zeros(3)},
                            "local": {"v": jnp.zeros(2)}}),
        jax.vmap(lambda i: jax.random.fold_in(jax.random.PRNGKey(0), i))(
            jnp.arange(N)))
    pend = jax.tree_util.tree_map(
        np.zeros_like, trees.select(stacked, lambda p: p.startswith("shared")))
    store = PopulationStore({"trainable": stacked, "opt": st_op,
                             "pending": pend})

    step = build_supervised_round(local_step,
                                  lambda p: p.startswith("shared"),
                                  donate=False, robust=True)
    ids = ClientSampler("uniform", N, K, seed=5).sample()
    batches = {"tgt": jnp.asarray(rng.randn(K, 2, 1), np.float32)}
    train = jnp.asarray([1.0, 0.0, 1.0, 1.0])     # client 1 straggles
    aggw = jnp.asarray([1.0, 0.5, 1.0, 1.0])
    recv = rej = None
    recv, rej, ontime = jnp.ones(K), jnp.zeros(K), jnp.ones(K)

    # standalone reference: the K clients as their own cohort
    ref_tr = jax.tree_util.tree_map(jnp.asarray,
                                    store.gather("trainable", ids))
    ref_op = jax.tree_util.tree_map(jnp.asarray, store.gather("opt", ids))
    ref_pd = jax.tree_util.tree_map(jnp.asarray,
                                    store.gather("pending", ids))
    ref = step(ref_tr, ref_op, ref_pd, batches, train, aggw, recv, rej,
               ontime)

    # population path: gather → round → scatter → read the rows back
    tr_d = jax.tree_util.tree_map(jnp.asarray,
                                  store.gather("trainable", ids))
    op_d = jax.tree_util.tree_map(jnp.asarray, store.gather("opt", ids))
    pd_d = jax.tree_util.tree_map(jnp.asarray, store.gather("pending", ids))
    out = step(tr_d, op_d, pd_d, batches, train, aggw, recv, rej, ontime)
    store.scatter("trainable", ids, out[0])
    store.scatter("opt", ids, out[1])
    store.scatter("pending", ids, out[2])

    got_tr = store.gather("trainable", ids)
    for k, leaf in trees.flatten(ref[0]).items():
        np.testing.assert_allclose(np.asarray(leaf),
                                   trees.flatten(got_tr)[k], atol=1e-6,
                                   err_msg=k)
    got_pd = store.gather("pending", ids)
    for k, leaf in trees.flatten(ref[2]).items():
        np.testing.assert_allclose(np.asarray(leaf),
                                   trees.flatten(got_pd)[k], atol=1e-6,
                                   err_msg=k)


# ---------------------------------------------------------------------------
# child spans and byte counters of the round driver and the store
# ---------------------------------------------------------------------------

CHILD_SPANS = {"gather.take": "gather", "gather.settle": "gather",
               "device-step.draw": "device-step",
               "device-step.wait": "device-step", "scatter.pull": "scatter",
               "scatter.write": "scatter"}


def _toy_runner(N=12, K=3, rounds=3, tracer=None, n_rows=None,
                fortran=False, donate=False, record=None, devices=1):
    """A PopulationRunner over the toy cohort: robust round step, Rayleigh
    uplink, no faults; ``n_rows`` > K adds ghost rows (the cohort sharded
    over a ``("data",)`` mesh of ``devices`` devices); ``fortran`` hands
    the store column-major trainable and optimizer leaves; ``donate``
    builds the round step that donates its stacked inputs, as the
    program's does; ``record`` (a list) receives host copies of each
    round step call's batches and fault masks."""
    from repro.comms import ChannelBudget
    from repro.core.cohort import HostBatchStacker, build_supervised_round
    from repro.core.robust import StalenessConfig, StalenessTracker
    from repro.sharding import CohortSharding, auto_mesh
    from repro.wireless import CommLedger, FaultPlan, RayleighChannel

    local_step, opt, stacked, _ = _toy_cohort(N)
    upload = lambda p: p.startswith("shared")
    st_op = stacked_client_init(
        lambda k: opt.init({"shared": {"w": jnp.zeros(3)},
                            "local": {"v": jnp.zeros(2)}}),
        jax.vmap(lambda i: jax.random.fold_in(jax.random.PRNGKey(0), i))(
            jnp.arange(N)))
    if fortran:
        stacked, st_op = jax.tree_util.tree_map(np.asfortranarray,
                                                (stacked, st_op))
    pend = jax.tree_util.tree_map(np.zeros_like,
                                  trees.select(stacked, upload))
    store = PopulationStore({"trainable": stacked, "opt": st_op,
                             "pending": pend})
    channel = RayleighChannel(seed=3)
    cs = None if n_rows is None else CohortSharding(
        mesh=auto_mesh((devices,), ("data",)), axes=("data",), n_clients=K,
        total=n_rows)
    runner = PopulationRunner(
        pop=PopulationConfig(population=N, cohort_size=K),
        store=store,
        global_shared=jax.tree_util.tree_map(
            np.array, trees.select(store.row("trainable", 0), upload)),
        upload_pred=upload, channel=channel,
        budget=ChannelBudget(channel), ledger=CommLedger(),
        tracker=StalenessTracker(N, StalenessConfig()),
        trace=FaultPlan().realize(N, rounds),
        strace=Scenario().realize(N, rounds),
        sampler=ClientSampler("uniform", N, K, seed=7), cs=cs,
        tracer=tracer)
    step = build_supervised_round(local_step, upload, donate=donate,
                                  robust=True)
    if record is not None:
        inner = step

        def step(*args):
            record.append(jax.tree_util.tree_map(np.array, args[3:]))
            return inner(*args)

    def draw(cid, rnd):
        r = np.random.RandomState(100 * cid + rnd)
        return [{"tgt": r.randn(1).astype(np.float32)} for _ in range(2)]

    def one_round(rnd):
        return runner.run_round(rnd, round_step=step,
                                stacker=HostBatchStacker(),
                                draw_batches=draw, local_steps=2,
                                payload_bits=96.0)
    return runner, store, one_round


def test_run_round_records_child_spans_inside_parents():
    """Four of six clients a round, so consecutive cohorts always share
    clients and every round after the first settles some in its gather.
    A round writes the previous round's results, so round 0 has no
    ``scatter.pull``/``scatter.write`` and the last round's come with
    ``settle()``."""
    runner, _, one_round = _toy_runner(N=6, K=4,
                                       tracer=SpanTracer(enabled=True))
    tracer = runner.tracer
    for rnd in range(3):
        one_round(rnd)
        phases = tracer.pop_round()
        want = set(CHILD_SPANS) - (
            {"gather.settle", "scatter.pull", "scatter.write"}
            if rnd == 0 else set())
        assert want <= set(phases)
        for parent in set(CHILD_SPANS.values()):
            kids = sum(v for k, v in phases.items()
                       if CHILD_SPANS.get(k) == parent)
            assert kids <= phases[parent]
    runner.settle()
    # one gather.take, scatter.pull and scatter.write per slot and round
    ev = tracer.chrome_trace()["traceEvents"]
    for name in ("gather.take", "scatter.pull", "scatter.write"):
        slots = [e["args"]["slot"] for e in ev if e["name"] == name]
        assert slots == ["trainable", "opt", "pending"] * 3
    # every child lies inside one of its parent's events
    spans = [e for e in ev if e["ph"] == "X"]
    for c in spans:
        if c["name"] not in CHILD_SPANS:
            continue
        assert any(p["name"] == CHILD_SPANS[c["name"]]
                   and p["ts"] <= c["ts"]
                   and c["ts"] + c["dur"] <= p["ts"] + p["dur"] + 1e-3
                   for p in spans)
    # host_s still reads the parents alone
    tot = tracer.totals()
    assert runner.host_s == pytest.approx(
        tot["sample"] + tot["gather"] + tot["scatter"])


@pytest.mark.parametrize("n_rows", [None, 4])
def test_run_round_byte_counters(n_rows):
    """gather.bytes is the staging buffers' nbytes (ghost rows included);
    scatter.bytes is the round's result trees'."""
    runner, store, one_round = _toy_runner(n_rows=n_rows)
    out = one_round(0)
    staged = sum(leaf.nbytes for buf in store._bufs.values()
                 for leaf in jax.tree_util.tree_leaves(buf))
    rows = n_rows or 3
    assert staged == rows * store.nbytes() // store.n_clients
    counts = runner.tracer.counts()
    assert counts["gather.bytes"] == staged
    # the three result slots have the staged slots' shapes
    assert counts["scatter.bytes"] == staged
    assert sum(leaf.nbytes for leaf in jax.tree_util.tree_leaves(
        out["cohort_tr"])) == rows * sum(
        leaf.nbytes for leaf in jax.tree_util.tree_leaves(
            store.slots["trainable"])) // store.n_clients
    one_round(1)
    got = runner.tracer.counts()
    assert {k: got[k] for k in counts} == {k: 2 * v
                                          for k, v in counts.items()}


@pytest.mark.parametrize("fortran", [False, True])
def test_runner_counts_store_relaid_bytes(fortran):
    """Handing the store its tracer counts, once, the bytes the store
    re-laid out: a store built from C-ordered host arrays reads 0, one
    built from column-major leaves reads their bytes."""
    runner, store, one_round = _toy_runner(fortran=fortran)
    # column-major leaves of two or more axes are not C-contiguous
    want = sum(leaf.nbytes for tree in store.slots.values()
               for leaf in jax.tree_util.tree_leaves(tree)
               if fortran and leaf.ndim > 1)
    assert (want > 0) == fortran
    assert store.relaid_bytes == want
    assert runner.tracer.counts()["store.relaid_bytes"] == \
        store.relaid_bytes
    one_round(0)
    assert runner.tracer.counts()["store.relaid_bytes"] == \
        store.relaid_bytes


def test_run_round_store_identical_for_any_input_layout():
    """Rounds over a store built from column-major leaves (as a TPU pull
    may be laid out; the pending slot from ``np.zeros_like`` of them,
    writable) leave a store bit-identical to the C-ordered one's, every
    leaf C-contiguous."""
    run_a, fort, one_a = _toy_runner(fortran=True)
    run_b, plain, one_b = _toy_runner()
    for rnd in range(3):
        one_a(rnd)
        one_b(rnd)
    run_a.settle()
    run_b.settle()
    for slot in ("trainable", "opt", "pending"):
        _assert_host_c(fort, slot)
        a = trees.flatten(fort.slots[slot])
        b = trees.flatten(plain.slots[slot])
        assert a.keys() == b.keys()
        for k in a:
            np.testing.assert_array_equal(a[k], b[k], err_msg=k)


def test_run_round_store_identical_with_and_without_tracing():
    """Tracing changes nothing the store holds: an enabled tracer's round
    and a default (disabled) one's leave bit-identical stores."""
    run_a, traced, one_a = _toy_runner(tracer=SpanTracer(enabled=True))
    run_b, plain, one_b = _toy_runner()
    for rnd in range(3):
        one_a(rnd)
        one_b(rnd)
    run_a.settle()
    run_b.settle()
    for slot in ("trainable", "opt", "pending"):
        a = trees.flatten(traced.slots[slot])
        b = trees.flatten(plain.slots[slot])
        assert a.keys() == b.keys()
        for k in a:
            np.testing.assert_array_equal(a[k], b[k], err_msg=k)


# ---------------------------------------------------------------------------
# the deferred scatter: a round's results are written during the next round
# ---------------------------------------------------------------------------

UPLOAD = lambda p: p.startswith("shared")


def _settled_copy(store):
    """The store's slots as ``settle()`` would leave them, read without
    settling, so the next round still finds its pending scatter."""
    out = {k: jax.tree_util.tree_map(np.array, v)
           for k, v in store._slots.items()}
    p = store._pending
    if p is not None:
        k = len(p.ids)
        for slot, res in p.results.items():
            jax.tree_util.tree_map(
                lambda dst, src: dst.__setitem__(p.ids, np.asarray(src)[:k]),
                out[slot], res)
    return out


def _serial_rounds(N, K, n_rows, rounds, calls, plans, donate, devices):
    """The serial order, written from ``PopulationStore.gather``/``scatter``
    and the round step alone: each round's results are in the store before
    the next round gathers.  The batches and fault masks are the runner's
    (``calls``), the merge gates its plans'.  Yields per round the cohort,
    the store, the global and the losses."""
    from repro.core.cohort import build_supervised_round
    ref_run, store, _ = _toy_runner(N=N, K=K, rounds=rounds, n_rows=n_rows,
                                    devices=devices)
    local_step = _toy_cohort(N)[0]
    step = build_supervised_round(local_step, UPLOAD, donate=donate,
                                  robust=True)
    put = jax.device_put if n_rows is None else \
        (lambda t: jax.device_put(t, ref_run.cs.named))
    sampler = ClientSampler("uniform", N, K, seed=7)
    glob = jax.tree_util.tree_map(
        np.array, trees.select(store.row("trainable", 0), UPLOAD))
    for rnd in range(rounds):
        ids = sampler.sample()
        tr = store.gather("trainable", ids, pad_to=n_rows or K)
        flat_g = trees.flatten(glob)
        for path, leaf in trees.flatten(tr).items():
            if flat_g.get(path) is not None:
                leaf[:] = flat_g[path]
        op = store.gather("opt", ids, pad_to=n_rows or K)
        pd = store.gather("pending", ids, pad_to=n_rows or K)
        batches, margs = calls[rnd][0], calls[rnd][1:]
        outs = step(put(tr), put(op), put(pd), jax.device_put(batches),
                    *[put(m) for m in margs])
        for slot, res in zip(("trainable", "opt", "pending"), outs[:3]):
            store.scatter(slot, ids, res)
        plan = plans[rnd]
        if float(plan.agg_w.sum()) > 0 and plan.quorum_ok:
            recv_rows = np.where(plan.recv[ids] > 0)[0]
            if len(recv_rows):
                glob = jax.tree_util.tree_map(np.array, trees.select(
                    store.row("trainable", int(ids[recv_rows[0]])), UPLOAD))
        yield (ids, jax.tree_util.tree_map(np.array, store.slots), glob,
               np.array(outs[3]))


def _assert_trees_equal(a, b):
    fa, fb = trees.flatten(a), trees.flatten(b)
    assert fa.keys() == fb.keys()
    for k in fa:
        np.testing.assert_array_equal(fa[k], fb[k], err_msg=k)


@pytest.mark.parametrize("donate", [False, True])
@pytest.mark.parametrize("n_rows", [None, 6])
def test_deferred_scatter_matches_serial_order(n_rows, donate):
    """Four of six clients a round (consecutive cohorts always share
    clients), with and without ghost rows, with the round step donating
    its inputs or not: after every round the settled store, the global
    and the losses are bit-identical to the serial order, and every
    result row is written once, by the next gather (the clients sampled
    again) or after the next dispatch (the rest)."""
    _check_serial_order(n_rows, donate)


def _check_serial_order(n_rows, donate, devices=1):
    N, K, R = 6, 4, 7
    calls = []
    runner, store, one_round = _toy_runner(N=N, K=K, rounds=R,
                                           n_rows=n_rows, donate=donate,
                                           record=calls, devices=devices)
    got = []
    for rnd in range(R):
        out = one_round(rnd)
        got.append((out["ids"], _settled_copy(store), runner.global_shared,
                    np.array(out["losses"]), out["plan"]))
        assert store._pending is not None          # nothing written yet
    runner.settle()
    ref = list(_serial_rounds(N, K, n_rows, R, calls,
                              [g[4] for g in got], donate, devices))
    for (ids, slots, glob, losses, _), (r_ids, r_slots, r_glob, r_losses) \
            in zip(got, ref):
        np.testing.assert_array_equal(ids, r_ids)
        _assert_trees_equal(slots, r_slots)
        _assert_trees_equal(glob, r_glob)
        np.testing.assert_array_equal(losses, r_losses)
    _assert_trees_equal(store.slots, ref[-1][1])
    inter = [len(np.intersect1d(a[0], b[0])) for a, b in zip(got, got[1:])]
    assert min(inter) > 0
    counts = runner.tracer.counts()
    assert counts["gather.settled_rows"] == sum(inter)
    # the last round's rows all came with settle()
    assert counts["scatter.deferred_rows"] == sum(K - i for i in inter) + K


@pytest.mark.parametrize("n_rows", [None, 6])
def test_deferred_scatter_resume_matches_uninterrupted(n_rows):
    """A runner checkpointed (``state_dict`` through JSON, as the sidecar
    holds it, and ``checkpoint_tree``) after round 3 and resumed into a
    fresh runner ends with a store and global bit-identical to an
    uninterrupted run's, whose rounds never settled in between."""
    _check_resume(n_rows)


def _check_resume(n_rows, devices=1):
    import json
    N, K, R, cut = 6, 4, 7, 3
    kw = dict(N=N, K=K, rounds=R, n_rows=n_rows, devices=devices)
    full, f_store, one_f = _toy_runner(**kw)
    for rnd in range(R):
        one_f(rnd)
    a, _, one_a = _toy_runner(**kw)
    for rnd in range(cut):
        one_a(rnd)
    state = json.loads(json.dumps(a.state_dict()))
    tree = jax.tree_util.tree_map(np.array, a.checkpoint_tree())
    b, b_store, one_b = _toy_runner(**kw)
    b.load_state_dict(state)
    b.load_checkpoint_tree(tree)
    b.burn_rounds(cut)
    for rnd in range(cut, R):
        one_b(rnd)
    _assert_trees_equal(b_store.slots, f_store.slots)
    _assert_trees_equal(b.global_shared, full.global_shared)


SHARDED_SUBPROC = """
import os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
os.environ["JAX_PLATFORMS"] = "cpu"
sys.path.insert(0, "tests")
import test_population as tp
for donate in (False, True):
    tp._check_serial_order(8, donate, devices=4)
tp._check_resume(8, devices=4)
print("SHARDED_OK")
"""


@pytest.mark.multidevice
def test_deferred_scatter_sharded_4dev_matches_serial_order():
    """The cohort sharded over four devices, four real rows and four
    ghost rows: the deferred scatter drops the ghost rows and is
    bit-identical to the serial order, and a resume to an uninterrupted
    run (subprocess, so the forced device count stays there)."""
    import os
    import subprocess
    import sys
    proc = subprocess.run([sys.executable, "-c", SHARDED_SUBPROC],
                          capture_output=True, text=True, timeout=900,
                          env={**os.environ, "PYTHONPATH": "src"})
    assert "SHARDED_OK" in proc.stdout, (proc.stdout, proc.stderr[-3000:])


def test_pending_scatter_holds_no_view_of_reused_buffers():
    """On the CPU backend ``np.asarray`` of a result is a view of its
    buffer.  The round step donates its inputs, not its outputs, and the
    staging buffers are refilled in place every round: the pending
    results share memory with no staging buffer and keep their values
    through the next round's refill and donation, which come before its
    deferred write."""
    runner, store, one_round = _toy_runner(N=6, K=4, donate=True)
    one_round(0)
    p = store._pending
    host = {slot: p.pulled(slot) for slot in p.results}
    before = jax.tree_util.tree_map(np.array, host)
    staging = jax.tree_util.tree_leaves(store._bufs)
    for leaf in jax.tree_util.tree_leaves(host):
        assert not any(np.shares_memory(leaf, buf) for buf in staging)
    one_round(1)
    _assert_trees_equal(host, before)


def _deferred_store(n=8):
    """A toy store with new values pending for rows 1, 3 and 5 (one ghost
    row after them)."""
    store, ref = _toy_store(n)
    store.tracer = SpanTracer()
    ids = np.asarray([1, 3, 5])
    r = np.random.RandomState(2)
    dev = jax.tree_util.tree_map(
        lambda l: jnp.asarray(r.randn(4, *l.shape[1:]).astype(l.dtype)), ref)
    store.defer_scatter(ids, {"trainable": dev})
    want = jax.tree_util.tree_map(np.array, ref)
    for k, leaf in trees.flatten(want).items():
        leaf[ids] = np.asarray(trees.flatten(dev)[k])[:3]
    return store, ref, want


def _load_other(store):
    """Load a checkpoint of other values; returns them."""
    other, _ = _toy_store(store.n_clients, seed=5)
    tree = jax.tree_util.tree_map(np.array, other.slots["trainable"])
    store.load_checkpoint_tree({"trainable": tree})
    return tree


SETTLING_CALLS = {
    "row": lambda s: s.row("trainable", 0),
    "slots": lambda s: s.slots,
    "checkpoint_tree": lambda s: s.checkpoint_tree(),
    "zero_rows": lambda s: s.zero_rows("trainable", [0]),
    "scatter": lambda s: s.scatter("trainable", np.asarray([0]),
                                   jax.tree_util.tree_map(
                                       lambda l: jnp.asarray(l[:1]),
                                       s._slots["trainable"])),
    "load_checkpoint_tree": _load_other,
    "settle": lambda s: s.settle(),
}


@pytest.mark.parametrize("call", sorted(SETTLING_CALLS))
def test_store_call_settles_pending_scatter_first(call):
    """Every read of the store and every other write finishes the pending
    scatter first: after the call no row is owed, and the store holds the
    pending rows (row 0, which each call may touch, aside), or all of a
    loaded checkpoint's, which no pending row overwrites later."""
    store, _, want = _deferred_store()
    loaded = SETTLING_CALLS[call](store)
    assert store._pending is None
    if call == "load_checkpoint_tree":
        want = loaded
    for k, leaf in trees.flatten(want).items():
        np.testing.assert_array_equal(
            trees.flatten(store._slots["trainable"])[k][1:], leaf[1:])
    assert store.tracer.counts()["scatter.deferred_rows"] == 3


def test_store_gather_settles_only_sampled_pending_rows():
    """A gather writes the pending rows of the clients it samples, and
    only those, before it reads; ``settle`` writes the rest."""
    store, ref, want = _deferred_store()
    g = store.gather("trainable", np.asarray([3, 4]))
    for k, leaf in trees.flatten(g).items():
        np.testing.assert_array_equal(leaf, trees.flatten(want)[k][[3, 4]])
    flat = trees.flatten(store._slots["trainable"])
    for k, leaf in trees.flatten(ref).items():
        np.testing.assert_array_equal(flat[k][[1, 5]], leaf[[1, 5]])
    assert store.tracer.counts()["gather.settled_rows"] == 1
    assert "scatter.deferred_rows" not in store.tracer.counts()
    store.gather("trainable", np.asarray([3, 4]))   # nothing left to settle
    assert store.tracer.counts()["gather.settled_rows"] == 1
    store.settle()
    _assert_trees_equal(store._slots["trainable"], want)
    assert store.tracer.counts()["scatter.deferred_rows"] == 2
    assert {"gather.settle", "scatter", "scatter.pull",
            "scatter.write"} <= set(store.tracer.totals())


def test_store_scatter_pull_then_write_matches_per_leaf_copy():
    """A store with a tracer pulls every leaf, then writes; the store ends
    bit-identical to a store without one and to a per-leaf
    ``dst[ids] = np.array(src)[:k]``, and holds no view of the device
    tree."""
    traced, ref = _toy_store(10)
    traced.tracer = SpanTracer()
    plain, _ = _toy_store(10)
    ids = np.asarray([8, 2, 5])
    r = np.random.RandomState(1)
    dev = jax.tree_util.tree_map(
        lambda l: jnp.asarray(r.randn(4, *l.shape[1:]).astype(l.dtype)),
        ref)                                     # one ghost row
    traced.scatter("trainable", ids, dev)
    plain.scatter("trainable", ids, dev)
    for k, leaf in trees.flatten(ref).items():
        want = leaf.copy()
        want[ids] = np.array(trees.flatten(dev)[k])[:3]
        np.testing.assert_array_equal(
            trees.flatten(traced.slots["trainable"])[k], want)
        np.testing.assert_array_equal(
            trees.flatten(plain.slots["trainable"])[k], want)
    for leaf, d in zip(jax.tree_util.tree_leaves(traced.slots["trainable"]),
                       jax.tree_util.tree_leaves(dev)):
        assert not np.shares_memory(leaf, np.asarray(d))   # rows copied in
    assert traced.tracer.counts() == {"scatter.bytes": sum(
        l.nbytes for l in jax.tree_util.tree_leaves(dev))}
    assert set(traced.tracer.totals()) == {"scatter.pull", "scatter.write"}


def test_store_without_tracer_times_nothing():
    store, _ = _toy_store(6)
    assert store.tracer is None
    g = store.gather("trainable", np.asarray([1, 2]), pad_to=3)
    store.scatter("trainable", np.asarray([1, 2]),
                  jax.tree_util.tree_map(jnp.asarray, g))
    tracer = SpanTracer()
    store.tracer = tracer
    store.gather("trainable", np.asarray([4]))
    assert set(tracer.totals()) == {"gather.take"}
    assert tracer.counts() == {"gather.bytes": store.nbytes() // 6}


# ---------------------------------------------------------------------------
# PopulationData: pure-function draws
# ---------------------------------------------------------------------------


def _toy_pool(n=64, n_classes=4, seed=0):
    r = np.random.RandomState(seed)
    return {"tokens": r.randint(0, 100, (n, 8)).astype(np.int32),
            "label": np.arange(n) % n_classes}


def test_population_data_draws_are_pure():
    probs = np.full((4, 4), 0.25)
    d1 = PopulationData(_toy_pool(), probs, seed=3)
    d2 = PopulationData(_toy_pool(), probs, seed=3)
    b1 = d1.round_batches(2, 7, local_steps=2, batch=4)
    # consumption order doesn't matter: draw other clients/rounds first
    d2.round_batches(0, 0, 2, 4)
    d2.test_set(2, 8)
    b2 = d2.round_batches(2, 7, local_steps=2, batch=4)
    for x, y in zip(b1, b2):
        np.testing.assert_array_equal(x["tokens"], y["tokens"])


def test_population_data_respects_class_probs():
    probs = np.zeros((2, 4))
    probs[0, 1] = 1.0            # client 0 only ever sees class 1
    probs[1] = 0.25
    d = PopulationData(_toy_pool(256), probs, seed=0)
    for b in d.round_batches(0, 0, local_steps=4, batch=16):
        assert np.all(b["label"] == 1)


def test_population_data_test_set_disjoint_stream():
    probs = np.full((1, 4), 0.25)
    d = PopulationData(_toy_pool(), probs, seed=0)
    te = d.test_set(0, 16)
    te2 = d.test_set(0, 16)
    np.testing.assert_array_equal(te["tokens"], te2["tokens"])


# ---------------------------------------------------------------------------
# Scenario traces
# ---------------------------------------------------------------------------


def test_scenario_inert_default():
    s = Scenario()
    assert s.is_inert()
    tr = s.realize(8, 5)
    np.testing.assert_array_equal(tr.avail, 1.0)
    np.testing.assert_array_equal(tr.gain_scale, 1.0)
    np.testing.assert_allclose(tr.class_probs, 0.25)


def test_scenario_dirichlet_noniid():
    tr = Scenario(alpha=0.1, seed=1).realize(100, 3)
    assert tr.class_probs.shape == (100, 4)
    np.testing.assert_allclose(tr.class_probs.sum(1), 1.0, atol=1e-9)
    # α=0.1 is strongly skewed: the dominant class carries far more mass
    # than the IID 0.25
    assert tr.class_probs.max(1).mean() > 0.6


def test_scenario_axes_are_independent_draw_blocks():
    """Enabling availability must not perturb the Dirichlet draw (fixed
    per-axis block order in realize)."""
    a = Scenario(alpha=0.1, seed=2).realize(32, 4)
    b = Scenario(alpha=0.1, avail="diurnal", seed=2).realize(32, 4)
    np.testing.assert_array_equal(a.class_probs, b.class_probs)


def test_scenario_horizon_prefix_stable():
    """Re-realizing with a longer horizon reproduces the shorter run's
    rows (kill/resume emulates the kill by running fewer rounds)."""
    s = Scenario(alpha=0.1, avail="diurnal", mobility="waypoint", seed=1)
    a, b = s.realize(16, 3), s.realize(16, 9)
    np.testing.assert_array_equal(a.class_probs, b.class_probs)
    np.testing.assert_array_equal(a.avail, b.avail[:3])
    np.testing.assert_array_equal(a.avail_p, b.avail_p[:3])
    np.testing.assert_array_equal(a.gain_scale, b.gain_scale[:3])


def test_scenario_diurnal_availability_bounds():
    s = Scenario(avail="diurnal", avail_period=8, avail_min=0.05, seed=0)
    tr = s.realize(16, 32)
    assert tr.avail_p.min() >= 0.05 - 1e-12
    assert tr.avail_p.max() <= 1.0 + 1e-12
    assert set(np.unique(tr.avail)) <= {0.0, 1.0}
    # a diurnal population is not always-on
    assert 0.0 < tr.avail.mean() < 1.0


def test_scenario_periodic_duty_cycle():
    s = Scenario(avail="periodic", avail_period=4, avail_duty=0.5, seed=0)
    tr = s.realize(64, 16)
    assert abs(tr.avail_p.mean() - 0.5) < 0.2


def test_scenario_waypoint_gains():
    s = Scenario(mobility="waypoint", seed=4)
    tr = s.realize(32, 10)
    assert tr.gain_scale.shape == (10, 32)
    assert tr.gain_scale.min() > 0.0
    assert tr.gain_scale.max() <= 1.0 + 1e-6       # unit gain inside ref_m
    # clients move: per-client gains change over rounds
    assert np.abs(np.diff(tr.gain_scale, axis=0)).max() > 0.0


def test_scenario_trace_clamps_past_horizon():
    tr = Scenario(avail="diurnal", mobility="waypoint", seed=0).realize(4, 3)
    np.testing.assert_array_equal(tr.avail_round(99), 1.0)
    np.testing.assert_array_equal(tr.gain_round(99), 1.0)
    np.testing.assert_array_equal(tr.avail_probs(99), 1.0)


def test_scenario_from_spec_roundtrip():
    s = Scenario.from_spec("alpha=0.1,avail=diurnal,avail_period=8,"
                           "mobility=waypoint,seed=3")
    assert s.alpha == 0.1 and s.avail == "diurnal" and s.seed == 3
    assert Scenario.from_dict(s.to_dict()) == s
    assert Scenario.from_spec(None) is None
    assert Scenario.from_spec("none") is None
    assert math.isinf(Scenario.from_spec("alpha=inf").alpha)


def test_scenario_from_spec_unknown_key_raises():
    with pytest.raises(ValueError):
        Scenario.from_spec("alpha=0.1,warp=9")
    with pytest.raises(ValueError):
        Scenario.from_dict({"alpha": 0.1, "warp": 9})
    with pytest.raises(ValueError):
        Scenario(avail="sometimes")


# ---------------------------------------------------------------------------
# end-to-end: population PFTT determinism + resume (the fused stack)
# ---------------------------------------------------------------------------

POP_KW = dict(rounds=3, local_steps=2, batch=4, pretrain_steps=10,
              samples_per_client=32, test_samples=8, d_model=32,
              lora_rank=2, adapter_dim=4, seed=0, verbose=False)


def _pop_cfg(tmp_path=None, resume=False, rounds=3, **kw):
    from repro.core.pftt import PFTTConfig
    pop = PopulationConfig(
        population=16, cohort_size=4, sampler="availability",
        scenario=Scenario(alpha=0.1, avail="diurnal", avail_period=6,
                          mobility="waypoint", seed=1))
    base = dict(POP_KW, rounds=rounds, **kw)
    return PFTTConfig(population=pop,
                      ckpt_dir=None if tmp_path is None else str(tmp_path),
                      resume=resume, **base)


@pytest.mark.slow
def test_population_pftt_deterministic():
    from repro.core.pftt import run_pftt
    a = run_pftt(_pop_cfg())
    b = run_pftt(_pop_cfg())
    np.testing.assert_array_equal(a["acc_per_round"], b["acc_per_round"])
    assert a["total_bytes"] == b["total_bytes"]
    assert 0.0 < a["participation_frac"] <= 1.0


@pytest.mark.slow
def test_population_pftt_kill_resume_exact(tmp_path):
    """A run killed after 2 of 4 rounds and resumed must reproduce the
    uninterrupted run exactly: store + global from the npz, sampler RNG /
    tracker / flags from the sidecar, channel draws burned."""
    from repro.core.pftt import run_pftt
    full = run_pftt(_pop_cfg(rounds=4))
    run_pftt(_pop_cfg(tmp_path, rounds=2))              # "killed" after 2
    res = run_pftt(_pop_cfg(tmp_path, resume=True, rounds=4))
    np.testing.assert_array_equal(full["acc_per_round"],
                                  res["acc_per_round"])
    assert full["total_bytes"] == res["total_bytes"]


def test_population_pfit_rejects_full_tree_methods():
    from repro.core.pfit import PFITConfig, run_pfit
    cfg = PFITConfig(rounds=1, population=PopulationConfig(
        population=8, cohort_size=2), method="pfit")
    with pytest.raises(ValueError):
        run_pfit(cfg)
