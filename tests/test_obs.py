"""Run telemetry (repro.obs): JSONL schema, span tracer, on-device
health parity, and the kill/resume event-stream contract.

The contract under test:

* ``RunTelemetry`` writes one JSON object per line; a written stream
  reads back equal (NaN sanitized to null), validates clean, and the
  validator catches out-of-order / duplicate / schema-less streams.
* ``SpanTracer`` accumulates per-phase seconds whether or not Chrome
  recording is on; recorded "X" events nest by time containment (a
  child's [ts, ts+dur] interval lies inside its parent's).  Its
  counters accumulate apart from the span seconds, and every span is
  also a host event of the same name in a running ``jax.profiler``
  trace.
* The health scalars computed INSIDE the fused round body match a
  float64 host recomputation from the same inputs to ≤1e-6 — and
  enabling them does not perturb the round's state outputs.
* A population run killed after 2 of 4 rounds and resumed reproduces
  the uninterrupted run's canonical event stream byte-for-byte
  (round events, ``wall`` stripped).
"""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import trees
from repro.obs import (HEALTH_KEYS, RunTelemetry, SpanTracer,
                       canonical_stream, cohort_health, host_health,
                       read_events, validate_events)

# ---------------------------------------------------------------------------
# JSONL schema round-trip + validation
# ---------------------------------------------------------------------------


def _write_stream(tmp_path, rounds=3):
    tele = RunTelemetry(str(tmp_path))
    tele.start({"mode": "test", "rounds": rounds})
    for r in range(rounds):
        tele.round_event(r, {
            "acc": 0.5 + 0.1 * r,
            "cohort": [r, r + 1],
            "comm": {"record_id": r, "round": r, "bytes": 1000 * (r + 1),
                     "delay_s": float("nan") if r == 1 else 0.25,
                     "outages": 0},
            "staleness": {"pending": 0, "abandoned": 0,
                          "retransmissions": 0, "quorum_noops": 0},
            "health": {k: 0.1 for k in HEALTH_KEYS},
        }, wall={"phases": {"device-step": 0.01 * (r + 1)}})
        tele.checkpoint(r)
    return tele


def test_jsonl_round_trip_and_validate(tmp_path):
    tele = _write_stream(tmp_path)
    events = read_events(tele.path)
    assert validate_events(events) == []
    assert [e["event"] for e in events] == \
        ["run", "round", "checkpoint", "round", "checkpoint",
         "round", "checkpoint"]
    rounds = [e for e in events if e["event"] == "round"]
    # NaN is not JSON: the all-outage round's delay must read back None
    assert rounds[1]["comm"]["delay_s"] is None
    assert rounds[0]["comm"]["delay_s"] == 0.25
    assert rounds[2]["health"]["update_norm"] == pytest.approx(0.1)
    # canonical stream is deterministic and wall-free
    canon = canonical_stream(events)
    assert len(canon) == 3
    assert all("wall" not in json.loads(c) for c in canon)
    assert canon == canonical_stream(read_events(tele.path))


def test_validator_catches_bad_streams(tmp_path):
    assert validate_events([]) == ["empty event stream"]
    # missing run header
    assert any("expected 'run'" in e for e in validate_events(
        [{"event": "round", "round": 0, "comm": {}, "wall": {}}]))
    # wrong schema version
    assert any("schema version" in e for e in validate_events(
        [{"event": "run", "schema": 999, "meta": {}}]))
    ok = [{"event": "run", "schema": 1, "meta": {}},
          {"event": "round", "round": 1, "comm": {}, "wall": {}},
          {"event": "round", "round": 0, "comm": {}, "wall": {}}]
    assert any("out of order" in e for e in validate_events(ok))
    dup = [{"event": "run", "schema": 1, "meta": {}},
           {"event": "round", "round": 0, "comm": {}, "wall": {}},
           {"event": "round", "round": 0, "comm": {}, "wall": {}}]
    assert any("duplicate round 0" in e for e in validate_events(dup))
    missing = [{"event": "run", "schema": 1, "meta": {}},
               {"event": "round", "round": 0, "wall": {}}]
    assert any("missing 'comm'" in e for e in validate_events(missing))
    assert any("unknown type" in e for e in validate_events(
        [{"event": "run", "schema": 1, "meta": {}}, {"event": "warp"}]))


def test_disabled_telemetry_is_a_noop(tmp_path):
    tele = RunTelemetry(None)
    assert not tele.enabled
    tele.start({})
    tele.round_event(0, {"comm": {}})
    tele.checkpoint(0)
    tele.close()   # nothing written anywhere


# ---------------------------------------------------------------------------
# span tracer: accumulation, nesting, Chrome trace shape
# ---------------------------------------------------------------------------


def test_tracer_accumulates_even_when_disabled():
    tr = SpanTracer(enabled=False)
    with tr.span("round") as sp:
        with tr.span("gather"):
            pass
    assert sp.dur >= 0.0
    phases = tr.pop_round()
    assert set(phases) == {"round", "gather"}
    assert phases["round"] >= phases["gather"] >= 0.0
    assert tr.pop_round() == {}                    # reset on pop
    assert set(tr.totals()) == {"round", "gather"}  # totals never reset
    assert tr.chrome_trace()["traceEvents"] == []   # nothing recorded


def test_tracer_chrome_events_nest_and_order(tmp_path):
    tr = SpanTracer(enabled=True)
    with tr.span("round"):
        with tr.span("gather"):
            pass
        with tr.span("device-step", rnd=3):
            pass
    with tr.span("eval"):
        pass
    ev = tr.chrome_trace()["traceEvents"]
    assert [e["name"] for e in ev] == \
        ["gather", "device-step", "round", "eval"]   # closed-order append
    by = {e["name"]: e for e in ev}
    # children lie inside the parent interval (Perfetto nesting rule)
    rnd = by["round"]
    for child in ("gather", "device-step"):
        c = by[child]
        assert c["ts"] >= rnd["ts"]
        assert c["ts"] + c["dur"] <= rnd["ts"] + rnd["dur"] + 1e-3
    assert by["eval"]["ts"] >= rnd["ts"] + rnd["dur"] - 1e-3
    assert by["device-step"]["args"] == {"rnd": 3}
    assert all(e["ph"] == "X" and e["tid"] == 1 for e in ev)
    # write() produces a loadable JSON object file
    p = tmp_path / "trace.json"
    tr.write(str(p))
    with open(p) as f:
        assert json.load(f)["traceEvents"] == ev


def test_tracer_counts_accumulate_while_disabled():
    tr = SpanTracer(enabled=False)
    with tr.span("gather"):
        tr.count("gather.bytes", 96)
    tr.count("gather.bytes", 32)
    tr.count("scatter.bytes", 8)
    assert tr.counts() == {"gather.bytes": 128, "scatter.bytes": 8}
    assert tr.chrome_trace()["traceEvents"] == []   # nothing recorded
    # counters never mix into the span seconds
    assert set(tr.totals()) == {"gather"}
    assert set(tr.pop_round()) == {"gather"}
    tr.count("gather.bytes", 1)
    assert tr.pop_round() == {}
    assert tr.counts()["gather.bytes"] == 129       # never reset


def test_tracer_counter_events_only_when_enabled(tmp_path):
    tr = SpanTracer(enabled=True)
    with tr.span("scatter"):
        tr.count("scatter.bytes", 10)
        tr.count("scatter.bytes", 5)
    ev = tr.chrome_trace()["traceEvents"]
    counters = [e for e in ev if e["ph"] == "C"]
    assert [e["name"] for e in counters] == ["scatter.bytes"] * 2
    assert [e["args"] for e in counters] == [{"value": 10}, {"value": 15}]
    span = next(e for e in ev if e["ph"] == "X")
    for c in counters:                  # stamped on the spans' clock
        assert span["ts"] <= c["ts"] <= span["ts"] + span["dur"] + 1e-3
    p = tmp_path / "trace.json"
    tr.write(str(p))
    with open(p) as f:
        assert json.load(f)["traceEvents"] == ev
    assert tr.counts() == {"scatter.bytes": 15}
    assert set(tr.totals()) == {"scatter"}


@pytest.mark.parametrize("enabled", [False, True])
def test_tracer_spans_land_in_profiler_trace(tmp_path, enabled):
    """Every span is a host event of exactly its name in the .xplane.pb a
    running jax.profiler session writes (args stay out of the name)."""
    import glob
    import os

    from jax.profiler import ProfileData

    tr = SpanTracer(enabled=enabled)
    with jax.profiler.trace(str(tmp_path)):
        with tr.span("gather"):
            with tr.span("gather.take", slot="opt"):
                jnp.ones(4).block_until_ready()
        with tr.span("device-step.wait"):
            pass
    (path,) = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                        recursive=True)
    host = {ev.name
            for plane in ProfileData.from_file(path).planes
            if plane.name.startswith("/host:")
            for line in plane.lines for ev in line.events}
    assert {"gather", "gather.take", "device-step.wait"} <= host
    assert not any("slot" in n for n in host if n.startswith("gather"))


# ---------------------------------------------------------------------------
# health scalars: engine output vs float64 host oracle
# ---------------------------------------------------------------------------


def _toy_round(health, seed=0):
    """The population bench's toy workload through the robust fused round."""
    from repro.core.cohort import build_supervised_round
    from repro.optim import sgd

    C = 4
    opt = sgd(1e-2)

    def loss_fn(tr, batch):
        return jnp.mean((tr["shared"]["w"].sum() + tr["local"]["v"].sum()
                         - batch["tgt"]) ** 2)

    def local_step(tr, op, batch):
        loss, grads = jax.value_and_grad(loss_fn)(tr, batch)
        upd, op = opt.update(grads, op, tr)
        return jax.tree_util.tree_map(lambda p, u: p + u, tr, upd), op, loss

    rng = np.random.RandomState(seed)
    stacked = trees.stack(
        [{"shared": {"w": rng.randn(3).astype(np.float32)},
          "local": {"v": rng.randn(2).astype(np.float32)}}
         for _ in range(C)])
    opt0 = opt.init(jax.tree_util.tree_map(jnp.zeros_like,
                                           trees.unstack(stacked, C)[0]))
    st_op = jax.tree_util.tree_map(
        lambda l: np.broadcast_to(np.asarray(l), (C,) + np.shape(l)).copy(),
        opt0)
    pend = jax.tree_util.tree_map(
        np.zeros_like, trees.select(stacked,
                                    lambda p: p.startswith("shared")))
    step = build_supervised_round(local_step,
                                  lambda p: p.startswith("shared"),
                                  donate=False, robust=True, health=health)
    batches = {"tgt": jnp.asarray(rng.randn(C, 2, 1), np.float32)}
    ones, zeros = jnp.ones(C), jnp.zeros(C)
    w = jnp.asarray([1.0, 0.5, 0.25, 0.0])
    # (train_m, agg_w, recv_m, rejoin_m, ontime_m)
    margs = (ones, w, ones, zeros, ones)
    outs = step(jax.tree_util.tree_map(jnp.asarray, stacked),
                jax.tree_util.tree_map(jnp.asarray, st_op),
                jax.tree_util.tree_map(jnp.asarray, pend), batches, *margs)
    return stacked, w, outs


def test_health_parity_vs_host_oracle():
    stacked, w, outs = _toy_round(health=True)
    st_tr, _, send, losses, hstats = outs
    assert set(hstats) == set(HEALTH_KEYS)
    ref = trees.select(stacked, lambda p: p.startswith("shared"))
    oracle = host_health(send, ref, losses, w, 1.0)
    for k in HEALTH_KEYS:
        assert float(hstats[k]) == pytest.approx(oracle[k], abs=1e-6), k
    # sanity on magnitudes: 3 of 4 clients delivered, every row trained
    assert float(hstats["delivered"]) == 3.0
    assert float(hstats["agg_weight_sum"]) == pytest.approx(1.75)
    assert float(hstats["update_norm"]) > 0.0
    assert float(hstats["codec_err"]) == 0.0        # no codec in this round


def test_health_output_does_not_perturb_the_round():
    _, _, base = _toy_round(health=False)
    _, _, with_h = _toy_round(health=True)
    assert len(with_h) == len(base) + 1
    for a, b in zip(jax.tree_util.tree_leaves(base[:4]),
                    jax.tree_util.tree_leaves(with_h[:4])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_cohort_health_off_mesh_matches_oracle_with_codec():
    rng = np.random.RandomState(3)
    send = {"w": jnp.asarray(rng.randn(4, 3), np.float32)}
    ref = {"w": jnp.asarray(rng.randn(4, 3), np.float32)}
    raw = {"w": jnp.asarray(rng.randn(4, 3), np.float32)}
    dec = jax.tree_util.tree_map(
        lambda x: x + 0.01 * jnp.asarray(rng.randn(4, 3), np.float32), raw)
    losses = jnp.asarray(rng.rand(4, 2), np.float32)
    w = jnp.asarray([1.0, 1.0, 0.0, 0.5])
    out = cohort_health(send, ref, losses, w, jnp.float32(1.0),
                        raw=raw, decoded=dec)
    oracle = host_health(send, ref, losses, w, 1.0, raw=raw, decoded=dec)
    for k in HEALTH_KEYS:
        assert float(out[k]) == pytest.approx(oracle[k], abs=1e-6), k
    assert float(out["codec_err"]) > 0.0


# ---------------------------------------------------------------------------
# kill/resume: canonical event stream byte-identity (population PFTT)
# ---------------------------------------------------------------------------

POP_KW = dict(local_steps=2, batch=4, pretrain_steps=10,
              samples_per_client=32, test_samples=8, d_model=32,
              lora_rank=2, adapter_dim=4, seed=0, verbose=False)


def _pop_cfg(tele_dir, ckpt_dir=None, resume=False, rounds=4):
    from repro.core.pftt import PFTTConfig
    from repro.fl.population import PopulationConfig
    from repro.obs import TelemetryConfig
    from repro.wireless.scenarios import Scenario
    pop = PopulationConfig(
        population=16, cohort_size=4, sampler="availability",
        scenario=Scenario(alpha=0.1, avail="diurnal", avail_period=6,
                          mobility="waypoint", seed=1))
    return PFTTConfig(population=pop, rounds=rounds,
                      ckpt_dir=None if ckpt_dir is None else str(ckpt_dir),
                      resume=resume,
                      telemetry=TelemetryConfig(out_dir=str(tele_dir)),
                      **POP_KW)


@pytest.mark.slow
def test_population_kill_resume_event_stream_exact(tmp_path):
    """Killed after 2 of 4 rounds + resumed → the canonical stream
    (round events, wall stripped) is byte-identical to the uninterrupted
    run's, and both validate clean."""
    from repro.core.pftt import run_pftt

    run_pftt(_pop_cfg(tmp_path / "full", rounds=4))
    full = read_events(tmp_path / "full" / "events.jsonl")

    kdir = tmp_path / "killed"
    run_pftt(_pop_cfg(kdir, ckpt_dir=tmp_path / "ck", rounds=2))
    run_pftt(_pop_cfg(kdir, ckpt_dir=tmp_path / "ck", resume=True,
                      rounds=4))
    resumed = read_events(kdir / "events.jsonl")

    assert validate_events(full) == []
    assert validate_events(resumed) == []
    assert sum(1 for e in resumed if e["event"] == "resume") == 1
    cf, cr = canonical_stream(full), canonical_stream(resumed)
    assert len(cf) == 4
    assert cf == cr
