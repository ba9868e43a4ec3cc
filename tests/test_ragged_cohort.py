"""Ragged cohorts compile to ONE fused dispatch — pad-and-mask machinery.

``HostBatchStacker`` pads unequal per-client batch shapes to the per-leaf
max and emits a ``"valid"`` sample mask; the losses weight by it, so padded
rows contribute exactly zero to loss, gradients, and aggregation.  The PFTT
engine therefore never falls back to the legacy per-client loop: parity
with that loop must hold to ≤1e-5 on ragged cohorts, the fused round must
be a single dispatch, and the sharded (ghost-padded, non-divisible) case
must agree across 8 devices."""
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.cohort import HostBatchStacker


# ---------------------------------------------------------------------------
# HostBatchStacker pad-and-mask unit behavior
# ---------------------------------------------------------------------------


def test_stacker_ragged_pads_and_masks():
    stacker = HostBatchStacker()
    batches = [
        [{"x": np.full((3, 2), 1.0, np.float32)}],
        [{"x": np.full((2, 2), 5.0, np.float32)}],
    ]
    out = stacker(batches)
    assert out["x"].shape == (2, 1, 3, 2)        # padded to max batch 3
    v = np.asarray(out["valid"])
    np.testing.assert_array_equal(v, [[[1, 1, 1]], [[1, 1, 0]]])
    x = np.asarray(out["x"])
    np.testing.assert_array_equal(x[1, 0, 2], np.zeros(2))   # pad row defined
    np.testing.assert_array_equal(x[1, 0, :2], np.full((2, 2), 5.0))


def test_stacker_ragged_buffer_reuse_no_stale_rows():
    """The reused buffer must not leak a previous round's rows into the pad
    region: the valid mask is rewritten fully each call and masked rows are
    exactly the non-filled ones."""
    stacker = HostBatchStacker()
    big = [[{"x": np.full((4, 2), 7.0, np.float32)}],
           [{"x": np.full((3, 2), 8.0, np.float32)}]]
    small = [[{"x": np.full((2, 2), 1.0, np.float32)}],
             [{"x": np.full((4, 2), 2.0, np.float32)}]]
    stacker(big)
    buf_id = id(stacker._bufs["x"])
    out = stacker(small)
    assert id(stacker._bufs["x"]) == buf_id      # no realloc
    v = np.asarray(out["valid"])
    np.testing.assert_array_equal(v, [[[1, 1, 0, 0]], [[1, 1, 1, 1]]])
    # stale 7.0 rows may remain in the pad region — the mask excludes them
    x = np.asarray(out["x"])
    np.testing.assert_array_equal(x[0, 0, :2], np.full((2, 2), 1.0))
    assert float((x[0, 0] * v[0, 0, :, None]).sum()) == 4 * 1.0


def test_stacker_uniform_to_ragged_reallocates():
    """A cohort whose shapes drift after the first allocation (uniform →
    ragged, or a new max batch) must pay a realloc, not crash."""
    stacker = HostBatchStacker()
    uni = [[{"x": np.full((4, 2), 7.0, np.float32)}],
           [{"x": np.full((4, 2), 8.0, np.float32)}]]
    out = stacker(uni)
    assert "valid" not in out
    rag = [[{"x": np.full((2, 2), 1.0, np.float32)}],
           [{"x": np.full((5, 2), 2.0, np.float32)}]]
    out = stacker(rag)
    assert out["x"].shape == (2, 1, 5, 2)
    np.testing.assert_array_equal(np.asarray(out["valid"]),
                                  [[[1, 1, 0, 0, 0]], [[1, 1, 1, 1, 1]]])


def test_stacker_uniform_cohort_unchanged():
    """Equal shapes: no "valid" leaf, no padding — bitwise the old layout."""
    stacker = HostBatchStacker()
    batches = [[{"x": np.full((2, 3), 1.0 + ci, np.float32)}
                for _ in range(2)] for ci in range(2)]
    out = stacker(batches)
    assert "valid" not in out
    assert out["x"].shape == (2, 2, 2, 3)


# ---------------------------------------------------------------------------
# PFTT: ragged cohorts run the engine and match the legacy loop
# ---------------------------------------------------------------------------


def _pftt_kw(**over):
    # samples_per_client chosen so the Dirichlet split leaves clients with
    # unequal train counts < batch → ragged per-client batch sizes
    kw = dict(n_clients=3, rounds=2, local_steps=2, pretrain_steps=10,
              samples_per_client=30, batch=16, d_model=32, seed=0)
    kw.update(over)
    return kw


def test_pftt_ragged_cohort_engine_matches_legacy_loop():
    from repro.core.pftt import PFTTConfig, run_pftt
    eng = run_pftt(PFTTConfig(engine=True, **_pftt_kw()))
    assert eng["ragged_cohort"], "workload no longer ragged — retune sizes"
    assert eng["fused_engine"]
    leg = run_pftt(PFTTConfig(engine=False, **_pftt_kw()))
    np.testing.assert_allclose(eng["acc_per_round"], leg["acc_per_round"],
                               atol=1e-5)
    assert eng["mean_round_bytes"] == leg["mean_round_bytes"]
    # eval side: whole ragged cohort scored in one fused dispatch per round
    assert eng["eval_dispatches_per_round"] == 1


def test_arch_round_ragged_single_dispatch():
    """The generic arch round (ragged by construction) is one dispatch per
    round with exact oracle parity — raggedness never re-triggers the
    legacy loop."""
    from repro.core.arch_round import ArchRoundConfig, run_arch_round
    res = run_arch_round(ArchRoundConfig(
        arch="gpt2-small", n_clients=3, rounds=2, local_steps=1, batch=3,
        seq_len=12, d_model=32, oracle=True))
    assert res["ragged"]
    assert res["dispatches_per_round"] == 1.0
    assert res["dense_merges_in_engine"] == 0
    assert res["oracle_loss_max_err"] <= 1e-5


# ---------------------------------------------------------------------------
# sharded ragged cohort: ghost-padded non-divisible case over 8 devices
# ---------------------------------------------------------------------------

RAGGED_SHARD_SUBPROC = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax
    import numpy as np
    assert len(jax.devices()) == 8
    from repro.sharding import auto_mesh
    mesh = auto_mesh((8,), ("data",))
    from repro.core.arch_round import ArchRoundConfig, run_arch_round
    # 3 ragged clients over 8 shards → 5 zero-weight ghosts
    cfg = ArchRoundConfig(arch="gpt2-small", n_clients=3, rounds=2,
                          local_steps=1, batch=3, seq_len=12, d_model=32,
                          oracle=True)
    shard = run_arch_round(cfg, mesh=mesh, client_axes=("data",))
    assert shard["n_ghosts"] == 5, shard["n_ghosts"]
    assert shard["ragged"]
    assert shard["dispatches_per_round"] == 1.0
    assert shard["dense_merges_in_engine"] == 0
    assert shard["oracle_loss_max_err"] <= 1e-5, shard["oracle_loss_max_err"]
    base = run_arch_round(cfg)
    np.testing.assert_allclose(shard["loss_per_round"],
                               base["loss_per_round"], atol=1e-5)
    print("RAGGED_SHARD_OK", shard["loss_per_round"])
""")


@pytest.mark.multidevice
@pytest.mark.slow
def test_ragged_cohort_ghost_padded_8dev():
    import os
    proc = subprocess.run([sys.executable, "-c", RAGGED_SHARD_SUBPROC],
                          capture_output=True, text=True, timeout=1800,
                          env={**os.environ, "PYTHONPATH": "src"})
    assert "RAGGED_SHARD_OK" in proc.stdout, (proc.stdout,
                                              proc.stderr[-3000:])
