"""Mesh context + divisibility-aware sharding policy.

``MeshCtx`` carries the mesh and logical axis names through the model code
(the MoE layer runs a ``shard_map`` over it; the launcher builds param/batch
shardings from it).  The policy is rule-based: a tensor dim is sharded on an
axis only when divisible by the axis size, otherwise it is replicated — this
is what lets one config system drive 10 architectures × 4 shapes × 2 meshes
without per-case hand-tuning.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P


def auto_mesh(shape: Sequence[int], axes: Sequence[str],
              devices=None) -> Mesh:
    """The one mesh constructor: every axis ``Auto``, so sharding is left to
    the partitioner and plain jnp indexing (embedding gathers, cache
    writes, ``trees.unstack``) needs no per-op sharding annotations.
    ``devices`` defaults to the first ``prod(shape)`` of ``jax.devices()``."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


# ---------------------------------------------------------------------------
# Cohort (client-axis) sharding — the federated engine's device layout
# ---------------------------------------------------------------------------


def client_shard_axes(mesh: Mesh, client_axes=None) -> Tuple[str, ...]:
    """Mesh axes the stacked client dim shards over: explicit ``client_axes``
    if given, else every non-"model" axis (("pod","data") on the production
    mesh, ("data",) on a flat one) — tensor parallelism stays orthogonal."""
    if client_axes is not None:
        return tuple(client_axes)
    axes = tuple(a for a in mesh.axis_names if a != "model")
    return axes or tuple(mesh.axis_names)


@dataclasses.dataclass(frozen=True)
class CohortSharding:
    """Device layout for one stacked federated cohort.

    The leading client axis of every stacked leaf is sharded over
    ``axes``; cohorts whose size does not divide the shard count are
    padded with **ghost clients** — copies of client 0 that train
    normally but carry aggregation weight 0, so the weighted-mean /
    masked-mean math (and its all-outage gate) excludes them exactly
    (copies, not zeros: a ghost's forward must be as numerically
    well-behaved as a real client's, since NaN·0 = NaN would poison the
    psum).  Everything without a client axis (frozen base, global model)
    stays replicated."""

    mesh: Mesh
    axes: Tuple[str, ...]
    n_clients: int       # real cohort size
    total: int           # ghost-padded size (multiple of n_shards)

    @property
    def n_shards(self) -> int:
        return int(np.prod([self.mesh.shape[a] for a in self.axes]))

    @property
    def n_pad(self) -> int:
        return self.total - self.n_clients

    @property
    def named(self) -> NamedSharding:
        """Client-axis sharding (prefix spec: dim 0 over ``axes``)."""
        return NamedSharding(self.mesh, P(self.axes))

    @property
    def replicated(self) -> NamedSharding:
        return NamedSharding(self.mesh, P())

    def pad(self, per_client: Sequence) -> list:
        """[n_clients] list → [total] list, ghosts = copies of entry 0."""
        per_client = list(per_client)
        assert len(per_client) == self.n_clients, (len(per_client),
                                                   self.n_clients)
        return per_client + [per_client[0]] * self.n_pad

    def pad_vec(self, values, fill: float = 0.0) -> np.ndarray:
        """Append ``fill`` entries for every ghost client (fault masks pad
        with 1.0 so ghosts keep training/receiving like the sync engine)."""
        v = np.asarray(values, np.float32)
        return np.concatenate([v, np.full((self.n_pad,), fill, np.float32)])

    def pad_weights(self, weights) -> np.ndarray:
        """Append zero aggregation weight for every ghost client."""
        return self.pad_vec(weights, 0.0)


def cohort_sharding(mesh: Mesh, n_clients: int,
                    client_axes=None) -> CohortSharding:
    axes = client_shard_axes(mesh, client_axes)
    n_shards = int(np.prod([mesh.shape[a] for a in axes]))
    total = -(-n_clients // n_shards) * n_shards
    return CohortSharding(mesh=mesh, axes=axes, n_clients=n_clients,
                          total=total)


@dataclasses.dataclass(frozen=True)
class MeshCtx:
    mesh: Mesh
    batch_axes: Tuple[str, ...] = ("data",)   # ("pod","data") on multi-pod
    model_axis: str = "model"

    @property
    def data_size(self) -> int:
        return int(np.prod([self.mesh.shape[a] for a in self.batch_axes]))

    @property
    def model_size(self) -> int:
        return int(self.mesh.shape[self.model_axis])

    @property
    def all_axes(self) -> Tuple[str, ...]:
        return self.batch_axes + (self.model_axis,)

    def sharding(self, spec: P) -> NamedSharding:
        return NamedSharding(self.mesh, spec)

    @classmethod
    def single_device(cls) -> "MeshCtx":
        return cls(mesh=auto_mesh((1, 1), ("data", "model")))

    # -- divisibility-aware spec construction --------------------------------
    def dim_axis(self, size: int, axis) -> Optional[object]:
        """Return ``axis`` (a name or tuple of names) if ``size`` is divisible
        by its total extent, else None (replicate)."""
        if axis is None:
            return None
        names = axis if isinstance(axis, tuple) else (axis,)
        extent = int(np.prod([self.mesh.shape[a] for a in names]))
        if extent <= 1:
            return None
        return axis if size % extent == 0 else None

    def spec(self, shape: Sequence[int], axes: Sequence[object]) -> P:
        """Build a PartitionSpec, dropping any axis that doesn't divide."""
        assert len(shape) == len(axes), (shape, axes)
        return P(*[self.dim_axis(s, a) for s, a in zip(shape, axes)])


def local_batch(meshctx: MeshCtx, global_batch: int) -> int:
    d = meshctx.data_size
    return max(1, math.ceil(global_batch / d))


# ---------------------------------------------------------------------------
# Parameter / batch / cache sharding rules
# ---------------------------------------------------------------------------
#
# Rules are (path-suffix regex → per-dim logical axes); meshctx.spec() then
# drops any axis that does not divide the dim.  "model" below is the logical
# tensor-parallel axis; batch dims use meshctx.batch_axes (("pod","data") on
# the multi-pod mesh).  Unmatched leaves replicate.

import re as _re

_M = "model"
_F = "__fsdp__"   # sentinel → meshctx.batch_axes (ZeRO/FSDP-style sharding
                  # of weights + optimizer moments over the data axes)

# (regex, axes-per-dim counted from the LAST dim backwards).  Standard
# 2-D layout: contracting/row dim over FSDP, output/col dim over model
# (column-parallel) or vice versa (row-parallel).  The divisibility guard in
# meshctx.spec() silently drops axes that don't divide (e.g. whisper's odd
# 51865 vocab replicates over model but still FSDP-shards d_model).
_PARAM_RULES = [
    (r"embed$", (_M, _F)),
    (r"lm_head$", (_F, _M)),
    (r"pos_embed$", (_M, _F)),
    (r"enc_pos$", (None, None)),
    (r"projector$", (_F, _M)),
    (r"(mixer|cross)/w[qkv]$", (_F, _M)),
    (r"(mixer|cross)/wo$", (_M, _F)),
    (r"mixer/wq_a$", (_F, _M)),
    (r"mixer/wq_b$", (_F, _M)),
    (r"mixer/wkv_a$", (_F, _M)),
    (r"mixer/wkv_b$", (_F, _M)),
    (r"mixer/in_proj$", (_F, _M)),
    (r"mixer/out_proj$", (_M, _F)),
    (r"mixer/conv_w$", (None, _M)),
    (r"mixer/conv_b$", (_M,)),
    (r"mixer/gate_norm/scale$", (_M,)),
    (r"ff/wg$", (_F, _M)),
    (r"ff/wu$", (_F, _M)),
    (r"ff/wd$", (_M, _F)),
    (r"ff/shared/w[gu]$", (_F, _M)),
    (r"ff/shared/wd$", (_M, _F)),
    (r"ff/router$", (None, None)),
    (r"adapter/w[du]$", (None, None)),
]

# MoE expert slabs (…, E, d, f): experts over model, d over FSDP
_EXPERT_RULES = [
    (r"ff/wg$", (_M, _F, None)),
    (r"ff/wu$", (_M, _F, None)),
    (r"ff/wd$", (_M, None, _F)),
]


def param_specs(meshctx: MeshCtx, params_shapes, cfg=None,
                policy: str = "fsdp"):
    """Build a PartitionSpec tree for a params(-shaped) tree.

    ``cfg`` (ModelConfig) identifies which stage/pattern positions are MoE —
    their ff weights are expert slabs (E, d, f) sharded over experts; dense
    ff weights are sharded column/row-parallel instead.

    ``policy`` (§Perf sharding experiments):
      * ``fsdp``              — weights+moments sharded over (data × model)
                                (ZeRO-3-style; baseline)
      * ``fsdp_experts_only`` — FSDP only on expert slabs (the bulk of MoE
                                params); everything else pure TP — removes
                                the per-layer dense-weight all-gathers
      * ``tp``                — pure tensor parallelism (memory-permitting)
      * ``dp``                — pure data parallelism: weights replicated,
                                batch sharded over ALL axes — the right
                                layout for small models (whisper) that a
                                16-way model axis only slows down
    """
    from repro import trees as _trees

    moe_positions = set()
    if cfg is not None:
        for si, stage in enumerate(cfg.stages):
            for pi, kind in enumerate(stage.pattern):
                if kind.ff == "moe":
                    moe_positions.add(f"stages/{si}/layers/{pi}/ff/")

    def resolve(ax, is_expert=False):
        if policy == "dp":
            return None
        if ax == _F:
            if policy == "tp":
                return None
            if policy == "fsdp_experts_only" and not is_expert:
                return None
            return meshctx.batch_axes
        return ax

    def leaf_spec(path, leaf):
        shape = leaf.shape
        if len(shape) == 0:
            return P()
        is_moe = any(path.startswith(p) for p in moe_positions)
        if is_moe and not _re.search(r"/(router|shared/w[gud])$", path):
            for pat, axes in _EXPERT_RULES:
                if _re.search(pat, path):
                    # axes aligned to the LAST 3 dims: (R?, E, d, f)
                    full = (None,) * (len(shape) - 3) + tuple(
                        resolve(a, is_expert=True) for a in axes)
                    return meshctx.spec(shape, full)
        for pat, axes in _PARAM_RULES:
            if _re.search(pat, path):
                n = len(axes)
                if len(shape) < n:
                    return P(*([None] * len(shape)))
                full = (None,) * (len(shape) - n) + tuple(
                    resolve(a) for a in axes)
                return meshctx.spec(shape, full)
        return P(*([None] * len(shape)))

    return _trees.map_with_path(leaf_spec, params_shapes)


def batch_specs(meshctx: MeshCtx, batch_shapes):
    """Batch dims shard over the data axes; everything else replicated."""
    from repro import trees as _trees

    def leaf_spec(path, leaf):
        shape = leaf.shape
        if len(shape) == 0:
            return P()
        return meshctx.spec(shape, (meshctx.batch_axes,)
                            + (None,) * (len(shape) - 1))

    return _trees.map_with_path(leaf_spec, batch_shapes)


def cache_specs(meshctx: MeshCtx, cache_shapes, *, batch: int):
    """Decode-cache sharding: batch over data axes when divisible; the cache
    sequence dim over the model axis (flash-decode style partial softmax) —
    and over (data+model) when batch cannot shard (long_500k, B=1).
    Mamba states shard heads/feature dims over model."""
    from repro import trees as _trees

    batch_ok = batch % max(meshctx.data_size, 1) == 0 and meshctx.data_size > 1
    seq_axes = _M if batch_ok else tuple(meshctx.batch_axes) + (_M,)

    def leaf_spec(path, leaf):
        shape = leaf.shape
        if len(shape) == 0:
            return P()
        b_ax = meshctx.batch_axes if batch_ok else None
        if path.endswith(("/k", "/v", "/xk", "/xv", "/k_pers", "/v_pers")):
            # (R, B, S, K, hd)
            return meshctx.spec(shape, (None, b_ax, seq_axes, None, None))
        if path.endswith(("/k_ring", "/v_ring")):
            return meshctx.spec(shape, (None, b_ax, None, None, None))
        if path.endswith(("/ckv", "/kpe")):
            return meshctx.spec(shape, (None, b_ax, seq_axes, None))
        if path.endswith("/h"):       # (R, B, H, P, N)
            return meshctx.spec(shape, (None, b_ax, _M, None, None))
        if path.endswith("/conv"):    # (R, B, W-1, conv_dim)
            return meshctx.spec(shape, (None, b_ax, None, _M))
        return P(*([None] * len(shape)))

    return _trees.map_with_path(leaf_spec, cache_shapes)


def with_specs(shapes_tree, specs_tree, mesh):
    """Attach NamedShardings to a ShapeDtypeStruct tree (for .lower())."""
    import jax as _jax

    def attach(sds, spec):
        return _jax.ShapeDtypeStruct(sds.shape, sds.dtype,
                                     sharding=NamedSharding(mesh, spec))

    return _jax.tree_util.tree_map(attach, shapes_tree, specs_tree,
                                   is_leaf=lambda x: isinstance(
                                       x, _jax.ShapeDtypeStruct))
