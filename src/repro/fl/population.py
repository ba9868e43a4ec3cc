"""Host-resident client population for sampled-cohort federated training.

Production FL draws a small cohort per round from a huge population; until
now the repo's ``n_clients`` WAS the cohort.  The factored LoRA path makes
each client's trainable state a few-KB rank-r tree, so a 10k+ client
population fits comfortably in host RAM — this module keeps it there:

* ``PopulationStore`` — named slots ("trainable", "opt", "pending"), each a
  stacked numpy tree with a leading (n_clients,) axis.  ``gather`` copies
  the sampled rows into a preallocated staging buffer (the
  ``HostBatchStacker`` discipline: allocate once, refill in place, one
  ``jax.device_put`` per round — steady-state rounds do ZERO reallocation)
  and ``scatter`` writes the round's device results back.  The fused
  compiled round body never sees more than the cohort.
* ``ClientSampler`` — seeded per-round cohort selection: ``uniform``
  (without replacement) or ``availability`` (probability ∝ the scenario's
  per-round availability — clients that are reachable get sampled, the
  regime the Federated Fine-Tuning surveys evaluate).  The RNG is stateful
  so the sequence of cohorts is one stream; ``state_dict`` serializes the
  generator for checkpoint resume (mid-stream resume reproduces the
  uninterrupted sampling stream exactly).
* ``PopulationData`` — lazy non-IID client data: each client owns a
  Dirichlet label distribution (``ScenarioTrace.class_probs``) over a
  shared class-bucketed sample pool; batches are drawn by a PURE function
  of (seed, client id, round), so no per-client iterator state exists to
  replay on resume and 10k clients cost O(n_clients × n_classes) memory,
  not 10k materialized datasets.

``PopulationConfig`` is the knob bundle ``run_pftt``/``run_pfit`` accept
(``PFTTConfig(population=...)``); the round loops own the orchestration
(sample → gather → fused round → scatter) and the ``StalenessTracker``
runs population-wide — pending payloads are keyed by population client id,
so a straggler's payload survives rounds it is not sampled in.
"""
from __future__ import annotations

import contextlib
import dataclasses
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro import trees
from repro.obs.trace import SpanTracer
from repro.wireless.scenarios import Scenario

SAMPLER_KINDS = ("uniform", "availability")


def _writable(leaf) -> np.ndarray:
    """Host numpy array the store may mutate and gather rows from.

    It must be writable (``np.asarray`` of a jax array is a READ-ONLY view
    — scatter would fail on it) and C-contiguous: an array pulled from a
    TPU keeps the device's layout, and ``np.take(..., out=)`` copies a
    non-C-contiguous source whole before it reads the rows it needs.  Any
    other input is copied once, in C order."""
    a = np.asarray(leaf)
    if a.flags.writeable and a.flags.c_contiguous:
        return a
    return np.array(a, order="C")


@dataclasses.dataclass
class _PendingScatter:
    """One round's results between ``PopulationStore.defer_scatter`` and
    the write of their last row: each slot's result tree on the device
    (``results``) and, once pulled, on the host (``host``); ``done`` marks
    the cohort rows a gather has already written."""
    ids: np.ndarray
    results: Dict[str, object]
    done: np.ndarray
    host: Dict[str, object] = dataclasses.field(default_factory=dict)

    def pulled(self, slot: str):
        """The slot's results on the host.  ``np.asarray`` waits for the
        copy ``defer_scatter`` started and makes no second host copy (on
        the CPU backend it is a view of the result buffer, which nothing
        donates)."""
        if slot not in self.host:
            self.host[slot] = jax.tree_util.tree_map(np.asarray,
                                                     self.results[slot])
        return self.host[slot]


@dataclasses.dataclass(frozen=True)
class PopulationConfig:
    """Population-mode knobs for ``run_pftt``/``run_pfit``.

    ``population`` clients live in the host store; every round samples a
    ``cohort_size`` cohort (the compiled round body's client axis — the
    body itself is the same program a ``n_clients=cohort_size`` run
    compiles).  ``scenario`` shapes the population (non-IID partitions,
    availability, mobility — ``wireless/scenarios.py``); ``sampler`` picks
    who participates."""
    population: int
    cohort_size: int
    sampler: str = "uniform"          # uniform | availability
    scenario: Optional[Scenario] = None
    seed: int = 0

    def __post_init__(self):
        if self.sampler not in SAMPLER_KINDS:
            raise ValueError(f"sampler must be one of {SAMPLER_KINDS}, "
                             f"got {self.sampler!r}")
        if not (0 < self.cohort_size <= self.population):
            raise ValueError(
                f"need 0 < cohort_size ({self.cohort_size}) <= "
                f"population ({self.population})")
        if (self.sampler == "availability"
                and not (self.scenario is not None
                         and self.scenario.has_availability())):
            raise ValueError("availability sampler needs a scenario with "
                             "avail != 'none'")


class PopulationStore:
    """Stacked host-numpy client state with buffered gather/scatter.

    Each slot is a pytree whose leaves carry a leading (n_clients,) axis.
    ``gather(slot, ids, pad_to=)`` refills the slot's preallocated staging
    buffer (rows beyond ``len(ids)`` repeat row ``ids[0]`` — the ghost-pad
    convention of ``repro.sharding.CohortSharding``) and returns it;
    callers ``jax.device_put`` the result themselves so sharded and
    single-device paths place it once.  ``scatter(slot, ids, tree)`` pulls
    the device tree to host and writes the first ``len(ids)`` rows back.

    ``defer_scatter(ids, results)`` is ``PopulationRunner``'s scatter: it only
    starts the device→host copies of a round's result trees and keeps them
    as the *pending* scatter.  A later ``gather`` first writes the pending
    rows of the clients it samples (so a client sampled twice in a row
    starts from its last results); ``settle()`` writes every pending row.
    Each read of the store (``row``, ``slots``, ``checkpoint_tree``) and
    each other write (``scatter``, ``zero_rows``,
    ``load_checkpoint_tree``) settles first, so none sees a row the
    pending scatter still owes.

    With a ``tracer`` (``PopulationRunner`` hands the store its own), each
    gather times its ``np.take`` as a ``gather.take`` span and counts the
    staging bytes it filled (``gather.bytes``); the pending rows a gather
    writes fall in a ``gather.settle`` span (counter
    ``gather.settled_rows``).  A scatter counts the result bytes
    (``scatter.bytes``) and times the device→host pull (``scatter.pull``)
    and the row writes (``scatter.write``) of each slot; ``settle()``
    puts those two inside a ``scatter`` span of its own and counts the
    rows no gather had written (``scatter.deferred_rows``).  Without a
    tracer the store times and counts nothing.

    Every leaf the store holds is host-owned, writable and C-contiguous
    (``_writable``).  ``relaid_bytes`` sums the bytes of the inputs that
    were not C-contiguous and so were copied into C order, over
    ``__init__`` and every ``load_checkpoint_tree``; the runner counts it
    as ``store.relaid_bytes``."""

    def __init__(self, slots: Dict[str, object],
                 tracer: Optional[SpanTracer] = None):
        self.tracer = tracer
        self.relaid_bytes = 0
        self._slots = {}
        self._bufs: Dict[str, object] = {}
        self._pending: Optional[_PendingScatter] = None
        n = None
        for name, tree in slots.items():
            tree = self._own(tree)
            for leaf in jax.tree_util.tree_leaves(tree):
                n = leaf.shape[0] if n is None else n
                assert leaf.shape[0] == n, \
                    f"slot {name!r} leading axis {leaf.shape[0]} != {n}"
            self._slots[name] = tree
        assert n is not None, "empty store"
        self._n = int(n)

    @property
    def n_clients(self) -> int:
        return self._n

    @property
    def slots(self) -> Dict[str, object]:
        self.settle()
        return self._slots

    def _own(self, tree):
        """``tree`` with every leaf ``_writable``; the non-C-contiguous
        leaves' bytes are added to ``relaid_bytes``."""
        def own(leaf):
            a = np.asarray(leaf)
            if not a.flags.c_contiguous:
                self.relaid_bytes += a.nbytes
            return _writable(a)
        return jax.tree_util.tree_map(own, tree)

    def _span(self, name: str, **args):
        if self.tracer is None:
            return contextlib.nullcontext()
        return self.tracer.span(name, **args)

    def _count(self, name: str, tree) -> None:
        if self.tracer is not None:
            self.tracer.count(name, sum(
                leaf.nbytes for leaf in jax.tree_util.tree_leaves(tree)))

    def _count_rows(self, name: str, n: int) -> None:
        if self.tracer is not None and n:
            self.tracer.count(name, n)

    def nbytes(self) -> int:
        return sum(leaf.nbytes
                   for tree in self._slots.values()
                   for leaf in jax.tree_util.tree_leaves(tree))

    def gather(self, slot: str, ids: np.ndarray, pad_to: int = 0):
        """Rows ``ids`` of ``slot`` → the slot's reused staging buffer
        (allocated on first use, refilled in place afterwards)."""
        ids = np.asarray(ids, np.int64)
        self._settle_rows(ids)
        k = len(ids)
        rows = max(pad_to, k)
        tree = self._slots[slot]
        buf = self._bufs.get(slot)
        if buf is None or jax.tree_util.tree_leaves(buf)[0].shape[0] != rows:
            buf = jax.tree_util.tree_map(
                lambda l: np.empty((rows,) + l.shape[1:], l.dtype), tree)
            self._bufs[slot] = buf
        # ghost rows repeat the first sampled client (copies, not zeros:
        # they must be numerically well-behaved under the psum)
        full = np.concatenate([ids, np.full(rows - k, ids[0], np.int64)])

        def fill(src, dst):
            np.take(src, full, axis=0, out=dst)
            return dst

        with self._span("gather.take", slot=slot):
            out = jax.tree_util.tree_map(fill, tree, buf)
        self._count("gather.bytes", out)
        return out

    def scatter(self, slot: str, ids: np.ndarray, device_tree) -> None:
        """Write the first ``len(ids)`` rows of ``device_tree`` back into
        ``slot`` (ghost-padded rows are dropped)."""
        self.settle()
        ids = np.asarray(ids, np.int64)
        with self._span("scatter.pull", slot=slot):
            # np.asarray, no second host copy: the rows are copied into the
            # store below, so no view of a (later donated) jax buffer
            # outlives this call
            host = jax.tree_util.tree_map(np.asarray, device_tree)
        self._count("scatter.bytes", host)
        with self._span("scatter.write", slot=slot):
            self._write(slot, ids, host, slice(None, len(ids)))

    def _write(self, slot: str, dst_rows, host, src_rows) -> None:
        def put(dst, src):
            dst[dst_rows] = src[src_rows]

        jax.tree_util.tree_map(put, self._slots[slot], host)

    # ---- the deferred scatter ----------------------------------------------

    def defer_scatter(self, ids: np.ndarray, results: Dict[str, object]):
        """Start the device→host copies of one round's results (slot →
        result tree, rows beyond ``len(ids)`` ghost rows) and keep them as
        the pending scatter; nothing is written yet.  The result trees must
        not be donated to a later computation: their host copies are read
        until the pending scatter settles."""
        self.settle()
        for tree in results.values():
            for leaf in jax.tree_util.tree_leaves(tree):
                leaf.copy_to_host_async()
            self._count("scatter.bytes", tree)
        ids = np.asarray(ids, np.int64)
        self._pending = _PendingScatter(ids, dict(results),
                                        np.zeros(len(ids), bool))

    def _settle_rows(self, ids: np.ndarray) -> None:
        """Write the pending rows of the clients in ``ids`` (all slots)."""
        p = self._pending
        if p is None:
            return
        rows = np.where(np.isin(p.ids, ids) & ~p.done)[0]
        if len(rows) == 0:
            return
        with self._span("gather.settle"):
            for slot in p.results:
                self._write(slot, p.ids[rows], p.pulled(slot), rows)
        p.done[rows] = True
        self._count_rows("gather.settled_rows", len(rows))

    def settle(self) -> float:
        """Write every row the pending scatter still owes; returns the
        seconds its ``scatter`` span took (0.0 with nothing pending or no
        tracer).  The write covers the whole cohort: a row a gather already
        wrote gets the same values again, which spares an index copy of the
        others."""
        p = self._pending
        if p is None:
            return 0.0
        self._pending = None
        with self._span("scatter") as sp:
            host = {}
            for slot in p.results:
                with self._span("scatter.pull", slot=slot):
                    host[slot] = p.pulled(slot)
            for slot in p.results:
                with self._span("scatter.write", slot=slot):
                    self._write(slot, p.ids, host[slot],
                                slice(None, len(p.ids)))
        self._count_rows("scatter.deferred_rows", int((~p.done).sum()))
        return sp.dur if sp is not None else 0.0

    def zero_rows(self, slot: str, ids: Sequence[int]) -> None:
        """Zero the given rows (deferred crash-rejoin optimizer reset for
        clients whose rejoin round fell outside a sampled cohort)."""
        ids = np.asarray(ids, np.int64)
        if len(ids) == 0:
            return
        self.settle()
        jax.tree_util.tree_map(lambda l: l.__setitem__(ids, 0),
                               self._slots[slot])

    def row(self, slot: str, i: int):
        self.settle()
        return jax.tree_util.tree_map(lambda l: l[i], self._slots[slot])

    # ---- checkpointing -----------------------------------------------------

    def checkpoint_tree(self):
        """The whole store as one pytree (slot-name-prefixed) for
        ``checkpoint.ckpt.save_checkpoint``."""
        self.settle()
        return dict(self._slots)

    def load_checkpoint_tree(self, tree) -> None:
        self.settle()
        before = self.relaid_bytes
        for name in self._slots:
            self._slots[name] = self._own(tree[name])
        if self.tracer is not None:
            self.tracer.count("store.relaid_bytes",
                              self.relaid_bytes - before)


class ClientSampler:
    """Seeded per-round cohort sampling over the population.

    ``uniform``: every client equally likely, without replacement.
    ``availability``: probability ∝ the round's availability probabilities
    (``ScenarioTrace.avail_probs``) — the server preferentially samples
    reachable clients, so diurnal populations induce participation skew.

    One stateful ``RandomState`` drives the whole run: the cohort sequence
    is a single stream, so ``state_dict``/``load_state_dict`` (stored in
    the checkpoint sidecar) make a mid-stream resume reproduce the
    uninterrupted sequence exactly."""

    def __init__(self, kind: str, population: int, cohort_size: int,
                 seed: int = 0):
        if kind not in SAMPLER_KINDS:
            raise ValueError(f"unknown sampler kind {kind!r}")
        self.kind = kind
        self.population = population
        self.cohort_size = cohort_size
        self._rng = np.random.RandomState(seed)

    def sample(self, avail_probs: Optional[np.ndarray] = None) -> np.ndarray:
        """One round's cohort (sorted client ids, without replacement)."""
        if self.kind == "uniform" or avail_probs is None:
            ids = self._rng.choice(self.population, size=self.cohort_size,
                                   replace=False)
        else:
            p = np.asarray(avail_probs, np.float64)
            assert p.shape == (self.population,), p.shape
            p = np.maximum(p, 1e-12)
            ids = self._rng.choice(self.population, size=self.cohort_size,
                                   replace=False, p=p / p.sum())
        return np.sort(ids)

    # ---- checkpoint/resume -------------------------------------------------

    def state_dict(self) -> Dict:
        kind, keys, pos, has_gauss, cached = self._rng.get_state()
        return {"kind": self.kind, "rng": [kind, np.asarray(keys).tolist(),
                                          int(pos), int(has_gauss),
                                          float(cached)]}

    def load_state_dict(self, d: Dict) -> None:
        assert d["kind"] == self.kind, (d["kind"], self.kind)
        kind, keys, pos, has_gauss, cached = d["rng"]
        self._rng.set_state((kind, np.asarray(keys, np.uint32), int(pos),
                             int(has_gauss), float(cached)))


class PopulationData:
    """Lazy non-IID client data over a shared class-bucketed pool.

    The pool is one synthetic corpus; each client draws samples from its
    own label distribution (``class_probs[cid]``) by picking a class, then
    a pool index within that class.  Draws are pure functions of
    (seed, client id, round) — 10k clients need no per-client iterator
    state, and checkpoint resume needs no replay."""

    def __init__(self, pool: Dict[str, np.ndarray], class_probs: np.ndarray,
                 seed: int = 0, label_key: str = "label"):
        self.pool = {k: v for k, v in pool.items()
                     if isinstance(v, np.ndarray) and v.ndim >= 1
                     and len(v) == len(pool[label_key])}
        self.scalars = {k: v for k, v in pool.items()
                        if k not in self.pool}      # e.g. prompt_len
        self.class_probs = np.asarray(class_probs, np.float64)
        self.n_classes = self.class_probs.shape[1]
        self.seed = seed
        labels = pool[label_key]
        self.buckets = [np.where(labels == c)[0]
                        for c in range(self.n_classes)]
        for c, b in enumerate(self.buckets):
            assert len(b) > 0, f"pool has no samples of class {c}"

    def _rng(self, cid: int, tag: int) -> np.random.RandomState:
        # splitmix-style mix keeps client/round streams independent
        h = (self.seed * 0x9E3779B1 + cid * 0x85EBCA77 + tag * 0xC2B2AE3D
             ) & 0xFFFFFFFF
        return np.random.RandomState(h)

    def _draw(self, rng, cid: int, n: int) -> np.ndarray:
        cls = rng.choice(self.n_classes, size=n, p=self.class_probs[cid]
                         / self.class_probs[cid].sum())
        return np.asarray([self.buckets[c][rng.randint(len(self.buckets[c]))]
                           for c in cls], np.int64)

    def round_batches(self, cid: int, rnd: int, local_steps: int,
                      batch: int) -> List[Dict[str, np.ndarray]]:
        """The client's ``local_steps`` training batches for round
        ``rnd`` (deterministic in (seed, cid, rnd))."""
        rng = self._rng(cid, rnd)
        out = []
        for _ in range(local_steps):
            sel = self._draw(rng, cid, batch)
            b = {k: v[sel] for k, v in self.pool.items()}
            b.update(self.scalars)
            out.append(b)
        return out

    def test_set(self, cid: int, n: int) -> Dict[str, np.ndarray]:
        """The client's held-out eval draw (deterministic in (seed, cid);
        tag -1 keeps it off every round's training stream)."""
        rng = self._rng(cid, 0x7FFFFFFF)
        sel = self._draw(rng, cid, n)
        b = {k: v[sel] for k, v in self.pool.items()}
        b.update(self.scalars)
        return b


def stacked_client_init(init_fn, keys) -> object:
    """Vmap a per-client init over stacked PRNG keys → one stacked tree
    (constant leaves broadcast), pulled to host numpy for the store."""
    stacked = jax.vmap(init_fn)(keys)
    return jax.tree_util.tree_map(np.asarray, stacked)


class PopulationRunner:
    """Per-round population orchestration around the fused cohort body.

    The compiled round step (``core.cohort.build_supervised_round`` with
    ``robust=True``) is untouched — it still sees a stacked cohort of
    ``cohort_size`` (+ghost) rows.  Everything population-specific is host
    work this runner owns, in order each round:

    1. **sample** — ``ClientSampler`` draws the cohort (availability-
       weighted from the scenario trace when configured);
    2. **plan** — the ``StalenessTracker`` (sized to the POPULATION, so a
       straggler's pending payload survives rounds it isn't sampled in)
       resolves a population-wide ``RoundPlan`` from the fault trace ∧
       sampled-mask ∧ realized availability;
    3. **gather** — the sampled rows of every store slot refill their
       staging buffers, the current ``global_shared`` tree is overlaid into
       the uploaded subtree (the downlink: participants start from the
       server's global, which also keeps the codec's delta-vs-broadcast
       reference contract), one ``device_put`` per slot;
    4. the **fused round** is dispatched on cohort-indexed slices of the
       plan; while the device runs it, the previous round's results are
       written into the store (``PopulationStore.settle``), then the host
       waits for the round;
    5. **scatter** — the store starts the device→host copies of the
       results and keeps them pending (``defer_scatter``): they land while
       the caller evaluates the round, and the next round writes them,
       the rows it samples again in its gather and the rest after its
       dispatch.  The new global is taken, when first read, from the
       results' row of a client whose merge gate passed (host-known from
       the plan).

    Crash-rejoins that land on unsampled rounds set a ``needs_opt_reset``
    flag; the reset is applied to the store the next time that client is
    gathered.  ``state_dict``/``checkpoint_tree`` capture the whole host
    state (sampler RNG mid-stream, tracker, flags, store, global) so a
    killed run resumes into the uninterrupted sequence; both settle the
    pending scatter first, as ``settle()`` does, which a round loop calls
    after its last round."""

    def __init__(self, *, pop: PopulationConfig, store: PopulationStore,
                 global_shared, upload_pred, channel, budget, ledger,
                 tracker, trace, strace, sampler: ClientSampler,
                 arrivals=None, dl=None, cs=None, est_bits=None,
                 act_bits: float = 0.0, tracer=None, health: bool = False):
        self.pop = pop
        self.N = pop.population
        self.K = pop.cohort_size
        self.store = store
        self.global_shared = global_shared   # the setter below
        self.upload_pred = upload_pred
        self.channel = channel
        self.budget = budget
        self.ledger = ledger
        self.tracker = tracker
        self.trace = trace
        self.strace = strace
        self.sampler = sampler
        self.arrivals = arrivals
        self.dl = dl
        self.cs = cs                      # CohortSharding over the cohort
        self.n_rows = cs.total if cs is not None else self.K
        self.est_bits = None if est_bits is None else \
            np.asarray(est_bits, np.float64)
        self.act_bits = float(act_bits)
        self.needs_opt_reset = np.zeros(self.N, bool)
        # the tracer owns all host timing (a disabled tracer still times);
        # host_s/round_s: sample+gather+scatter (both scatter spans) vs
        # whole-round wall
        self.tracer = tracer if tracer is not None else SpanTracer()
        store.tracer = self.tracer        # store spans nest in the round's
        self.tracer.count("store.relaid_bytes", store.relaid_bytes)
        self.health = health              # round_step returns a trailing
        #                                 # health-scalar dict (obs.health)
        self.host_s = 0.0                 # sample+gather+scatter time
        self.round_s = 0.0                # total round wall time
        self.round_wall = []              # per-round wall (round_s addends):
        #                                 # [0] holds the compile, [1:] are
        #                                 # steady-state (obs overhead bench)
        self.seen = np.zeros(self.N, bool)  # ever-sampled coverage

    # ---- helpers -----------------------------------------------------------

    def _put(self, tree):
        return jax.device_put(tree, self.cs.named) \
            if self.cs is not None else jax.device_put(tree)

    def _vec(self, v, fill):
        full = np.concatenate(
            [np.asarray(v, np.float32),
             np.full(self.n_rows - self.K, fill, np.float32)])
        return self._put(full)

    def _overlay_global(self, tr_buf) -> None:
        """Broadcast the server's global into the gathered rows' uploaded
        subtree, in place (numpy staging buffer)."""
        flat_g = trees.flatten(self.global_shared)

        def f(path, leaf):
            g = flat_g.get(path)
            if g is not None:
                leaf[:] = np.asarray(g)
            return leaf

        trees.map_with_path(f, tr_buf)

    @property
    def global_shared(self):
        """The server's global uploaded subtree (host numpy).  After a
        round whose merge gate passed it is that round's result row of the
        first receiving client, copied off the pulled results when first
        read."""
        if self._global_row is not None:
            tree, j = self._global_row
            self._global_row = None
            self._global = jax.tree_util.tree_map(
                lambda l: np.array(np.asarray(l)[j], order="C"), tree)
        return self._global

    @global_shared.setter
    def global_shared(self, tree) -> None:
        self._global, self._global_row = tree, None

    # ---- the round ---------------------------------------------------------

    def run_round(self, rnd: int, *, round_step, stacker, draw_batches,
                  local_steps: int, payload_bits: Optional[float] = None,
                  codec_key=None) -> Dict:
        """One sampled-cohort round.  ``draw_batches(cid, rnd)`` returns the
        client's ``local_steps`` host batches; ``payload_bits`` is the
        uncompressed fresh-upload size (ignored under a codec, where the
        fused body reports realized encoded bits); ``codec_key`` is the
        run-level codec PRNG key (per-round/per-CLIENT-ID keys are folded
        here, so a client's stochastic-rounding stream is stable no matter
        which cohorts it lands in)."""
        tracer = self.tracer
        with tracer.span("round") as sp_round:
            with tracer.span("sample") as sp_sample:
                probs = self.strace.avail_probs(rnd) \
                    if self.sampler.kind == "availability" else None
                ids = self.sampler.sample(probs)
                self.seen[ids] = True

            with tracer.span("plan"):
                # population-wide plan: faults ∧ sampled ∧ realized
                # availability
                gains = (self.channel.realize(self.N)
                         * self.strace.gain_round(rnd))
                rf = self.trace.round(rnd)
                gains = gains * rf.gain_scale
                s = np.zeros(self.N, np.float32)
                s[ids] = 1.0
                avail = self.strace.avail_round(rnd)
                rf_pop = dataclasses.replace(
                    rf, train=rf.train * s * avail, tx=rf.tx * s * avail,
                    recv=rf.recv * s * avail, rejoin=rf.rejoin * s)
                # a crash-rejoin on an unsampled round resets the optimizer
                # the next time the client is gathered
                self.needs_opt_reset |= (rf.rejoin > 0) & (s == 0)
                rplan = self.tracker.begin_round(
                    rf_pop, self.channel.outage_weights(gains), gains=gains,
                    fresh_bits=self.est_bits)

            with tracer.span("gather") as sp_gather:
                reset = ids[self.needs_opt_reset[ids]]
                self.store.zero_rows("opt", reset)
                self.needs_opt_reset[ids] = False
                tr_h = self.store.gather("trainable", ids,
                                         pad_to=self.n_rows)
                self._overlay_global(tr_h)
                tr_d = self._put(tr_h)
                opt_d = self._put(self.store.gather("opt", ids,
                                                    pad_to=self.n_rows))
                pend_d = self._put(self.store.gather("pending", ids,
                                                     pad_to=self.n_rows))

            # the batch draw rides inside the device-step window (as it did
            # in the t0..t6 accounting: it is not host_s overhead)
            hstats = None
            with tracer.span("device-step"):
                with tracer.span("device-step.draw"):
                    rows = [draw_batches(int(c), rnd) for c in ids]
                    rows += [rows[0]] * (self.n_rows - self.K)  # ghost rows
                    batches = stacker(rows)
                w = rplan.agg_w_pre if self.dl is not None else rplan.agg_w
                ontime = rplan.ontime if self.dl is not None \
                    else np.ones(self.N, np.float32)
                margs = (self._vec(rplan.train[ids], 1.0),
                         self._vec(w[ids], 0.0),
                         self._vec(rplan.recv[ids], 1.0),
                         self._vec(rplan.rejoin[ids], 0.0),
                         self._vec(ontime[ids], 1.0))
                if codec_key is None:
                    outs = round_step(tr_d, opt_d, pend_d, batches, *margs)
                else:
                    with tracer.span("encode"):
                        rk = jax.random.fold_in(codec_key, rnd)
                        ck = jnp.stack(
                            [jax.random.fold_in(rk, int(c)) for c in ids]
                            + [jax.random.fold_in(rk, int(ids[0]))]
                            * (self.n_rows - self.K))
                    outs = round_step(tr_d, opt_d, pend_d, batches, *margs,
                                      self._put(ck))
                # the last round's results go into the store while the
                # device runs this one (its own "scatter" span)
                scatter_s = self.store.settle()
                with tracer.span("device-step.wait"):
                    jax.block_until_ready(outs)
                tr_d, opt_d, pend_d, losses = outs[:4]
                if codec_key is None:
                    fresh_c = np.full(self.K, (payload_bits or 0.0),
                                      np.float64)
                else:
                    fresh_c = (np.asarray(outs[4], np.float64)[:self.K]
                               + self.act_bits)
                if self.health:
                    hstats = outs[-1]

            with tracer.span("scatter") as sp_scatter:
                # outputs of round_step, which donates only its inputs
                self.store.defer_scatter(ids, {"trainable": tr_d,
                                               "opt": opt_d,
                                               "pending": pend_d})
                # the merge gate is host-known: the new global is any
                # cohort row that received the broadcast
                gate = float(rplan.agg_w.sum()) > 0 and rplan.quorum_ok
                if gate:
                    recv_rows = np.where(rplan.recv[ids] > 0)[0]
                    if len(recv_rows):
                        self._global_row = (
                            trees.select(tr_d, self.upload_pred),
                            int(recv_rows[0]))

            with tracer.span("ledger"):
                fresh_n = np.zeros(self.N, np.float64)
                fresh_n[ids] = fresh_c
                charged = self.tracker.end_round(rplan, fresh_n)
                extra = None
                if self.dl is not None:
                    extra = {"sim_dt_s": float(rplan.sim_dt_s),
                             "quorum_noop": not rplan.quorum_ok,
                             "n_delivered": int(rplan.n_delivered),
                             "corrupt": int(np.asarray(rplan.corrupt).sum())}
                    if codec_key is not None:  # realized size → next est.
                        self.est_bits = np.where(
                            np.asarray(rplan.train) > 0, fresh_n,
                            self.est_bits)
                att = np.where(np.asarray(rplan.attempt) > 0)[0]
                if self.dl is None:
                    reports = [self.budget.report(charged[ci], gains[ci])
                               for ci in att]
                else:
                    reports = [self.budget.attempt_report(
                        charged[ci], gains[ci],
                        tx_time_s=float(rplan.tx_time_s[ci]),
                        arrival_s=float(rplan.arrival_s[ci]),
                        delivered=bool(rplan.delivered[ci] > 0))
                        for ci in att]
                self.ledger.log_round(reports, extra, round_id=rnd)

        self.host_s += (sp_sample.dur + sp_gather.dur + scatter_s
                        + sp_scatter.dur)
        self.round_s += sp_round.dur
        self.round_wall.append(sp_round.dur)
        if hstats is not None:
            hstats = {k: float(v) for k, v in hstats.items()}
        return {"ids": ids, "cohort_tr": tr_d, "losses": losses,
                "plan": rplan, "health": hstats}

    def burn_rounds(self, n: int) -> None:
        """Replay the host RNG draws of ``n`` skipped rounds on resume
        (the sampler/tracker restore from state_dict instead)."""
        for _ in range(n):
            self.channel.realize(self.N)
            if self.arrivals is not None:
                self.arrivals.burn_round()

    def settle(self) -> None:
        """Write the last round's pending results into the store."""
        self.host_s += self.store.settle()

    # ---- checkpoint/resume -------------------------------------------------

    def state_dict(self) -> Dict:
        self.settle()
        d = {"sampler": self.sampler.state_dict(),
             "tracker": self.tracker.state_dict(),
             "needs_opt_reset": np.where(self.needs_opt_reset)[0].tolist(),
             "seen": np.where(self.seen)[0].tolist(),
             "host_s": self.host_s, "round_s": self.round_s}
        if self.est_bits is not None:
            d["est_bits"] = [float(b) for b in self.est_bits]
        return d

    def load_state_dict(self, d: Dict) -> None:
        self.sampler.load_state_dict(d["sampler"])
        self.tracker.load_state_dict(d["tracker"])
        self.needs_opt_reset = np.zeros(self.N, bool)
        self.needs_opt_reset[np.asarray(d["needs_opt_reset"],
                                        np.int64)] = True
        self.seen = np.zeros(self.N, bool)
        self.seen[np.asarray(d["seen"], np.int64)] = True
        self.host_s = float(d.get("host_s", 0.0))
        self.round_s = float(d.get("round_s", 0.0))
        if "est_bits" in d:
            self.est_bits = np.asarray(d["est_bits"], np.float64)

    def checkpoint_tree(self):
        self.settle()
        return {"store": self.store.checkpoint_tree(),
                "global": self.global_shared}

    def load_checkpoint_tree(self, tree) -> None:
        self.store.load_checkpoint_tree(tree["store"])
        self.global_shared = tree["global"]

    @property
    def host_overhead_frac(self) -> float:
        return self.host_s / self.round_s if self.round_s > 0 else 0.0
