"""Streaming data plane: chunked out-of-core implementation of ``DataPlane``.

Points arrive as fixed-size chunks from a :class:`repro.data.ChunkSource`
(or a fault-wrapping ``ResilientChunkSource``); everything the algorithm
needs about them is folded into per-block sufficient statistics
``(Σx, |B|, min x, max x)`` chunk by chunk. Host keeps 4 bytes/point of
block memberships (``int32``) — the only full-length state (ADR 0001) —
and the pruned-Lloyd bound state lives as one compact host array per chunk
between passes (12 bytes/point).

All chunk programs have static shapes (chunks are padded, validity is a
traced row count), so a full pass reuses one compiled executable, and the
per-chunk assignment work dispatches through ``kernels.ops`` — exactly as
the in-core plane does.
"""

from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import bwkm as core_bwkm
from repro.core import init_partition, kmeanspp
from repro.core import kmeans_ll as core_ll
from repro.core import lloyd as lloyd_mod
from repro.core import partition as part_mod
from repro.core.partition import BlockStats, Partition, SplitPlan
from repro.data.chunks import ChunkSource, padded_device_chunks, reservoir_sample
from repro.engine.plane import global_extent
from repro.health import RunHealth
from repro.kernels import ops

__all__ = [
    "StreamBWKMResult",
    "StreamLLSession",
    "StreamStats",
    "StreamingLloydSession",
    "StreamingPlane",
    "default_init_sample_size",
    "streaming_initial_partition",
]

_BIG = 3.0e38


@dataclasses.dataclass
class StreamStats:
    """Out-of-core accounting: how much data moved to reach the result."""

    n_chunks: int
    chunk_size: int
    passes: int = 0  # full-dataset streaming passes
    points_streamed: int = 0  # Σ chunk rows fed to the device


@dataclasses.dataclass
class StreamBWKMResult(core_bwkm.BWKMResult):
    stream: StreamStats | None = None


# ----------------------------------------------------------- chunk programs
@partial(jax.jit, static_argnames=("m",))
def _box_route_stats(x, nv, lo, hi, active, *, m):
    """Route one padded chunk into the partition's boxes (the shared
    ``core.partition.route_into_boxes`` rule — containment for interior
    points, nearest box for tails) and fold its block statistics.

    ``lo/hi/active`` are sliced by the caller to the live row prefix (block
    rows are allocated densely from 0), so the ``[cs, m_live]`` distance
    matrix scales with actual blocks, not the 64·m capacity; only the
    ``[m, ·]`` output statistics use full capacity ``m``.
    """
    valid = jnp.arange(x.shape[0]) < nv
    bid = part_mod.route_into_boxes(x, lo, hi, active)
    return bid, part_mod.block_stats(x, bid, m, valid=valid)


@partial(jax.jit, static_argnames=("m",))
def _split_route_stats(x, bid, nv, plan, *, m):
    """Repair one chunk's memberships against a split plan and fold stats."""
    valid = jnp.arange(x.shape[0]) < nv
    new_bid = part_mod.route_split(x, bid, plan)
    return new_bid, part_mod.block_stats(x, new_bid, m, valid=valid)


_combine = jax.jit(part_mod.combine_block_stats)


@partial(jax.jit, static_argnames=("impl",))
def _chunk_assign_stats(x, nv, c, *, impl):
    """Per-chunk Lloyd sufficient statistics over the full dataset, in ONE
    fused pass through ``kernels.ops.assign_update_chunk`` — the same shared
    hot path the in-core Lloyd and the sharded stats body use. The validity
    prefix doubles as the weight vector, so padding rows are inert in
    sums/counts/err by the kernel's zero-weight contract; ``x`` is already
    padded to the static chunk shape, so the pad inside is a no-op."""
    wv = (jnp.arange(x.shape[0]) < nv).astype(jnp.float32)
    fu = ops.assign_update_chunk(x, wv, c, chunk_size=x.shape[0], impl=impl)
    return fu.sums, fu.counts, fu.err


# ------------------------------------------------------------ data passes
def _pad_bid(bid: np.ndarray, chunk_size: int) -> np.ndarray:
    if bid.shape[0] == chunk_size:
        return bid
    out = np.zeros((chunk_size,), np.int32)
    out[: bid.shape[0]] = bid
    return out


def _routing_pass(
    source: ChunkSource, part: Partition, stats: StreamStats
) -> tuple[Partition, list[np.ndarray]]:
    """Stream the dataset once: route every chunk into the current boxes,
    record memberships on the host, accumulate tight block statistics."""
    m, d = part.capacity, source.dim
    # Live rows are the dense prefix [0, n_blocks); n_blocks is host-known
    # before the pass. Routing against the prefix (padded up to a multiple of
    # 128 for shape stability) keeps the per-chunk distance matrix at
    # [cs, ~n_blocks] instead of [cs, 64·m] capacity.
    m_live = min(m, max(128, -(-int(part.n_blocks) // 128) * 128))
    acc = part_mod.empty_block_stats(m, d)
    bids: list[np.ndarray] = []
    for x_dev, nv in padded_device_chunks(source):
        bid, st = _box_route_stats(
            x_dev, nv,
            part.lo[:m_live], part.hi[:m_live], part.active[:m_live], m=m,
        )
        acc = _combine(acc, st)
        bids.append(np.asarray(bid[:nv], np.int32))
        stats.points_streamed += nv
    stats.passes += 1
    return _with_stats(part, acc), bids


def _split_pass(
    source: ChunkSource,
    bids: list[np.ndarray],
    part: Partition,
    plan: SplitPlan,
    stats: StreamStats,
) -> tuple[Partition, list[np.ndarray]]:
    """Stream the dataset once to execute a split round: repair memberships
    chunk-by-chunk and re-tighten every block's statistics."""
    m, d = part.capacity, source.dim
    acc = part_mod.empty_block_stats(m, d)
    new_bids: list[np.ndarray] = []
    for i, (x_dev, nv) in enumerate(padded_device_chunks(source)):
        bid_dev = jnp.asarray(_pad_bid(bids[i], source.chunk_size))
        nb, st = _split_route_stats(x_dev, bid_dev, nv, plan, m=m)
        acc = _combine(acc, st)
        new_bids.append(np.asarray(nb[:nv], np.int32))
        stats.points_streamed += nv
    stats.passes += 1
    part = part_mod.apply_split_plan(part, plan)
    return _with_stats(part, acc), new_bids


def _with_stats(part: Partition, st: BlockStats) -> Partition:
    # block_id stays empty: full-length membership lives on the host, not in
    # the pytree (the whole point of the streaming plane).
    return part._replace(
        psum=st.psum, count=st.count, lo=st.lo, hi=st.hi,
        block_id=jnp.zeros((0,), jnp.int32),
    )


# ------------------------------------------------------------ initial sample
def default_init_sample_size(n: int, p: dict) -> int:
    """Sample size for the init pass: enough for every Alg-3/4 subsample to
    be a genuine subsample (matches the sharded plane's choice)."""
    return min(n, max(p["s"] * p["r"] * 4, 4 * p["m"]))


def streaming_initial_partition(
    key: jax.Array,
    source: ChunkSource,
    k: int,
    *,
    m: int,
    m_prime: int,
    s: int,
    r: int,
    capacity: int,
    sample_size: int,
    init: str = "kmeans++",
) -> Partition:
    """Algorithm 2 over a one-pass uniform sample of ``source``.

    ``init`` names the strategy in the ``repro.api.inits`` registry whose
    ``sample`` hook draws the first-pass sample (imported lazily: the api
    layer imports the engines, not vice versa — same convention as
    ``core.bwkm.seed_centroids``).

    The returned partition's boxes/active rows describe the spatial
    partition; its statistics and ``block_id`` reflect only the sample. The
    caller must re-route the full stream through the boxes and replace the
    statistics (``_routing_pass``) before using them.
    """
    from repro.api.inits import resolve_init

    key, k_seed = jax.random.split(key)
    seed = int(jax.random.randint(k_seed, (), 0, 2**31 - 1))
    sample = resolve_init(init).sample(source, sample_size, seed)
    return init_partition.build_initial_partition(
        key,
        jnp.asarray(sample),
        k,
        m=m,
        m_prime=m_prime,
        s=min(s, sample.shape[0]),
        r=r,
        capacity=capacity,
    )


# ------------------------------------------------------------------ plane
class StreamingPlane:
    """Chunked out-of-core execution plane (``engine="streaming"``)."""

    name = "streaming"

    def __init__(self, source: ChunkSource):
        self.source = source
        self.stats = StreamStats(
            n_chunks=source.n_chunks, chunk_size=source.chunk_size
        )
        self.bids: list[np.ndarray] = []
        self.run_health = RunHealth()

    @property
    def n_points(self) -> int:
        return int(self.source.n_points)

    @property
    def dim(self) -> int:
        return int(self.source.dim)

    def split_key(self, key):
        key, k_init, k_pp = jax.random.split(key, 3)
        return key, k_init, k_pp

    def build_partition(self, k_init, config, p) -> Partition:
        n = self.n_points
        s_init = config.init_sample_size or default_init_sample_size(n, p)
        part = streaming_initial_partition(
            k_init, self.source, config.k,
            m=p["m"], m_prime=p["m_prime"], s=p["s"], r=p["r"],
            capacity=p["capacity"], sample_size=s_init, init=config.init,
        )
        self.stats.passes += 1  # the reservoir-sample pass
        self.stats.points_streamed += n
        part, self.bids = _routing_pass(self.source, part, self.stats)
        return part

    def extent(self, part: Partition) -> float:
        return global_extent(part)

    def route_round(self, part: Partition, plan: SplitPlan, round_index: int) -> Partition:
        part, self.bids = _split_pass(self.source, self.bids, part, plan, self.stats)
        return part

    def on_iteration(self, it, c, part, distances) -> None:
        pass

    def make_result(self, **fields) -> StreamBWKMResult:
        # A ResilientChunkSource (repro.data.resilient) carries the fault
        # ledger for the whole fit — retries, skipped chunks, quarantined
        # rows; a bare source means a clean run by construction (any fault
        # would have raised).
        health = getattr(self.source, "health", None)
        return StreamBWKMResult(
            stream=self.stats,
            health=health if isinstance(health, RunHealth) else RunHealth(),
            **fields,
        )


# ------------------------------------------------------- k-means|| session
def _pad_batch(cands: np.ndarray, cap: int, d: int) -> tuple[jax.Array, jax.Array]:
    """Pack a ragged candidate batch into the static ``[cap, d]`` shape the
    chunk program compiles once for, unfilled rows parked at the far
    sentinel with validity 0 (the in-core kernel contract)."""
    batch = np.full((cap, d), core_ll._FAR, np.float32)
    valid = np.zeros((cap,), np.float32)
    m = min(len(cands), cap)
    if m:
        batch[:m] = cands[:m]
        valid[:m] = 1.0
    return jnp.asarray(batch), jnp.asarray(valid)


def _gather_rows(
    source: ChunkSource, wanted: dict[int, np.ndarray]
) -> dict[int, np.ndarray]:
    """Fetch ``{chunk_index: rows[idx]}`` from the source. Backends with
    random access pay only for the touched chunks; iterator-only sources
    fall back to ONE host scan for all of them (never a per-chunk rescan)."""
    if not wanted:
        return {}
    if getattr(source, "chunk_at", None) is not None:
        return {
            i: np.asarray(source.chunk_at(i), np.float32)[idx]
            for i, idx in wanted.items()
        }
    out: dict[int, np.ndarray] = {}
    for i, chunk in enumerate(source.chunks()):
        if i in wanted:
            out[i] = np.asarray(chunk, np.float32)[wanted[i]]
    return out


class StreamLLSession:
    """Out-of-core k-means|| session (ADR 0005; DESIGN §12).

    The per-point min-d² state lives on the host as one f32 array per chunk
    (4 bytes/point) and is re-fed to the jitted chunk program each pass.
    Each round folds the previous round's candidates FIRST (one device read
    of x per round), which makes the accumulated cost the EXACT current
    normaliser φ for the driver's Bernoulli draw; the accepted rows are
    gathered back by random access (O(ℓ·d) bytes, not a pass). RNG stream:
    round ``rnd`` draws under ``fold_in(key, rnd+1)``, chunk ``i`` under
    ``fold_in(·, i)`` — pinned by the per-round φ-normaliser regression
    test. ``rounds + 1`` device passes total (the weighting pass subsumes
    the final round's fold).
    """

    def __init__(self, key, source: ChunkSource, *, k, l, rounds, cap_round, impl):  # noqa: E741
        self.key = key
        self.source = source
        self.k, self.l, self.rounds, self.cap_round = k, l, rounds, cap_round
        self.impl = impl
        self.d = source.dim
        self.cs = source.chunk_size
        self.mind2: list[np.ndarray] = []  # per-chunk host state
        self.phi = float("inf")
        self.distances = 0.0
        self.passes = 0
        key_seed, self.key_pp = jax.random.split(jax.random.fold_in(key, 0), 2)
        seed_int = int(jax.random.randint(key_seed, (), 0, 2**31 - 1))
        first = np.asarray(reservoir_sample(source, 1, seed_int), np.float32)
        self.cands: list[np.ndarray] = [first]
        self.pending: np.ndarray | None = first

    def _fold(self, batch_cands: np.ndarray, first_pass: bool) -> None:
        """One device pass: fold ``batch_cands`` into every chunk's min-d²,
        leaving ``phi`` the exact cost of the full current candidate set."""
        batch, bvalid = _pad_batch(batch_cands, self.cap_round, self.d)
        phi_acc = 0.0
        for i, (x_dev, nv) in enumerate(padded_device_chunks(self.source)):
            if first_pass:
                self.mind2.append(np.full((nv,), _BIG, np.float32))
            wv = (jnp.arange(self.cs) < nv).astype(jnp.float32)
            m_in = np.zeros((self.cs,), np.float32)
            m_in[:nv] = self.mind2[i]
            out = ops.min_sqdist_update_chunk(
                x_dev, wv, batch, bvalid, jnp.asarray(m_in),
                chunk_size=self.cs, impl=self.impl,
            )
            self.mind2[i] = np.asarray(out.mind2[:nv], np.float32)
            phi_acc += float(out.cost)
            self.distances += float(out.n_dist)
        self.phi = phi_acc
        self.passes += 1

    def seed(self) -> None:
        self._fold(self.pending, first_pass=True)  # pass 0: φ₀ exact
        self.pending = None

    def begin_round(self, rnd: int):
        if self.pending is not None and len(self.pending):
            self._fold(self.pending, first_pass=False)  # φ_{rnd−1} exact
        self.pending = None
        # Per-chunk uniforms under the historical key chain, concatenated so
        # the driver's single Bernoulli call site sees one flat dataset view.
        key_round = jax.random.fold_in(self.key, rnd + 1)
        us = [
            np.asarray(
                jax.random.uniform(jax.random.fold_in(key_round, i), (m_i.shape[0],))
            )
            for i, m_i in enumerate(self.mind2)
        ]
        u = np.concatenate(us) if us else np.zeros((0,), np.float32)
        mind2 = (
            np.concatenate(self.mind2) if self.mind2
            else np.zeros((0,), np.float32)
        )
        return u, np.ones_like(mind2), mind2, self.phi

    def select(self, rnd: int, u, accept) -> None:
        accept = np.asarray(accept)
        u = np.asarray(u)
        wanted: dict[int, np.ndarray] = {}
        wanted_u: dict[int, np.ndarray] = {}
        off = 0
        for i, m_i in enumerate(self.mind2):
            nv = m_i.shape[0]
            idx = np.flatnonzero(accept[off : off + nv])
            if idx.size:
                wanted[i] = idx
                wanted_u[i] = u[off : off + nv][idx]
            off += nv
        rows = _gather_rows(self.source, wanted)
        if wanted:
            sel = np.concatenate([rows[i] for i in sorted(wanted)])
            sel_u = np.concatenate([wanted_u[i] for i in sorted(wanted)])
            if len(sel) > self.cap_round:  # tail event: E[draws] <= l
                sel = sel[np.argsort(sel_u)[: self.cap_round]]
            self.pending = sel
            self.cands.append(sel)
        else:
            self.pending = np.zeros((0, self.d), np.float32)

    def finish(self, normalisers: tuple) -> dict:
        # weighting pass: nearest-candidate assignment over the full
        # candidate set (this fold subsumes the final round's candidates)
        cand_all = jnp.asarray(np.concatenate(self.cands))
        weights = jnp.zeros((cand_all.shape[0],), jnp.float32)
        for x_dev, nv in padded_device_chunks(self.source):
            wv = (jnp.arange(self.cs) < nv).astype(jnp.float32)
            au = ops.assign_update_chunk(
                x_dev, wv, cand_all, chunk_size=self.cs, impl=self.impl
            )
            weights = weights + au.counts
            self.distances += float(au.n_dist)
        self.passes += 1

        self.distances += float(cand_all.shape[0]) * max(self.k - 1, 1)
        c = kmeanspp.weighted_kmeanspp(self.key_pp, cand_all, weights, self.k)
        return {
            "centroids": c,
            "n_candidates": int(cand_all.shape[0]),
            "distances": self.distances,
            "passes": self.passes,
            "normalisers": normalisers,
        }


# ------------------------------------------------ full-stream Lloyd session
@partial(jax.jit, static_argnames=("impl",))
def _chunk_dense_full(x, nv, c, *, impl):
    """Initial dense chunk pass for the streaming Lloyd session: per-row
    top-2 (seeding the drift bounds) + the fold statistics + Σ w‖x‖² for
    the algebraic error identity."""
    wv = (jnp.arange(x.shape[0]) < nv).astype(jnp.float32)
    fu = ops.assign_update(x, wv, c, impl=impl)
    w2 = jnp.sum(wv * jnp.sum(x.astype(jnp.float32) ** 2, axis=-1))
    ub = jnp.sqrt(jnp.maximum(fu.d1, 0.0))
    lb = jnp.sqrt(jnp.maximum(fu.d2, 0.0))
    return fu.assign, ub, lb, fu.sums, fu.counts, fu.err, fu.n_dist, w2


@partial(jax.jit, static_argnames=("impl", "prune"))
def _chunk_pruned_stats(x, nv, c_new, assign, ub, lb, drift, *, impl, prune):
    """One pruned Lloyd chunk fold: update this chunk's carried bounds from
    the centroid drift, rescan only the rows the bounds can't settle, and
    return the chunk's full statistics under the composed assignment —
    exactly the in-core ``pruned_body`` with the bound state living on the
    host between passes instead of in the ``while_loop`` carry."""
    valid = jnp.arange(x.shape[0]) < nv
    wv = valid.astype(jnp.float32)
    if prune:
        ub, lb = lloyd_mod.drift_bound_update(ub, lb, assign, drift)
        active = (ub >= lb) & valid
        fu = ops.assign_update_pruned(x, wv, c_new, assign, active, impl=impl)
        ub = jnp.where(active, jnp.sqrt(jnp.maximum(fu.d1, 0.0)), ub)
        lb = jnp.where(active, jnp.sqrt(jnp.maximum(fu.d2, 0.0)), lb)
        return fu.assign, ub, lb, fu.sums, fu.counts, fu.n_dist
    fu = ops.assign_update(x, wv, c_new, impl=impl)
    ub = jnp.sqrt(jnp.maximum(fu.d1, 0.0))
    lb = jnp.sqrt(jnp.maximum(fu.d2, 0.0))
    return fu.assign, ub, lb, fu.sums, fu.counts, fu.n_dist


class StreamingLloydSession:
    """Full-stream Lloyd with drift-bound pruning carried ACROSS chunk folds.

    The in-core pruned loop keeps (assignment, upper bound, lower bound)
    per row in the ``while_loop`` carry; out-of-core the same state lives
    on the host as one compact f32/i32 array per chunk (12 bytes/point) and
    is re-fed to the jitted chunk program each pass — the plane-owned bound
    state of ADR 0010.
    """

    def __init__(self, source: ChunkSource, k: int, *, impl, prune: bool):
        self.source = source
        self.k = k
        self.impl = impl
        self.prune = prune
        self.denom = max(k * source.n_points, 1)
        self.assigns: list[np.ndarray] = []
        self.ubs: list[np.ndarray] = []
        self.lbs: list[np.ndarray] = []

    def seed(self, c):
        k, d = self.k, c.shape[1]
        sums = jnp.zeros((k, d), jnp.float32)
        counts = jnp.zeros((k,), jnp.float32)
        err = jnp.zeros((), jnp.float32)
        w2sum = jnp.zeros((), jnp.float32)
        n_dist = 0.0
        for x_dev, nv in padded_device_chunks(self.source):
            a_, ub_, lb_, s_, n_, e_, nd_, w2_ = _chunk_dense_full(
                x_dev, nv, c, impl=self.impl
            )
            self.assigns.append(np.asarray(a_, np.int32))
            self.ubs.append(np.asarray(ub_, np.float32))
            self.lbs.append(np.asarray(lb_, np.float32))
            sums, counts, err, w2sum = (
                sums + s_, counts + n_, err + e_, w2sum + w2_,
            )
            n_dist += float(nd_)
        return sums, counts, err, w2sum, n_dist

    def step(self, c_new, drift):
        sums = jnp.zeros((self.k, c_new.shape[1]), jnp.float32)
        counts = jnp.zeros((self.k,), jnp.float32)
        n_dist = 0.0
        for i, (x_dev, nv) in enumerate(padded_device_chunks(self.source)):
            a_, ub_, lb_, s_, n_, nd_ = _chunk_pruned_stats(
                x_dev, nv, c_new,
                jnp.asarray(self.assigns[i]), jnp.asarray(self.ubs[i]),
                jnp.asarray(self.lbs[i]),
                drift, impl=self.impl, prune=self.prune,
            )
            self.assigns[i] = np.asarray(a_, np.int32)
            self.ubs[i] = np.asarray(ub_, np.float32)
            self.lbs[i] = np.asarray(lb_, np.float32)
            sums, counts = sums + s_, counts + n_
            n_dist += float(nd_)
        return sums, counts, n_dist
