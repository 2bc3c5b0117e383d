"""``repro.BWKM`` — one estimator over every execution engine (DESIGN.md §9).

The paper's pitch is a single algorithm that scales across dataset regimes;
this is the single front door to it. Callers describe *what* to cluster —
the engine registry decides *how*:

    >>> model = BWKM(k=27).fit("shards/part-*.npy")   # auto → streaming
    >>> labels = model.predict("shards/part-*.npy")    # chunked, out-of-core
    >>> model.result_.stop_reason, model.engine_
    ('boundary-empty', 'streaming')

``fit`` accepts a ``jax.Array``/NumPy array, a ``.npy`` path, a glob or
directory of shards, a list of shard paths, or any ``ChunkSource``; see
``repro.api.adapters``. ``predict``/``score``/``transform`` stream their
input through the chunk-shaped kernels, so they work on datasets that never
fit in memory regardless of which engine fitted the model.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.api import adapters, engines
from repro.api.inits import resolve_init
from repro.api.result import FitResult
from repro.core.bwkm import BWKMConfig
from repro.data.chunks import padded_device_chunks
from repro.kernels import ops
from repro.service.session import BWKMSession, ServiceConfig

__all__ = ["BWKM", "DEFAULT_CHUNK_SIZE"]

#: rows per streamed chunk for fit/predict/score/transform (f32·d per row)
DEFAULT_CHUNK_SIZE = 65_536

_CONFIG_FIELDS = {f.name for f in dataclasses.fields(BWKMConfig)}


@partial(jax.jit, static_argnames=("impl",))
def _chunk_error(x, nv, c, *, impl):
    """One chunk's contribution to E^D(C): Σ d1 over the valid row prefix.
    Error-only — unlike ``streaming_lloyd_step`` it skips the cluster
    sums/counts reductions ``score`` would discard. ``impl`` is static so
    flipping the session kernel default retraces instead of reusing the
    cached program."""
    _, d1, _ = ops.assign_top2_chunk(x, c, chunk_size=x.shape[0], impl=impl)
    valid = (jnp.arange(x.shape[0]) < nv).astype(jnp.float32)
    return jnp.sum(valid * d1)


class BWKM:
    """Boundary Weighted K-means estimator (paper Algorithm 5).

    Parameters
    ----------
    k:
        number of clusters.
    engine:
        ``"auto"`` (default) or an explicit engine name — see
        ``repro.list_engines()``. Auto-selection rules are documented in
        docs/adr/0002-estimator-api.md.
    init:
        initialisation strategy name — see ``repro.list_inits()``. Defaults
        to ``"kmeans++"``; when a prebuilt ``config`` is passed, ``None``
        (the default) keeps the config's own ``init``.
    chunk_size:
        rows per chunk for the streaming engine and for out-of-core
        ``predict``/``score``/``transform``.
    seed:
        PRNG seed; ``fit(..., key=...)`` overrides it per call.
    trace:
        record per-iteration snapshots in ``result_.trace`` (the paper's
        trade-off curves are plotted from them).
    checkpoint_dir:
        where engines that checkpoint (distributed) persist driver state.
    config:
        a prebuilt :class:`BWKMConfig`; mutually exclusive with passing
        config fields as keyword overrides.
    **config_overrides:
        any :class:`BWKMConfig` field (``max_iters``, ``distance_budget``,
        ``init_sample_size``, …) forwarded to the config.

    After ``fit``: ``result_`` (unified :class:`FitResult`), ``centroids_``,
    ``engine_`` (resolved name), ``n_iter_``.
    """

    def __init__(
        self,
        k: int | None = None,
        *,
        engine: str = "auto",
        init: str | None = None,
        chunk_size: int = DEFAULT_CHUNK_SIZE,
        seed: int = 0,
        trace: bool = False,
        checkpoint_dir: str | None = None,
        incore_limit_bytes: int = engines.INCORE_LIMIT_BYTES,
        config: BWKMConfig | None = None,
        service: ServiceConfig | None = None,
        **config_overrides: Any,
    ):
        if engine != "auto":
            engines.get_engine(engine)  # fail fast on typos
        if service is not None:
            if config is not None:
                raise ValueError(
                    "pass either service= (which carries its own base config) "
                    "or config=, not both"
                )
            if k is not None and k != service.base.k:
                raise ValueError(f"k={k} conflicts with service.base.k={service.base.k}")
            config = service.base
        if config is not None:
            if k is not None and k != config.k:
                raise ValueError(f"k={k} conflicts with config.k={config.k}")
            if config_overrides:
                raise ValueError(
                    "pass either a prebuilt config or config overrides, not both: "
                    f"{sorted(config_overrides)}"
                )
            if init is not None:  # None keeps the config's own init
                config = dataclasses.replace(config, init=init)
            self.config = config
        else:
            if k is None:
                raise ValueError("BWKM requires k (or a prebuilt config)")
            unknown = set(config_overrides) - _CONFIG_FIELDS
            if unknown:
                raise TypeError(
                    f"unknown BWKMConfig fields {sorted(unknown)}; "
                    f"valid: {sorted(_CONFIG_FIELDS)}"
                )
            self.config = BWKMConfig(
                k=k, init="kmeans++" if init is None else init, **config_overrides
            )
        resolve_init(self.config.init)  # fail fast on typos
        self.engine = engine
        self.chunk_size = int(chunk_size)
        self.seed = int(seed)
        self.trace = bool(trace)
        self.checkpoint_dir = checkpoint_dir
        self.incore_limit_bytes = int(incore_limit_bytes)

        self.service = service

        self.result_: FitResult | None = None
        self.centroids_ = None
        self.engine_: str | None = None
        self.n_iter_: int | None = None
        self.session_: BWKMSession | None = None

    @property
    def k(self) -> int:
        return self.config.k

    @property
    def init(self) -> str:
        return self.config.init

    # ------------------------------------------------------------------ fit
    def fit(self, data: Any, *, key: jax.Array | None = None) -> "BWKM":
        """Cluster ``data`` with the selected (or auto-selected) engine."""
        if key is None:
            key = jax.random.PRNGKey(self.seed)
        with obs.fit_scope():
            name = engines.select_engine(
                data, self.engine, incore_limit_bytes=self.incore_limit_bytes
            )
            res = engines.get_engine(name).fit(
                key,
                data,
                self.config,
                chunk_size=self.chunk_size,
                trace_centroids=self.trace,
                checkpoint_dir=self.checkpoint_dir,
            )
        self.result_ = res
        self.centroids_ = res.centroids
        self.engine_ = name
        self.n_iter_ = res.iterations
        return self

    def fit_predict(self, data: Any, *, key: jax.Array | None = None) -> np.ndarray:
        return self.fit(data, key=key).predict(data)

    # --------------------------------------------------------- online updates
    def partial_fit(self, batch: Any) -> "BWKM":
        """Consume one mini-batch of an unbounded stream (DESIGN.md §13).

        The first call opens a :class:`~repro.service.BWKMSession` (exposed
        as ``session_``) configured from ``service=`` — or, when none was
        given, a default :class:`ServiceConfig` around this estimator's
        ``config`` and ``seed``. After every call ``centroids_`` tracks the
        live session, so ``predict``/``score``/``transform`` serve the
        current model. Per-batch metrics land in
        ``session_.last_metrics``.
        """
        if self.session_ is None:
            service = self.service or ServiceConfig(base=self.config, seed=self.seed)
            self.session_ = BWKMSession(service)
        self.session_.partial_fit(batch)
        self.centroids_ = self.session_.centroids
        self.engine_ = "service"
        self.n_iter_ = int(self.session_.state.batches)
        return self

    # ------------------------------------------------- chunked inference ops
    def _require_fitted(self):
        if self.centroids_ is None:
            raise RuntimeError("this BWKM instance is not fitted yet; call fit()")

    def predict(self, data: Any) -> np.ndarray:
        """Closest-centroid labels, computed chunk-by-chunk through
        ``kernels.ops.assign_top2_chunk`` — works on out-of-core inputs."""
        self._require_fitted()
        src = adapters.to_chunk_source(data, self.chunk_size)
        c = self.centroids_
        out = [np.zeros((0,), np.int32)]
        for x_dev, nv in padded_device_chunks(src):
            assign, _, _ = ops.assign_top2_chunk(x_dev, c, chunk_size=x_dev.shape[0])
            out.append(np.asarray(assign[:nv], np.int32))
        return np.concatenate(out)

    def score(self, data: Any) -> float:
        """Full-dataset K-means error ``E^D(C)`` (paper Eq. 1; lower is
        better), in one streaming pass through the chunked kernel."""
        self._require_fitted()
        src = adapters.to_chunk_source(data, self.chunk_size)
        c = self.centroids_
        impl = ops.resolve_impl(None)
        err = jnp.zeros((), jnp.float32)  # device-side: no per-chunk host sync
        for x_dev, nv in padded_device_chunks(src):
            err = err + _chunk_error(x_dev, nv, c, impl=impl)
        return float(err)

    def transform(self, data: Any) -> np.ndarray:
        """Squared distances to every centroid, ``[n, K]``, chunked."""
        self._require_fitted()
        src = adapters.to_chunk_source(data, self.chunk_size)
        c = self.centroids_
        out = [np.zeros((0, c.shape[0]), np.float32)]
        for x_dev, nv in padded_device_chunks(src):
            d2 = ops.pairwise_sqdist_chunk(x_dev, c, chunk_size=x_dev.shape[0])
            out.append(np.asarray(d2[:nv], np.float32))
        return np.concatenate(out)

    def __repr__(self) -> str:
        fitted = f", engine_={self.engine_!r}" if self.engine_ else ""
        return f"BWKM(k={self.config.k}, engine={self.engine!r}, init={self.init!r}{fitted})"
