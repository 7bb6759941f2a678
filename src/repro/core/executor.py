"""Plan-based stage-graph executor for SC_RB (the paper's Algorithm 2).

The five stages —

  1. Z  ← RB features of X          (Alg. 1, hashed ELL)          O(NRd)
  2. D̂ ← Z(Zᵀ1); Ẑ = D̂^{-1/2} Z    (Eq. 6)                       O(NR)
  3. U  ← top-K left singular vecs of Ẑ (blocked LOBPCG)          O(KNRm)
  4. Û ← row-normalize(U)
  5. labels ← k-means(Û, K)                                        O(NK²t)

— are written once here against the ``repro.core.rowmatrix`` protocol; an
``ExecutionPlan`` selects the data representation per run:

  placement  ``single`` | ``mesh``          (one device vs SPMD row shards)
  residency  ``device`` | ``host_chunked``  (whole arrays on device vs
             row-chunk streaming; under ``mesh`` placement, ``host_chunked``
             means within-shard chunk scans bounding per-device working
             sets to O(chunk))

plus the orthogonal knobs ``prefetch`` (double-buffered H2D uploads),
``impl`` (pallas/xla kernel dispatch), ``collective_compress`` (bf16 psum
payload on the mesh), ``block_rows`` (per-op Pallas row-tile caps),
``feature_map`` (a ``repro.core.featuremap`` registry instance for stage 1 —
None means Random Binning from the config; this is how the paper's
baselines share the executor) and ``laplacian_normalize`` (the D̂^{-1/2}
degree normalization; False gives the SV-style plain feature SVD).

The public entry points — ``pipeline.sc_rb``, ``pipeline.spectral_embed``,
``distributed.sc_rb_distributed`` — are thin wrappers that build a plan from
an ``SCRBConfig`` and call :func:`execute`. Guarantees preserved from the
hand-written pipelines: ``chunk_size=None`` single-device runs are
bit-identical to the seed single-shot path (same ops, same order, same
keys), and the streaming two-pass degrees are integer-exact for any
chunking.

Plan-selection guide (also in README): chunk (``residency="host_chunked"``)
when the (N, R) ELL matrix or the (N, K) embedding does not fit one
device; shard (``placement="mesh"``) when you have devices to spread rows
over; do both when each shard is still bigger than you want resident —
chunked-within-shard sweeps keep per-device temporaries O(chunk) while the
only cross-device traffic stays the (D, K) psum per mat-vec and the O(K·dim)
k-means statistics.
"""
from __future__ import annotations

import dataclasses
import warnings
from typing import Any, Mapping, Optional

import jax
import numpy as np

from repro.core import compressive, featuremap, rowmatrix, streaming
from repro.core.kmeans import row_normalize
from repro.core.options import (
    UNSET, CompressiveOptions, PartitionOptions, SolverOptions,
    normalize_config,
)
from repro.kernels import ops
from repro.obs import memory as obs_memory
from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace
from repro.utils import StageTimer, fold_key

_FITS_TOTAL = obs_metrics.REGISTRY.counter(
    "repro_fits_total", "Completed executor fits.", ("placement", "solver"))
_FIT_ROWS = obs_metrics.REGISTRY.counter(
    "repro_fit_rows_total", "Rows processed by completed executor fits.",
    ("placement",))

# flat fields kept as deprecated shims; everything typed Any so the UNSET
# sentinel can flow through (see repro.core.options.normalize_config)
_Flat = Any


@dataclasses.dataclass(frozen=True)
class SCRBConfig:
    """Run configuration. Solver/compressive/partition knobs live in typed
    groups (``repro.core.options``); the historical flat ``solver_*`` /
    ``compressive_*`` kwargs still work as deprecated shims — they fold into
    the groups with a ``DeprecationWarning`` and the flat attributes always
    mirror the canonical group values, so old call sites and artifact
    configs keep loading unchanged."""

    n_clusters: int
    n_grids: int = 256            # R
    sigma: float = 1.0            # Laplacian kernel bandwidth
    d_g: Optional[int] = None     # hashed features per grid (power of 2);
                                  # None → auto-size from occupied-bin probe
    # -- deprecated flat shims (fold into solver_options) -------------------
    solver: _Flat = UNSET         # → SolverOptions.solver
    solver_iters: _Flat = UNSET   # → SolverOptions.iters
    solver_tol: _Flat = UNSET     # → SolverOptions.tol
    solver_buffer: _Flat = UNSET  # → SolverOptions.buffer
    solver_precond: _Flat = UNSET          # → SolverOptions.precond
    solver_stable_tol: _Flat = UNSET       # → SolverOptions.stable_tol
    # -- deprecated flat shims (fold into compressive_options) --------------
    compressive_signals: _Flat = UNSET     # → CompressiveOptions.signals
    compressive_degree: _Flat = UNSET      # → CompressiveOptions.degree
    compressive_probes: _Flat = UNSET      # → CompressiveOptions.probes
    compressive_subset: _Flat = UNSET      # → CompressiveOptions.subset
    compressive_lambdas: _Flat = UNSET     # → CompressiveOptions.lambdas
    compressive_auto_n: _Flat = UNSET      # → CompressiveOptions.auto_n
    # -----------------------------------------------------------------------
    kmeans_iters: int = 25
    kmeans_replicates: int = 10
    seed: int = 0
    impl: str = "auto"            # kernel dispatch: auto | pallas | xla
    chunk_size: Optional[int] = None
    # ^ rows resident at once. None → whole-array residency (bit-identical
    #   to the pre-streaming pipeline on a single device); an int selects
    #   residency="host_chunked": on a single device every stage streams
    #   host-resident row chunks (peak device residency O(chunk·(R+K)),
    #   requires a host-driven solver); on a mesh it bounds every
    #   within-shard sweep (Gram mat-vec and k-means stats) to O(chunk)
    #   working sets; under placement="partitioned" each partition streams
    #   its own chunks.
    prefetch: bool = True
    # ^ double-buffer H2D chunk uploads on the streaming path: the transfer
    #   of chunk i+1 is issued before the chunk-i compute (bitwise-identical
    #   results; only the overlap changes). Ignored when chunk_size is None.
    block_rows: Optional[Mapping[str, int]] = None
    # ^ per-op Pallas row-tile caps (keys of ops.DEFAULT_BLOCK_ROWS, e.g.
    #   {"ell_spmm": 256}); None keeps the defaults. Applied to every kernel
    #   dispatch of the run via ops.block_rows_overrides.
    trace: Optional[str] = None
    # ^ directory for a jax.profiler trace of this fit (program spans on
    #   the host plane beside the device lanes, Perfetto-viewable); none is
    #   started when a session is already live. None (default) starts none;
    #   REPRO_TRACE=<dir> traces process-wide instead. A run-local setting,
    #   never part of the saved artifact.
    # -- typed option groups (canonical; see repro.core.options) ------------
    solver_options: Optional[SolverOptions] = None
    # ^ None → SolverOptions() defaults (or the deprecated flat kwargs).
    compressive_options: Optional[CompressiveOptions] = None
    # ^ None → CompressiveOptions() defaults (or the flat kwargs).
    partition: Optional[PartitionOptions] = None
    # ^ a PartitionOptions selects the divide-and-conquer
    #   placement="partitioned" fit (repro.core.partitioned); None keeps the
    #   single global solve.

    def __post_init__(self):
        normalize_config(self)

    # -- artifact round-trip ------------------------------------------------
    def to_dict(self) -> dict:
        """JSON-ready config dict in the *flat* spelling (plus a nested
        ``partition`` entry when set) — same-major artifacts written by this
        build stay readable by older same-major builds, whose loaders only
        know the flat keys."""
        d = {}
        for f in dataclasses.fields(self):
            # trace is a run-local observability knob, not model config:
            # keeping it out of the dict keeps same-major artifacts readable
            # by older loaders (their from_dict is cls(**d))
            if f.name in ("solver_options", "compressive_options",
                          "partition", "trace"):
                continue
            d[f.name] = getattr(self, f.name)
        if d.get("block_rows") is not None:
            d["block_rows"] = dict(d["block_rows"])
        if d.get("compressive_lambdas") is not None:
            d["compressive_lambdas"] = list(d["compressive_lambdas"])
        if self.partition is not None:
            d["partition"] = dataclasses.asdict(self.partition)
        return d

    @classmethod
    def from_dict(cls, d: Mapping) -> "SCRBConfig":
        """Rebuild from ``to_dict`` output (or a pre-grouping artifact
        config, which is flat-only). Flat keys here are round-trip data, not
        user calls, so the deprecation warning is suppressed."""
        with warnings.catch_warnings():
            warnings.simplefilter("ignore", DeprecationWarning)
            return cls(**dict(d))


@dataclasses.dataclass
class FitResult:
    """The typed result of one executor run — returned by ``execute`` and
    threaded through ``SCRBModel.fit`` (as ``model.fit_result``) and the
    ``sc_rb`` / ``spectral_embed`` wrappers. Unpacks as the historical
    ``(embedding, singular_values)`` pair for legacy ``spectral_embed``
    call sites."""

    labels: Optional[np.ndarray]  # (N,) int32; None when stages stop early
    embedding: np.ndarray         # (N, K) row-normalized spectral embedding
    singular_values: np.ndarray   # (K,) of Ẑ  (σ_i = sqrt(eigval of ẐẐᵀ))
    timer: StageTimer
    diagnostics: dict
    state: Optional[dict] = None  # fitted internals (``execute(keep_state=
    # True)``): the RowMatrix ``z``, fitted ``features``, raw ``eig`` pairs,
    # ``u_hat`` and ``km`` — what ``SCRBModel.fit`` turns into a deployable
    # artifact. None by default so one-shot runs don't pin O(N) state.

    def __iter__(self):
        yield self.embedding
        yield self.singular_values

    @property
    def timings(self) -> dict:
        """Per-stage wall-clock seconds (``timer.times`` view)."""
        return self.timer.times


#: Deprecated alias — the result type was renamed to :class:`FitResult`.
SCRBResult = FitResult


@dataclasses.dataclass(frozen=True)
class ExecutionPlan:
    """Placement × residency (+ orthogonal knobs) for one SC_RB run.

    See the module docstring for the plan-selection guide. Validation is
    eager so a bad combination fails before any stage runs.
    """

    placement: str = "single"            # single | mesh
    residency: str = "device"            # device | host_chunked
    chunk_size: Optional[int] = None     # rows per chunk (host or in-shard)
    prefetch: bool = True                # double-buffered H2D uploads
    impl: str = "auto"                   # kernel dispatch: auto|pallas|xla
    collective_compress: bool = False    # bf16 (D, K) psum payload on mesh
    mesh: Optional[Any] = None           # jax.sharding.Mesh for placement=mesh
    block_rows: Optional[Mapping[str, int]] = None
    feature_map: Optional[Any] = None    # stage-1 repro.core.featuremap
    # instance (unfitted); None → Random Binning from the SCRBConfig. This
    # is how the paper's baselines become plan points: same executor, same
    # stages, a different registered map.
    laplacian_normalize: bool = True     # D̂^{-1/2} degree normalization
    # (False → plain feature SVD, the SV_RF baseline variant)
    eig_x0: Optional[Any] = None         # warm start for the eigensolve: a
    # prior EigResult / (N, k) block / ChunkedDense from a related solve
    # (previous R-sweep point, earlier fit on the same rows). Truncated or
    # Gaussian-padded to the block width; a converged warm start exits the
    # solver at iteration 0. See eigensolver.prepare_start_block.

    def __post_init__(self):
        if self.placement not in ("single", "mesh", "partitioned"):
            raise ValueError(f"unknown placement {self.placement!r}")
        if self.residency not in ("device", "host_chunked"):
            raise ValueError(f"unknown residency {self.residency!r}")
        if self.placement == "mesh" and self.mesh is None:
            raise ValueError("placement='mesh' requires a mesh")
        if self.placement == "single" and self.mesh is not None:
            # partitioned MAY carry a mesh: one partition per mesh-axis shard
            raise ValueError("placement='single' must not carry a mesh")
        if (self.residency == "host_chunked" and self.placement != "mesh"
                and self.chunk_size is None):
            raise ValueError("residency='host_chunked' requires chunk_size")


_REPRESENTATIONS = {
    ("single", "device"): rowmatrix.DeviceRows,
    ("single", "host_chunked"): rowmatrix.HostChunkedRows,
    ("mesh", "device"): rowmatrix.MeshRows,
    ("mesh", "host_chunked"): rowmatrix.MeshRows,
    # the divide-and-conquer fit: per-partition single-placement sub-fits
    # (each its own DeviceRows/HostChunkedRows) under one shared feature map
    ("partitioned", "device"): rowmatrix.PartitionedRows,
    ("partitioned", "host_chunked"): rowmatrix.PartitionedRows,
}


def plan_from_config(config: SCRBConfig, mesh=None) -> ExecutionPlan:
    """The config → plan mapping behind the three public entry points."""
    so = config.solver_options
    if config.chunk_size is not None and mesh is None \
            and so.solver not in ("lobpcg", "lobpcg_host", "randomized",
                                  "auto", "compressive"):
        raise ValueError(
            f"chunk_size streaming requires a host-driven solver "
            f"('lobpcg', 'lobpcg_host', 'randomized', 'auto' or "
            f"'compressive'), got {so.solver!r}")
    part = config.partition
    placement = "single"
    if part is not None and part.n_partitions > 1:
        placement = "partitioned"
    elif mesh is not None:
        placement = "mesh"
    return ExecutionPlan(
        placement=placement,
        residency="host_chunked" if config.chunk_size is not None
        else "device",
        chunk_size=config.chunk_size,
        prefetch=config.prefetch,
        impl=config.impl,
        mesh=mesh if placement != "single" else None,
        block_rows=config.block_rows,
    )


def representation(plan: ExecutionPlan):
    """The RowMatrix class a plan selects (exposed for tests/benchmarks)."""
    return _REPRESENTATIONS[(plan.placement, plan.residency)]


def effective_solver(config: SCRBConfig, n: int) -> str:
    """The solver a run actually executes: ``"auto"`` routes to the
    eigendecomposition-free compressive cell once the dense (N, K+buffer)
    iterate would dominate (n ≥ ``compressive_auto_n``); everything else is
    taken literally. Exposed so benchmarks/tests can predict the routing."""
    so, co = config.solver_options, config.compressive_options
    if so.solver == "compressive":
        return "compressive"
    if (so.solver == "auto" and co.auto_n is not None and n >= co.auto_n):
        return "compressive"
    return so.solver


def execute(
    x,
    config: SCRBConfig,
    plan: Optional[ExecutionPlan] = None,
    *,
    final_stage: str = "kmeans",
    keep_embedding: bool = True,
    keep_state: bool = False,
) -> FitResult:
    """Run Algorithm 2 under a plan; every entry point goes through here.

    ``final_stage="normalize"`` stops after stage 4 (the ``spectral_embed``
    entry point) — labels are ``None`` and the k-means stage never runs.
    ``keep_embedding=False`` skips materializing the (N, K) embedding into
    the result (the distributed wrapper's default: the embedding stays
    sharded/chunked and only the labels leave the run).
    ``keep_state=True`` attaches the fitted internals (RowMatrix, fitted
    feature map, raw eigenpairs, k-means result) to ``result.state`` — the
    handle ``repro.core.model.SCRBModel.fit`` builds its out-of-sample
    extension from.

    Observability: the whole run executes under a root ``fit`` span (stage
    spans from ``StageTimer`` nest inside; a partitioned run's per-partition
    sub-fits land on their worker-thread lanes), ``cfg.trace`` records this
    run into a profiler trace in that directory, completed fits
    feed ``repro_fits_total``/``repro_fit_rows_total``, and a host/device
    memory watermark lands in ``diagnostics["memory"]``.

    Every matmul the fit traces runs at ``Precision.HIGHEST``: the TPU's
    default float32 matmul is a single bf16 pass, whose ~1e-3 relative
    error keeps the eigensolver's residuals far above ``SolverOptions.tol``
    (on a v5e chip, poker-sized LOBPCG stopped at its 300-iteration cap
    with residuals of 1e-2). CPU matmuls are float32 either way.
    """
    cfg = config
    if plan is None:
        plan = plan_from_config(cfg)
    if final_stage not in ("normalize", "kmeans"):
        raise ValueError(f"unknown final_stage {final_stage!r}")
    with obs_trace.tracing(cfg.trace), jax.default_matmul_precision("highest"):
        with obs_memory.Watermark() as wm:
            with obs_trace.span("fit", placement=plan.placement,
                                residency=plan.residency) as root:
                res = _execute_impl(
                    x, cfg, plan, final_stage=final_stage,
                    keep_embedding=keep_embedding, keep_state=keep_state)
                solver = res.diagnostics.get(
                    "solver", cfg.solver_options.solver)
                root.set_metadata(solver=solver)
        res.diagnostics.setdefault("memory", wm.as_dict())
    n_rows = (res.labels.shape[0] if res.labels is not None
              else res.embedding.shape[0] if res.embedding is not None
              else 0)
    _FITS_TOTAL.inc(placement=plan.placement, solver=solver)
    if n_rows:
        _FIT_ROWS.inc(n_rows, placement=plan.placement)
    return res


def _execute_impl(
    x,
    cfg: SCRBConfig,
    plan: ExecutionPlan,
    *,
    final_stage: str,
    keep_embedding: bool,
    keep_state: bool,
) -> FitResult:
    if plan.placement == "partitioned":
        # lazy import: partitioned re-enters execute() per partition
        from repro.core import partitioned
        return partitioned.execute_partitioned(
            x, cfg, plan, final_stage=final_stage,
            keep_embedding=keep_embedding, keep_state=keep_state)
    rep_cls = _REPRESENTATIONS[(plan.placement, plan.residency)]
    fm = plan.feature_map
    if fm is None:
        fm = featuremap.from_config(cfg, impl=plan.impl)
    key = jax.random.PRNGKey(cfg.seed)
    timer = StageTimer()
    k = cfg.n_clusters

    with ops.block_rows_overrides(plan.block_rows):
        with timer.stage("rb_features"):
            feats = rep_cls.fit_transform(x, fm, cfg, plan, key)
        with timer.stage("degrees"):
            z = rep_cls.from_features(feats, cfg, plan)
        solver = effective_solver(cfg, z.n)
        eig, comp = None, None
        if solver == "compressive":
            # eigendecomposition-free cell: Chebyshev-filter d = O(log K)
            # random signals through the shared Gram mat-vec, then cluster
            # a random subset — no (N, K+buffer) iterate anywhere
            with timer.stage("svd"):
                comp = compressive.compressive_embed(
                    z, k, fold_key(key, "eig"), cfg,
                    laplacian_normalize=plan.laplacian_normalize)
            with timer.stage("normalize"):
                u_hat = z.map_row_chunks(row_normalize, comp.embedding)
            km, cluster_diag = None, {}
            if final_stage == "kmeans":
                with timer.stage("kmeans"):
                    km, cluster_diag = compressive.subset_cluster(
                        z, u_hat, fold_key(key, "kmeans"), cfg)
        else:
            with timer.stage("svd"):
                eig = z.eigenpairs(k, fold_key(key, "eig"), cfg,
                                   x0=plan.eig_x0)
            with timer.stage("normalize"):
                u_hat = z.map_row_chunks(row_normalize, eig.vectors)
            km, cluster_diag = None, {}
            if final_stage == "kmeans":
                with timer.stage("kmeans"):
                    km, cluster_diag = z.cluster(fold_key(key, "kmeans"),
                                                 u_hat, cfg)

    fitted = feats.fmap
    if comp is not None:
        # Ritz values of Â on the filtered span, padded/truncated to k so
        # downstream consumers see the usual (K,) spectrum estimate
        sig_full = np.sqrt(np.maximum(np.asarray(comp.theta), 0.0))
        sigmas = np.zeros((k,), sig_full.dtype)
        sigmas[:min(k, sig_full.shape[0])] = sig_full[:k]
        # leading-k Ritz residuals only: the trailing d − rank directions of
        # the filtered span are null by design, not unconverged pairs
        resnorms = np.zeros((k,), np.float32)
        resnorms[:min(k, comp.resnorms.shape[0])] = comp.resnorms[:k]
        iterations = comp.iterations
    else:
        sigmas = np.sqrt(np.maximum(np.asarray(eig.theta), 0.0))
        iterations, resnorms = eig.iterations, eig.resnorms
    deg_min, deg_max = z.degree_range()
    diagnostics = {
        "plan": {"placement": plan.placement, "residency": plan.residency,
                 "chunk_size": plan.chunk_size, "prefetch": plan.prefetch,
                 "impl": plan.impl},
        "feature_map": fitted.name,
        "solver": solver,
        "solver_requested": cfg.solver_options.solver,
        "solver_precond": cfg.solver_options.precond,
        "solver_warm_start": plan.eig_x0 is not None,
        "solver_iterations": int(iterations),
        "solver_resnorms": np.asarray(resnorms),
        "degrees_min": deg_min,
        "degrees_max": deg_max,
        "n_features_D": fitted.n_features,
        "nnz": z.n * (fitted.n_grids if fitted.kind == "ell"
                      else fitted.n_features),
    }
    diagnostics.update(z.residency_diagnostics(cfg))
    if comp is not None:
        est = comp.estimate
        diagnostics["compressive"] = {
            "lambda_k": est.lambda_k, "lambda_k1": est.lambda_k1,
            "cutoff": est.cutoff, "filter_degree": comp.filter_degree,
            "signals": comp.signals, "probes": est.probes,
        }
        if isinstance(z, rowmatrix.HostChunkedRows):
            # the widest dense chunk on device is the d-wide filter block,
            # not a LOBPCG (chunk, k+buffer) iterate
            diagnostics["embedding_device_bytes_peak"] = (
                z.store.max_chunk_rows * 4 * comp.signals)
    diagnostics.update(cluster_diag)
    if km is not None:
        diagnostics["kmeans_inertia"] = float(km.inertia)

    embedding = None
    if keep_embedding:
        embedding = (u_hat.to_array()
                     if isinstance(u_hat, streaming.ChunkedDense)
                     else np.asarray(u_hat))
    state = None
    if keep_state:
        state = {"z": z, "features": feats, "eig": eig, "u_hat": u_hat,
                 "km": km, "plan": plan,
                 "oos_proj": None if comp is None else comp.proj}
    return FitResult(
        labels=None if km is None else np.asarray(km.labels),
        embedding=embedding,
        singular_values=sigmas,
        timer=timer,
        diagnostics=diagnostics,
        state=state,
    )
