"""SCRBModel — the fitted-model API over the plan-based executor.

The RB feature matrix Z *implicitly* carries the similarity graph, so
everything needed to embed and label a **new** point is already computed at
fit time: the feature-map parameters, the degree dual (bin occupancies /
Φᵀ1), the right singular subspace, and the k-means centroids. ``fit`` runs
Algorithm 2 once through ``executor.execute`` (any plan: single/mesh ×
device/host_chunked) and additionally materializes

  V = Ẑᵀ U Σ⁻¹                  (D, K) right singular subspace —
                                 one extra chunked O(NR) pass,
  dual = Zᵀ 1                    (D,) out-of-sample degree oracle,

after which ``transform``/``predict`` are the Nyström-style out-of-sample
extension (standard for sampling-based SC — Pourkamali-Anaraki, "Scalable
Spectral Clustering with Nyström Approximation"), fully jit-able and O(D·K)
in state — **no O(N_train) array is stored or allocated**:

  φ = map.transform(x_new)             row-local features
  deg = φ · dual                       degree vs the *fitted* graph
  ẑ = D̂^{-1/2} φ                      fitted-degree normalization
  u = ẑ · V Σ⁻¹                        project into the singular subspace
  û = u / ‖u‖                          row-normalize (Alg. 2 step 4)
  label = argmin_k ‖û − c_k‖           nearest fitted centroid

``save``/``load`` round-trip the model through one ``.npz`` (arrays) with a
JSON metadata header (config + feature-map statics) — a fitted model is a
deployable artifact; ``load().predict`` is bit-identical to the saved
model's.

``pipeline.sc_rb``, ``pipeline.spectral_embed`` and
``distributed.sc_rb_distributed`` are thin wrappers over ``SCRBModel.fit``.
"""
from __future__ import annotations

import dataclasses
import functools
import json
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import executor as _executor, featuremap, streaming
from repro.core.kmeans import row_normalize
from repro.kernels import ops

#: Serialization format. Major bumps break ``load`` (reject with a clear
#: error); minor bumps are additive and readable by any same-major build.
FORMAT_VERSION = "1.1"

#: Geometric batch-bucket grid shared by ``transform``/``predict`` and the
#: serving engine (``serve.cluster_engine``). Padding every batch up to a
#: bucket means each (model, bucket, mode) pair compiles exactly once; all
#: out-of-sample ops are row-local, so zero-padded rows never contaminate
#: real rows and slicing the output back is bit-identical (regression-tested).
BUCKET_GRID = (64, 256, 1024, 4096)


def _ceil_to(n: int, m: int) -> int:
    return -(-n // m) * m


def round_to_bucket(n: int, grid=BUCKET_GRID, *, multiple_of: int = 1) -> int:
    """Smallest bucket in ``grid`` that fits ``n`` rows; above the top
    bucket, the next multiple of the top bucket. ``multiple_of`` lifts the
    result so mesh paths can shard the padded batch evenly."""
    if n < 1:
        raise ValueError(f"need at least one row, got {n}")
    top = grid[-1]
    size = next((b for b in grid if n <= b), _ceil_to(n, top))
    return _ceil_to(size, multiple_of) if multiple_of > 1 else size


def _oos_embed_impl(fm, dual, proj, x, *, laplacian: bool) -> jax.Array:
    """Out-of-sample embedding of a feature-map pytree ``fm``: transform →
    fitted-degree normalize → project onto V Σ⁻¹ → row-normalize. Plain
    function so callers (the serving engine) can AOT-compile it per batch
    bucket with their own donation policy."""
    feats = fm.transform(jnp.asarray(x, jnp.float32))
    return row_normalize(fm.oos_project(feats, dual, proj,
                                        laplacian=laplacian))


def _oos_predict_impl(fm, dual, proj, cents, x, *, laplacian: bool,
                      impl: str) -> jax.Array:
    u = _oos_embed_impl(fm, dual, proj, x, laplacian=laplacian)
    labels, _ = ops.kmeans_assign(u, cents, impl=impl)
    return labels


_oos_embed = jax.jit(_oos_embed_impl, static_argnames=("laplacian",))
_oos_predict = jax.jit(_oos_predict_impl, static_argnames=("laplacian",
                                                           "impl"))


def _per_row_shard(fn, mesh, n_state: int, out_rank: int):
    """``fn(*state, x)`` run on each row shard of ``mesh`` with the state
    replicated: GSPMD cannot partition the Mosaic kernels inside the
    out-of-sample ops, and every one of them is row-local."""
    from jax.sharding import PartitionSpec as P

    from repro.core import rowmatrix
    spec = rowmatrix.MeshRows._row_spec(mesh)
    return jax.jit(jax.shard_map(
        fn, mesh=mesh, in_specs=(P(),) * n_state + (spec,),
        out_specs=P(*spec[:out_rank]), check_vma=False))


@dataclasses.dataclass
class SCRBModel:
    """A fitted SC_RB (or registry-baseline) model with out-of-sample
    ``transform``/``predict`` — state is O(D·K), independent of N_train."""

    config: _executor.SCRBConfig
    feature_map: Any                    # fitted featuremap.FeatureMap
    degree_dual: np.ndarray             # (D,) Zᵀ1 / Φᵀ1
    right_vectors: np.ndarray           # (D, K) V = Ẑᵀ U Σ⁻¹
    singular_values: np.ndarray         # (K,)
    centroids: Optional[np.ndarray]     # (n_clusters, K); None if fit
                                        # stopped before the k-means stage
    laplacian_normalize: bool = True
    fit_result: Optional[_executor.FitResult] = None   # train-run result
    # (labels/embedding/timings); not serialized — the artifact stays O(D·K)

    # -- fitting -----------------------------------------------------------
    @classmethod
    def fit(
        cls,
        x,
        config: _executor.SCRBConfig,
        *,
        k: "Optional[int | str]" = None,
        mesh=None,
        plan: Optional[_executor.ExecutionPlan] = None,
        final_stage: str = "kmeans",
        keep_embedding: bool = True,
        x0=None,
    ) -> "SCRBModel":
        """Run Algorithm 2 under any plan and keep the out-of-sample state.

        ``mesh`` / ``plan`` select placement and residency exactly as for
        ``executor.execute``; the train-run ``FitResult`` rides along as
        ``model.fit_result`` (so the one-shot wrappers stay thin).

        ``k`` overrides ``config.n_clusters``; ``k="auto"`` picks K by the
        eigengap criterion over the already-computed rank-``n_clusters``
        spectrum (``config.n_clusters`` acts as K_max) — the chosen K and
        the gap profile land in ``fit_result.diagnostics["k_auto"]``.

        ``x0`` warm-starts the eigensolve from a prior subspace — a previous
        fit's ``eig`` state, an ``EigResult``, or an (N, k) block over the
        same rows (e.g. the neighboring R-sweep point). Plumbed through
        ``ExecutionPlan.eig_x0``; refitting with a converged subspace exits
        the solver at iteration 0.
        """
        auto_k = False
        if isinstance(k, str):
            if k != "auto":
                raise ValueError(f"k must be an int or 'auto', got {k!r}")
            auto_k = True
        elif k is not None:
            config = dataclasses.replace(config, n_clusters=int(k))
        if plan is None:
            plan = _executor.plan_from_config(config, mesh=mesh)
        if x0 is not None:
            plan = dataclasses.replace(plan, eig_x0=x0)
        if auto_k:
            res, config = cls._execute_auto_k(
                x, config, plan, final_stage=final_stage,
                keep_embedding=keep_embedding)
        else:
            res = _executor.execute(x, config, plan, final_stage=final_stage,
                                    keep_embedding=keep_embedding,
                                    keep_state=True)
        st = res.state
        z, eig, km = st["z"], st["eig"], st["km"]
        fitted = st["features"].fmap
        with res.timer.stage("oos_state"):
            oos_proj = st.get("oos_proj")
            part_state = st.get("partitioned")
            if part_state is not None:
                # partitioned fit: the merge already factored the
                # representative matrix into (V, Σ) and summed the degree
                # dual — the O(D·K) serving state is precomputed
                v = np.asarray(part_state["right_vectors"], np.float32)
                sig = np.asarray(part_state["singular_values"], np.float32)
                dual = np.asarray(part_state["degree_dual"], np.float32)
            elif oos_proj is not None:
                # compressive solver: the (D, d) filter projection q IS the
                # serving subspace — the fit embedding was E = Ẑ q, so unit
                # "singular values" make _projection = q exactly and
                # predict/transform on training rows reproduce the fit
                # embedding and labels (no extra pass needed)
                v = np.asarray(oos_proj, np.float32)
                sig = np.ones((v.shape[1],), np.float32)
                dual = np.asarray(z.degree_dual(), np.float32)
            else:
                sig = np.asarray(res.singular_values, np.float32)
                inv_sig = np.where(sig > 1e-6,
                                   1.0 / np.maximum(sig, 1e-30),
                                   0.0).astype(np.float32)
                # V = Ẑᵀ U Σ⁻¹ — one extra chunked O(NR) pass over the
                # fitted representation (ChunkedDense-aware rmatvec on
                # streaming plans, psum'd Ẑᵀ on mesh plans)
                v = np.asarray(z.rmatvec(eig.vectors), np.float32) \
                    * inv_sig[None, :]
                dual = np.asarray(z.degree_dual(), np.float32)
        res.state = None          # drop the O(N) internals; model is O(D·K)
        return cls(
            config=config,
            feature_map=fitted,
            degree_dual=dual,
            right_vectors=v,
            singular_values=sig,
            centroids=None if km is None
            else np.asarray(km.centroids, np.float32),
            laplacian_normalize=plan.laplacian_normalize,
            fit_result=res,
        )

    @staticmethod
    def _execute_auto_k(x, config, plan, *, final_stage, keep_embedding):
        """The ``k="auto"`` path: one executor run stopped after the
        normalize stage with K_max = ``config.n_clusters`` eigenpairs, the
        eigengap pick over the spectrum, then prefix-truncation of the
        already-computed eigenvectors and the usual k-means at the chosen K
        — no second eigensolve. Returns ``(FitResult, k-updated config)``."""
        from repro.utils import fold_key

        if plan.placement == "partitioned":
            raise ValueError(
                "k='auto' needs the global eigenspectrum; it is not "
                "available under placement='partitioned' (pick k first, "
                "then fit partitioned)")
        k_max = config.n_clusters
        if k_max < 3:
            raise ValueError(
                f"k='auto' needs n_clusters (K_max) >= 3, got {k_max}")
        res = _executor.execute(x, config, plan, final_stage="normalize",
                                keep_embedding=False, keep_state=True)
        if res.diagnostics["solver"] == "compressive":
            raise ValueError(
                "k='auto' needs an eigensolver spectrum; solver="
                "'compressive' never computes one (its Ritz values span a "
                "filtered subspace, not the leading eigenpairs)")
        st = res.state
        z, eig = st["z"], st["eig"]
        theta = np.asarray(res.singular_values, np.float64) ** 2
        # eigengap: λ_1..λ_K ≈ 1 for K well-separated clusters, then a drop
        # — choose the k ∈ [2, K_max-1] maximizing λ_k − λ_{k+1}
        gaps = theta[:-1] - theta[1:]                    # gaps[i] = k=i+1
        chosen = int(np.argmax(gaps[1:k_max - 1])) + 2
        vecs = eig.vectors
        if isinstance(vecs, streaming.ChunkedDense):
            vecs_k = streaming.ChunkedDense(
                tuple(c[:, :chosen] for c in vecs.chunks))
        else:
            vecs_k = vecs[:, :chosen]
        eig_k = eig._replace(theta=np.asarray(eig.theta)[:chosen],
                             vectors=vecs_k,
                             resnorms=np.asarray(eig.resnorms)[:chosen])
        cfg_k = dataclasses.replace(config, n_clusters=chosen)
        key = jax.random.PRNGKey(config.seed)
        with res.timer.stage("normalize"):
            u_hat = z.map_row_chunks(row_normalize, vecs_k)
        km, cluster_diag = None, {}
        if final_stage == "kmeans":
            with res.timer.stage("kmeans"):
                km, cluster_diag = z.cluster(fold_key(key, "kmeans"),
                                             u_hat, cfg_k)
        res.labels = None if km is None else np.asarray(km.labels)
        if keep_embedding:
            res.embedding = (u_hat.to_array()
                             if isinstance(u_hat, streaming.ChunkedDense)
                             else np.asarray(u_hat))
        res.singular_values = np.asarray(res.singular_values)[:chosen]
        st["eig"], st["km"], st["u_hat"] = eig_k, km, u_hat
        res.diagnostics.update(cluster_diag)
        if km is not None:
            res.diagnostics["kmeans_inertia"] = float(km.inertia)
        res.diagnostics["k_auto"] = {
            "k": chosen, "k_max": k_max,
            "spectrum": [float(t) for t in theta],
            "gaps": [float(g) for g in gaps],
        }
        return res, cfg_k

    # -- inference ---------------------------------------------------------
    @property
    def _projection(self) -> np.ndarray:
        """V Σ⁻¹ (D, K): Ẑ_new · (V Σ⁻¹) ≈ U_new (Eq. 7 out-of-sample)."""
        sig = self.singular_values
        inv_sig = np.where(sig > 1e-6, 1.0 / np.maximum(sig, 1e-30),
                           0.0).astype(np.float32)
        return self.right_vectors * inv_sig[None, :]

    def _serve_setup(self, mesh, *, with_centroids: bool):
        """Device-side serving state + (sharding, n_shards) for one call.

        With a mesh the O(D·K) state is replicated (it is tiny — that is the
        whole point of the artifact) and batches are row-sharded exactly like
        ``MeshRows``; the OOS ops then run once per row shard.
        """
        fm = self.feature_map
        dual = jnp.asarray(self.degree_dual)
        proj = jnp.asarray(self._projection)
        cents = jnp.asarray(self.centroids) if with_centroids else None
        if mesh is None:
            return fm, dual, proj, cents, None, 1
        from jax.sharding import NamedSharding, PartitionSpec

        from repro.core import rowmatrix
        axes = rowmatrix.MeshRows._axes(mesh)
        n_shards = int(np.prod([mesh.shape[a] for a in axes]))
        rep = NamedSharding(mesh, PartitionSpec())
        fm, dual, proj = jax.device_put((fm, dual, proj), rep)
        if cents is not None:
            cents = jax.device_put(cents, rep)
        return fm, dual, proj, cents, rowmatrix.MeshRows._row_sharding(mesh), \
            n_shards

    @staticmethod
    def _serve_batches(x, batch_size, sharding, n_shards):
        """Yield (device_batch, n_real_rows) pairs, zero-padding each chunk
        up to the bucket grid (``batch_size`` set) and/or to a multiple of
        ``n_shards`` (mesh). ``batch_size=None`` on a single device keeps the
        legacy unpadded single-compile path byte-for-byte."""
        eff = None if batch_size is None else \
            round_to_bucket(batch_size, multiple_of=n_shards)
        for c in streaming.as_row_chunks(x, eff):
            c = np.asarray(c, np.float32)
            rows = c.shape[0]
            if batch_size is not None and rows > 0:
                target = round_to_bucket(rows, multiple_of=n_shards)
            elif n_shards > 1:
                target = _ceil_to(max(rows, 1), n_shards)
            else:
                target = rows
            if target != rows:
                pad = np.zeros((target, c.shape[1]), np.float32)
                pad[:rows] = c
                c = pad
            xb = jnp.asarray(c) if sharding is None \
                else jax.device_put(c, sharding)
            yield xb, rows

    def transform(self, x, *, batch_size: Optional[int] = None,
                  mesh=None) -> np.ndarray:
        """Out-of-sample spectral embedding (n_new, K), streamed in batches
        of ``batch_size`` rows (peak device residency O(batch·(R+K))).

        ``batch_size`` is rounded up to the serving bucket grid
        (``BUCKET_GRID``) and every chunk — ragged tail included — is
        zero-padded to its bucket, so repeated ad-hoc calls reuse at most
        ``len(BUCKET_GRID)`` compiled shapes instead of one per ragged
        batch. Padded rows are sliced off; outputs are bit-identical to the
        unpadded path. ``mesh`` replicates the state and row-shards batches.
        """
        fm, dual, proj, _, sharding, n_shards = \
            self._serve_setup(mesh, with_centroids=False)
        lap = self.laplacian_normalize
        embed = functools.partial(_oos_embed, laplacian=lap) if mesh is None \
            else _per_row_shard(functools.partial(_oos_embed_impl,
                                                  laplacian=lap), mesh, 3, 2)
        outs = [
            np.asarray(embed(fm, dual, proj, xb))[:rows]
            for xb, rows in self._serve_batches(x, batch_size, sharding,
                                                n_shards)
        ]
        return np.concatenate(outs, axis=0)

    def predict(self, x, *, batch_size: Optional[int] = None,
                mesh=None) -> np.ndarray:
        """Nearest-fitted-centroid labels for new points, (n_new,) int32.

        Batching/padding/mesh semantics are identical to ``transform``.
        """
        if self.centroids is None:
            raise ValueError(
                "model has no centroids (fit stopped before the k-means "
                "stage); use transform() or refit with final_stage='kmeans'")
        fm, dual, proj, cents, sharding, n_shards = \
            self._serve_setup(mesh, with_centroids=True)
        static = dict(laplacian=self.laplacian_normalize,
                      impl=self.config.impl)
        label = functools.partial(_oos_predict, **static) if mesh is None \
            else _per_row_shard(functools.partial(_oos_predict_impl,
                                                  **static), mesh, 4, 1)
        outs = [
            np.asarray(label(fm, dual, proj, cents, xb))[:rows]
            for xb, rows in self._serve_batches(x, batch_size, sharding,
                                                n_shards)
        ]
        return np.concatenate(outs, axis=0)

    @property
    def data_dim(self) -> Optional[int]:
        """Input dimensionality d expected by ``transform``/``predict``,
        recovered from the fitted map's state (None for unknown map types).
        The serving engine uses this to pre-allocate staging buffers and
        warm the jit cache before the first request arrives."""
        field, axis = {"rb": ("widths", -1), "rff": ("w", 0),
                       "nystrom": ("landmarks", -1),
                       "lsc": ("anchors", -1)}.get(
            getattr(self.feature_map, "name", None), (None, None))
        state = self.feature_map.state_dict()
        if field is None or field not in state:
            return None
        return int(np.asarray(state[field]).shape[axis])

    @property
    def nbytes(self) -> int:
        """Serialized state size — independent of N_train by construction."""
        arrays = [self.degree_dual, self.right_vectors, self.singular_values]
        if self.centroids is not None:
            arrays.append(self.centroids)
        arrays.extend(self.feature_map.state_dict().values())
        return int(sum(np.asarray(a).nbytes for a in arrays))

    # -- serialization -----------------------------------------------------
    def save(self, path: str) -> None:
        """One-file artifact: npz arrays + JSON metadata header."""
        cfg = self.config.to_dict()
        meta = {
            "format_version": FORMAT_VERSION,
            "config": cfg,
            "laplacian_normalize": bool(self.laplacian_normalize),
            "has_centroids": self.centroids is not None,
            "feature_map": self.feature_map.meta_dict(),
            "data_dim": self.data_dim,          # 1.1: serving convenience
        }
        arrays = {
            "degree_dual": self.degree_dual,
            "right_vectors": self.right_vectors,
            "singular_values": self.singular_values,
        }
        if self.centroids is not None:
            arrays["centroids"] = self.centroids
        for k, v in self.feature_map.state_dict().items():
            arrays[f"fm_{k}"] = v
        meta_bytes = np.frombuffer(json.dumps(meta).encode("utf-8"),
                                   dtype=np.uint8)
        with open(path, "wb") as f:
            np.savez(f, _meta=meta_bytes, **arrays)

    @classmethod
    def load(cls, path: str) -> "SCRBModel":
        with np.load(path, allow_pickle=False) as npz:
            meta = json.loads(bytes(npz["_meta"].tobytes()).decode("utf-8"))
            ver = meta.get("format_version")
            # v1.0 artifacts stamped the bare int 1; ≥1.1 stamps "major.minor"
            try:
                major = ver if isinstance(ver, int) \
                    else int(str(ver).split(".", 1)[0])
            except ValueError:
                major = None
            if major != int(FORMAT_VERSION.split(".", 1)[0]):
                raise ValueError(
                    f"unsupported model artifact format_version={ver!r}: "
                    f"this build reads major "
                    f"{FORMAT_VERSION.split('.', 1)[0]} "
                    f"(writes {FORMAT_VERSION}); re-save the model with a "
                    "matching repro version")
            fm_arrays = {k[3:]: npz[k] for k in npz.files
                         if k.startswith("fm_")}
            fitted = featuremap.load_fitted(meta["feature_map"], fm_arrays)
            return cls(
                config=_executor.SCRBConfig.from_dict(meta["config"]),
                feature_map=fitted,
                degree_dual=npz["degree_dual"],
                right_vectors=npz["right_vectors"],
                singular_values=npz["singular_values"],
                centroids=npz["centroids"] if meta["has_centroids"] else None,
                laplacian_normalize=meta["laplacian_normalize"],
            )
