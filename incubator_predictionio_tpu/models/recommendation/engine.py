"""The recommendation engine: event store → BiMap reindex → TPU ALS →
device-resident top-K serving.

Reference parity (examples/scala-parallel-recommendation/custom-query/):

- ``Query(user, num, creationYear?)`` / ``PredictedResult(itemScores)``
  (Engine.scala:23-28).
- DataSource reads ``rate`` events and extracts the ``rating`` property
  (DataSource.scala:60-75); a ``buy`` event counts as rating 4.0 (the
  quickstart variant's convention).
- ALSAlgorithm trains MLlib ALS with (rank, numIterations, lambda, seed)
  (ALSAlgorithm.scala:25-31) — here ops.als on the TPU mesh.
- Model keeps String↔Int BiMaps next to the factors (ALSModel.scala).
- Serving returns the first algorithm's result (Serving.scala).

TPU-first deltas: batch predict is a single jitted (B×K)·(K×I) matmul +
top-k rather than a per-query loop, and the whole catalog is scored on
device at serve time (ops/topk.py).
"""

from __future__ import annotations

import dataclasses
import logging
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from incubator_predictionio_tpu.core import (
    Algorithm,
    DataSource,
    Engine,
    EngineFactory,
    OptionAverageMetric,
    Params,
    Preparator,
    Serving,
)
from incubator_predictionio_tpu.core.self_cleaning import (
    EventWindow,
    SelfCleaningDataSource,
)
from incubator_predictionio_tpu.data.bimap import BiMap
from incubator_predictionio_tpu.data.storage.base import Interactions
from incubator_predictionio_tpu.data.store import EventStore
from incubator_predictionio_tpu.obs.trace import stage
from incubator_predictionio_tpu.parallel.context import RuntimeContext
from incubator_predictionio_tpu.utils.item_scores import render_item_scores

logger = logging.getLogger(__name__)


# ---------------------------------------------------------------------------
# Query / result model (Engine.scala:23-28)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Query:
    __camel_case__ = True  # wire format parity: creationYear, excludeSeen

    user: str
    num: int
    creation_year: Optional[int] = None  # custom-query variant filter
    categories: Optional[Tuple[str, ...]] = None  # filter-by-category variant
    whitelist: Optional[Tuple[str, ...]] = None
    blacklist: Optional[Tuple[str, ...]] = None
    exclude_seen: bool = False  # drop items the user already interacted with


@dataclasses.dataclass(frozen=True)
class ItemScore:
    __camel_case__ = True

    item: str
    score: float
    creation_year: Optional[int] = None


@dataclasses.dataclass(frozen=True)
class PredictedResult:
    __camel_case__ = True  # serves {"itemScores": [...]} like the reference

    item_scores: Tuple[ItemScore, ...]


@dataclasses.dataclass(frozen=True)
class Rating:
    user: str
    item: str
    rating: float


# ---------------------------------------------------------------------------
# DataSource (DataSource.scala:55-90)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class DataSourceParams(Params):
    __camel_case__ = True  # engine.json parity: appName, eventWindow...

    app_name: str
    channel_name: Optional[str] = None
    buy_rating: float = 4.0  # implicit weight of a "buy" event
    eval_k: int = 0          # >0 enables k-fold read_eval
    eval_queries_num: int = 10
    event_window: Optional[str] = None  # SelfCleaningDataSource duration


@dataclasses.dataclass
class TrainingData:
    """Training set in columnar form (``interactions``) or, for hand-built
    fixtures and the legacy path, a ``ratings`` list. The columnar form is
    what the event store's streamed ingest produces (SURVEY §7(b)) — no
    per-event Python objects exist on that path."""

    ratings: Optional[List[Rating]] = None
    item_years: Dict[str, int] = dataclasses.field(default_factory=dict)
    item_categories: Dict[str, Tuple[str, ...]] = dataclasses.field(
        default_factory=dict
    )
    interactions: Optional[Interactions] = None

    def __len__(self) -> int:
        if self.interactions is not None:
            return len(self.interactions)
        return len(self.ratings or [])

    def materialize_ratings(self) -> List[Rating]:
        """Compat view for consumers that want per-triple objects."""
        if self.ratings is None and self.interactions is not None:
            inter = self.interactions
            self.ratings = [
                Rating(inter.user_ids[int(u)], inter.item_ids[int(i)],
                       float(v))
                for u, i, v in zip(inter.user_idx, inter.item_idx,
                                   inter.values)
            ]
        return self.ratings or []

    def sanity_check(self) -> None:
        if not len(self):
            raise ValueError(
                "TrainingData has no ratings — ingest rate/buy events first"
            )


class RecommendationDataSource(DataSource, SelfCleaningDataSource):
    def __init__(self, params: DataSourceParams):
        super().__init__(params)
        self.app_name = params.app_name
        self.channel_name = params.channel_name
        if params.event_window:
            self.event_window = EventWindow(duration=params.event_window)
        else:
            self.event_window = None

    def _read_interactions(self) -> Interactions:
        """Columnar ingest: rate events contribute their ``rating``
        property (missing/non-numeric skipped, DataSource.scala:66-72),
        buy events the fixed implicit weight — streamed straight to COO
        arrays by the store backend, no Event objects."""
        return EventStore.interactions(
            app_name=self.params.app_name,
            channel_name=self.params.channel_name,
            entity_type="user",
            target_entity_type="item",
            event_names=("rate", "buy"),
            value_prop="rating",
            event_values={"buy": self.params.buy_rating},
        )

    def _read_item_meta(self) -> Tuple[Dict[str, int], Dict[str, Tuple[str, ...]]]:
        props = EventStore.aggregate_properties(
            app_name=self.params.app_name,
            channel_name=self.params.channel_name,
            entity_type="item",
        )
        years, cats = {}, {}
        for item_id, pm in props.items():
            year = pm.opt("creationYear", int)
            if year is not None:
                years[item_id] = year
            categories = pm.opt("categories", list)
            if categories:
                cats[item_id] = tuple(str(c) for c in categories)
        return years, cats

    def read_training(self, ctx: RuntimeContext) -> TrainingData:
        if self.event_window is not None:
            self.clean_persisted_events()
        years, cats = self._read_item_meta()
        return TrainingData(
            interactions=self._read_interactions(),
            item_years=years, item_categories=cats,
        )

    def read_eval(self, ctx: RuntimeContext):
        """k-fold split (parity: e2 CrossValidation + the integration-test
        engine's Evaluation). Queries ask top-N for each user in the test
        fold; actuals are that user's held-out items. Folds are columnar
        slices — no per-triple objects."""
        k = self.params.eval_k
        if k <= 0:
            return []
        td = self.read_training(ctx)
        inter = td.interactions
        nnz = len(inter)
        out = []
        for fold in range(k):
            mask = (np.arange(nnz) % k) != fold
            train_inter = Interactions(
                user_idx=inter.user_idx[mask],
                item_idx=inter.item_idx[mask],
                values=inter.values[mask],
                user_ids=inter.user_ids,
                item_ids=inter.item_ids,
            )
            by_user: Dict[str, set] = {}
            for u, i in zip(inter.user_idx[~mask], inter.item_idx[~mask]):
                by_user.setdefault(inter.user_ids[int(u)], set()).add(
                    inter.item_ids[int(i)])
            qa = [
                (Query(user=user, num=self.params.eval_queries_num,
                       exclude_seen=True),
                 ActualResult(items=tuple(sorted(items))))
                for user, items in sorted(by_user.items())
            ]
            out.append(
                (
                    TrainingData(interactions=train_inter,
                                 item_years=td.item_years,
                                 item_categories=td.item_categories),
                    EvalInfo(fold=fold),
                    qa,
                )
            )
        return out


@dataclasses.dataclass(frozen=True)
class EvalInfo:
    fold: int


@dataclasses.dataclass(frozen=True)
class ActualResult:
    items: Tuple[str, ...]


# ---------------------------------------------------------------------------
# Preparator (Preparator.scala — reindex to dense COO for the device)
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class PreparedData:
    users: np.ndarray           # [nnz] int32
    items: np.ndarray           # [nnz] int32
    ratings: np.ndarray         # [nnz] float32
    user_bimap: BiMap
    item_bimap: BiMap
    item_years: Dict[str, int]
    item_categories: Dict[str, Tuple[str, ...]]


class RecommendationPreparator(Preparator):
    """BiMap reindex + COO assembly — the host/device boundary. Duplicate
    (user, item) pairs keep the latest occurrence (event-ordered reads make
    that the newest rating), matching the template's dedup-by-entity
    convention."""

    def prepare(self, ctx: RuntimeContext, td: TrainingData) -> PreparedData:
        if td.interactions is not None:
            return self._prepare_columnar(td)
        user_bimap = BiMap.string_int(r.user for r in td.ratings)
        item_bimap = BiMap.string_int(r.item for r in td.ratings)
        latest: Dict[Tuple[int, int], float] = {}
        for r in td.ratings:
            latest[(user_bimap[r.user], item_bimap[r.item])] = r.rating
        coo = np.array(
            [(u, i, v) for (u, i), v in latest.items()], dtype=np.float64
        ).reshape(-1, 3)
        return PreparedData(
            users=coo[:, 0].astype(np.int32),
            items=coo[:, 1].astype(np.int32),
            ratings=coo[:, 2].astype(np.float32),
            user_bimap=user_bimap,
            item_bimap=item_bimap,
            item_years=td.item_years,
            item_categories=td.item_categories,
        )

    def _prepare_columnar(self, td: TrainingData) -> PreparedData:
        """Vectorized reindex: the scan already interned ids, so the BiMaps
        are direct table views and the latest-wins dedup is one np.unique
        over packed (user, item) keys — O(nnz log nnz) C work, no Python
        loop over triples."""
        inter = td.interactions
        user_bimap = BiMap({u: i for i, u in enumerate(inter.user_ids)})
        item_bimap = BiMap({t: i for i, t in enumerate(inter.item_ids)})
        n_items = max(len(inter.item_ids), 1)
        keys = inter.user_idx.astype(np.int64) * n_items \
            + inter.item_idx.astype(np.int64)
        # keep the LAST occurrence of each (user, item): scan order is
        # event-time order, so the newest rating wins (template convention)
        _, first_in_rev = np.unique(keys[::-1], return_index=True)
        keep = np.sort(len(keys) - 1 - first_in_rev)
        return PreparedData(
            users=inter.user_idx[keep],
            items=inter.item_idx[keep],
            ratings=inter.values[keep],
            user_bimap=user_bimap,
            item_bimap=item_bimap,
            item_years=td.item_years,
            item_categories=td.item_categories,
        )


def _plan_key(tag: str, pd: Any) -> str:
    """Process-resident prep-plan key for one training stream.

    Derived from the stream's FIRST interned ids — stable across tail
    folds (first-seen interning never reorders existing ids). Two
    streams sharing first ids would collide, which is SAFE: the plan
    verifies a full COO prefix digest before any reuse, so a collision
    only costs a fresh rebuild, never a wrong splice."""
    return (f"{tag}:{next(iter(pd.user_bimap), '')}"
            f":{next(iter(pd.item_bimap), '')}")


# ---------------------------------------------------------------------------
# ALS algorithm (ALSAlgorithm.scala:25-31 → ops.als)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ALSAlgorithmParams(Params):
    __camel_case__ = True  # engine.json parity: numIterations, lambda

    rank: int = 10
    num_iterations: int = 20
    lambda_: float = 0.01
    seed: Optional[int] = None
    #: mixed-precision schedule: run this many early sweeps with bf16
    #: gathers/matmuls before the f32 polish sweeps (ops/als.py
    #: ``_mixed_run``) — the TPU fast path; 0 = all-f32 (MLlib parity)
    bf16_sweeps: int = 0


@dataclasses.dataclass
class ALSModel:
    user_factors: Any           # [U, K] device/host array
    item_factors: Any           # [I, K]
    user_bimap: BiMap
    item_bimap: BiMap
    item_years: Dict[str, int]
    item_categories: Dict[str, Tuple[str, ...]]
    #: user index -> sorted np.ndarray of seen item indices (exclude_seen)
    user_seen: Dict[int, Any] = dataclasses.field(default_factory=dict)

    def year_of(self, item_index: int) -> Optional[int]:
        return self.item_years.get(self.item_bimap.inverse[item_index])


class ALSAlgorithm(Algorithm):
    params_class = ALSAlgorithmParams
    query_class_ = Query

    def __init__(self, params: ALSAlgorithmParams = ALSAlgorithmParams()):
        super().__init__(params)

    def train(self, ctx: RuntimeContext, pd: PreparedData) -> ALSModel:
        from incubator_predictionio_tpu.ops import als_train

        n_users, n_items = len(pd.user_bimap), len(pd.item_bimap)
        if n_users == 0 or n_items == 0:
            raise ValueError("No ratings to train on")
        seed = self.params.seed if self.params.seed is not None else ctx.seed
        from incubator_predictionio_tpu.parallel.placement import (
            placement_for_ctx,
        )

        placement = placement_for_ctx(ctx, n_users, n_items)
        if placement is not None:
            # `pio train --model-parallelism N` (or PIO_SHARD_TABLES=1):
            # BOTH factor tables shard on rows over the mesh (the ALX
            # layout, ops/als.py als_train_placed) and each device
            # solves the row buckets it owns under shard_map. The model
            # keeps host-shaped (unplaced) factors — serving re-routes
            # to the sharded top-k merge whenever placed tables are
            # handed to it directly.
            from incubator_predictionio_tpu.ops.als import als_train_placed

            state = als_train_placed(
                pd.users, pd.items, pd.ratings, n_users, n_items,
                placement=placement,
                rank=self.params.rank,
                iterations=self.params.num_iterations,
                l2=self.params.lambda_,
                seed=seed,
                bf16_sweeps=self.params.bf16_sweeps,
            )
            state = placement.unplace_state(state)
        else:
            state, _ = als_train(
                pd.users, pd.items, pd.ratings,
                n_users=n_users, n_items=n_items,
                rank=self.params.rank,
                iterations=self.params.num_iterations,
                l2=self.params.lambda_,
                seed=seed,
                bf16_sweeps=self.params.bf16_sweeps,
            )
        logger.info(
            "ALS trained: %d users × %d items, rank %d",
            n_users, n_items, self.params.rank,
        )
        model = self._assemble_model(pd, state)
        self._refresh_mips_index(model)
        return model

    def train_with_previous(
        self, ctx: RuntimeContext, pd: PreparedData, prev_model: Any
    ) -> ALSModel:
        """Continuation retrain (ops/retrain.py): seed from the previous
        model's factors when its id space is an exact prefix of this
        PreparedData's, and let the convergence early-stop turn the warm
        start into fewer sweeps. Any incompatibility (rank change, index
        space rebuilt) falls back to a fresh train. Under a mesh
        placement the retrain runs the sharded one-dispatch path —
        ``continue_state`` + ``place_state`` re-distribute a previous
        model even when it was trained at a different mesh shape."""
        seed = self.params.seed if self.params.seed is not None else ctx.seed
        prev_state = self._continuation_seed(pd, prev_model)
        if prev_state is None:
            return self.train(ctx, pd)
        from incubator_predictionio_tpu.ops.retrain import als_retrain
        from incubator_predictionio_tpu.parallel.placement import (
            placement_for_ctx,
        )

        n_users, n_items = len(pd.user_bimap), len(pd.item_bimap)
        placement = placement_for_ctx(ctx, n_users, n_items)
        stats: Dict[str, Any] = {}
        state = als_retrain(
            pd.users, pd.items, pd.ratings, n_users, n_items,
            rank=self.params.rank, iterations=self.params.num_iterations,
            l2=self.params.lambda_, seed=seed,
            bf16_sweeps=self.params.bf16_sweeps,
            prev_state=prev_state, plan_key=_plan_key("rec", pd),
            stats=stats, placement=placement)
        if placement is not None:
            state = placement.unplace_state(state)
        logger.info(
            "ALS continuation retrain: %d users × %d items, rank %d, "
            "%s sweeps (mode=%s, delta=%.3e)", n_users, n_items,
            self.params.rank, stats.get("sweeps_used"),
            stats.get("mode"), stats.get("final_delta", float("nan")))
        model = self._assemble_model(pd, state)
        self._refresh_mips_index(model, prev_model=prev_model,
                                 retrain_stats=stats)
        return model

    def _continuation_seed(self, pd: PreparedData, prev_model: Any):
        """Prior factors as an (ungrown) ALSState, or None when they
        cannot seed this training run."""
        from incubator_predictionio_tpu.ops.als import ALSState

        if not isinstance(prev_model, ALSModel):
            return None
        uf = np.asarray(prev_model.user_factors)
        vf = np.asarray(prev_model.item_factors)
        if uf.ndim != 2 or vf.ndim != 2 or uf.shape[1] != vf.shape[1] \
                or uf.shape[1] != self.params.rank:
            return None
        if not (prev_model.user_bimap.is_index_prefix_of(pd.user_bimap)
                and prev_model.item_bimap.is_index_prefix_of(
                    pd.item_bimap)):
            return None
        return ALSState(user_factors=uf, item_factors=vf)

    def _assemble_model(self, pd: PreparedData, state) -> ALSModel:
        user_seen: Dict[int, Any] = {}
        for u, i in zip(pd.users.tolist(), pd.items.tolist()):
            user_seen.setdefault(u, []).append(i)
        user_seen = {
            u: np.asarray(sorted(ids), np.int32)
            for u, ids in user_seen.items()
        }
        return ALSModel(
            user_factors=state.user_factors,
            item_factors=state.item_factors,
            user_bimap=pd.user_bimap,
            item_bimap=pd.item_bimap,
            item_years=pd.item_years,
            item_categories=pd.item_categories,
            user_seen=user_seen,
        )

    def prepare_model(self, ctx: RuntimeContext, model: ALSModel) -> ALSModel:
        """Push restored factors back onto the device (TPU-resident serving
        state; see Algorithm.prepare_model)."""
        import jax

        from incubator_predictionio_tpu.ops.host_serving import (
            warm_host_arrays,
        )

        from incubator_predictionio_tpu.ops import mips

        prev_table = model.item_factors
        np_users = np.asarray(model.user_factors)
        np_items = np.asarray(model.item_factors)
        model = dataclasses.replace(
            model,
            user_factors=jax.device_put(np_users),
            item_factors=jax.device_put(np_items),
        )
        # pre-warm the host mirror (same field order as the serving call
        # sites) — the first query never pays a device→host factor fetch
        warm_host_arrays(
            model, user_factors=np_users, item_factors=np_items)
        # deploy-time MIPS index: a just-trained-in-this-process model
        # already carries one — ADOPT it onto the re-device_put table
        # (same values, new object) instead of paying a second full
        # build; disk-restored models build fresh from the host copy
        # already in hand
        if mips.adopt_index(prev_table, model.item_factors) is None:
            self._refresh_mips_index(model, host_factors=np_items)
        return model

    def _refresh_mips_index(self, model: ALSModel, prev_model=None,
                            retrain_stats=None,
                            host_factors=None) -> None:
        """Keep the two-stage MIPS serving index (ops/mips.py) riding
        the model's item table: O(delta) splice on a plan-reusing
        continuation retrain (only the touched rows re-quantize and
        re-home), full rebuild otherwise. Gated by PIO_SERVE_MIPS +
        the auto-mode catalogue floor; never fatal — exhaustive
        serving is always a correct fallback."""
        from incubator_predictionio_tpu.ops import mips

        n_items = len(model.item_bimap)
        if not mips.build_enabled(n_items):
            return
        try:
            if prev_model is not None and retrain_stats is not None:
                touched = retrain_stats.get("touched_item_rows")
                if touched is not None and mips.update_index(
                        prev_model.item_factors, model.item_factors,
                        n_items, touched) is not None:
                    # the splice only re-quantizes the delta rows while
                    # a retrain nudges EVERY factor row — re-probe so
                    # pio_serve_mips_recall reads the post-splice truth
                    # (the runbook's recall-sag trigger). The probe's
                    # one table fetch + tiny host oracle is O(I·K),
                    # bounded by the retrain that triggered it (each
                    # ALS sweep already streams ≥ nnz·K ≫ I·K).
                    mips.recall_probe(model.item_factors)
                    return
            mips.build_index(model.item_factors, n_items,
                             seed=self.params.seed or 0,
                             host_factors=host_factors,
                             probe_recall=True,
                             engine="recommendation")
        except Exception:  # index is an optimization, never a failure
            logger.exception("MIPS index build failed; serving stays "
                             "exhaustive")

    # -- speed layer -------------------------------------------------------
    def make_speed_overlay(self, model: ALSModel, app_name, channel_name,
                           data_source_params=None):
        """Explicit fold-in over the frozen item factors: same event
        shape as the DataSource's training read (rate events carry
        ``rating``; buy events the fixed implicit weight) and the same
        ALS-WR regularization (λ·nnz) the trainer used — a dirty or
        brand-new user's overlay row IS the row training would solve."""
        if app_name is None:
            return None
        from incubator_predictionio_tpu.speed.overlay import (
            SpeedOverlay,
            SpeedOverlayConfig,
        )

        buy_rating = float(getattr(data_source_params, "buy_rating", 4.0))
        return SpeedOverlay(
            SpeedOverlayConfig(
                app_name=app_name, channel_name=channel_name,
                engine="recommendation",
                entity_type="user", target_entity_type="item",
                event_names=("rate", "buy"), value_prop="rating",
                event_values={"buy": buy_rating},
                key_side="entity",
                l2=self.params.lambda_, reg_nnz=True, implicit=False,
            ),
            other_factors=np.asarray(model.item_factors),
            other_index=model.item_bimap,
            key_index=model.user_bimap,
        )

    # -- serving ----------------------------------------------------------
    def _allowed_mask(
        self, model: ALSModel, query: Query
    ) -> Optional[np.ndarray]:
        """Serve-time filters (custom-query creationYear; filter-by-category;
        white/blacklists) → boolean mask over item indices; seen-item
        exclusion is handled in predict. Always a fixed [n_items] shape so
        the jitted scoring path compiles once."""
        n_items = len(model.item_bimap)
        mask = None

        def ensure() -> np.ndarray:
            nonlocal mask
            if mask is None:
                mask = np.ones(n_items, dtype=bool)
            return mask

        if query.creation_year is not None:
            m = ensure()
            for item, idx in model.item_bimap.items():
                if model.item_years.get(item) is None or \
                        model.item_years[item] < query.creation_year:
                    m[idx] = False
        if query.categories:
            m = ensure()
            wanted = set(query.categories)
            for item, idx in model.item_bimap.items():
                if not wanted.intersection(model.item_categories.get(item, ())):
                    m[idx] = False
        if query.whitelist:
            m = ensure()
            allowed = {
                model.item_bimap[i] for i in query.whitelist
                if i in model.item_bimap
            }
            for idx in range(n_items):
                if idx not in allowed:
                    m[idx] = False
        if query.blacklist:
            m = ensure()
            for item in query.blacklist:
                idx = model.item_bimap.get(item)
                if idx is not None:
                    m[idx] = False
        return mask

    def predict(self, model: ALSModel, query: Query) -> PredictedResult:
        import jax.numpy as jnp

        from incubator_predictionio_tpu.ops.topk import score_user_and_top_k

        user_idx = model.user_bimap.get(query.user)
        # speed layer: a folded-in vector (fresh session / dirty user)
        # takes precedence over the frozen base row — exact model-quality
        # scores seconds after the first events, not after the retrain
        ov = self.speed_overlay
        ov_vec = ov.lookup(query.user) if ov is not None else None
        if user_idx is None and ov_vec is None:
            # unknown user → empty result (ALSAlgorithm.scala predict miss)
            return PredictedResult(item_scores=())
        mask = self._allowed_mask(model, query)
        seen = None
        if query.exclude_seen and user_idx is not None:
            seen = model.user_seen.get(user_idx)
            if seen is not None and not len(seen):
                seen = None
        k = min(query.num, len(model.item_bimap))
        if k <= 0:
            # num=0 must be an empty result on BOTH serving paths
            return PredictedResult(item_scores=())

        from incubator_predictionio_tpu.ops.host_serving import (
            host_arrays, host_top_k,
        )
        host = host_arrays(model, "user_factors", "item_factors")
        if host is not None:
            np_users, np_items = host
            scores = np_items @ (np.asarray(ov_vec, np.float32)
                                 if ov_vec is not None
                                 else np_users[user_idx])
            if seen is not None:
                scores = scores.copy()
                scores[np.asarray(seen)] = -3.4e38
            top_s, top_i = host_top_k(scores, k, allowed_mask=mask)
            packed = np.stack([top_s, top_i.astype(np.float64)])
        elif ov_vec is not None:
            from incubator_predictionio_tpu.ops.topk import (
                pad_exclude,
                score_and_top_k,
            )

            exclude = pad_exclude(seen) if seen is not None else None
            packed = np.asarray(score_and_top_k(
                jnp.asarray(np.asarray(ov_vec, np.float32)),
                model.item_factors, k=k, exclude=exclude,
                allowed_mask=None if mask is None else jnp.asarray(mask),
            ))
        else:
            from incubator_predictionio_tpu.ops.topk import pad_exclude

            # pow2-padded (-1 = no-op slots) so the jitted serve call
            # compiles O(log max-seen) times total
            exclude = pad_exclude(seen) if seen is not None else None
            packed = np.asarray(score_user_and_top_k(  # ONE dispatch+fetch
                model.user_factors,
                model.item_factors,
                int(user_idx),
                k=k,
                exclude=exclude,
                allowed_mask=None if mask is None else jnp.asarray(mask),
            ))
        scores, indices = packed[0], packed[1].astype(np.int64)
        inv = model.item_bimap.inverse
        out = []
        for s, i in zip(scores, indices):
            if s <= -1e37:  # masked-out filler
                continue
            item = inv[int(i)]
            out.append(
                ItemScore(item=item, score=float(s),
                          creation_year=model.item_years.get(item))
            )
        return PredictedResult(item_scores=tuple(out))

    def batch_predict(
        self, model: ALSModel, queries: Sequence[Tuple[int, Query]]
    ) -> List[Tuple[int, PredictedResult]]:
        """Batched serving/evaluation path: one (B×K)·(K×I) matmul + batched
        top-k for all unfiltered queries (the MXU-shaped path; the serving
        micro-batcher routes concurrent /queries.json traffic here —
        CreateServer.scala:523 leaves this as "TODO: Parallelize"). Filtered
        queries fall back to per-query predict."""
        ov = self.speed_overlay
        plain = [
            (qx, q) for qx, q in queries
            if q.creation_year is None and not q.categories
            and not q.whitelist and not q.blacklist and not q.exclude_seen
            and model.user_bimap.get(q.user) is not None
            # overlay-covered users have a FRESHER vector than the base
            # row — they take the per-query path (which consults it)
            and (ov is None or not ov.covers(q.user))
        ]
        out: List[Tuple[int, PredictedResult]] = []
        if plain:
            k = min(max(q.num for _qx, q in plain), len(model.item_bimap))
            rows = [model.user_bimap[q.user] for _qx, q in plain]
            tops = self._score_plain_batch(model, rows, k)
            for (qx, q), (top_s, top_i) in zip(plain, tops):
                out.append((qx, self._pack_scores(
                    model, top_s[: q.num], top_i[: q.num])))
        handled = {qx for qx, _ in out}
        for qx, q in queries:
            if qx not in handled:
                out.append((qx, self.predict(model, q)))
        return out

    @staticmethod
    def _score_plain_batch(model: ALSModel, rows, k: int):
        """Score a batch of user rows and return per-row ``(top_s, top_i)``
        pairs — the ONE copy of the host/device crossover shared by
        ``batch_predict`` and ``batch_serve_json`` (the byte-identity
        contract between those two paths depends on them scoring
        identically)."""
        from incubator_predictionio_tpu.ops.host_serving import (
            host_arrays, host_batch_top_k,
        )
        from incubator_predictionio_tpu.ops.topk import batch_score_top_k

        host = host_arrays(model, "user_factors", "item_factors")
        if host is not None:
            # model small enough for a host copy: one [B,K]@[K,I] numpy
            # matmul is a few ms at any batch size, always under the
            # device dispatch+fetch round trip such a model would pay
            np_users, np_items = host
            with stage("serve.host_score"):
                top_s, top_i = host_batch_top_k(
                    np_users[rows] @ np_items.T, k)
            return [(top_s[b], top_i[b]) for b in range(len(rows))]
        # the launch is one call into the runtime (the batch's rows ride
        # up as the scoring program's own argument) and returns once the
        # program is enqueued; the fetch then waits for the device and
        # copies down, so the device's busy time lies inside serve.fetch
        with stage("serve.launch"):
            on_device = batch_score_top_k(
                model.user_factors, model.item_factors, rows, k)
        with stage("serve.fetch"):
            packed = np.asarray(on_device)     # ONE fetch
        return [(packed[0][b], packed[1][b].astype(np.int64))
                for b in range(len(rows))]

    def warmup(self, model: ALSModel, max_batch: int = 1) -> None:
        """Pre-compile the serving dispatches (core/base.py Algorithm.warmup):
        the singleton path once, then the batched path at each power-of-two
        size up to the micro-batch cap (batch_score_top_k pads B to the
        next power of two, so these are exactly the shapes concurrency can
        produce). Uses a real known user so the device path executes."""
        first = next(iter(model.user_bimap), None)
        if first is None:
            return
        q = Query(user=str(first), num=10)
        self.predict(model, q)
        if int(max_batch) <= 0:
            return  # micro-batching disabled: the batched path never runs
        from incubator_predictionio_tpu.ops.topk import ladder_rungs

        # the SAME ladder the scheduler can dispatch (ops/topk
        # ladder_rungs — one rule, shared, so warmed shapes cannot
        # drift from dispatchable shapes). Rung 1 included: a lone plain
        # query rides the columnar fast path (batch_serve_json →
        # batch_score_top_k at B=1), not predict(). Skipped, its program
        # compiled on the first live query — 0.2–0.3 s on a v5e — and
        # that one wall pushed the latency histogram's p99 over the
        # serve SLO, so the scheduler shed the next burst with 503s
        # (seen on the chip, PERF.md PR 21)
        for size in ladder_rungs(int(max_batch)):
            self.batch_predict(model, [(i, q) for i in range(size)])

    def _pack_scores(self, model: ALSModel, scores, indices) -> PredictedResult:
        inv = model.item_bimap.inverse
        years = model.item_years
        packed = []
        for s, i in zip(scores, indices):
            if s > -1e37:
                iid = inv[int(i)]
                packed.append(ItemScore(item=iid, score=float(s),
                                        creation_year=years.get(iid)))
        return PredictedResult(item_scores=tuple(packed))

    def batch_serve_json(self, model: ALSModel, docs) -> list:
        """Columnar serving fast path (core/base.py batch_serve_json): the
        plain ``{"user": ..., "num": ...}`` wire shape renders straight
        from the batched top-k arrays to response bytes — no Query /
        ItemScore / PredictedResult objects, no jsonable tree walk. Output
        is byte-identical to ``json.dumps(to_jsonable(...))`` of the
        object path (pinned by tests/test_prediction_server.py); anything
        else (extra keys, unknown user, filters) stays None and falls to
        the object path."""
        get_row = model.user_bimap.get
        ov = self.speed_overlay
        plain = []  # (slot, row, num)
        with stage("serve.lookup"):
            for slot, d in enumerate(docs):
                if (type(d) is dict and len(d) == 2 and "user" in d
                        and "num" in d):
                    u, num = d["user"], d["num"]
                    if (isinstance(u, str) and isinstance(num, int)
                            and not isinstance(num, bool) and num > 0):
                        row = get_row(u)
                        # overlay-covered users fall to the object path:
                        # the rendered bytes must reflect the folded-in
                        # vector
                        if row is not None and (ov is None
                                                or not ov.covers(u)):
                            plain.append((slot, row, num))
        out: list = [None] * len(docs)
        if not plain:
            return out
        k = min(max(num for _s, _r, num in plain), len(model.item_bimap))
        rows = [r for _s, r, _n in plain]
        tops = self._score_plain_batch(model, rows, k)
        inv = model.item_bimap.inverse
        years = model.item_years

        def year(iid):
            y = years.get(iid)
            return ', "creationYear": ' + ("null" if y is None else repr(y))

        with stage("serve.render"):
            for (slot, _row, num), (top_s, top_i) in zip(plain, tops):
                out[slot] = render_item_scores(top_s, top_i, num,
                                               inv.__getitem__, year)
        return out


# ---------------------------------------------------------------------------
# Serving + metrics + factory
# ---------------------------------------------------------------------------

class RecommendationServing(Serving):
    """First-algorithm serving (Serving.scala / LFirstServing)."""

    FIRST_PREDICTION_ONLY = True

    def serve(self, query: Query, predictions: Sequence[PredictedResult]) -> PredictedResult:
        return predictions[0]


class PrecisionAtK(OptionAverageMetric):
    """Precision@K against held-out items (parity: the integration-test
    engine's Evaluation metric)."""

    def __init__(self, k: int = 10):
        super().__init__()
        self.k = k

    def header(self) -> str:
        return f"Precision@{self.k}"

    def calculate_qpa(self, q: Query, p: PredictedResult, a: ActualResult):
        if not a.items:
            return None
        predicted = [s.item for s in p.item_scores[: self.k]]
        hits = sum(1 for item in predicted if item in set(a.items))
        # standard precision@k: divide by k, not by the returned count —
        # returning fewer than k items must not inflate the score
        return hits / self.k


class RecommendationEngine(EngineFactory):
    """EngineFactory (Engine.scala:30-40 of the template)."""

    def apply(self) -> Engine:
        return Engine(
            RecommendationDataSource,
            RecommendationPreparator,
            {"als": ALSAlgorithm},
            RecommendationServing,
        )
