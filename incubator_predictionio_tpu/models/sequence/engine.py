"""Session-based sequence recommendation engine (next-item prediction).

Capability parity target: the reference's closest artifact is the
MarkovChain top-N transition model (e2/.../engine/MarkovChain.scala:33,71)
used by experimental session templates. This engine is its TPU-native
upgrade: a SASRec-style causal transformer (ops/transformer.py) trained on
each user's time-ordered item-event sequence from the event store.

- ``Query(user, num, recentItems?)`` / ``PredictedResult(itemScores)`` —
  the standard template wire shape. ``recentItems`` lets stateless clients
  pass the session history explicitly; otherwise the algorithm reads the
  user's recent events from the event store at serve time (the ecommerce
  template's recentFeatures pattern).
- Long sessions are first-class: ``seq_parallel`` ∈ {none, ring, ulysses}
  selects sequence/context parallelism over the mesh's ``sp`` axis
  (parallel/ring.py) for training on long histories.
- The block is a description (ops/transformer.py ``BlockSpec``): the
  SASRec block this engine trains, or — restored by a loader — a
  published language-model block (sliding-window and full grouped-query
  layers over routed experts).
- Serving keeps every known user's window RESIDENT on the device
  (``SeqRecModel.windows``): a plain ``{"user", "num"}`` query costs no
  event-store read, the scheduler fuses such queries
  (``batch_serve_json``) and one dispatch is one launch — the batch's
  rows as the program's own argument, the windows gathered on the device
  — and one fetch. One rule the code can observe decides: a query with
  ``recentItems``, an unknown user, or a store whose write cursor moved
  since the model was prepared takes the object path and reads the live
  history, as before.
"""

from __future__ import annotations

import dataclasses
import functools
import logging
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from incubator_predictionio_tpu.core import (
    Algorithm,
    AverageMetric,
    DataSource,
    Engine,
    EngineFactory,
    FirstServing,
    Params,
    Preparator,
)
from incubator_predictionio_tpu.data.bimap import BiMap
from incubator_predictionio_tpu.data.store import EventStore
from incubator_predictionio_tpu.obs import metrics as obs_metrics
from incubator_predictionio_tpu.obs.trace import stage
from incubator_predictionio_tpu.parallel.context import RuntimeContext
from incubator_predictionio_tpu.utils.item_scores import render_item_scores

logger = logging.getLogger(__name__)

# booked once a dispatch from the fetched array, nothing a request
_TOKENS = obs_metrics.REGISTRY.counter(
    "pio_seq_tokens_total",
    "window positions the sequence engine's serving dispatches ran, "
    "the padding rows of a rung included")
_PAD_TOKENS = obs_metrics.REGISTRY.counter(
    "pio_seq_pad_tokens_total",
    "of pio_seq_tokens_total, the positions that did no work for an "
    "answer: PAD inside a window, and every position of a padding row")
_EXPERT_TOKENS = obs_metrics.REGISTRY.counter(
    "pio_seq_moe_expert_tokens_total",
    "tokens the routed feed-forward sent to each expert, summed over "
    "layers (padding rows included: the device routed them)",
    labels=("expert",))
_ROWS_MULTIPLIED = obs_metrics.REGISTRY.counter(
    "pio_seq_moe_rows_multiplied_total",
    "rows the experts' products multiplied, summed over layers: on a TPU "
    "every block of the kernel a group of routed rows touches, elsewhere "
    "the routed rows; pio_seq_moe_expert_tokens_total summed over it is "
    "the share of the products that were kept")


@dataclasses.dataclass(frozen=True)
class Query:
    __camel_case__ = True

    user: str
    num: int
    #: explicit session history (most recent last); overrides the event store
    recent_items: Optional[Tuple[str, ...]] = None


@dataclasses.dataclass(frozen=True)
class ItemScore:
    __camel_case__ = True

    item: str
    score: float


@dataclasses.dataclass(frozen=True)
class PredictedResult:
    __camel_case__ = True

    item_scores: Tuple[ItemScore, ...]


@dataclasses.dataclass(frozen=True)
class DataSourceParams(Params):
    __camel_case__ = True

    app_name: str
    channel_name: Optional[str] = None
    event_names: Tuple[str, ...] = ("view", "buy")
    #: sessions shorter than this are dropped (nothing to predict from)
    min_session_length: int = 2


@dataclasses.dataclass
class TrainingData:
    #: per-user time-ordered item id sequences
    sessions: List[List[str]]
    #: the user of each session, aligned (empty: the sessions are nameless)
    users: List[str] = dataclasses.field(default_factory=list)

    def sanity_check(self) -> None:
        if not self.sessions:
            raise ValueError("TrainingData has no usable sessions")


class SequenceDataSource(DataSource):
    def __init__(self, params: DataSourceParams):
        super().__init__(params)

    def read_training(self, ctx: RuntimeContext) -> TrainingData:
        events = EventStore.find(
            app_name=self.params.app_name,
            channel_name=self.params.channel_name,
            entity_type="user",
            target_entity_type="item",
            event_names=list(self.params.event_names),
        )
        per_user: Dict[str, List[Tuple[Any, str]]] = {}
        for e in events:
            if e.target_entity_id:
                per_user.setdefault(e.entity_id, []).append(
                    (e.event_time, e.target_entity_id)
                )
        sessions, users = [], []
        for user, items in per_user.items():
            items.sort(key=lambda t: t[0])
            seq = [i for _, i in items]
            if len(seq) >= self.params.min_session_length:
                sessions.append(seq)
                users.append(user)
        return TrainingData(sessions=sessions, users=users)


@dataclasses.dataclass
class PreparedData:
    #: [N, max_len] int32, PAD(0)-left-padded, items indexed from 1
    sequences: np.ndarray
    item_bimap: BiMap
    #: user → row of ``sequences`` (None: the rows are nameless)
    user_bimap: Optional[BiMap] = None


@dataclasses.dataclass(frozen=True)
class PreparatorParams(Params):
    __camel_case__ = True

    max_len: int = 64


class SequencePreparator(Preparator):
    def __init__(self, params: PreparatorParams = PreparatorParams()):
        super().__init__(params)

    def prepare(self, ctx: RuntimeContext, td: TrainingData) -> PreparedData:
        # index items from 1; 0 is the PAD token
        item_bimap = BiMap.string_int(
            i for s in td.sessions for i in s
        )
        max_len = self.params.max_len
        rows = np.zeros((len(td.sessions), max_len), np.int32)
        for r, seq in enumerate(td.sessions):
            idx = [item_bimap[i] + 1 for i in seq][-max_len:]
            rows[r, max_len - len(idx):] = idx
        user_bimap = (BiMap({u: r for r, u in enumerate(td.users)})
                      if len(td.users) == len(td.sessions) and td.users
                      else None)
        return PreparedData(sequences=rows, item_bimap=item_bimap,
                            user_bimap=user_bimap)


@dataclasses.dataclass(frozen=True)
class SeqRecAlgorithmParams(Params):
    __camel_case__ = True

    app_name: str
    channel_name: Optional[str] = None
    d_model: int = 64
    n_heads: int = 2
    n_layers: int = 2
    epochs: int = 20
    batch_size: int = 128
    learning_rate: float = 1e-3
    seed: Optional[int] = None
    #: sequence-parallel strategy for long sessions: none | ring | ulysses
    seq_parallel: str = "none"
    #: event types read to reconstruct a live session at serve time
    recent_events: Tuple[str, ...] = ("view", "buy")
    #: the block's description (ops/transformer.py ``BlockSpec`` as
    #: JSON) of a model a loader restores; None is the SASRec block of
    #: ``d_model`` / ``n_heads`` / ``n_layers``, the one ``train`` fits
    block: Optional[Dict[str, Any]] = None


#: a model no ``prepare_model`` has seen serves nothing from residence
_UNPREPARED = "unprepared"


@dataclasses.dataclass
class SeqRecModel:
    #: ops.transformer.TransformerWeights, or BlockWeights under ``spec``
    weights: Any
    item_bimap: BiMap
    n_heads: int
    max_len: int
    final_loss: float
    #: ops.transformer.BlockSpec; None: the SASRec block of ``weights``
    spec: Any = None
    #: [n_users, window] int32 on the device: every known user's window
    windows: Any = None
    user_bimap: Optional[BiMap] = None


class SeqRecAlgorithm(Algorithm):
    params_class = SeqRecAlgorithmParams
    query_class_ = Query

    def __init__(self, params: SeqRecAlgorithmParams):
        super().__init__(params)
        # bounded TTL micro-cache in front of the per-query session-history
        # read (`serve-blocking-io`): versioned by the store's write
        # cursor, so new events invalidate immediately and repeat queries
        # between writes stop paying a storage scan
        from incubator_predictionio_tpu.speed.cache import (
            TTLCache,
            serve_cache_ttl,
        )

        self._history_cache = TTLCache(maxsize=4096,
                                       ttl_s=serve_cache_ttl())

    def _store_version(self):
        from incubator_predictionio_tpu.speed.cache import store_version

        return store_version(self.params.app_name,
                             self.params.channel_name)

    def _attn_fn(self, ctx: RuntimeContext, train_len: int):
        """Sequence-parallel attention backend per params.seq_parallel.

        Builds a dedicated 1-axis ``sp`` mesh whose degree is the largest
        device count that divides the training sequence length
        (``max_len - 1`` after the next-item shift) — and, for ulysses, the
        head count. Degenerates to single-device attention (None) when no
        useful degree exists.
        """
        mode = self.params.seq_parallel
        if mode == "none":
            return None
        if mode not in ("ring", "ulysses"):
            raise ValueError(f"unknown seq_parallel mode: {mode!r}")
        import jax
        from jax.sharding import Mesh

        from incubator_predictionio_tpu.parallel.mesh import SEQ_AXIS
        from incubator_predictionio_tpu.parallel.ring import (
            ring_attention, ulysses_attention,
        )

        sp = len(jax.devices())
        while sp > 1 and (
            train_len % sp != 0
            or (mode == "ulysses" and self.params.n_heads % sp != 0)
        ):
            sp -= 1
        if sp <= 1:
            logger.warning(
                "sequence: seq_parallel=%s requested but no device count "
                "≤ %d divides train length %d%s; training single-device",
                mode, len(jax.devices()), train_len,
                f" and {self.params.n_heads} heads" if mode == "ulysses"
                else "",
            )
            return None
        mesh = Mesh(np.array(jax.devices()[:sp]), (SEQ_AXIS,))
        fn = ring_attention if mode == "ring" else ulysses_attention
        return functools.partial(fn, mesh=mesh)

    def train(self, ctx: RuntimeContext, pd: PreparedData) -> SeqRecModel:
        from incubator_predictionio_tpu.ops.transformer import sasrec_fit

        if self.params.block is not None:
            raise NotImplementedError(
                "sequence: training fits the SASRec block; a described "
                "block is restored by its loader, not trained here")
        seed = self.params.seed if self.params.seed is not None else ctx.seed
        weights, losses = sasrec_fit(
            pd.sequences,
            n_items=len(pd.item_bimap),  # token ids 1..n; fit adds the PAD slot
            d_model=self.params.d_model,
            n_heads=self.params.n_heads,
            n_layers=self.params.n_layers,
            epochs=self.params.epochs,
            batch_size=self.params.batch_size,
            learning_rate=self.params.learning_rate,
            seed=seed,
            attn_fn=self._attn_fn(ctx, train_len=pd.sequences.shape[1] - 1),
        )
        logger.info("sequence: trained %d sessions, loss %.4f → %.4f",
                    len(pd.sequences), losses[0], losses[-1])
        return SeqRecModel(
            weights=weights,
            item_bimap=pd.item_bimap,
            n_heads=self.params.n_heads,
            max_len=pd.sequences.shape[1],
            final_loss=float(losses[-1]),
            # scored at the width training ran at (see _serve_len): the
            # last max_len − 1 events of every user
            windows=(pd.sequences[:, 1:] if pd.user_bimap is not None
                     else None),
            user_bimap=pd.user_bimap,
        )

    def prepare_model(self, ctx, model: SeqRecModel) -> SeqRecModel:
        import jax

        model.weights = jax.tree_util.tree_map(
            lambda x: jax.device_put(jax.numpy.asarray(x)), model.weights
        )
        if model.windows is not None:
            model.windows = jax.device_put(
                jax.numpy.asarray(model.windows, jax.numpy.int32))
        # the windows stand for the store as it was now: a later write
        # sends every query back to the live history
        model._resident_version = self._store_version()
        return model

    @staticmethod
    def _block(model: SeqRecModel):
        """(description, weights) the serving programs take; a trained
        SASRec model's are derived once."""
        if model.spec is not None:
            return model.spec, model.weights
        block = getattr(model, "_sasrec_block", None)
        if block is None or block[2] is not model.weights:
            from incubator_predictionio_tpu.ops.transformer import (
                sasrec_block,
            )

            block = (*sasrec_block(model.weights, model.n_heads),
                     model.weights)
            model._sasrec_block = block
        return block[0], block[1]

    @staticmethod
    def _serve_len(model: SeqRecModel) -> int:
        """The width a window is scored at. The SASRec block scores at
        ``max_len − 1`` — the width training ran at (sasrec_fit shifts
        ``batch[:, :-1]`` → ``batch[:, 1:]``), so every positional-
        embedding row used received gradients; a described block at its
        own length."""
        return model.spec.max_len if model.spec is not None \
            else model.max_len - 1

    def _resident(self, model: SeqRecModel) -> bool:
        """Whether plain queries may be answered from the resident
        windows: there are some, and the store has not been written since
        ``prepare_model`` took them to stand for it."""
        return (model.windows is not None and model.user_bimap is not None
                and getattr(model, "_resident_version", _UNPREPARED)
                == self._store_version())

    def _history(self, query: Query, model: SeqRecModel) -> List[int]:
        """Session history as model token ids, oldest first. The
        event-store read goes through the TTL micro-cache (new writes
        invalidate via the store cursor)."""
        if query.recent_items is not None:
            names: Sequence[str] = query.recent_items
        else:
            names = self._history_cache.get_or_load(
                query.user,
                lambda: self._load_history_names(query.user, model),
                version=self._store_version())
        return [model.item_bimap[n] + 1 for n in names
                if n in model.item_bimap]

    def _load_history_names(self, user: str,
                            model: SeqRecModel) -> List[str]:
        try:
            events = list(EventStore.find_by_entity(
                app_name=self.params.app_name,
                channel_name=self.params.channel_name,
                entity_type="user",
                entity_id=user,
                event_names=list(self.params.recent_events),
                limit=model.max_len,
                latest=True,
            ))
        except Exception:
            logger.warning(
                "sequence: recent-event lookup failed for user %r",
                user, exc_info=True,
            )
            events = []
        return [e.target_entity_id for e in reversed(events)
                if e.target_entity_id]

    def warmup(self, model: SeqRecModel, max_batch: int = 1) -> None:
        """Pre-compile the serving forward (core/base.py Algorithm.warmup):
        the transformer's first query otherwise pays the full XLA compile
        — the most expensive cold path of any template. The explicit-
        history program once (a one-item history, so no event-store read
        happens), then the resident-window program at every width
        ``_score_rows`` can launch (:meth:`_widths`)."""
        first = next(iter(model.item_bimap), None)
        if first is not None:
            self.predict(model, Query(user="__warmup__", num=10,
                                      recent_items=(str(first),)))
        user = next(iter(model.user_bimap), None) \
            if self._resident(model) else None
        if user is None or int(max_batch) <= 0:
            return  # nothing resident, or micro-batching disabled
        q = Query(user=str(user), num=10)
        for size in self._widths(int(max_batch)):
            self.batch_predict(model, [(i, q) for i in range(size)])

    # -- scoring: two programs, one readout ---------------------------------
    #: a batch this wide or narrower runs at its own width. The factor
    #: engines pad a batch to the next power of two because a dispatch
    #: streams the item table once whatever its width; here a padded row
    #: is a whole forward over its window (13.3 ms of a 17–112 ms
    #: dispatch at the measured configuration, PERF.md §6 PR 33), so three
    #: queries fused must not pay for four
    EXACT_WIDTHS = 8

    @classmethod
    def _width(cls, n: int) -> int:
        """The program width a batch of ``n`` resident rows runs at."""
        from incubator_predictionio_tpu.ops.topk import next_pow2

        return n if n <= cls.EXACT_WIDTHS else next_pow2(n)

    @classmethod
    def _widths(cls, cap: int) -> Tuple[int, ...]:
        """Every width :meth:`_width` gives a batch of up to ``cap`` rows
        (the scheduler's ladder cap): what ``warmup`` compiles."""
        from incubator_predictionio_tpu.ops.topk import ladder_rungs

        rungs = ladder_rungs(cap)
        return tuple(range(1, min(rungs[-1], cls.EXACT_WIDTHS) + 1)) \
            + tuple(r for r in rungs if r > cls.EXACT_WIDTHS)

    @staticmethod
    def _k_pad(model: SeqRecModel, k: int) -> int:
        from incubator_predictionio_tpu.ops.topk import next_pow2

        # pow2 like the factor engines' dispatch, so varying ``num``
        # compiles O(log catalog) variants
        return min(next_pow2(int(k)), len(model.item_bimap))

    @staticmethod
    def _book(n_real: int, n_pad: int, width: int, pads, routed,
              dtype: str) -> None:
        from incubator_predictionio_tpu.ops import moe

        _TOKENS.inc(float(n_pad * width))
        _PAD_TOKENS.inc(float(int(pads[:n_real].sum())
                              + (n_pad - n_real) * width))
        if routed.size:
            for expert, n in enumerate(routed.sum(axis=0).tolist()):
                _EXPERT_TOKENS.labels(expert=expert).inc(float(n))
            # every layer routes the dispatch's rows: one width, one block
            m, n_experts = int(routed[0].sum()), routed.shape[1]
            rows = moe.kernel_rows(m, n_experts) \
                if moe.kernel_serves(m, dtype) else None
            _ROWS_MULTIPLIED.inc(float(moe.rows_multiplied(routed, rows)))

    def _score_rows(self, model: SeqRecModel, rows, k: int):
        """Per-row ``(scores, item tokens)`` for resident users ``rows``:
        ONE launch — the padded int32 rows go up as the jitted program's
        own argument (the factor engines' pattern, ops/topk
        ``batch_score_top_k``) and the windows are gathered on the device
        — and ONE fetch."""
        from incubator_predictionio_tpu.ops.transformer import (
            block_top_k_rows,
            unpack_top_k,
        )

        spec, weights = self._block(model)
        n = len(rows)
        pad = self._width(n)
        rows_np = np.asarray(rows, np.int32).reshape(n)
        if pad > n:
            rows_np = np.concatenate(
                [rows_np, np.full(pad - n, rows_np[0], np.int32)])
        k_pad = self._k_pad(model, k)
        with stage("serve.launch"):
            on_device = block_top_k_rows(spec, weights, model.windows,
                                         rows_np, k_pad)
        with stage("serve.fetch"):
            packed = np.asarray(on_device)     # ONE fetch
        top_s, top_i, pads, routed = unpack_top_k(packed, pad, k_pad, spec)
        self._book(n, pad, model.windows.shape[1], pads, routed,
                   spec.dtype)
        return [(top_s[b], top_i[b]) for b in range(n)]

    def _score_tokens(self, model: SeqRecModel, hist: List[int], k: int):
        """``(scores, item tokens)`` for one explicit history."""
        from incubator_predictionio_tpu.ops.transformer import (
            block_top_k_tokens,
            unpack_top_k,
        )

        spec, weights = self._block(model)
        window = self._serve_len(model)
        tokens = np.zeros((1, window), np.int32)
        hist = hist[-window:]
        tokens[0, window - len(hist):] = hist
        k_pad = self._k_pad(model, k)
        packed = np.asarray(block_top_k_tokens(spec, weights, tokens,
                                               k_pad))
        top_s, top_i, pads, routed = unpack_top_k(packed, 1, k_pad, spec)
        self._book(1, 1, window, pads, routed, spec.dtype)
        return top_s[0], top_i[0]

    @staticmethod
    def _pack(model: SeqRecModel, scores, tokens) -> PredictedResult:
        inv = model.item_bimap.inverse
        return PredictedResult(item_scores=tuple(
            ItemScore(item=inv[int(i) - 1], score=float(s))
            for s, i in zip(scores, tokens)
            if s > -1e37 and int(i) != 0))  # masked filler, PAD

    def _resident_row(self, model: SeqRecModel, query: Query):
        if query.recent_items is not None:
            return None
        return model.user_bimap.get(query.user)

    def predict(self, model: SeqRecModel, query: Query) -> PredictedResult:
        k = min(query.num, len(model.item_bimap))
        if k <= 0:
            return PredictedResult(item_scores=())
        row = self._resident_row(model, query) \
            if self._resident(model) else None
        if row is not None:
            top_s, top_i = self._score_rows(model, [row], k)[0]
        else:
            hist = self._history(query, model)
            if not hist:
                return PredictedResult(item_scores=())
            top_s, top_i = self._score_tokens(model, hist, k)
        return self._pack(model, top_s[:query.num], top_i[:query.num])

    def batch_predict(
        self, model: SeqRecModel, queries: Sequence[Tuple[int, Query]]
    ) -> List[Tuple[int, PredictedResult]]:
        """The resident users of the batch in one dispatch (the program
        ``batch_serve_json`` runs); every other query through
        ``predict``."""
        out: List[Tuple[int, PredictedResult]] = []
        plain = []
        if self._resident(model):
            plain = [(qx, q, row) for qx, q in queries if q.num > 0
                     for row in [self._resident_row(model, q)]
                     if row is not None]
        if plain:
            k = min(max(q.num for _qx, q, _r in plain),
                    len(model.item_bimap))
            tops = self._score_rows(model, [r for _qx, _q, r in plain], k)
            for (qx, q, _row), (top_s, top_i) in zip(plain, tops):
                out.append((qx, self._pack(model, top_s[:q.num],
                                           top_i[:q.num])))
        handled = {qx for qx, _ in out}
        for qx, q in queries:
            if qx not in handled:
                out.append((qx, self.predict(model, q)))
        return out

    def batch_serve_json(self, model: SeqRecModel, docs):
        """Columnar serving fast path (core/base.py batch_serve_json): the
        plain ``{"user": ..., "num": ...}`` wire shape for RESIDENT users
        renders straight from the dispatch's packed array to response
        bytes, byte for byte what the object path gives; anything else
        (``recentItems``, an unknown user, a store written since the
        model was prepared) stays None and takes the object path."""
        if not self._resident(model):
            return None
        get_row = model.user_bimap.get
        plain = []  # (slot, row, num)
        with stage("serve.lookup"):
            for slot, d in enumerate(docs):
                if (type(d) is dict and len(d) == 2 and "user" in d
                        and "num" in d):
                    u, num = d["user"], d["num"]
                    if (isinstance(u, str) and isinstance(num, int)
                            and not isinstance(num, bool) and num > 0):
                        row = get_row(u)
                        if row is not None:
                            plain.append((slot, row, num))
        out: list = [None] * len(docs)
        if not plain:
            return out
        k = min(max(num for _s, _r, num in plain), len(model.item_bimap))
        tops = self._score_rows(model, [r for _s, r, _n in plain], k)
        inv = model.item_bimap.inverse
        with stage("serve.render"):
            # a masked slot (PAD, the window's own items) scores NEG_INF
            # and is left out; token t is item t − 1
            for (slot, _row, num), (top_s, top_i) in zip(plain, tops):
                out[slot] = render_item_scores(
                    top_s, top_i, num, lambda t: inv[t - 1])
        return out


class HitAtK(AverageMetric):
    """Next-item hit rate over held-out (query, actual) pairs."""

    def calculate_one(self, query: Query, predicted: PredictedResult,
                      actual: Any) -> float:
        wanted = actual if isinstance(actual, str) else actual.item
        return 1.0 if any(s.item == wanted for s in predicted.item_scores) \
            else 0.0


class SequenceEngine(EngineFactory):
    def apply(self) -> Engine:
        return Engine(
            SequenceDataSource,
            SequencePreparator,
            {"sasrec": SeqRecAlgorithm},
            FirstServing,
        )
