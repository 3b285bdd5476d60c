"""Classification, similarproduct and ecommerce templates end-to-end."""

import numpy as np
import pytest

from incubator_predictionio_tpu.core import EngineParams
from incubator_predictionio_tpu.data.datamap import DataMap
from incubator_predictionio_tpu.data.event import Event
from incubator_predictionio_tpu.data.storage import App, Storage
from incubator_predictionio_tpu.parallel.context import RuntimeContext
from incubator_predictionio_tpu.workflow import CoreWorkflow


@pytest.fixture(autouse=True)
def mem_storage():
    Storage.configure({
        "PIO_STORAGE_SOURCES_MEM_TYPE": "memory",
        "PIO_STORAGE_REPOSITORIES_METADATA_NAME": "m",
        "PIO_STORAGE_REPOSITORIES_METADATA_SOURCE": "MEM",
        "PIO_STORAGE_REPOSITORIES_EVENTDATA_NAME": "e",
        "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": "MEM",
        "PIO_STORAGE_REPOSITORIES_MODELDATA_NAME": "d",
        "PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE": "MEM",
    })
    yield
    Storage.reset()


def seed_app(name):
    Storage.get_meta_data_apps().insert(App(0, name))
    return Storage.get_meta_data_apps().get_by_name(name).id


# ---------------------------------------------------------------------------
# classification
# ---------------------------------------------------------------------------

def seed_classification(app_id):
    dao = Storage.get_events()
    rng = np.random.default_rng(0)
    for n in range(80):
        # plan 1.0 users: high attr0; plan 0.0 users: high attr2
        plan = float(n % 2)
        attrs = (
            {"attr0": int(rng.integers(5, 10)), "attr1": int(rng.integers(0, 3)),
             "attr2": int(rng.integers(0, 2))}
            if plan == 1.0 else
            {"attr0": int(rng.integers(0, 2)), "attr1": int(rng.integers(0, 3)),
             "attr2": int(rng.integers(5, 10))}
        )
        dao.insert(Event(
            event="$set", entity_type="user", entity_id=f"u{n}",
            properties=DataMap({"plan": plan, **attrs}),
        ), app_id)


def test_classification_template():
    from incubator_predictionio_tpu.models.classification import (
        ClassificationEngine,
        DataSourceParams,
        LogRegAlgorithmParams,
        NaiveBayesAlgorithmParams,
        Query,
    )

    app_id = seed_app("clf")
    seed_classification(app_id)
    engine = ClassificationEngine().apply()
    ep = EngineParams(
        data_source_params=("", DataSourceParams(app_name="clf")),
        algorithm_params_list=[
            ("naive", NaiveBayesAlgorithmParams(lambda_=1.0)),
            ("logreg", LogRegAlgorithmParams(steps=200)),
        ],
    )
    iid = CoreWorkflow.run_train(engine, ep, engine_variant="clf")
    models = CoreWorkflow.load_models(iid, engine, ep)
    nb_algo, lr_algo = engine.algorithms(ep)
    q_plan1 = Query(features=(8.0, 1.0, 0.0))
    q_plan0 = Query(features=(0.0, 1.0, 8.0))
    assert nb_algo.predict(models[0], q_plan1).label == 1.0
    assert nb_algo.predict(models[0], q_plan0).label == 0.0
    assert lr_algo.predict(models[1], q_plan1).label == 1.0
    assert lr_algo.predict(models[1], q_plan0).label == 0.0


def test_classification_wire_format():
    from incubator_predictionio_tpu.models.classification import Query
    from incubator_predictionio_tpu.utils import json_codec

    q = json_codec.extract(Query, {"features": [1.0, 2.0, 3.0]})
    assert q.features == (1.0, 2.0, 3.0)


# ---------------------------------------------------------------------------
# similarproduct
# ---------------------------------------------------------------------------

def seed_views(app_id, extra_like=False):
    dao = Storage.get_events()
    rng = np.random.default_rng(1)
    # block structure: uA* view iA*, uB* view iB*
    for block, (users, items) in enumerate((
        ([f"uA{i}" for i in range(6)], [f"iA{i}" for i in range(8)]),
        ([f"uB{i}" for i in range(6)], [f"iB{i}" for i in range(8)]),
    )):
        for u in users:
            for it in items:
                if rng.random() < 0.6:
                    dao.insert(Event(event="view", entity_type="user",
                                     entity_id=u, target_entity_type="item",
                                     target_entity_id=it), app_id)
    if extra_like:
        dao.insert(Event(event="like", entity_type="user", entity_id="uA0",
                         target_entity_type="item", target_entity_id="iA1"),
                   app_id)
    for i in range(8):
        dao.insert(Event(
            event="$set", entity_type="item", entity_id=f"iA{i}",
            properties=DataMap({"categories": ["catA"]}),
        ), app_id)


def test_similarproduct_template():
    from incubator_predictionio_tpu.models.similarproduct import (
        ALSAlgorithmParams,
        DataSourceParams,
        Query,
        SimilarProductEngine,
    )

    app_id = seed_app("simapp")
    seed_views(app_id, extra_like=True)
    engine = SimilarProductEngine().apply()
    ep = EngineParams(
        data_source_params=("", DataSourceParams(app_name="simapp")),
        algorithm_params_list=[
            ("als", ALSAlgorithmParams(rank=8, num_iterations=10,
                                       lambda_=0.05, alpha=2.0, seed=7)),
        ],
    )
    models = engine.train(RuntimeContext(), ep)
    algo = engine.algorithms(ep)[0]
    r = algo.predict(models[0], Query(items=("iA0",), num=3))
    assert r.item_scores
    assert all(s.item.startswith("iA") for s in r.item_scores)
    assert "iA0" not in {s.item for s in r.item_scores}  # query item excluded
    # unknown item → empty
    assert algo.predict(models[0], Query(items=("nope",), num=3)).item_scores == ()
    # blacklist
    r2 = algo.predict(models[0], Query(items=("iA0",), num=4,
                                       black_list=("iA1",)))
    assert "iA1" not in {s.item for s in r2.item_scores}
    # category filter restricts to cat-A even for a B-block query item
    r3 = algo.predict(models[0], Query(items=("iB0",), num=3,
                                       categories=("catA",)))
    assert all(s.item.startswith("iA") for s in r3.item_scores)


def test_similarproduct_dimsum_variant():
    """The similarproduct-dimsum variant: exact item-item cosine
    similarities replacing Spark's sampled columnSimilarities
    (ops/dimsum.py)."""
    from incubator_predictionio_tpu.models.similarproduct import (
        DataSourceParams,
        Query,
        SimilarProductEngine,
    )
    from incubator_predictionio_tpu.models.similarproduct.engine import (
        DIMSUMAlgorithmParams,
    )

    app_id = seed_app("dimapp")
    seed_views(app_id)
    engine = SimilarProductEngine().apply()
    ep = EngineParams(
        data_source_params=("", DataSourceParams(app_name="dimapp")),
        algorithm_params_list=[
            ("dimsum", DIMSUMAlgorithmParams(threshold=0.05, top_n=10)),
        ],
    )
    models = engine.train(RuntimeContext(), ep)
    algo = engine.algorithms(ep)[0]
    r = algo.predict(models[0], Query(items=("iA0",), num=3))
    assert r.item_scores
    # co-viewed block items are the cosine neighbors
    assert all(s.item.startswith("iA") for s in r.item_scores)
    assert "iA0" not in {s.item for s in r.item_scores}
    # multi-item query sums similarities (indexScores groupBy-sum)
    r2 = algo.predict(models[0], Query(items=("iA0", "iA1"), num=3))
    assert r2.item_scores
    assert {"iA0", "iA1"}.isdisjoint({s.item for s in r2.item_scores})
    # scores descending + filters shared with the ALS variant
    scores = [s.score for s in r2.item_scores]
    assert scores == sorted(scores, reverse=True)
    r3 = algo.predict(models[0], Query(items=("iA0",), num=4,
                                       black_list=("iA1",)))
    assert "iA1" not in {s.item for s in r3.item_scores}
    assert algo.predict(
        models[0], Query(items=("nope",), num=3)).item_scores == ()


def test_dimsum_matches_numpy_cosine():
    """ops/dimsum.py produces the exact cosine matrix (what DIMSUM merely
    approximates) — checked against a dense numpy reference."""
    from incubator_predictionio_tpu.ops.dimsum import column_cosine_topk

    rng = np.random.default_rng(4)
    n_users, n_items, nnz = 40, 12, 200
    users = rng.integers(0, n_users, nnz)
    items = rng.integers(0, n_items, nnz)
    weights = rng.random(nnz).astype(np.float32)
    dense = np.zeros((n_users, n_items), np.float64)
    np.add.at(dense, (users, items), weights)
    gram = dense.T @ dense
    norms = np.sqrt(np.maximum(np.diag(gram), 1e-12))
    ref = gram / np.outer(norms, norms)
    np.fill_diagonal(ref, 0.0)
    ref[ref < 0.2] = 0.0

    scores, indices = column_cosine_topk(
        users, items, weights, n_items=n_items, threshold=0.2,
        top_n=n_items)
    got = np.zeros((n_items, n_items), np.float32)
    for i in range(n_items):
        got[i, indices[i]] = scores[i]
    np.testing.assert_allclose(got, ref, atol=2e-3)


# ---------------------------------------------------------------------------
# ecommerce
# ---------------------------------------------------------------------------

def test_ecommerce_template():
    from incubator_predictionio_tpu.models.ecommerce import (
        DataSourceParams,
        ECommAlgorithmParams,
        ECommerceEngine,
        Query,
    )

    app_id = seed_app("shop2")
    seed_views(app_id)
    dao = Storage.get_events()
    # buys strengthen block A for uA0
    dao.insert(Event(event="buy", entity_type="user", entity_id="uA0",
                     target_entity_type="item", target_entity_id="iA2"), app_id)
    engine = ECommerceEngine().apply()
    ep = EngineParams(
        data_source_params=("", DataSourceParams(app_name="shop2")),
        algorithm_params_list=[
            ("ecomm", ECommAlgorithmParams(app_name="shop2", rank=8,
                                           num_iterations=10, lambda_=0.05,
                                           alpha=2.0, seed=5)),
        ],
    )
    models = engine.train(RuntimeContext(), ep)
    algo = engine.algorithms(ep)[0]

    r = algo.predict(models[0], Query(user="uA1", num=3))
    assert r.item_scores
    # top unseen recommendation comes from the user's own block (implicit
    # ALS scores *all* unobserved cells near 0, so only the best in-block
    # unseen item clearly outranks the other block on a tiny catalog)
    assert r.item_scores[0].item.startswith("iA")
    # unseen_only: none of uA1's seen items
    seen = {
        e.target_entity_id for e in Storage.get_events().find(
            app_id=app_id, entity_id="uA1")
    }
    assert not seen.intersection({s.item for s in r.item_scores})

    # unavailable items constraint ($set without retraining)
    first = r.item_scores[0].item
    dao.insert(Event(
        event="$set", entity_type="constraint",
        entity_id="unavailableItems",
        properties=DataMap({"items": [first]}),
    ), app_id)
    r2 = algo.predict(models[0], Query(user="uA1", num=3))
    assert first not in {s.item for s in r2.item_scores}

    # unknown user with recent views → item-based vector
    dao.insert(Event(event="view", entity_type="user", entity_id="fresh",
                     target_entity_type="item", target_entity_id="iB0"), app_id)
    dao.insert(Event(event="view", entity_type="user", entity_id="fresh",
                     target_entity_type="item", target_entity_id="iB1"), app_id)
    r3 = algo.predict(models[0], Query(user="fresh", num=2))
    assert r3.item_scores
    assert all(s.item.startswith("iB") for s in r3.item_scores)

    # totally cold user → popularity fallback still answers
    r4 = algo.predict(models[0], Query(user="nobody", num=2))
    assert len(r4.item_scores) == 2


def test_ecommerce_weighted_items():
    """weightedItems constraint multiplies matching items' scores at serve
    time (weighted-items/ECommAlgorithm.scala:234-261)."""
    from incubator_predictionio_tpu.models.ecommerce import (
        DataSourceParams,
        ECommAlgorithmParams,
        ECommerceEngine,
        Query,
    )

    app_id = seed_app("wshop")
    seed_views(app_id)
    dao = Storage.get_events()
    engine = ECommerceEngine().apply()
    ep = EngineParams(
        data_source_params=("", DataSourceParams(app_name="wshop")),
        algorithm_params_list=[
            ("ecomm", ECommAlgorithmParams(app_name="wshop", rank=8,
                                           num_iterations=10, lambda_=0.05,
                                           alpha=2.0, seed=5)),
        ],
    )
    models = engine.train(RuntimeContext(), ep)
    algo = engine.algorithms(ep)[0]

    base = algo.predict(models[0], Query(user="uA1", num=4))
    assert len(base.item_scores) >= 2
    first, second = base.item_scores[0], base.item_scores[1]

    # boost the runner-up enough to overtake; demote the old leader
    dao.insert(Event(
        event="$set", entity_type="constraint", entity_id="weightedItems",
        properties=DataMap({"weights": [
            {"items": [second.item], "weight": 100.0},
            {"items": [first.item], "weight": 0.001},
        ]}),
    ), app_id)
    boosted = algo.predict(models[0], Query(user="uA1", num=4))
    assert boosted.item_scores[0].item == second.item
    by_item = {s.item: s.score for s in boosted.item_scores}
    assert by_item[second.item] == pytest.approx(second.score * 100.0,
                                                 rel=1e-4)

    # a later $set replaces the groups: back to the natural order
    dao.insert(Event(
        event="$set", entity_type="constraint", entity_id="weightedItems",
        properties=DataMap({"weights": []}),
    ), app_id)
    reset = algo.predict(models[0], Query(user="uA1", num=4))
    assert reset.item_scores[0].item == first.item


# ---------------------------------------------------------------------------
# similarproduct: recommended-user variant
# ---------------------------------------------------------------------------

def seed_follows(app_id):
    dao = Storage.get_events()
    rng = np.random.default_rng(3)
    # two communities: cA* follow each other, cB* follow each other;
    # one bridge edge from cA0 to cB0
    groups = (
        [f"cA{i}" for i in range(7)],
        [f"cB{i}" for i in range(7)],
    )
    for members in groups:
        for u in members:
            for v in members:
                if u != v and rng.random() < 0.7:
                    dao.insert(Event(
                        event="follow", entity_type="user", entity_id=u,
                        target_entity_type="user", target_entity_id=v,
                    ), app_id)
    dao.insert(Event(event="follow", entity_type="user", entity_id="cA0",
                     target_entity_type="user", target_entity_id="cB0"),
               app_id)


def test_recommended_user_template():
    from incubator_predictionio_tpu.models.similarproduct.recommended_user import (
        ALSAlgorithmParams,
        DataSourceParams,
        Query,
        RecommendedUserEngine,
    )

    app_id = seed_app("social")
    seed_follows(app_id)
    engine = RecommendedUserEngine().apply()
    ep = EngineParams(
        data_source_params=("", DataSourceParams(app_name="social")),
        algorithm_params_list=[
            ("als", ALSAlgorithmParams(rank=8, num_iterations=10,
                                       lambda_=0.05, seed=11)),
        ],
    )
    models = engine.train(RuntimeContext(), ep)
    algo = engine.algorithms(ep)[0]

    r = algo.predict(models[0], Query(users=("cA1", "cA2"), num=3))
    assert r.similar_user_scores
    # same community dominates; the query users themselves are excluded
    names = [s.user for s in r.similar_user_scores]
    assert all(u.startswith("cA") for u in names)
    assert not {"cA1", "cA2"}.intersection(names)
    # scores are positive, descending
    scores = [s.score for s in r.similar_user_scores]
    assert all(s > 0 for s in scores)
    assert scores == sorted(scores, reverse=True)

    # blacklist removes a recommendation
    r2 = algo.predict(models[0], Query(users=("cA1",), num=5,
                                       black_list=(names[0],)))
    assert names[0] not in {s.user for s in r2.similar_user_scores}

    # whitelist restricts candidates
    r3 = algo.predict(models[0], Query(users=("cA1",), num=5,
                                       white_list=("cB0", "cB1")))
    assert {s.user for s in r3.similar_user_scores} <= {"cB0", "cB1"}

    # unknown query users → empty result (ALSAlgorithm.scala:149-151)
    r4 = algo.predict(models[0], Query(users=("ghost",), num=3))
    assert r4.similar_user_scores == ()


def test_recommended_user_wire_format():
    from incubator_predictionio_tpu.models.similarproduct.recommended_user import (
        PredictedResult,
        Query,
        SimilarUserScore,
    )
    from incubator_predictionio_tpu.utils import json_codec

    q = json_codec.extract(Query, {
        "users": ["u1", "u2"], "num": 5, "whiteList": ["u3"],
    })
    assert q.users == ("u1", "u2") and q.white_list == ("u3",)
    out = json_codec.to_jsonable(PredictedResult(
        similar_user_scores=(SimilarUserScore(user="u9", score=1.5),)))
    assert out == {"similarUserScores": [{"user": "u9", "score": 1.5}]}


def test_ecommerce_seen_events_config():
    """seen_events controls which event types mark items as 'seen'."""
    from incubator_predictionio_tpu.models.ecommerce import (
        DataSourceParams,
        ECommAlgorithmParams,
        ECommerceEngine,
        Query,
    )

    app_id = seed_app("shop3")
    seed_views(app_id)
    engine = ECommerceEngine().apply()
    ep = EngineParams(
        data_source_params=("", DataSourceParams(app_name="shop3")),
        algorithm_params_list=[
            ("ecomm", ECommAlgorithmParams(app_name="shop3", rank=8,
                                           num_iterations=5, lambda_=0.05,
                                           alpha=2.0, seed=5,
                                           seen_events=("buy",))),
        ],
    )
    models = engine.train(RuntimeContext(), ep)
    algo = engine.algorithms(ep)[0]
    # uA1 only VIEWED items (no buys) -> nothing is "seen" -> viewed items
    # may be recommended again
    r = algo.predict(models[0], Query(user="uA1", num=5))
    viewed = {
        e.target_entity_id for e in Storage.get_events().find(
            app_id=app_id, entity_id="uA1", event_names=["view"])
    }
    assert viewed.intersection({s.item for s in r.item_scores})


def test_warmup_hooks_run_on_template_models():
    """Each template algorithm's warmup must execute cleanly against a
    freshly trained model (the prediction server calls these on deploy)."""
    from incubator_predictionio_tpu.models.similarproduct import (
        ALSAlgorithmParams as SPParams,
        DataSourceParams as SPDS,
        SimilarProductEngine,
    )

    app_id = seed_app("warmapp")
    seed_views(app_id, extra_like=True)
    engine = SimilarProductEngine().apply()
    ep = EngineParams(
        data_source_params=("", SPDS(app_name="warmapp")),
        algorithm_params_list=[
            ("als", SPParams(rank=8, num_iterations=4, seed=3)),
        ],
    )
    models = engine.train(RuntimeContext(), ep)
    algo = engine.algorithms(ep)[0]
    algo.warmup(models[0], max_batch=4)      # must not raise

    from incubator_predictionio_tpu.models.ecommerce import (
        DataSourceParams as EcDS,
        ECommAlgorithmParams,
        ECommerceEngine,
    )

    ec_engine = ECommerceEngine().apply()
    ec_ep = EngineParams(
        data_source_params=("", EcDS(app_name="warmapp")),
        algorithm_params_list=[
            ("ecomm", ECommAlgorithmParams(app_name="warmapp", rank=8,
                                           num_iterations=4,
                                           seed=3)),
        ],
    )
    ec_models = ec_engine.train(RuntimeContext(), ec_ep)
    ec_engine.algorithms(ec_ep)[0].warmup(ec_models[0], max_batch=4)


def test_warmup_recommendation_batched_and_sequence():
    """The two complex warmups: the ALS batched loop must exercise the
    exact power-of-two shapes live traffic compiles, and the SASRec
    warmup must run the transformer forward without touching the store."""
    from incubator_predictionio_tpu.models.recommendation.engine import (
        ALSAlgorithm,
        ALSAlgorithmParams,
        Query as RecQuery,
    )
    import incubator_predictionio_tpu.models.recommendation.engine as rec_mod

    app_id = seed_app("warmrec")
    ev = Storage.get_events()
    rng = np.random.default_rng(2)
    for u in range(12):
        for i in rng.choice(20, 5, replace=False):
            ev.insert(Event(
                event="rate", entity_type="user", entity_id=f"u{u}",
                target_entity_type="item", target_entity_id=f"i{int(i)}",
                properties=DataMap({"rating": float(1 + int(i) % 5)})),
                app_id)
    from incubator_predictionio_tpu.models.recommendation import (
        RecommendationEngine,
        DataSourceParams as RecDS,
    )

    engine = RecommendationEngine().apply()
    ep = EngineParams(
        data_source_params=("", RecDS(app_name="warmrec")),
        algorithm_params_list=[
            ("als", ALSAlgorithmParams(rank=8, num_iterations=3, seed=1)),
        ],
    )
    models = engine.train(RuntimeContext(), ep)
    algo = engine.algorithms(ep)[0]
    calls = []
    orig = algo.batch_predict

    def spy(model, queries):
        calls.append(len(queries))
        return orig(model, queries)

    algo.batch_predict = spy
    algo.warmup(models[0], max_batch=5)
    # the whole ladder, rung 1 included (a lone plain query rides the
    # batched fast path at B=1); cap = next_pow2(5) = 8 → [1, 2, 4, 8]
    assert calls == [1, 2, 4, 8]
    algo.batch_predict = orig
    algo.warmup(models[0], max_batch=0)   # disabled batcher: singleton only

    # sequence: explicit-history warmup, no event-store read
    from incubator_predictionio_tpu.models.sequence.engine import (
        SeqRecAlgorithm,
        SeqRecAlgorithmParams,
        PreparedData as SeqPD,
    )
    import numpy as _np

    seqs = _np.array([[1, 2, 3, 4], [2, 3, 4, 5]], _np.int32)
    from incubator_predictionio_tpu.data.bimap import BiMap

    algo2 = SeqRecAlgorithm(SeqRecAlgorithmParams(
        app_name="warmrec", d_model=8, n_heads=2, n_layers=1, epochs=1))
    pd = SeqPD(sequences=seqs,
               item_bimap=BiMap({f"i{k}": k for k in range(6)}))
    model2 = algo2.train(RuntimeContext(), pd)
    algo2.warmup(model2)                  # must not raise or hit storage
