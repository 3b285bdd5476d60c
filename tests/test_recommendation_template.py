"""End-to-end recommendation template: events → train → predict → eval.

Parity: the reference's quickstart flow (tests/pio_tests/tests.py
QuickStartTest) at unit scale.
"""

import numpy as np
import pytest

from incubator_predictionio_tpu.core import EngineParams, MetricEvaluator
from incubator_predictionio_tpu.core.evaluation import Evaluation
from incubator_predictionio_tpu.data.datamap import DataMap
from incubator_predictionio_tpu.data.event import Event
from incubator_predictionio_tpu.data.storage import App, Storage
from incubator_predictionio_tpu.models.recommendation import (
    ALSAlgorithmParams,
    DataSourceParams,
    PredictedResult,
    Query,
    RecommendationEngine,
)
from incubator_predictionio_tpu.models.recommendation.engine import PrecisionAtK
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


@pytest.fixture
def seeded_app():
    """Block-structured ratings: users uA* love items iA*, users uB* love
    iB* — so recommendations are unambiguous."""
    Storage.get_meta_data_apps().insert(App(0, "recapp"))
    dao = Storage.get_events()
    app_id = Storage.get_meta_data_apps().get_by_name("recapp").id
    rng = np.random.default_rng(0)
    events = []
    for g, (users, items) in enumerate(
        ((["uA%d" % i for i in range(8)], ["iA%d" % i for i in range(6)]),
         (["uB%d" % i for i in range(8)], ["iB%d" % i for i in range(6)]))
    ):
        for u in users:
            for it in items:
                if rng.random() < 0.7:
                    events.append(Event(
                        event="rate", entity_type="user", entity_id=u,
                        target_entity_type="item", target_entity_id=it,
                        properties=DataMap({"rating": float(rng.integers(4, 6))}),
                    ))
        # cross-group low ratings
        for u in users:
            other = "iB0" if g == 0 else "iA0"
            events.append(Event(
                event="rate", entity_type="user", entity_id=u,
                target_entity_type="item", target_entity_id=other,
                properties=DataMap({"rating": 1.0}),
            ))
    # one "buy" event (implicit 4.0)
    events.append(Event(event="buy", entity_type="user", entity_id="uA0",
                        target_entity_type="item", target_entity_id="iA5"))
    # item metadata for the custom-query filter
    for i in range(6):
        events.append(Event(
            event="$set", entity_type="item", entity_id="iA%d" % i,
            properties=DataMap({"creationYear": 1990 + i,
                                "categories": ["groupA"]}),
        ))
    for e in events:
        dao.insert(e, app_id)
    return app_id


def engine_params(eval_k=0, iters=10):
    return EngineParams(
        data_source_params=("", DataSourceParams(app_name="recapp",
                                                 eval_k=eval_k)),
        algorithm_params_list=[
            ("als", ALSAlgorithmParams(rank=8, num_iterations=iters,
                                       lambda_=0.05, seed=42))
        ],
    )


def test_train_and_predict(seeded_app):
    engine = RecommendationEngine().apply()
    ctx = RuntimeContext()
    models = engine.train(ctx, engine_params())
    algo = engine.algorithms(engine_params())[0]
    result = algo.predict(models[0], Query(user="uA1", num=3))
    assert len(result.item_scores) == 3
    # group-A user gets group-A items
    assert all(s.item.startswith("iA") for s in result.item_scores)
    # scores descending
    scores = [s.score for s in result.item_scores]
    assert scores == sorted(scores, reverse=True)


def test_unknown_user_empty_result(seeded_app):
    engine = RecommendationEngine().apply()
    models = engine.train(RuntimeContext(), engine_params())
    algo = engine.algorithms(engine_params())[0]
    assert algo.predict(models[0], Query(user="ghost", num=3)).item_scores == ()


def test_query_filters(seeded_app):
    engine = RecommendationEngine().apply()
    models = engine.train(RuntimeContext(), engine_params())
    algo = engine.algorithms(engine_params())[0]
    # creationYear filter: only iA3+ (1993+) qualify
    r = algo.predict(models[0], Query(user="uA1", num=6, creation_year=1993))
    assert r.item_scores
    assert all(s.creation_year and s.creation_year >= 1993 for s in r.item_scores)
    # category filter
    r = algo.predict(models[0], Query(user="uB1", num=4,
                                      categories=("groupA",)))
    assert all(s.item.startswith("iA") for s in r.item_scores)
    # whitelist / blacklist
    r = algo.predict(models[0], Query(user="uA1", num=4,
                                      whitelist=("iA0", "iA1")))
    assert {s.item for s in r.item_scores} <= {"iA0", "iA1"}
    r = algo.predict(models[0], Query(user="uA1", num=10, blacklist=("iA0",)))
    assert "iA0" not in {s.item for s in r.item_scores}


def test_full_workflow_train_store_reload(seeded_app):
    engine = RecommendationEngine().apply()
    iid = CoreWorkflow.run_train(engine, engine_params(),
                                 engine_variant="rec-test")
    models = CoreWorkflow.load_models(iid, engine, engine_params())
    algo = engine.algorithms(engine_params())[0]
    result = algo.predict(models[0], Query(user="uA2", num=2))
    assert len(result.item_scores) == 2


def test_batch_predict_matches_single(seeded_app):
    engine = RecommendationEngine().apply()
    models = engine.train(RuntimeContext(), engine_params())
    algo = engine.algorithms(engine_params())[0]
    queries = [(i, Query(user=u, num=3)) for i, u in
               enumerate(["uA0", "uB0", "ghost"])]
    batch = dict(algo.batch_predict(models[0], queries))
    for qx, q in queries:
        single = algo.predict(models[0], q)
        assert [s.item for s in batch[qx].item_scores] == \
               [s.item for s in single.item_scores]


def test_evaluation_precision_at_k(seeded_app):
    engine = RecommendationEngine().apply()
    evaluation = Evaluation()
    evaluation.engine_metric = (engine, PrecisionAtK(k=3))
    iid, result = CoreWorkflow.run_evaluation(
        evaluation, [engine_params(eval_k=2, iters=5)],
    )
    assert 0.0 <= result.best_score.score <= 1.0
    # block structure should make precision decent
    assert result.best_score.score > 0.2


def test_wire_format_parity():
    """Reference clients speak camelCase (Engine.scala:23-28 JSON)."""
    from incubator_predictionio_tpu.utils import json_codec

    q = json_codec.extract(Query, {"user": "u1", "num": 4,
                                   "creationYear": 1995})
    assert q.creation_year == 1995
    from incubator_predictionio_tpu.models.recommendation import ItemScore
    out = json_codec.to_jsonable(
        PredictedResult(item_scores=(ItemScore("i1", 1.5, 1990),))
    )
    assert out == {"itemScores": [
        {"item": "i1", "score": 1.5, "creationYear": 1990}
    ]}


def test_train_with_model_parallelism_matches_single(seeded_app):
    """ctx.model_parallelism > 1 routes through als_train_sharded (the
    mp-sharded ALX layout) and must produce the same model (the tests run
    on the virtual 8-device CPU mesh)."""
    engine = RecommendationEngine().apply()
    ref = engine.train(RuntimeContext(), engine_params())
    mp = engine.train(RuntimeContext(model_parallelism=2), engine_params())
    import numpy as np
    # tolerance: sharding changes the CG matvec reduction order, so the two
    # runs differ by the solver residual (~1e-5/solve at the default 16
    # iterations) amplified across the 10 alternating sweeps
    np.testing.assert_allclose(
        np.asarray(ref[0].user_factors), np.asarray(mp[0].user_factors),
        rtol=2e-3, atol=2e-4)
    algo = engine.algorithms(engine_params())[0]
    result = algo.predict(mp[0], Query(user="uA1", num=3))
    assert all(s.item.startswith("iA") for s in result.item_scores)


def test_host_and_device_serving_paths_agree(seeded_app):
    """Small models serve from a host factor copy; forcing the device path
    must give identical rankings (same scoring, same filters)."""
    engine = RecommendationEngine().apply()
    models = engine.train(RuntimeContext(), engine_params())
    algo = engine.algorithms(engine_params())[0]
    q = Query(user="uA1", num=3, exclude_seen=True)
    host = algo.predict(models[0], q)
    object.__setattr__(models[0], "_np_cache", False)  # force device path
    dev = algo.predict(models[0], q)
    assert [s.item for s in host.item_scores] == \
           [s.item for s in dev.item_scores]
    for a, b in zip(host.item_scores, dev.item_scores):
        assert abs(a.score - b.score) < 1e-4


def test_num_zero_returns_empty_on_both_paths(seeded_app):
    engine = RecommendationEngine().apply()
    models = engine.train(RuntimeContext(), engine_params())
    algo = engine.algorithms(engine_params())[0]
    assert algo.predict(models[0], Query(user="uA1", num=0)).item_scores == ()
    object.__setattr__(models[0], "_np_cache", False)
    assert algo.predict(models[0], Query(user="uA1", num=0)).item_scores == ()


@pytest.mark.parametrize("max_batch", [8, 16])
def test_warmup_covers_every_live_width(seeded_app, max_batch):
    """A lone plain query rides the columnar fast path (batch_serve_json →
    batch_score_top_k at B=1), not predict(): deploy-time warmup must
    have compiled that rung too, or the first live query compiles on the
    dispatcher thread (0.2–0.3 s on a v5e — enough to push the latency
    p99 over the serve SLO and make the scheduler shed the next burst).
    And the warm-up and the live dispatch call one function with one
    kind of argument (the rows as a host array), so what was warmed is
    what is dispatched: a batch of every size 1…max_batch leaves the
    count of compiled serving variants where the warm-up left it."""
    from incubator_predictionio_tpu.ops import topk

    engine = RecommendationEngine().apply()
    models = engine.train(RuntimeContext(), engine_params())
    algo = engine.algorithms(engine_params())[0]
    object.__setattr__(models[0], "_np_cache", False)  # device path
    algo.warmup(models[0], max_batch=max_batch)
    warm = topk.serve_compile_cache_size()
    assert warm > 0
    users = ["uA%d" % i for i in range(8)] + ["uB%d" % i for i in range(8)]
    for width in range(1, max_batch + 1):
        docs = [{"user": u, "num": 10} for u in users[:width]]  # warmed k
        assert all(algo.batch_serve_json(models[0], docs))
        assert topk.serve_compile_cache_size() == warm, \
            f"a batch of {width} compiled after warmup"
    assert algo.predict(models[0], Query(user="uA1", num=10)).item_scores
    assert topk.serve_compile_cache_size() == warm, \
        "the singleton path compiled after warmup"
