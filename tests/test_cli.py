"""CLI verb coverage (parity: tools/.../console/Console.scala matrix +
the integration suite's BasicAppUsecases)."""

import json
import os

import numpy as np
import pytest

from incubator_predictionio_tpu.cli.main import main
from incubator_predictionio_tpu.data.datamap import DataMap
from incubator_predictionio_tpu.data.event import Event
from incubator_predictionio_tpu.data.storage import Storage


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


def test_version_and_help():
    assert main(["version"]) == 0
    assert main([]) == 1


def test_status():
    assert main(["status"]) == 0


def test_app_lifecycle(capsys):
    assert main(["app", "new", "CliApp", "--description", "d"]) == 0
    out = capsys.readouterr().out
    assert "Access Key:" in out
    # duplicate fails
    assert main(["app", "new", "CliApp"]) == 1
    assert main(["app", "list"]) == 0
    assert "CliApp" in capsys.readouterr().out
    assert main(["app", "show", "CliApp"]) == 0
    # channels
    assert main(["app", "channel-new", "CliApp", "chan-a"]) == 0
    assert main(["app", "channel-new", "CliApp", "chan-a"]) == 1  # dup
    assert main(["app", "channel-new", "CliApp", "bad name!"]) == 1
    assert main(["app", "channel-delete", "CliApp", "chan-a", "-f"]) == 0
    assert main(["app", "channel-delete", "CliApp", "ghost", "-f"]) == 1
    # data + delete
    assert main(["app", "data-delete", "CliApp", "-f"]) == 0
    assert main(["app", "delete", "CliApp", "-f"]) == 0
    assert main(["app", "show", "CliApp"]) == 1


def test_accesskey_lifecycle(capsys):
    main(["app", "new", "KeyApp"])
    capsys.readouterr()
    assert main(["accesskey", "new", "KeyApp", "--key", "my-key",
                 "--events", "rate", "buy"]) == 0
    assert main(["accesskey", "list", "KeyApp"]) == 0
    out = capsys.readouterr().out
    assert "my-key" in out and "rate, buy" in out
    assert main(["accesskey", "delete", "my-key"]) == 0
    assert main(["accesskey", "delete", "my-key"]) == 1
    assert main(["accesskey", "new", "GhostApp"]) == 1


def test_import_export_round_trip(tmp_path, capsys):
    main(["app", "new", "IOApp"])
    src = tmp_path / "events.jsonl"
    events = [
        {"event": "rate", "entityType": "user", "entityId": f"u{i}",
         "targetEntityType": "item", "targetEntityId": "i1",
         "properties": {"rating": i}, "eventTime": "2020-01-01T00:00:00.000Z"}
        for i in range(5)
    ]
    src.write_text("\n".join(json.dumps(e) for e in events))
    assert main(["import", "--appid-or-name", "IOApp",
                 "--input", str(src)]) == 0
    dst = tmp_path / "out.jsonl"
    assert main(["export", "--appid-or-name", "IOApp",
                 "--output", str(dst)]) == 0
    lines = [json.loads(l) for l in dst.read_text().splitlines()]
    assert len(lines) == 5
    assert {l["entityId"] for l in lines} == {f"u{i}" for i in range(5)}
    # malformed line fails loudly with position
    bad = tmp_path / "bad.jsonl"
    bad.write_text('{"entityType": "user"}\n')
    assert main(["import", "--appid-or-name", "IOApp",
                 "--input", str(bad)]) == 1


def test_parquet_export_import_round_trip(tmp_path, capsys):
    """--format parquet on both verbs (EventsToFile.scala:44 parity),
    preserving properties / tags / prId / times through the round trip."""
    pytest.importorskip("pyarrow")
    main(["app", "new", "PqApp"])
    main(["app", "new", "PqApp2"])
    from incubator_predictionio_tpu.data.store import EventStore

    EventStore.write([
        Event(event="rate", entity_type="user", entity_id=f"u{i}",
              target_entity_type="item", target_entity_id="i1",
              properties=DataMap({"rating": float(i), "nested": {"a": [i]}}),
              tags=("t1", "t2"), pr_id="pr-9" if i == 0 else None)
        for i in range(3)
    ], app_name="PqApp")
    pq_file = tmp_path / "events.parquet"
    assert main(["export", "--appid-or-name", "PqApp",
                 "--output", str(pq_file), "--format", "parquet"]) == 0
    assert pq_file.stat().st_size > 0
    assert main(["import", "--appid-or-name", "PqApp2",
                 "--input", str(pq_file), "--format", "parquet"]) == 0
    got = sorted(EventStore.find(app_name="PqApp2"),
                 key=lambda e: e.entity_id)
    assert [e.entity_id for e in got] == ["u0", "u1", "u2"]
    assert got[1].properties.get("rating") == 1.0
    assert got[1].properties.get("nested") == {"a": [1]}
    assert got[0].tags == ("t1", "t2")
    assert got[0].pr_id == "pr-9"
    assert got[2].event_time is not None


def _seed_quickstart_events(app_name):
    from incubator_predictionio_tpu.data.store import EventStore

    rng = np.random.default_rng(0)
    events = []
    for u in range(30):
        for i in rng.choice(20, size=8, replace=False):
            events.append(Event(
                event="rate", entity_type="user", entity_id=f"u{u}",
                target_entity_type="item", target_entity_id=f"i{i}",
                properties=DataMap({"rating": float(rng.integers(1, 6))}),
            ))
    EventStore.write(events, app_name=app_name)


def test_build_train_from_engine_json(tmp_path, monkeypatch, capsys):
    main(["app", "new", "MyApp1"])
    _seed_quickstart_events("MyApp1")
    variant = {
        "id": "cli-test",
        "engineFactory":
            "incubator_predictionio_tpu.models.recommendation:RecommendationEngine",
        "datasource": {"params": {"appName": "MyApp1"}},
        "algorithms": [{"name": "als", "params": {
            "rank": 8, "numIterations": 5, "lambda": 0.05, "seed": 1,
        }}],
    }
    (tmp_path / "engine.json").write_text(json.dumps(variant))
    monkeypatch.chdir(tmp_path)
    assert main(["build"]) == 0
    assert main(["train"]) == 0
    out = capsys.readouterr().out
    assert "Engine instance ID:" in out
    from incubator_predictionio_tpu.cli.commands import (
        engine_id_for_variant_path,
    )
    # engine identity is directory-derived (manifest-id semantics), the
    # variant id only names the params variant — two engines shipping the
    # default variant id must not collide in the instance registry
    latest = Storage.get_meta_data_engine_instances().get_latest_completed(
        engine_id_for_variant_path(str(tmp_path / "engine.json"), variant),
        "NOT_VERSIONED", "cli-test"
    )
    assert latest is not None
    assert latest.status == "COMPLETED"
    # camelCase params round-trip through the stored instance
    assert '"numIterations": 5' in latest.algorithms_params
    from incubator_predictionio_tpu.cli import commands as cli_commands
    engine, _ = cli_commands.engine_from_variant(variant)
    restored = engine.engine_params_from_instance(latest)
    assert restored.algorithm_params_list[0][1].num_iterations == 5
    assert restored.algorithm_params_list[0][1].lambda_ == 0.05


def test_train_missing_engine_json(tmp_path, monkeypatch):
    monkeypatch.chdir(tmp_path)
    assert main(["train"]) == 1
    assert main(["build"]) == 1


def test_eval_via_cli(tmp_path, monkeypatch, capsys):
    main(["app", "new", "MyApp1"])
    _seed_quickstart_events("MyApp1")
    repo_examples = os.path.join(os.path.dirname(__file__), "..", "examples",
                                 "recommendation-quickstart")
    monkeypatch.chdir(repo_examples)
    monkeypatch.setattr("sys.path", ["."] + __import__("sys").path)
    assert main(["eval", "evaluation:evaluation",
                 "evaluation:engine_params_generator",
                 "--output-best", str(tmp_path / "best.json")]) == 0
    out = capsys.readouterr().out
    assert "Evaluation completed" in out
    assert (tmp_path / "best.json").exists()
    best = json.loads((tmp_path / "best.json").read_text())
    assert best["algorithmParamsList"][0]["name"] == "als"


def test_undeploy_nothing_running():
    assert main(["undeploy", "--port", "59999"]) == 1


def test_import_fast_path_uniform_batch(tmp_path, capsys, monkeypatch):
    """A uniform id-less interaction batch routes through the backend's
    native columnar import (cpplog), and the events remain readable
    through the generic query path."""
    from incubator_predictionio_tpu.cli import commands
    from incubator_predictionio_tpu import native

    if native.load() is None:
        pytest.skip("native library unavailable")
    Storage.reset()
    Storage.configure({
        "PIO_STORAGE_SOURCES_MEM_TYPE": "memory",
        "PIO_STORAGE_SOURCES_EV_TYPE": "cpplog",
        "PIO_STORAGE_SOURCES_EV_PATH": str(tmp_path / "ev"),
        "PIO_STORAGE_REPOSITORIES_METADATA_NAME": "m",
        "PIO_STORAGE_REPOSITORIES_METADATA_SOURCE": "MEM",
        "PIO_STORAGE_REPOSITORIES_EVENTDATA_NAME": "e",
        "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": "EV",
        "PIO_STORAGE_REPOSITORIES_MODELDATA_NAME": "d",
        "PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE": "MEM",
    })
    monkeypatch.setattr(commands, "_FAST_IMPORT_MIN", 10)
    main(["app", "new", "FastApp"])
    capsys.readouterr()
    src = tmp_path / "events.jsonl"
    docs = [
        {"event": "rate", "entityType": "user", "entityId": f"u{i % 7}",
         "targetEntityType": "item", "targetEntityId": f"i{i % 5}",
         "properties": {"rating": float(1 + i % 4)},
         "eventTime": f"2020-01-01T00:00:{i % 60:02d}.000Z"}
        for i in range(60)
    ]
    src.write_text("\n".join(json.dumps(d) for d in docs))
    assert main(["import", "--appid-or-name", "FastApp",
                 "--input", str(src)]) == 0
    assert "native columnar path" in capsys.readouterr().out
    inter = Storage.get_events().scan_interactions(
        app_id=1, entity_type="user", target_entity_type="item",
        event_names=("rate",), value_prop="rating")
    assert len(inter) == 60
    evs = list(Storage.get_events().find(app_id=1, limit=100))
    assert len(evs) == 60 and all(e.event == "rate" for e in evs)

    # events WITH ids must keep the per-event path (id-preserving upsert)
    src2 = tmp_path / "with_ids.jsonl"
    docs2 = [dict(d, eventId=f"e{i:032d}") for i, d in enumerate(docs)]
    src2.write_text("\n".join(json.dumps(d) for d in docs2))
    assert main(["import", "--appid-or-name", "FastApp",
                 "--input", str(src2)]) == 0
    out = capsys.readouterr().out
    assert "native columnar path" not in out
    assert Storage.get_events().get(
        "e" + "0" * 31 + "0", 1) is not None  # explicit id preserved


def test_accelerator_watchdog_times_out_and_propagates_errors(monkeypatch):
    """A device init that hangs must turn into an actionable error, and
    real init errors (on a local chip: libtpu's lock held by another
    process) must surface as themselves."""
    import time

    from incubator_predictionio_tpu.cli import main as climain
    import jax

    monkeypatch.setattr(jax, "devices", lambda: time.sleep(30))
    with pytest.raises(climain.CommandError, match="another process holds it"):
        climain._ensure_accelerator(0.2)

    def boom():
        raise RuntimeError("no backend at all")

    monkeypatch.setattr(jax, "devices", boom)
    with pytest.raises(climain.CommandError,
                       match="initialization failed.*no backend"):
        climain._ensure_accelerator(5.0)

    monkeypatch.setattr(jax, "devices", lambda: ["dev0"])
    climain._ensure_accelerator(5.0)  # healthy path: no raise


def test_fast_import_then_export_roundtrip(tmp_path, capsys, monkeypatch):
    """Events landed via the columnar fast path are compact (sidecar-only)
    records; export must render them as full canonical JSON events."""
    from incubator_predictionio_tpu import native
    from incubator_predictionio_tpu.cli import commands

    if native.load() is None:
        pytest.skip("native library unavailable")
    Storage.reset()
    Storage.configure({
        "PIO_STORAGE_SOURCES_MEM_TYPE": "memory",
        "PIO_STORAGE_SOURCES_EV_TYPE": "cpplog",
        "PIO_STORAGE_SOURCES_EV_PATH": str(tmp_path / "ev"),
        "PIO_STORAGE_REPOSITORIES_METADATA_NAME": "m",
        "PIO_STORAGE_REPOSITORIES_METADATA_SOURCE": "MEM",
        "PIO_STORAGE_REPOSITORIES_EVENTDATA_NAME": "e",
        "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": "EV",
        "PIO_STORAGE_REPOSITORIES_MODELDATA_NAME": "d",
        "PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE": "MEM",
    })
    monkeypatch.setattr(commands, "_FAST_IMPORT_MIN", 10)
    main(["app", "new", "RoundTrip"])
    capsys.readouterr()
    src = tmp_path / "in.jsonl"
    docs = [
        {"event": "rate", "entityType": "user", "entityId": f"u{i % 4}",
         "targetEntityType": "item", "targetEntityId": f"i{i % 3}",
         "properties": {"rating": float(1 + i % 5)},
         "eventTime": f"2021-05-01T00:00:{i % 60:02d}.000Z"}
        for i in range(30)
    ]
    src.write_text("\n".join(json.dumps(d) for d in docs))
    assert main(["import", "--appid-or-name", "RoundTrip",
                 "--input", str(src)]) == 0
    assert "native columnar path" in capsys.readouterr().out
    dst = tmp_path / "out.jsonl"
    assert main(["export", "--appid-or-name", "RoundTrip",
                 "--output", str(dst)]) == 0
    lines = [json.loads(l) for l in dst.read_text().splitlines()]
    assert len(lines) == 30
    for got, want in zip(lines, docs):
        assert got["event"] == want["event"]
        assert got["entityId"] == want["entityId"]
        assert got["targetEntityId"] == want["targetEntityId"]
        assert got["properties"] == want["properties"]
        assert got["eventTime"].startswith(want["eventTime"][:19])
        assert len(got["eventId"]) == 32  # generated ids present
    # and the exported file re-imports cleanly (per-event path: it now
    # carries eventIds)
    assert main(["import", "--appid-or-name", "RoundTrip",
                 "--input", str(dst)]) == 0
    out = capsys.readouterr().out
    assert "native columnar path" not in out  # ids force the upsert path
