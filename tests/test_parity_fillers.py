"""Tests for EngineManifests DAO, batch views, example webhook connectors,
template version gate, build/unregister, and FakeWorkflow.

Reference analogues: EngineManifests.scala, view/LBatchView.scala specs,
webhooks/{examplejson,exampleform}/*Spec.scala, commands/Template.scala,
RegisterEngine.scala, workflow/FakeWorkflow.scala.
"""

import json
from datetime import timedelta

import pytest

from incubator_predictionio_tpu.data.datamap import DataMap
from incubator_predictionio_tpu.data.event import Event
from incubator_predictionio_tpu.data.storage import (
    EngineManifest,
    Storage,
    StorageClientConfig,
)
from incubator_predictionio_tpu.data.storage import memory as memory_backend
from incubator_predictionio_tpu.data.storage import sqlite as sqlite_backend
from incubator_predictionio_tpu.data.view import BatchView, data_view
from incubator_predictionio_tpu.data.webhooks import ConnectorError
from incubator_predictionio_tpu.data.webhooks.examples import (
    ExampleFormConnector,
    ExampleJsonConnector,
)
from incubator_predictionio_tpu.utils.times import parse_iso8601

T0 = parse_iso8601("2021-06-01T00:00:00Z")


# ---------------------------------------------------------------------------
# EngineManifests conformance (both backends)
# ---------------------------------------------------------------------------

@pytest.fixture(params=["memory", "sqlite"])
def manifests(request):
    config = StorageClientConfig(test=True, properties={"PATH": ":memory:"})
    mod = {"memory": memory_backend, "sqlite": sqlite_backend}[request.param]
    client = mod.StorageClient(config)
    yield mod.DATA_OBJECTS["EngineManifests"](client, config, prefix="test_")
    client.close()


def test_engine_manifests_crud(manifests):
    m = EngineManifest(
        id="e1", version="v1", name="reco",
        engine_factory="pkg.mod:factory",
        description="d", files=("engine.json",),
    )
    manifests.insert(m)
    assert manifests.get("e1", "v1") == m
    assert manifests.get("e1", "v2") is None
    m2 = EngineManifest(id="e1", version="v2", name="reco",
                        engine_factory="pkg.mod:factory")
    assert manifests.update(m2) is False          # absent, no upsert
    assert manifests.update(m2, upsert=True) is True
    assert {x.version for x in manifests.get_all()} == {"v1", "v2"}
    assert manifests.delete("e1", "v1") is True
    assert manifests.delete("e1", "v1") is False
    assert manifests.get("e1", "v1") is None


# ---------------------------------------------------------------------------
# Batch views
# ---------------------------------------------------------------------------

def _ev(name, eid, props=None, minutes=0, **kw):
    return Event(
        event=name, entity_type="user", entity_id=eid,
        properties=DataMap(props or {}), event_time=T0 + timedelta(minutes=minutes),
        **kw,
    )


def test_batch_view_aggregate_properties():
    with pytest.warns(DeprecationWarning):
        view = BatchView([
            _ev("$set", "u1", {"a": 1, "b": 2}, minutes=0),
            _ev("$set", "u1", {"b": 3}, minutes=1),
            _ev("$unset", "u1", {"a": 0}, minutes=2),
            _ev("$set", "u2", {"x": 9}, minutes=0),
            _ev("$delete", "u2", minutes=5),
            _ev("rate", "u1", {"rating": 5}, minutes=3),  # non-special: no-op
        ])
    props = view.aggregate_properties("user")
    assert props["u1"].fields == {"b": 3}
    assert "u2" not in props  # $delete clears the entity


def test_batch_view_filter_start_time_exclusive():
    with pytest.warns(DeprecationWarning):
        view = BatchView([_ev("rate", "u1", minutes=m) for m in (0, 1, 2)])
    # ViewPredicates start-time predicate is exclusive (LBatchView.scala:39-41)
    out = view.filter(start_time=T0, until_time=T0 + timedelta(minutes=2))
    assert [e.event_time for e in out] == [T0 + timedelta(minutes=1)]


def test_data_view_rows():
    with pytest.warns(DeprecationWarning):
        rows = data_view([_ev("rate", "u1", {"rating": 4},
                              target_entity_type="item",
                              target_entity_id="i9")])
    assert rows[0]["event"] == "rate"
    assert rows[0]["targetEntityId"] == "i9"
    assert rows[0]["properties.rating"] == 4


# ---------------------------------------------------------------------------
# Example webhook connectors
# ---------------------------------------------------------------------------

def test_example_json_connector_user_action():
    out = ExampleJsonConnector().to_event_json({
        "type": "userAction", "userId": "as34smg4", "event": "do_something",
        "context": {"ip": "24.5.68.47"}, "anotherProperty1": 100,
        "anotherProperty2": "optional1",
        "timestamp": "2015-01-02T00:30:12.984Z",
    })
    assert out["event"] == "do_something"
    assert out["entityType"] == "user"
    assert out["entityId"] == "as34smg4"
    assert out["properties"]["anotherProperty1"] == 100
    assert "targetEntityType" not in out


def test_example_json_connector_user_action_item():
    out = ExampleJsonConnector().to_event_json({
        "type": "userActionItem", "userId": "u", "event": "view",
        "itemId": "i1", "context": {"ip": "1.2.3.4"},
        "anotherPropertyA": 4.567, "timestamp": "2015-01-15T04:20:23.567Z",
    })
    assert out["targetEntityType"] == "item"
    assert out["targetEntityId"] == "i1"
    assert out["properties"]["anotherPropertyA"] == pytest.approx(4.567)


def test_example_json_connector_rejects_unknown_type():
    with pytest.raises(ConnectorError):
        ExampleJsonConnector().to_event_json({"type": "nope"})
    with pytest.raises(ConnectorError):
        ExampleJsonConnector().to_event_json({})


def test_example_form_connector():
    out = ExampleFormConnector().to_event_json({
        "type": "userActionItem", "userId": "u", "event": "view",
        "itemId": "i1", "context[ip]": "1.2.3.4", "context[prop1]": "2.345",
        "context[prop2]": "value1", "anotherPropertyA": "4.567",
        "anotherPropertyB": "false", "timestamp": "2015-01-15T04:20:23.567Z",
    })
    assert out["properties"]["context"]["prop1"] == pytest.approx(2.345)
    assert out["properties"]["anotherPropertyB"] is False
    with pytest.raises(ConnectorError):
        ExampleFormConnector().to_event_json({"type": "bad"})
    with pytest.raises(ConnectorError):
        # userActionItem requires all context[...] fields
        ExampleFormConnector().to_event_json({
            "type": "userActionItem", "userId": "u", "event": "view",
            "itemId": "i1", "timestamp": "2015-01-15T04:20:23.567Z",
        })


# ---------------------------------------------------------------------------
# Template gate + build/unregister + FakeRun
# ---------------------------------------------------------------------------

def test_template_min_version_gate(tmp_path):
    from incubator_predictionio_tpu.cli.commands import (
        verify_template_min_version,
    )

    assert verify_template_min_version(str(tmp_path)) is None
    (tmp_path / "template.json").write_text(
        json.dumps({"pio": {"version": {"min": "0.0.1"}}})
    )
    assert verify_template_min_version(str(tmp_path)) is None
    (tmp_path / "template.json").write_text(
        json.dumps({"pio": {"version": {"min": "999.0.0"}}})
    )
    assert "999.0.0" in verify_template_min_version(str(tmp_path))


def test_build_and_unregister(tmp_path, monkeypatch):
    from incubator_predictionio_tpu.cli import commands

    monkeypatch.setenv("PIO_HOME", str(tmp_path / "home"))
    Storage.configure({
        "PIO_STORAGE_SOURCES_T_TYPE": "memory",
        "PIO_STORAGE_REPOSITORIES_METADATA_NAME": "meta",
        "PIO_STORAGE_REPOSITORIES_METADATA_SOURCE": "T",
        "PIO_STORAGE_REPOSITORIES_EVENTDATA_NAME": "event",
        "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": "T",
        "PIO_STORAGE_REPOSITORIES_MODELDATA_NAME": "model",
        "PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE": "T",
    })
    try:
        engine_dir = tmp_path / "engine"
        engine_dir.mkdir()
        (engine_dir / "engine.json").write_text(json.dumps({
            "id": "default", "version": "1",
            "engineFactory":
                "incubator_predictionio_tpu.models.recommendation:RecommendationEngine",
            "algorithms": [{"name": "als", "params": {"rank": 4}}],
        }))
        monkeypatch.chdir(engine_dir)
        manifest_id = commands.build(str(engine_dir))
        assert (engine_dir / "manifest.json").exists()
        manifests = Storage.get_meta_data_engine_manifests()
        assert len(manifests.get_all()) == 1
        assert manifests.get_all()[0].id == manifest_id
        commands.unregister(str(engine_dir))
        assert manifests.get_all() == []
        with pytest.raises(commands.CommandError):
            commands.unregister(str(engine_dir))  # already gone
    finally:
        Storage.reset()


def test_fake_run(tmp_path, monkeypatch):
    from incubator_predictionio_tpu.workflow import CoreWorkflow, FakeRun

    monkeypatch.setenv("PIO_HOME", str(tmp_path))
    Storage.configure({"PIO_STORAGE_SOURCES_T_TYPE": "memory",
                       "PIO_STORAGE_REPOSITORIES_METADATA_NAME": "m",
                       "PIO_STORAGE_REPOSITORIES_METADATA_SOURCE": "T",
                       "PIO_STORAGE_REPOSITORIES_EVENTDATA_NAME": "e",
                       "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": "T",
                       "PIO_STORAGE_REPOSITORIES_MODELDATA_NAME": "d",
                       "PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE": "T"})
    try:
        calls = []

        run = FakeRun()
        run.func = lambda ctx: calls.append(ctx)
        instance_id, result = CoreWorkflow.run_evaluation(
            run, run.engine_params_list, evaluation_class="test:fake",
        )
        assert len(calls) == 1
        assert calls[0] is not None  # func received the RuntimeContext
        assert result.no_save is True
        instance = Storage.get_meta_data_evaluation_instances().get(instance_id)
        assert instance.status == "EVALCOMPLETED"
        assert instance.evaluator_results == ""  # noSave: nothing persisted
    finally:
        Storage.reset()


def test_sigterm_exits_through_interpreter():
    """utils/lease.py: SIGTERM must unwind through finally blocks and
    exit 143 via SystemExit (abrupt death while holding the chip wedges
    the single-tenant lease; see the lease-safety contract)."""
    import subprocess
    import sys
    import time

    p = subprocess.Popen([sys.executable, "-c", (
        "from incubator_predictionio_tpu.utils.lease import "
        "install_sigterm_exit\n"
        "import time\n"
        "assert install_sigterm_exit()\n"
        "try:\n"
        "    print('ready', flush=True)\n"
        "    time.sleep(30)\n"
        "finally:\n"
        "    print('clean shutdown ran', flush=True)\n")],
        stdout=subprocess.PIPE, text=True)
    assert p.stdout.readline().strip() == "ready"
    p.terminate()
    out, _ = p.communicate(timeout=15)
    assert p.returncode == 143
    assert "clean shutdown ran" in out


def test_sigterm_inside_a_running_loop_is_raised_as_its_own_callback():
    """A SIGTERM that arrives while the main thread runs an event loop
    (every CLI server verb) must not raise in the middle of the loop's
    code: a callback that has left the ready queue and not yet run is
    lost with it, and ``asyncio.run``'s clean-up then waits for ever for
    the task it would have woken. The step the signal met finishes, and
    the loop ends with ``SystemExit(143)``."""
    import asyncio
    import signal
    import threading

    import pytest

    from incubator_predictionio_tpu.utils.lease import install_sigterm_exit

    if threading.current_thread() is not threading.main_thread():
        pytest.skip("signal handlers belong to the main thread")
    steps = []

    async def main():
        signal.raise_signal(signal.SIGTERM)     # the handler runs here
        steps.append("the step the signal met went on")
        await asyncio.sleep(30)
        steps.append("never")

    before = signal.getsignal(signal.SIGTERM)
    try:
        assert install_sigterm_exit()
        with pytest.raises(SystemExit) as e:
            asyncio.run(main())
    finally:
        signal.signal(signal.SIGTERM, before)
    assert e.value.code == 143
    assert steps == ["the step the signal met went on"]

