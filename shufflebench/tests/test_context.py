"""A configuration states the shuffle context its cells run in; without
that, a cell runs in one executor on the default conf."""

import copy
import io

import pytest

from conftest import ROOT, dropin_record_cell
from shufflebench import manifest as mf, run

@pytest.fixture
def built(monkeypatch):
    """Every ``TpuShuffleContext`` the harness builds, with the
    arguments it was given."""
    import sparkrdma_tpu.api as api

    got = []

    class Spy(api.TpuShuffleContext):
        def __init__(self, *args, **kwargs):
            got.append((args, kwargs, self))
            super().__init__(*args, **kwargs)

    monkeypatch.setattr(api, "TpuShuffleContext", Spy)
    return got


@pytest.mark.parametrize("cell", ["terasort.large", "wordcount.share"])
def test_a_config_without_context_runs_one_executor_on_the_default_conf(
        tiny_root, built, cell):
    m = mf.load(tiny_root)
    config = mf.config(m, mf.workload(m, cell)["config"], tiny_root)
    assert "context" not in config
    r = run.run_cell(cell, 11, 0.2, False, root=tiny_root,
                     require_tpu=False, log=io.StringIO())
    assert r["correct"], r["checks"]
    ((args, kwargs, ctx),) = built
    # no conf given: the context makes an empty one
    assert args == () and kwargs == {"num_executors": 1}
    assert ctx.conf.read_plane == "host"
    assert r["run"]["context"] == {"executors": 1, "conf": {},
                                   "read_plane": "host"}


@pytest.mark.parametrize("chips", [1, 4])
def test_a_config_with_context_gets_the_plane_it_names(tiny_root, built,
                                                       chips):
    name = dropin_record_cell(tiny_root, chips)
    r = run.run_cell(name, 12, 0.2, False, root=tiny_root,
                     require_tpu=False, log=io.StringIO())
    assert r["correct"], r["checks"]
    assert r["checks"]["records_misplaced"]["value"] == 0
    ((args, kwargs, ctx),) = built
    assert kwargs["num_executors"] == chips
    assert ctx.conf.read_plane == "bulk"
    assert ctx.conf.serializer_name == "columnar"
    assert len(ctx.executors) == chips
    assert ctx.bulk_session is not None
    assert r["run"]["context"] == {
        "executors": chips, "read_plane": "bulk",
        "conf": {"readPlane": "bulk", "serializer": "columnar"}}


def test_a_bad_context_does_not_run(tiny_root, built):
    m = mf.load(tiny_root)
    config = mf.config(m, "hibench_terasort", tiny_root)
    config["context"] = {"readPlan": "bulk"}
    with pytest.raises(ValueError, match="readPlan"):
        run._context(config, 1)
    assert built == []


GOOD = {"readPlane": "bulk", "serializer": "columnar"}


@pytest.mark.parametrize("edit,found", [
    (lambda c: c.update(readPlan="bulk"), "key 'readPlan'"),
    (lambda c: c.update(readPlane="rdma"), "readPlane 'rdma'"),
    (lambda c: c.update(serializer="kryo"), "serializer 'kryo'"),
    (lambda c: c.update(spillDir="/tmp/spill"), "key 'spillDir'"),
    (lambda c: c.update(trace=True), "key 'trace'"),
    (lambda c: c.update(metrics=True), "key 'metrics'"),
    (lambda c: c.update(verifyExchangeIntegrity=False),
     "key 'verifyExchangeIntegrity'"),
    (lambda c: c.update(deviceExchangeEnabled="true"),
     "deviceExchangeEnabled is 'true'"),
    (lambda c: c.update(exchangeTileBytes=True), "exchangeTileBytes is True"),
])
def test_a_bad_context_is_flagged(edit, found):
    spec = copy.deepcopy(GOOD)
    edit(spec)
    assert any(found in p for p in mf.context_problems("c", spec)), (
        mf.context_problems("c", spec))


@pytest.mark.parametrize("spec", [
    GOOD,
    {},
    {"readPlane": "host", "serializer": "pickle"},
    {"readPlane": "windowed", "deviceExchangeEnabled": False,
     "deviceExchangeWindowRounds": 0, "exchangeTileBytes": 1 << 20},
])
def test_a_sound_context_passes(spec):
    assert mf.context_problems("c", spec) == []


def test_the_accepted_configurations_state_no_context():
    m = mf.load(ROOT)
    for name in ("hibench_terasort", "hibench_wordcount"):
        assert "context" not in mf.config(m, name, ROOT)
    assert mf.problems(m) == []
