"""Persistent algorithm state: the warm-started GP-bandit (paper §6.3).

Covers the PolicyState record itself (strict decode, version/dim/fingerprint
validation), the warm-started GP fit (resume + convergence exit, cold path
pinned unchanged), cold-vs-warm suggestion equivalence through the service,
the state roundtrip through both topologies with frame counts asserted, the
corruption/version-skew fallback, and property-based metadata namespace
roundtrips via the hypothesis shim.
"""

import itertools
import json

import numpy as np
import pytest

from hypothesis import given, settings, strategies as st

from repro.core import Measurement, ScaleType, StudyConfig, Trial
from repro.core.metadata import Metadata, MetadataDelta, Namespace
from repro.pythia.gp_bandit import GaussianProcessBandit, GPBanditPolicy
from repro.pythia.policy import StudyDescriptor, SuggestRequest
from repro.pythia.state import (
    GP_BANDIT_NAMESPACE,
    STATE_KEY,
    STATE_SCHEMA_VERSION,
    PolicyState,
    StateDecodeError,
    load_state,
    store_state,
)
from repro.pythia.supporter import DatastorePolicySupporter
from repro.service import (
    DefaultVizierServer,
    DistributedVizierServer,
    VizierBatchClient,
    VizierClient,
)
from repro.service.datastore import InMemoryDatastore


def _gp_config() -> StudyConfig:
    cfg = StudyConfig()
    root = cfg.search_space.select_root()
    root.add_float_param("x", 0.0, 1.0, scale_type=ScaleType.LINEAR)
    root.add_float_param("y", 0.0, 1.0, scale_type=ScaleType.LINEAR)
    cfg.metrics.add("obj", "MAXIMIZE")
    cfg.algorithm = "GP_UCB"
    return cfg


def _objective(params: dict) -> float:
    return -((params["x"] - 0.37) ** 2) - 0.5 * (params["y"] - 0.61) ** 2


def _seed_study(client: VizierClient, n: int = 8) -> None:
    for i in range(n):
        x = (i + 1) / (n + 1.0)
        y = ((i * 3) % 7) / 7.0
        t = Trial(parameters={"x": x, "y": y})
        t.complete(Measurement(metrics={"obj": _objective({"x": x, "y": y})}))
        client.add_trial(t)


def _stored_state(datastore, study_name: str) -> PolicyState:
    md = datastore.get_study(study_name).study_config.metadata
    blob = md.abs_ns(Namespace(GP_BANDIT_NAMESPACE)).get(STATE_KEY)
    assert blob is not None, "no persisted GP-bandit state"
    return PolicyState.from_value(blob)


def _wipe_state(datastore, study_name: str) -> None:
    study = datastore.get_study(study_name)
    study.study_config.metadata.clear_ns(GP_BANDIT_NAMESPACE)
    datastore.update_study(study)


def _fit_data(n: int = 50, d: int = 3, seed: int = 0):
    rng = np.random.RandomState(seed)
    x = rng.rand(n, d)
    y = (np.sin(3 * x[:, 0]) + 0.5 * np.cos(2 * x[:, 1])
         - (x[:, 2] - 0.4) ** 2 + 0.05 * rng.randn(n))
    return x, (y - y.mean()) / (y.std() + 1e-9)


def _example_state(dim: int = 3, num_trials: int = 12, **overrides) -> PolicyState:
    vec = [0.1 * (i + 1) for i in range(dim)]
    fields = dict(
        dim=dim, num_trials=num_trials,
        raw={"log_amp": 0.25, "log_ell": vec, "log_noise": -4.0},
        adam_m={"log_amp": 0.0, "log_ell": [0.0] * dim, "log_noise": 0.01},
        adam_v={"log_amp": 0.5, "log_ell": [0.2] * dim, "log_noise": 0.3},
        adam_t=60, steps_run=60, warm_started=False, converged=True,
    )
    fields.update(overrides)
    return PolicyState(**fields)


# ---------------------------------------------------------------------------
# PolicyState record: strict decode + validation
# ---------------------------------------------------------------------------


def test_state_json_roundtrip():
    state = _example_state()
    back = PolicyState.from_value(state.to_value())
    assert back == state
    # bytes blobs (metadata values may be bytes on the wire) decode too
    assert PolicyState.from_value(state.to_value().encode()) == state


@pytest.mark.parametrize("blob", [
    None,
    b"\xff\xfe not utf-8 \x80",
    "not json at all",
    "[1, 2, 3]",
    '{"version": 1}',  # missing everything else
    json.dumps({"version": 999, "algorithm": "gp_bandit", "dim": 3}),
    # non-finite hyperparameters
    _example_state().to_value().replace("0.25", "NaN"),
    # wrong log_ell length vs dim
    json.dumps({**json.loads(_example_state().to_value()), "dim": 5}),
])
def test_state_decode_rejects_bad_blobs(blob):
    with pytest.raises(StateDecodeError):
        PolicyState.from_value(blob)


def test_state_v3_prior_levels_roundtrip():
    tree = {"log_amp": 0.1, "log_ell": [0.2, 0.3, 0.4], "log_noise": -4.0}
    state = _example_state(prior_levels=[
        {"name": "owners/o/studies/a", "num_trials": 10, "raw": tree}])
    back = PolicyState.from_value(state.to_value())
    assert back.prior_levels == [
        {"name": "owners/o/studies/a", "num_trials": 10, "raw": tree}]


@pytest.mark.parametrize("levels", [
    "not-a-list",
    ["not-a-dict"],
    [{"name": 7, "num_trials": 3,
      "raw": {"log_amp": 0.0, "log_ell": [0.0] * 3, "log_noise": 0.0}}],
    [{"name": "a", "num_trials": -1,
      "raw": {"log_amp": 0.0, "log_ell": [0.0] * 3, "log_noise": 0.0}}],
    [{"name": "a", "num_trials": 3, "raw": {"log_amp": 0.0}}],
    [{"name": "a", "num_trials": 3,
      "raw": {"log_amp": 0.0, "log_ell": [0.0] * 99, "log_noise": 0.0}}],
])
def test_state_decode_rejects_bad_prior_levels(levels):
    obj = json.loads(_example_state().to_value())
    obj["prior_levels"] = levels
    with pytest.raises(StateDecodeError):
        PolicyState.from_value(json.dumps(obj))


def test_load_prior_levels_prefix_semantics():
    """Reuse covers the longest matching (name, count) prefix; a mismatch
    invalidates that level and everything above it, never the prefix below.
    The top-level fingerprint is deliberately ignored."""
    from repro.pythia.state import load_prior_levels

    tree_a = {"log_amp": 0.1, "log_ell": [0.1] * 3, "log_noise": -4.0}
    tree_b = {"log_amp": 0.2, "log_ell": [0.2] * 3, "log_noise": -5.0}
    state = _example_state(num_trials=999, prior_levels=[
        {"name": "A", "num_trials": 10, "raw": tree_a},
        {"name": "B", "num_trials": 20, "raw": tree_b},
    ])
    md = Metadata()
    md.abs_ns(Namespace(GP_BANDIT_NAMESPACE))[STATE_KEY] = state.to_value()

    assert load_prior_levels(md, dim=3, priors=[("A", 10), ("B", 20)]) == \
        [tree_a, tree_b]
    # second prior changed: only the first level is reusable
    assert load_prior_levels(md, dim=3, priors=[("A", 10), ("B", 21)]) == \
        [tree_a]
    # first prior changed: nothing is reusable (residuals shifted downstream)
    assert load_prior_levels(md, dim=3, priors=[("A", 9), ("B", 20)]) == []
    # prior list reordered / renamed: prefix breaks at the first mismatch
    assert load_prior_levels(md, dim=3, priors=[("B", 20), ("A", 10)]) == []
    # more priors than stored levels: the stored prefix still helps
    assert load_prior_levels(md, dim=3,
                             priors=[("A", 10), ("B", 20), ("C", 5)]) == \
        [tree_a, tree_b]
    # dimension mismatch and corrupt blobs degrade to "refit everything"
    assert load_prior_levels(md, dim=4, priors=[("A", 10)]) == []
    md2 = Metadata()
    md2.abs_ns(Namespace(GP_BANDIT_NAMESPACE))[STATE_KEY] = "{corrupt"
    assert load_prior_levels(md2, dim=3, priors=[("A", 10)]) == []
    assert load_prior_levels(Metadata(), dim=3, priors=[("A", 10)]) == []


def test_state_compatibility_checks():
    state = _example_state(dim=3, num_trials=12)
    state.check_compatible(dim=3, num_trials=12)
    state.check_compatible(dim=3, num_trials=40)  # more trials now: fine
    with pytest.raises(StateDecodeError):
        state.check_compatible(dim=4, num_trials=12)  # search space changed
    with pytest.raises(StateDecodeError):
        state.check_compatible(dim=3, num_trials=5)  # datastore rewound
    with pytest.raises(StateDecodeError):
        state.check_compatible(dim=3, num_trials=12, algorithm="other")


def test_load_state_never_raises():
    md = Metadata()
    assert load_state(md, dim=3, num_trials=10) is None  # absent
    md.abs_ns(Namespace(GP_BANDIT_NAMESPACE))[STATE_KEY] = b"\x00garbage"
    assert load_state(md, dim=3, num_trials=10) is None  # corrupt
    delta = MetadataDelta()
    store_state(delta, _example_state(dim=3, num_trials=8))
    md2 = Metadata()
    md2.attach(delta.on_study)
    assert load_state(md2, dim=3, num_trials=10) is not None
    assert load_state(md2, dim=4, num_trials=10) is None  # dim skew
    assert load_state(md2, dim=3, num_trials=2) is None   # rewound store


# ---------------------------------------------------------------------------
# Warm-started fit: resume, convergence exit, cold path pinned unchanged
# ---------------------------------------------------------------------------


def test_fit_warm_start_resumes_and_converges():
    x, y = _fit_data()
    gp = GaussianProcessBandit(dim=3)
    gp.fit(x, y)
    info = gp.last_fit
    assert not info.warm and info.steps_run == gp.fit_steps and info.t == 60

    # roundtrip through the serialized record, as the service would
    state = PolicyState.from_value(
        PolicyState.from_fit(info, dim=3, num_trials=len(x)).to_value())
    for _ in range(6):  # resumed fits accumulate until the gradient plateaus
        gp.fit(x, y, init=state.fit_init())
        state = PolicyState.from_fit(gp.last_fit, dim=3, num_trials=len(x))
        if state.converged:
            break
    assert state.converged and state.warm_started
    assert state.adam_t > gp.fit_steps  # genuinely resumed, not restarted

    # once converged, a warm fit costs ONE gradient evaluation
    gp.fit(x, y, init=state.fit_init())
    assert gp.last_fit.steps_run == 1 and gp.last_fit.converged


def test_convergence_exit_cold_path_unchanged():
    """Regression (satellite fix): adding the convergence exit must not
    change what a default cold fit computes — the exit only fires when the
    MLL genuinely plateaus, which a 60-step cold trajectory never does."""
    x, y = _fit_data()
    raw_default = GaussianProcessBandit(dim=3).fit(x, y)
    gp_pinned = GaussianProcessBandit(dim=3, grad_tol=0.0)  # exit disabled
    raw_noexit = gp_pinned.fit(x, y)
    for key in raw_default:
        np.testing.assert_array_equal(np.asarray(raw_default[key]),
                                      np.asarray(raw_noexit[key]))
    gp = GaussianProcessBandit(dim=3)
    gp.fit(x, y)
    assert gp.last_fit.steps_run == gp.fit_steps and not gp.last_fit.converged


def test_warm_fit_divergence_self_heals_to_cold_init():
    """A restored point that diverges before any finite loss must NOT be
    persisted again — the checkpoint resets to the cold init so the next
    fit recovers instead of replaying the poisoned trajectory forever."""
    x = np.tile(np.array([[0.5, 0.5]]), (6, 1))
    y = np.full(6, 1e30)  # f32 overflow: first MLL evaluation is non-finite
    gp = GaussianProcessBandit(dim=2)
    poisoned = {"log_amp": 4.0, "log_ell": [-4.6, -4.6], "log_noise": -9.0}
    zeros = {"log_amp": 0.0, "log_ell": [0.0, 0.0], "log_noise": 0.0}
    gp.fit(x, y, init={"raw": poisoned, "adam_m": zeros, "adam_v": zeros,
                       "adam_t": 60})
    info = gp.last_fit
    assert info.diverged and info.warm
    # the persisted trajectory is the cold init with cold moments, not the
    # poisoned restore point
    assert float(np.asarray(info.raw["log_amp"])) == 0.0
    assert np.allclose(np.asarray(info.raw["log_ell"]), np.log(0.3))
    assert info.t == 0
    assert not np.any(np.asarray(info.m["log_ell"]))


def test_corrupt_init_is_rejected_before_fit():
    """A state blob that passes JSON decode but carries hostile values must
    be screened out by load_state (finite-ness), not crash the fit."""
    md = Metadata()
    bad = json.loads(_example_state(dim=3, num_trials=8).to_value())
    bad["raw"]["log_ell"] = [1e400, 0.1, 0.2]  # json inf
    md.abs_ns(Namespace(GP_BANDIT_NAMESPACE))[STATE_KEY] = json.dumps(bad)
    assert load_state(md, dim=3, num_trials=9) is None


# ---------------------------------------------------------------------------
# Through the service: equivalence, persistence, fallback
# ---------------------------------------------------------------------------


def test_warm_vs_cold_suggestions_agree_trial_for_trial():
    """Two identical deterministic studies; one server keeps its persisted
    state (warm path), the other has it wiped before every operation (cold
    path). Suggestions must agree trial-for-trial across rounds."""
    warm_srv = DefaultVizierServer()
    cold_srv = DefaultVizierServer()
    try:
        clients = {}
        for srv in (warm_srv, cold_srv):
            c = VizierClient.load_or_create_study(
                "equiv-state", _gp_config(), client_id="w", target=srv.address)
            _seed_study(c)
            clients[srv] = c
        name = clients[warm_srv].study_name
        for _ in range(3):
            _wipe_state(cold_srv.datastore, name)
            (tw,) = clients[warm_srv].get_suggestions(count=1)
            (tc,) = clients[cold_srv].get_suggestions(count=1)
            assert tw.parameters.as_dict() == tc.parameters.as_dict()
            metric = _objective(tw.parameters.as_dict())
            clients[warm_srv].complete_trial({"obj": metric}, trial_id=tw.id)
            clients[cold_srv].complete_trial({"obj": metric}, trial_id=tc.id)
        # the warm server's latest checkpoint really came from a warm fit...
        assert _stored_state(warm_srv.datastore, name).warm_started
        # ...and the cold server's from a cold one (its state was wiped)
        assert not _stored_state(cold_srv.datastore, name).warm_started
    finally:
        warm_srv.stop()
        cold_srv.stop()


def test_state_persists_in_process_topology():
    server = DefaultVizierServer()
    try:
        c = VizierClient.load_or_create_study(
            "inproc-state", _gp_config(), client_id="w", target=server.address)
        _seed_study(c)
        (t1,) = c.get_suggestions(count=1)
        state = _stored_state(server.datastore, c.study_name)
        assert not state.warm_started and state.num_trials == 8
        assert state.version == STATE_SCHEMA_VERSION
        c.complete_trial({"obj": 0.3}, trial_id=t1.id)
        c.get_suggestions(count=1)
        state2 = _stored_state(server.datastore, c.study_name)
        assert state2.warm_started and state2.num_trials == 9
        # the client-side metadata read surfaces the same blob
        md = c.get_study_metadata()
        assert md.abs_ns(Namespace(GP_BANDIT_NAMESPACE)).get(STATE_KEY) is not None
        c.close()
    finally:
        server.stop()


def test_state_roundtrip_remote_topology_zero_extra_frames():
    """Figure-2 split: the warm-start state rides the existing frames — the
    batch response carries the delta out, GetTrialsMulti(include_studies)
    carries it back in. Frame counts prove no UpdateMetadata/GetStudy frame
    is ever spent on it."""
    server = DistributedVizierServer()
    try:
        c = VizierClient.load_or_create_study(
            "remote-state", _gp_config(), client_id="w", target=server.address)
        _seed_study(c)
        batch = VizierBatchClient(server.address)
        (trials,) = batch.get_suggestions(
            [{"study_name": c.study_name, "client_id": "w", "count": 1}])
        state = _stored_state(server.datastore, c.study_name)
        assert not state.warm_started  # first fit on this study is cold
        c.complete_trial({"obj": 0.2}, trial_id=trials[0].id)

        server.servicer.reset_method_counts()
        server.pythia_servicer.reset_method_counts()
        (trials2,) = batch.get_suggestions(
            [{"study_name": c.study_name, "client_id": "w", "count": 1}])
        assert len(trials2) == 1
        state2 = _stored_state(server.datastore, c.study_name)
        assert state2.warm_started and state2.num_trials == 9

        pythia_counts = server.pythia_servicer.method_counts()
        api_counts = server.servicer.method_counts()
        assert pythia_counts.get("PythiaBatchSuggest") == 1
        assert api_counts.get("GetTrialsMulti") == 1
        # zero extra frames for state: no per-policy metadata RPC, no config
        # re-fetch, no trial re-fetch
        assert "UpdateMetadata" not in api_counts
        assert "GetStudy" not in api_counts
        assert "ListTrials" not in api_counts
        batch.close()
        c.close()
    finally:
        server.stop()


@pytest.mark.parametrize("blob", [
    b"\x00\xffgarbage-bytes",
    "definitely not json",
    json.dumps({"version": STATE_SCHEMA_VERSION + 7, "algorithm": "gp_bandit"}),
    json.dumps({**json.loads(_example_state(dim=7, num_trials=8,
                                            raw={"log_amp": 0.1,
                                                 "log_ell": [0.1] * 7,
                                                 "log_noise": -2.0},
                                            adam_m={"log_amp": 0.0,
                                                    "log_ell": [0.0] * 7,
                                                    "log_noise": 0.0},
                                            adam_v={"log_amp": 0.0,
                                                    "log_ell": [0.0] * 7,
                                                    "log_noise": 0.0},
                                            ).to_value())}),  # dim skew (7 != 3)
])
def test_corrupt_or_skewed_state_falls_back_to_cold_fit(blob):
    """Fault injection: a hostile/stale blob in the reserved namespace must
    never fail the suggestion operation — the fit falls back cold and the
    blob is overwritten with a fresh valid checkpoint."""
    server = DefaultVizierServer()
    try:
        c = VizierClient.load_or_create_study(
            "fallback-state", _gp_config(), client_id="w", target=server.address)
        _seed_study(c)
        delta = MetadataDelta()
        delta.assign(GP_BANDIT_NAMESPACE, STATE_KEY, blob)
        c.update_metadata(delta)  # plant the bad blob through the client API

        (t,) = c.get_suggestions(count=1)  # must not error
        assert t.id >= 1
        state = _stored_state(server.datastore, c.study_name)
        assert not state.warm_started  # fell back to the cold path
        assert state.version == STATE_SCHEMA_VERSION  # fresh valid checkpoint
        c.close()
    finally:
        server.stop()


def test_update_metadata_reports_skipped_dead_trials():
    """A per-trial update naming a dead trial must not fail the whole delta
    (the study half applies) but IS surfaced in the response."""
    server = DefaultVizierServer()
    try:
        c = VizierClient.load_or_create_study(
            "skipped-md", _gp_config(), client_id="w", target=server.address)
        delta = MetadataDelta()
        delta.assign("user.ns", "k", "v")
        delta.assign("user.ns", "k2", "v2", trial_id=9999)  # never existed
        skipped = c.update_metadata(delta)
        assert skipped == [9999]
        assert c.get_study_metadata().abs_ns(Namespace("user.ns")).get("k") == "v"
        c.close()
    finally:
        server.stop()


def test_warm_start_disabled_writes_no_state():
    ds = InMemoryDatastore()
    from repro.core.study import Study

    cfg = _gp_config()
    study = Study(name="owners/o/studies/nostate", study_config=cfg)
    ds.create_study(study)
    for i in range(8):
        x = (i + 1) / 9.0
        t = Trial(parameters={"x": x, "y": 0.5})
        t.complete(Measurement(metrics={"obj": -(x - 0.4) ** 2}))
        ds.create_trial(study.name, t)
    supporter = DatastorePolicySupporter(ds, study.name)
    policy = GPBanditPolicy(supporter, warm_start=False)
    descriptor = StudyDescriptor(config=cfg, guid=study.name)
    decision = policy.suggest(SuggestRequest(study_descriptor=descriptor, count=1))
    assert decision.metadata.empty()
    md = ds.get_study(study.name).study_config.metadata
    assert GP_BANDIT_NAMESPACE not in {ns.encode() for ns in md.namespaces()}


# ---------------------------------------------------------------------------
# Property-based metadata namespace roundtrips (hypothesis shim)
# ---------------------------------------------------------------------------

_ns_component = st.composite(
    lambda draw: draw(st.text(min_size=0, max_size=8)).replace(":", "_"))
_namespace = st.composite(
    lambda draw: ":".join(draw(st.lists(_ns_component(), min_size=0, max_size=3))))
_key = st.text(min_size=1, max_size=12)
_small_value = st.one_of(
    st.text(min_size=0, max_size=30),
    st.composite(lambda draw: draw(st.text(min_size=0, max_size=30)).encode())(),
)
# oversized values: tens of KiB, both str and bytes
_big_value = st.composite(
    lambda draw: draw(st.text(min_size=8, max_size=64))
    * draw(st.integers(min_value=256, max_value=2048)))
_value = st.one_of(_small_value, _big_value())


@settings(max_examples=40)
@given(ns=_namespace(), key=_key, value=_value)
def test_metadata_namespace_get_set_roundtrip_property(ns, key, value):
    md = Metadata()
    md.abs_ns(Namespace(ns))[key] = value
    assert md.abs_ns(Namespace(ns))[key] == value
    assert key in md.abs_ns(Namespace(ns))
    back = Metadata.from_proto(md.to_proto())
    assert back == md
    assert back.abs_ns(Namespace(ns)).get(key) == value


@settings(max_examples=40)
@given(entries=st.lists(
    st.tuples(_namespace(), _key, _small_value,
              st.one_of(st.sampled_from([None]), st.integers(1, 5))),
    min_size=0, max_size=8))
def test_metadata_delta_merge_roundtrip_property(entries):
    """assign() + to_proto/from_proto + attach == last-wins merge, for both
    study-level and per-trial updates."""
    delta = MetadataDelta()
    expect_study, expect_trial = {}, {}
    for ns, key, value, trial_id in entries:
        delta.assign(ns, key, value, trial_id=trial_id)
        if trial_id is None:
            expect_study[(ns, key)] = value
        else:
            expect_trial[(trial_id, ns, key)] = value
    assert delta.empty() == (not expect_study and not expect_trial)
    back = MetadataDelta.from_proto(delta.to_proto())
    merged = Metadata()
    merged.attach(back.on_study)
    for (ns, key), value in expect_study.items():
        assert merged.abs_ns(Namespace(ns)).get(key) == value
    for (trial_id, ns, key), value in expect_trial.items():
        assert back.on_trials[trial_id].abs_ns(Namespace(ns)).get(key) == value


def test_update_metadata_rpc_roundtrip_property():
    """Unicode keys and empty/oversized values survive the full wire path:
    UpdateMetadata over a real socket, msgpack framing, datastore, GetStudy."""
    server = DefaultVizierServer()
    counter = itertools.count()
    try:
        @settings(max_examples=15)
        @given(ns=_namespace(), key=_key, value=_value)
        def prop(ns, key, value):
            c = VizierClient.load_or_create_study(
                f"md-prop-{next(counter)}", _gp_config(), client_id="w",
                target=server.address)
            delta = MetadataDelta()
            delta.assign(ns, key, value)
            c.update_metadata(delta)
            back = c.get_study_metadata()
            assert back.abs_ns(Namespace(ns)).get(key) == value
            c.close()

        prop()
    finally:
        server.stop()
