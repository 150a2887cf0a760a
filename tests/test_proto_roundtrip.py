"""Wire-format roundtrips for Trials/Measurements/StudyConfigs (hypothesis)."""

from hypothesis import given, settings, strategies as st

from repro.core import (
    Measurement,
    Metadata,
    MetricInformation,
    ObjectiveMetricGoal,
    StudyConfig,
    Trial,
    TrialState,
    converters,
)

metric_values = st.floats(allow_nan=False, allow_infinity=False,
                          min_value=-1e12, max_value=1e12)
param_values = st.one_of(
    st.floats(allow_nan=False, allow_infinity=False, min_value=-1e9, max_value=1e9),
    st.integers(min_value=-10**9, max_value=10**9),
    st.text(min_size=0, max_size=20),
)


@st.composite
def measurements(draw):
    metrics = draw(st.dictionaries(st.text(min_size=1, max_size=10),
                                   metric_values, max_size=4))
    return Measurement(metrics=metrics,
                       steps=draw(st.integers(min_value=0, max_value=10**6)),
                       elapsed_secs=draw(st.floats(min_value=0, max_value=1e6)))


@st.composite
def trials(draw):
    t = Trial(
        id=draw(st.integers(min_value=1, max_value=10**6)),
        parameters=draw(st.dictionaries(st.text(min_size=1, max_size=8),
                                        param_values, max_size=5)),
    )
    for m in draw(st.lists(measurements(), max_size=3)):
        t.add_measurement(m)
    if draw(st.booleans()):
        t.complete(draw(measurements()))
    elif draw(st.booleans()):
        t.complete(infeasibility_reason="broken")
    t.metadata.ns("algo")["state"] = draw(st.text(max_size=30))
    return t


@given(trials())
@settings(max_examples=150, deadline=None)
def test_trial_roundtrip(trial):
    proto = trial.to_proto()
    back = Trial.from_proto(proto)
    assert back.to_proto() == proto
    assert back.id == trial.id
    assert back.state == trial.state
    assert back.parameters.as_dict() == trial.parameters.as_dict()
    assert back.metadata == trial.metadata


@given(measurements())
@settings(max_examples=100, deadline=None)
def test_measurement_roundtrip(m):
    assert Measurement.from_proto(m.to_proto()).to_proto() == m.to_proto()


def test_study_config_roundtrip(basic_config):
    proto = basic_config.to_proto()
    back = StudyConfig.from_proto(proto)
    assert back.to_proto() == proto
    assert back.algorithm == basic_config.algorithm
    assert [m.name for m in back.metrics] == [m.name for m in basic_config.metrics]


def test_converter_objects_match_paper_table2(basic_config):
    t = Trial(id=3, parameters={"a": 1.5})
    assert converters.TrialConverter.from_proto(
        converters.TrialConverter.to_proto(t)).id == 3
    protos = converters.TrialConverter.to_protos([t, t])
    assert len(converters.TrialConverter.from_protos(protos)) == 2
    mi = MetricInformation("m", ObjectiveMetricGoal.MINIMIZE)
    assert converters.MetricInformationConverter.from_proto(mi.to_proto()).goal \
        == ObjectiveMetricGoal.MINIMIZE


@st.composite
def search_spaces(draw):
    """Random search spaces: every parameter kind, every scale type."""
    from repro.core import ScaleType, SearchSpace

    space = SearchSpace()
    root = space.select_root()
    n_params = draw(st.integers(min_value=1, max_value=5))
    for i in range(n_params):
        kind = draw(st.sampled_from(["float", "log_float", "int",
                                     "categorical", "discrete"]))
        name = f"p{i}_{kind}"
        if kind == "float":
            lo = draw(st.floats(min_value=-1e6, max_value=1e6,
                                allow_nan=False, allow_infinity=False))
            span = draw(st.floats(min_value=1e-6, max_value=1e6,
                                  allow_nan=False, allow_infinity=False))
            root.add_float_param(name, lo, lo + span,
                                 scale_type=ScaleType.LINEAR)
        elif kind == "log_float":
            lo = draw(st.floats(min_value=1e-9, max_value=1e3,
                                allow_nan=False, allow_infinity=False))
            factor = draw(st.floats(min_value=1.5, max_value=1e6,
                                    allow_nan=False, allow_infinity=False))
            root.add_float_param(name, lo, lo * factor,
                                 scale_type=ScaleType.LOG)
        elif kind == "int":
            lo = draw(st.integers(min_value=-1000, max_value=1000))
            span = draw(st.integers(min_value=0, max_value=1000))
            root.add_int_param(name, lo, lo + span)
        elif kind == "categorical":
            values = draw(st.lists(st.text(min_size=1, max_size=6),
                                   min_size=1, max_size=5, unique=True))
            root.add_categorical_param(name, values)
        else:
            values = sorted(draw(st.lists(
                st.floats(min_value=-1e6, max_value=1e6, allow_nan=False,
                          allow_infinity=False),
                min_size=1, max_size=6, unique=True)))
            root.add_discrete_param(name, values)
    return space


@st.composite
def study_configs(draw):
    from repro.core import StudyConfig

    cfg = StudyConfig()
    cfg.search_space = draw(search_spaces())
    n_metrics = draw(st.integers(min_value=1, max_value=3))
    for i in range(n_metrics):
        cfg.metrics.add(
            f"m{i}", draw(st.sampled_from(["MAXIMIZE", "MINIMIZE"])),
            safety_threshold=draw(st.one_of(
                st.sampled_from([None]),   # shim-safe st.none()
                st.floats(min_value=-1e6, max_value=1e6, allow_nan=False,
                          allow_infinity=False))))
    cfg.algorithm = draw(st.sampled_from(
        ["RANDOM_SEARCH", "GP_UCB", "GRID_SEARCH", "CMA_ES"]))
    return cfg


@given(study_configs())
@settings(max_examples=40, deadline=None)
def test_study_config_roundtrip_property(cfg):
    """Arbitrary StudyConfigs — multi-metric, safety thresholds and all —
    survive the wire format bit-for-bit."""
    proto = cfg.to_proto()
    back = StudyConfig.from_proto(proto)
    assert back.to_proto() == proto
    assert back.algorithm == cfg.algorithm
    assert [m.name for m in back.metrics] == [m.name for m in cfg.metrics]
    assert [m.safety_threshold for m in back.metrics] == \
        [m.safety_threshold for m in cfg.metrics]
    assert len(back.search_space.parameters) == len(cfg.search_space.parameters)


def test_metrics_add_safety_threshold_and_duplicates():
    """MetricsConfig.add accepts safety_threshold (it used to silently lack
    the parameter), and duplicate metric ids are rejected on BOTH build
    paths — .add() and from_proto (which used to bare-append around the
    check, roundtripping ambiguous studies)."""
    import pytest

    cfg = StudyConfig()
    mi = cfg.metrics.add("safe_m", "MAXIMIZE", safety_threshold=0.25)
    assert mi.safety_threshold == 0.25
    assert StudyConfig.from_proto(cfg.to_proto()).metrics[0].safety_threshold \
        == 0.25
    with pytest.raises(ValueError, match="duplicate metric"):
        cfg.metrics.add("safe_m", "MINIMIZE")
    proto = cfg.to_proto()
    proto["metrics"].append(dict(proto["metrics"][0]))
    with pytest.raises(ValueError, match="duplicate metric"):
        StudyConfig.from_proto(proto)


@given(search_spaces(), st.integers(min_value=0, max_value=10**6))
@settings(max_examples=40, deadline=None)
def test_search_space_sampling_within_bounds(space, seed):
    """Every sampled assignment is feasible: in-bounds for continuous/int
    params, a member of the feasible set for categorical/discrete — and the
    space's own validator agrees, before and after a proto roundtrip."""
    import random as _random

    from repro.core import SearchSpace

    params = space.sample(_random.Random(seed))
    space.validate_parameters(params)
    by_name = {c.name: c for c in space.parameters}
    for name, value in params.items():
        cfg = by_name[name]
        if cfg.bounds is not None:
            lo, hi = cfg.bounds
            assert lo <= value.as_float <= hi, (name, value)
        elif cfg.categories is not None:
            assert value.as_str in cfg.categories
        else:
            assert value.as_float in cfg.feasible_values
    # same space after a wire roundtrip accepts the same assignment
    back = SearchSpace.from_proto(space.to_proto())
    back.validate_parameters(params)


@st.composite
def conditional_spaces(draw):
    """Random conditional trees: categorical parents, mixed-kind children,
    occasional grandchildren."""
    from repro.core import SearchSpace

    space = SearchSpace()
    root = space.select_root()
    for i in range(draw(st.integers(min_value=1, max_value=3))):
        cats = [f"c{j}" for j in range(draw(st.integers(min_value=2, max_value=4)))]
        parent = root.add_categorical_param(f"p{i}", cats)
        for k in range(draw(st.integers(min_value=0, max_value=3))):
            matches = draw(st.lists(st.sampled_from(cats), min_size=1,
                                    max_size=len(cats), unique=True))
            scope = parent.select_values(matches)
            name = f"p{i}_ch{k}"
            kind = draw(st.sampled_from(["float", "int", "cat"]))
            if kind == "float":
                scope.add_float_param(name, 0.0, 1.0)
            elif kind == "int":
                scope.add_int_param(name, 0, 5)
            else:
                sub = scope.add_categorical_param(name, ["x", "y"])
                if draw(st.booleans()):  # grandchild: depth-2 conditionality
                    sub.select_values(["x"]).add_float_param(
                        f"{name}_g", 0.0, 2.0)
    return space


def _tree_shape(space):
    """The conditional tree as a comparable value: names, types, and the
    parent-value matches guarding each child, recursively."""
    def shape(cfg):
        return (cfg.name, cfg.type.value, tuple(
            (tuple(matches), shape(child)) for matches, child in cfg.children))
    return tuple(shape(c) for c in space.parameters)


@given(conditional_spaces(), st.integers(min_value=0, max_value=10**6))
@settings(max_examples=40, deadline=None)
def test_conditional_tree_proto_roundtrip(space, seed):
    """from_proto(to_proto(space)) preserves the conditional tree exactly,
    and samples drawn from the roundtripped space validate in the original."""
    import random as _random

    from repro.core import SearchSpace

    proto = space.to_proto()
    back = SearchSpace.from_proto(proto)
    assert back.to_proto() == proto
    assert _tree_shape(back) == _tree_shape(space)
    space.validate_parameters(back.sample(_random.Random(seed)))


def test_prior_study_names_roundtrip(basic_config):
    basic_config.prior_studies = [
        "owners/o/studies/a", "owners/o/studies/b", "owners/o/studies/a"]
    assert basic_config.prior_study_names == [
        "owners/o/studies/a", "owners/o/studies/b"]  # deduped, order kept
    back = StudyConfig.from_proto(basic_config.to_proto())
    assert back.prior_study_names == basic_config.prior_study_names
    # empty stays absent from the wire form
    assert "prior_study_names" not in StudyConfig().to_proto()


def test_metadata_namespaces():
    md = Metadata()
    md["top"] = "1"
    sub = md.ns("gp")
    sub["state"] = "xyz"
    sub2 = md.ns("gp")
    assert sub2["state"] == "xyz"
    assert "top" not in sub2
    proto = md.to_proto()
    back = Metadata.from_proto(proto)
    assert back == md
    assert back.ns("gp")["state"] == "xyz"
