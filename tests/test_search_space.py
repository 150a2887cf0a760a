"""Search space: unit mapping, scaling, conditionals, proto roundtrip.

Property tests (hypothesis) cover the core invariants:
  * from_unit(u) is always feasible; to_unit(from_unit(u)) ~ u for DOUBLEs
  * samples always validate
  * proto roundtrips are exact
"""

import math
import random

import pytest
from hypothesis import given, settings, strategies as st

from repro.core import (
    ParameterConfig,
    ParameterDict,
    ParameterType,
    ParameterValue,
    ScaleType,
    SearchSpace,
    lehmer_decode,
    subset_decode,
)


@st.composite
def double_configs(draw):
    lo = draw(st.floats(min_value=1e-6, max_value=1e3, allow_nan=False))
    hi = lo * draw(st.floats(min_value=1.0 + 1e-6, max_value=1e4))
    scale = draw(st.sampled_from([ScaleType.LINEAR, ScaleType.LOG,
                                  ScaleType.REVERSE_LOG, None]))
    return ParameterConfig("x", ParameterType.DOUBLE, bounds=(lo, hi),
                           scale_type=scale)


@given(double_configs(), st.floats(min_value=0, max_value=1))
@settings(max_examples=200, deadline=None)
def test_unit_roundtrip_double(cfg, u):
    v = cfg.from_unit(u)
    assert cfg.contains(v), (cfg.scale_type, u, v)
    u2 = cfg.to_unit(v)
    assert math.isclose(u, u2, abs_tol=1e-6), (cfg.scale_type, u, u2)


@given(st.integers(min_value=-50, max_value=50), st.integers(min_value=0, max_value=100),
       st.floats(min_value=0, max_value=1))
@settings(max_examples=100, deadline=None)
def test_unit_integer_feasible(lo, span, u):
    cfg = ParameterConfig("n", ParameterType.INTEGER, bounds=(lo, lo + span))
    v = cfg.from_unit(u)
    assert cfg.contains(v)
    assert isinstance(v.value, int)


@given(st.lists(st.floats(min_value=-1e3, max_value=1e3, allow_nan=False),
                min_size=1, max_size=10, unique=True),
       st.floats(min_value=0, max_value=1))
@settings(max_examples=100, deadline=None)
def test_discrete_from_unit_feasible(values, u):
    cfg = ParameterConfig("d", ParameterType.DISCRETE, feasible_values=values)
    v = cfg.from_unit(u)
    assert cfg.contains(v)


def test_log_scaling_shape():
    cfg = ParameterConfig("lr", ParameterType.DOUBLE, bounds=(1e-3, 10.0),
                          scale_type=ScaleType.LOG)
    # log scaling: geometric midpoint at u=0.5
    assert math.isclose(cfg.from_unit(0.5).as_float, 0.1, rel_tol=1e-6)
    assert math.isclose(cfg.to_unit(ParameterValue(0.1)), 0.5, abs_tol=1e-9)


def test_validation_errors():
    with pytest.raises(ValueError):
        ParameterConfig("x", ParameterType.DOUBLE, bounds=(2.0, 1.0))
    with pytest.raises(ValueError):
        ParameterConfig("x", ParameterType.DOUBLE, bounds=(-1.0, 1.0),
                        scale_type=ScaleType.LOG)  # log needs positive domain
    with pytest.raises(ValueError):
        ParameterConfig("x", ParameterType.CATEGORICAL, categories=["a", "a"])
    with pytest.raises(ValueError):
        ParameterConfig("x", ParameterType.INTEGER, bounds=(0, 10),
                        default_value=11)


@pytest.mark.parametrize("scale", [ScaleType.LOG, ScaleType.REVERSE_LOG,
                                   ScaleType.LINEAR])
def test_categorical_with_scale_type_raises_clean_valueerror(scale):
    """Regression: a CATEGORICAL config with a scale_type used to crash with
    TypeError (min() over feasible_values=None in the LOG-domain check)
    before reaching the intended ValueError. The check order is now fixed."""
    with pytest.raises(ValueError, match="cannot have a scale_type"):
        ParameterConfig("act", ParameterType.CATEGORICAL,
                        categories=["relu", "gelu"], scale_type=scale)


@given(st.one_of(
    st.floats(min_value=-1e9, max_value=1e9, allow_nan=False,
              allow_infinity=False),
    st.integers(min_value=-10**9, max_value=10**9),
    st.text(max_size=20),
))
@settings(max_examples=200, deadline=None)
def test_parameter_value_proto_roundtrip_preserves_type(v):
    """Regression: integral DOUBLE values used to demote to int through the
    wire (3.0 -> 3), so as_dict() returned a different type than was set.
    (Bools are excluded: they serialize as "true"/"false" strings by design.)"""
    back = ParameterValue.from_proto(ParameterValue(v).to_proto())
    assert back.value == v
    assert type(back.value) is type(v)


def test_conditional_activation(conditional_config):
    space = conditional_config.search_space
    p = ParameterDict.from_dict({"model": "dnn", "num_layers": 3, "dropout": 0.1})
    space.validate_parameters(p)
    # forest params under dnn assignment must be rejected
    bad = ParameterDict.from_dict({"model": "dnn", "num_trees": 50,
                                   "num_layers": 3, "dropout": 0.1})
    with pytest.raises(ValueError):
        space.validate_parameters(bad)
    # missing active child
    missing = ParameterDict.from_dict({"model": "dnn", "num_layers": 2})
    with pytest.raises(ValueError):
        space.validate_parameters(missing)


@given(st.integers(min_value=0, max_value=10**6))
@settings(max_examples=100, deadline=None)
def test_conditional_sampling_always_valid(seed):
    space = SearchSpace()
    root = space.select_root()
    m = root.add_categorical_param("m", ["a", "b"])
    m.select_values(["a"]).add_float_param("fa", 0, 1)
    m.select_values(["b"]).add_int_param("ib", 0, 5)
    params = space.sample(random.Random(seed))
    space.validate_parameters(params)
    assert ("fa" in params) == (params["m"].as_str == "a")
    assert ("ib" in params) == (params["m"].as_str == "b")


@given(st.integers(min_value=0, max_value=10**6))
@settings(max_examples=50, deadline=None)
def test_space_proto_roundtrip(seed):
    space = SearchSpace()
    root = space.select_root()
    root.add_float_param("lr", 1e-4, 1e-1, scale_type=ScaleType.LOG)
    root.add_discrete_param("bs", [16, 32, 64])
    cat = root.add_categorical_param("opt", ["adam", "sgd"], default_value="adam")
    cat.select_values(["sgd"]).add_float_param("momentum", 0.0, 0.99)
    proto = space.to_proto()
    space2 = SearchSpace.from_proto(proto)
    assert space2.to_proto() == proto
    params = space2.sample(random.Random(seed))
    space.validate_parameters(params)


# -- combinatorial reparameterization (paper Appendix A.1.1) ----------------


@given(st.integers(min_value=1, max_value=8), st.integers(min_value=0, max_value=10**6))
@settings(max_examples=100, deadline=None)
def test_lehmer_decode_is_permutation(n, seed):
    rng = random.Random(seed)
    code = [rng.randrange(n - i) for i in range(n)]
    perm = lehmer_decode(code)
    assert sorted(perm) == list(range(n))


@given(st.integers(min_value=2, max_value=10), st.integers(min_value=0, max_value=10**6))
@settings(max_examples=100, deadline=None)
def test_subset_decode(n, seed):
    rng = random.Random(seed)
    k = rng.randint(1, n)
    code = [rng.randrange(n - i) for i in range(k)]
    sub = subset_decode(code, n)
    assert len(set(sub)) == k and all(0 <= s < n for s in sub)
