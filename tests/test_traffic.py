"""Traffic-subsystem tests: spec hierarchy, generator properties, transforms.

Property-based (hypothesis, with the offline fallback shim): every generator
must produce non-negative loads, calibrate its sample mean to the spec's
analytic mean within sampling tolerance, keep ext_frac in (0, 1], reproduce
bit-identically from the same seed, and match its eager path under jit.
The transform satellites (validated slice_trace, load-weighted
concat_traces, clear stack/pad errors) are pinned here too.
"""
try:                                     # pragma: no cover - env dependent
    from hypothesis import given, settings, strategies as st
except ImportError:                      # minimal container: use shim
    from hypothesis_fallback import given, settings, strategies as st

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import traffic
from repro.core.constants import NETWORK
from repro.core.traffic import (ALL_SYNTHETIC_SPECS, BurstySpec, HotspotSpec,
                                ParsecSpec, PermutationSpec, UniformSpec,
                                as_spec, expected_mean_ext_load, generate,
                                permutation_destinations)

CFG9 = NETWORK.with_topology(n_chiplets=9)


def _spec_of(kind: str, mean_load: float, n_intervals: int, aux: float):
    """Build one spec of each family from drawn parameters."""
    if kind == "uniform":
        return UniformSpec(mean_load=mean_load, cv=aux,
                           n_intervals=n_intervals)
    if kind == "hotspot":
        return HotspotSpec(mean_load=mean_load, hotspot_frac=0.3 + 0.5 * aux,
                           n_hotspots=1 + int(aux > 0.5),
                           n_intervals=n_intervals)
    if kind == "bursty":
        return BurstySpec(mean_load=mean_load, p_on=0.2 + 0.6 * aux,
                          p_off=0.8 - 0.6 * aux, n_intervals=n_intervals)
    if kind == "parsec":
        apps = traffic.APP_NAMES
        return ParsecSpec(app=apps[int(aux * (len(apps) - 1))],
                          n_intervals=n_intervals)
    return PermutationSpec(
        pattern=traffic.PERMUTATION_PATTERNS[
            int(aux * (len(traffic.PERMUTATION_PATTERNS) - 1))],
        mean_load=mean_load, n_intervals=n_intervals)


@settings(max_examples=10, deadline=None)
@given(st.sampled_from(["uniform", "hotspot", "bursty", "permutation",
                        "parsec"]),
       st.floats(min_value=0.005, max_value=0.05),
       st.integers(min_value=8, max_value=48),
       st.floats(min_value=0.0, max_value=1.0),
       st.integers(min_value=0, max_value=1 << 16))
def test_generator_properties(kind, mean_load, n_intervals, aux, seed):
    spec = _spec_of(kind, mean_load, n_intervals, aux)
    key = jax.random.PRNGKey(seed)
    tr = generate(spec, key, CFG9)

    ext = np.asarray(tr["ext_load"])
    assert ext.shape == (spec.n_intervals, CFG9.n_chiplets)
    assert np.all(ext >= 0), f"{spec} produced negative ext load"
    assert np.all(np.asarray(tr["int_load"]) >= 0)
    assert np.all(np.asarray(tr["mem_load"]) >= 0)
    assert np.all(np.isfinite(ext))

    frac = float(tr["ext_frac"])
    assert 0.0 < frac <= 1.0, f"{spec} ext_frac {frac} outside (0, 1]"

    # Seed reproducibility: same key -> bit-identical trace.
    tr2 = generate(spec, key, CFG9)
    np.testing.assert_array_equal(ext, np.asarray(tr2["ext_load"]))

    # jit-generation parity with the eager path.
    eager = generate(spec, key, CFG9, jit=False)
    np.testing.assert_allclose(ext, np.asarray(eager["ext_load"]),
                               rtol=1e-5, atol=1e-8)


def test_mean_load_calibration():
    """Sample mean of ext_load lands near the analytic calibration target.

    Long traces keep the sampling error small: tolerance is 15% for the
    i.i.d. generators and 35% for bursty (autocorrelated duty cycle).
    """
    specs = [UniformSpec(mean_load=0.03, n_intervals=256),
             HotspotSpec(mean_load=0.03, n_intervals=256),
             PermutationSpec(pattern="transpose", mean_load=0.03,
                             n_intervals=256),
             PermutationSpec(pattern="tornado", mean_load=0.03,
                             n_intervals=256),
             BurstySpec(mean_load=0.03, n_intervals=512)]
    for i, spec in enumerate(specs):
        tr = generate(spec, jax.random.PRNGKey(100 + i), CFG9)
        got = float(np.mean(np.asarray(tr["ext_load"])))
        want = expected_mean_ext_load(spec, CFG9)
        tol = 0.35 if isinstance(spec, BurstySpec) else 0.15
        assert abs(got - want) <= tol * want, \
            f"{spec.name}: sample mean {got:.5f} vs calibrated {want:.5f}"


def test_permutation_self_pairs_divert_to_intra():
    """Transpose diagonal chiplets inject zero ext (their load is intra)."""
    dst = permutation_destinations("transpose", 9)
    self_paired = np.flatnonzero(dst == np.arange(9))
    assert self_paired.tolist() == [0, 4, 8]      # 3x3 grid diagonal
    tr = generate(PermutationSpec(pattern="transpose", n_intervals=16),
                  jax.random.PRNGKey(0), CFG9)
    ext = np.asarray(tr["ext_load"])
    assert np.all(ext[:, self_paired] == 0)
    others = [i for i in range(9) if i not in self_paired]
    assert np.all(ext[:, others] > 0)
    assert np.all(np.asarray(tr["int_load"])[:, self_paired] > 0)
    # tornado/neighbor have no self pairs on 9 chiplets
    for pattern in ("tornado", "neighbor"):
        assert not np.any(permutation_destinations(pattern, 9)
                          == np.arange(9))


def test_bursty_is_actually_bursty():
    """The on/off chain produces zero-load intervals and on-load bursts."""
    spec = BurstySpec(mean_load=0.02, p_on=0.2, p_off=0.3, n_intervals=128)
    tr = generate(spec, jax.random.PRNGKey(7), CFG9)
    ext = np.asarray(tr["ext_load"])
    off_frac = np.mean(ext == 0)
    assert 0.2 < off_frac < 0.9, f"off fraction {off_frac} not bursty"


def test_spec_validation_errors():
    with pytest.raises(ValueError, match="unknown PARSEC app"):
        ParsecSpec(app="nosuchapp")
    with pytest.raises(ValueError, match="mean_load"):
        UniformSpec(mean_load=0.0)
    with pytest.raises(ValueError, match="ext_frac"):
        UniformSpec(ext_frac=1.5)
    with pytest.raises(ValueError, match="n_intervals"):
        UniformSpec(n_intervals=0)
    with pytest.raises(ValueError, match="pattern"):
        PermutationSpec(pattern="zigzag")
    with pytest.raises(ValueError, match="p_on"):
        BurstySpec(p_on=0.0)
    with pytest.raises(ValueError, match="hotspot_frac"):
        HotspotSpec(hotspot_frac=1.0)
    with pytest.raises(TypeError, match="TrafficSpec"):
        as_spec(42)


def test_as_spec_coercion():
    s = as_spec("dedup", n_intervals=17)
    assert isinstance(s, ParsecSpec) and s.n_intervals == 17
    assert as_spec(s) is s


def test_specs_are_hashable_static_keys():
    """Specs must work as jit static args / cache keys (frozen + hashable)."""
    a = UniformSpec(mean_load=0.02)
    b = UniformSpec(mean_load=0.02)
    assert hash(a) == hash(b) and a == b
    assert len({s for s in ALL_SYNTHETIC_SPECS}) == len(ALL_SYNTHETIC_SPECS)


# ---------------------------------------------------------------------------
# Transforms (the satellite fixes)
# ---------------------------------------------------------------------------

def test_slice_trace_validates_inputs():
    with pytest.raises(TypeError, match="trace dict"):
        traffic.slice_trace([1, 2, 3], 2)
    with pytest.raises(ValueError, match="missing.*mem_load"):
        traffic.slice_trace({"ext_load": jnp.zeros((4, 4)),
                             "int_load": jnp.zeros((4, 4)),
                             "ext_frac": 0.4}, 2)
    tr = traffic.generate_trace("dedup", 8, jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match="chiplets"):
        traffic.slice_trace(tr, 99)
    sl = traffic.slice_trace(tr, 2)
    assert sl["ext_load"].shape == (8, 2)


def test_concat_traces_load_weighted_ext_frac():
    """ext_frac is weighted by each segment's total ext load, so a
    near-idle segment cannot drag the composite fraction to its value."""
    heavy = traffic.generate_trace("blackscholes", 20, jax.random.PRNGKey(0))
    light = traffic.generate_trace("facesim", 20, jax.random.PRNGKey(1))
    out = traffic.concat_traces([heavy, light])
    f_heavy = float(heavy["ext_frac"])      # 0.40
    f_light = float(light["ext_frac"])      # 0.25
    f = float(out["ext_frac"])
    unweighted = 0.5 * (f_heavy + f_light)
    w_h = float(jnp.sum(heavy["ext_load"]))
    w_l = float(jnp.sum(light["ext_load"]))
    expected = (f_heavy * w_h + f_light * w_l) / (w_h + w_l)
    np.testing.assert_allclose(f, expected, rtol=1e-5)
    # blackscholes dominates the load, so the weighted frac sits close to
    # its fraction — and strictly above the old unweighted mean.
    assert f > unweighted
    assert out["ext_load"].shape[0] == 40
    assert out["app"] == "blackscholes+facesim"


def test_concat_traces_carries_unknown_keys():
    a = traffic.generate_trace("dedup", 6, jax.random.PRNGKey(0))
    b = traffic.generate_trace("dedup", 4, jax.random.PRNGKey(1))
    a2 = dict(a, phase_id=jnp.arange(6), tag="x")
    b2 = dict(b, phase_id=jnp.arange(4), tag="x")
    out = traffic.concat_traces([a2, b2])
    assert out["phase_id"].shape == (10,)     # per-interval arrays concat
    assert out["tag"] == "x"                  # constants carry through
    # a partial key raises instead of being silently dropped
    with pytest.raises(ValueError, match="only 1/2 segments"):
        traffic.concat_traces([dict(a, extra=1.0), b])
    # conflicting non-array constants raise
    with pytest.raises(ValueError, match="differs across segments"):
        traffic.concat_traces([dict(a, tag="x"), dict(b, tag="y")])


def test_pad_trace_and_length():
    tr = traffic.generate_trace("dedup", 10, jax.random.PRNGKey(0))
    assert traffic.trace_length(tr) == 10
    padded = traffic.pad_trace(tr, 16)
    assert padded["ext_load"].shape == (16, NETWORK.n_chiplets)
    np.testing.assert_array_equal(
        np.asarray(padded["t_mask"]), [1.0] * 10 + [0.0] * 6)
    assert traffic.trace_length(padded) == 10
    assert np.all(np.asarray(padded["ext_load"])[10:] == 0)
    # idempotent re-pad extends the mask
    again = traffic.pad_trace(padded, 20)
    assert traffic.trace_length(again) == 10
    with pytest.raises(ValueError, match="cannot pad"):
        traffic.pad_trace(tr, 4)


_LOAD_KEYS = ("ext_load", "mem_load", "int_load", "t_mask")


def _pad_and_chunk(tr):
    return [traffic.pad_trace(tr, 16)] \
        + list(traffic.chunk_trace(tr, 8, pad=True))


@pytest.mark.parametrize("kind", ["numpy", "jax", "jit"])
def test_pad_and_chunk_keep_the_array_kind(kind):
    """Host traces pad and chunk on the host (numpy float32, no device
    round trip); device arrays and tracers keep the `jax.numpy` path.
    The values and dtypes are the same either way."""
    tr = traffic.generate_trace("dedup", 10, jax.random.PRNGKey(0))
    want = _pad_and_chunk(tr)
    arrays = {k: v for k, v in tr.items() if k != "app"}
    if kind == "numpy":
        got = _pad_and_chunk(dict(jax.device_get(arrays), app=tr["app"]))
        expect_type = np.ndarray
    elif kind == "jax":
        got = _pad_and_chunk(tr)
        expect_type = jax.Array
    else:
        got = jax.jit(_pad_and_chunk)(arrays)
        expect_type = jax.Array
    assert len(got) == len(want) == 3
    for g, w in zip(got, want):
        for k in _LOAD_KEYS:
            assert isinstance(g[k], expect_type), (k, type(g[k]))
            assert g[k].dtype == np.float32, (k, g[k].dtype)
            np.testing.assert_array_equal(np.asarray(g[k]), np.asarray(w[k]))
    np.testing.assert_array_equal(np.asarray(got[-1]["t_mask"]),
                                  [1.0] * 2 + [0.0] * 6)


def test_concat_preserves_t_mask():
    a = traffic.pad_trace(
        traffic.generate_trace("dedup", 6, jax.random.PRNGKey(0)), 8)
    b = traffic.generate_trace("canneal", 4, jax.random.PRNGKey(1))
    out = traffic.concat_traces([a, b])
    np.testing.assert_array_equal(
        np.asarray(out["t_mask"]), [1.0] * 6 + [0.0] * 2 + [1.0] * 4)
    assert traffic.trace_length(out) == 10
