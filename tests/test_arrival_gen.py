"""Arrival-generation RNG modes: the frozen ``paper-default`` draw order
(golden trace), the vectorized generator's determinism and distributional
parity with the per-request loop, the columnar trace's equivalence to the
object trace, and the ``max_frame_arrivals`` envelope in both modes.

The vectorized mode is *opt-in* precisely because it consumes the RNG in a
different order — these tests pin (a) that the default mode's traces can
never drift (any RNG refactor that changes them fails the golden test) and
(b) that the vectorized mode draws the same thinned-Poisson process and the
same QoS/size laws, just batched."""
import dataclasses
import math

import numpy as np
import pytest

pytest.importorskip("jax")

from repro.core import (  # noqa: E402
    RNG_MODES,
    RequestColumns,
    Scenario,
    SimConfig,
    bucket_arrivals,
    bucket_columns,
    demo_cluster_spec,
    get_scenario,
    list_scenarios,
    max_frame_arrivals,
    simulate_fleet,
    stream_trace,
    stream_trace_columns,
)
from repro.core.scenarios import VEC_CHUNK, iter_edge_arrival_chunks  # noqa: E402


def cfg(**kw):
    base = dict(
        horizon_ms=12_000.0,
        arrival_rate_per_s=3.0,
        delay_req_ms=6000.0,
        acc_req_mean=50.0,
        acc_req_std=10.0,
    )
    base.update(kw)
    return SimConfig(**base)


def req_tuple(r):
    return (r.rid, r.arrival_ms, r.cover, r.service, r.A, r.C, r.size_bytes)


SCENARIO_NAMES = sorted(
    ["paper-default", "diurnal", "flash-crowd", "hetero-tiers", "sustained-overload"]
)


# ---------------------------------------------------------------------------
# Golden trace: the paper-default per-request draw order is frozen
# ---------------------------------------------------------------------------

# generate_arrivals(default_rng(123), n_edge=3, n_services=2, cfg()) — any
# refactor that changes the default mode's RNG consumption breaks these
# literals and must NOT ship as the default (that is the whole point of
# rng_mode being opt-in)
GOLDEN_N = 125
GOLDEN_FIRST3 = [
    (0, 198.9908317075506, 0, 1, 62.879252612892486, 6000.0, 38437.18106986697),
    (1, 229.59171846192464, 0, 0, 55.77103791257251, 6000.0, 112334.49980270564),
    (2, 281.13602104387934, 0, 1, 46.7761088384104, 6000.0, 71297.04552295318),
]
GOLDEN_SUM_ARRIVAL = 692928.7122563681
GOLDEN_SUM_A = 6440.5145223247655
GOLDEN_SUM_SIZE = 8635273.808705235
GOLDEN_COVER_PREFIX = [0, 0, 0, 0, 2, 2, 0, 1, 0, 2, 2, 0]

# stream_trace("paper-default", seed=123, ...) — the streaming engine's
# spawned-generator draw order, equally frozen
GOLDEN_STREAM_N = 105
GOLDEN_STREAM_SUM_ARRIVAL = 605829.1700185866
GOLDEN_STREAM_FIRST = (
    0, 88.68756074937487, 0, 0, 48.885479413246465, 6000.0, 35585.81796957245,
)


def test_paper_default_trace_is_bit_frozen():
    reqs = get_scenario("paper-default").generate_arrivals(
        np.random.default_rng(123), 3, 2, cfg()
    )
    assert len(reqs) == GOLDEN_N
    assert [req_tuple(r) for r in reqs[:3]] == GOLDEN_FIRST3
    assert [r.cover for r in reqs[:12]] == GOLDEN_COVER_PREFIX
    assert float(np.sum([r.arrival_ms for r in reqs])) == GOLDEN_SUM_ARRIVAL
    assert float(np.sum([r.A for r in reqs])) == GOLDEN_SUM_A
    assert float(np.sum([r.size_bytes for r in reqs])) == GOLDEN_SUM_SIZE


def test_streaming_trace_is_bit_frozen():
    s = stream_trace("paper-default", 123, 3, 2, cfg())
    assert len(s) == GOLDEN_STREAM_N
    assert req_tuple(s[0]) == GOLDEN_STREAM_FIRST
    assert float(np.sum([r.arrival_ms for r in s])) == GOLDEN_STREAM_SUM_ARRIVAL


def test_default_rng_mode_is_paper_default_everywhere():
    # mega-city is the one deliberate exception: at 10^5+ users per frame a
    # materialized per-Request trace is exactly what that scenario avoids,
    # so it declares the vectorized columnar generator as its default
    for name in list_scenarios():
        expected = "vectorized" if name == "mega-city" else "paper-default"
        assert get_scenario(name).rng_mode == expected, name
    assert RNG_MODES == ("paper-default", "vectorized")


def test_unknown_rng_mode_raises():
    scn = get_scenario("paper-default")
    with pytest.raises(ValueError, match="rng_mode"):
        scn.generate_arrivals(np.random.default_rng(0), 2, 2, cfg(), rng_mode="turbo")
    with pytest.raises(ValueError, match="rng_mode"):
        simulate_fleet(
            demo_cluster_spec(), cfg(), policy="gus", n_rep=1, rng_mode="turbo"
        )


# ---------------------------------------------------------------------------
# Vectorized mode: determinism, well-formedness, columnar equivalence
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("scenario", SCENARIO_NAMES)
def test_vectorized_deterministic_given_seed_and_seed_sensitive(scenario):
    scn = get_scenario(scenario)
    c = cfg()
    a = scn.generate_arrivals(np.random.default_rng(5), 4, 3, c, rng_mode="vectorized")
    b = scn.generate_arrivals(np.random.default_rng(5), 4, 3, c, rng_mode="vectorized")
    other = scn.generate_arrivals(np.random.default_rng(6), 4, 3, c, rng_mode="vectorized")
    assert [req_tuple(r) for r in a] == [req_tuple(r) for r in b]
    assert [req_tuple(r) for r in a] != [req_tuple(r) for r in other]


@pytest.mark.parametrize("scenario", SCENARIO_NAMES)
def test_vectorized_trace_well_formed(scenario):
    scn = get_scenario(scenario)
    c = cfg()
    reqs = scn.generate_arrivals(np.random.default_rng(7), 4, 3, c, rng_mode="vectorized")
    times = [r.arrival_ms for r in reqs]
    assert times == sorted(times)
    assert [r.rid for r in reqs] == list(range(len(reqs)))
    assert all(0.0 <= t < c.horizon_ms for t in times)
    assert all(0 <= r.cover < 4 and 0 <= r.service < 3 for r in reqs)
    assert all(1.0 <= r.A <= 99.0 for r in reqs)
    assert all(c.req_size_lo <= r.size_bytes <= c.req_size_hi for r in reqs)
    assert all(r.C > 0 for r in reqs)


@pytest.mark.parametrize("scenario", SCENARIO_NAMES)
def test_columns_and_requests_are_one_trace(scenario):
    """generate_arrivals(vectorized) is exactly generate_arrivals_columns
    wrapped into Request objects — same seed, same values, same order."""
    scn = get_scenario(scenario)
    c = cfg()
    reqs = scn.generate_arrivals(np.random.default_rng(9), 4, 3, c, rng_mode="vectorized")
    cols = scn.generate_arrivals_columns(
        np.random.default_rng(9), 4, 3, c, rng_mode="vectorized"
    )
    assert len(cols) == len(reqs)
    assert [req_tuple(r) for r in cols.to_requests()] == [req_tuple(r) for r in reqs]


def test_bucket_columns_matches_bucket_arrivals():
    scn = get_scenario("flash-crowd")
    c = cfg()
    cols = scn.generate_arrivals_columns(
        np.random.default_rng(3), 4, 3, c, rng_mode="vectorized"
    )
    n_frames = int(np.ceil(c.horizon_ms / c.frame_ms))
    by_req = bucket_arrivals(cols.to_requests(), c.frame_ms, n_frames)
    by_col = bucket_columns(cols, c.frame_ms, n_frames)
    assert [len(b) for b in by_req] == [len(b) for b in by_col]
    for br, bc in zip(by_req, by_col):
        assert [r.arrival_ms for r in br] == list(bc.arrival_ms)
        assert [r.cover for r in br] == list(bc.cover)
    # empty columnar buckets are falsy, like empty lists
    empty = RequestColumns.concatenate([])
    assert not empty and len(empty) == 0


def test_stream_trace_columns_matches_vectorized_stream():
    c = cfg()
    for scenario in SCENARIO_NAMES:
        via_stream = stream_trace(scenario, 21, 4, 3, c, rng_mode="vectorized")
        via_cols = stream_trace_columns(scenario, 21, 4, 3, c).to_requests()
        assert [req_tuple(r) for r in via_stream] == [req_tuple(r) for r in via_cols]


# ---------------------------------------------------------------------------
# Paper-default mode: the scalar draw loop written into columns
# ---------------------------------------------------------------------------


def _scalar_trace(scn, rng, n_edge, n_services, c):
    """The paper-default per-request loop as it was before it wrote columns,
    kept as the reference: ``(arrival_ms, cover, service, A, C, size_bytes)``
    rows in emission order, then stable-sorted by arrival."""
    rows = []
    for e in range(n_edge):
        rmax = float(scn.rate_bound(e, c))
        if rmax <= 0.0:
            continue
        t = 0.0
        while t < c.horizon_ms:
            t += rng.exponential(1000.0 / rmax)
            if t >= c.horizon_ms:
                break
            r_t = float(scn.rate(e, t, c))
            if r_t < rmax and rng.random() >= r_t / rmax:
                continue
            service = int(rng.integers(0, n_services))
            a, d = scn.draw_qos(rng, c)
            size = float(rng.uniform(c.req_size_lo, c.req_size_hi))
            rows.append((t, e, service, a, d, size))
    rows.sort(key=lambda r: r[0])
    return rows


@dataclasses.dataclass(frozen=True)
class _CoinQos(Scenario):
    """Overrides ``draw_qos`` with a variable number of draws per request
    and NumPy scalars in its answer."""

    name: str = "local-coin-qos"

    def draw_qos(self, rng, cfg):
        if rng.random() < 0.5:
            return np.float32(rng.normal(60.0, 5.0)), 900.0
        return np.float64(rng.uniform(20.0, 40.0)), float(rng.integers(500, 5000))


@dataclasses.dataclass(frozen=True)
class _Pulsed(Scenario):
    """A time-varying rate with only the scalar hook: full rate in even
    seconds, a fifth of it in odd ones, so most candidates are thinned."""

    name: str = "local-pulsed"

    def rate(self, edge, t_ms, cfg):
        on = int(t_ms // 1000.0) % 2 == 0
        return cfg.arrival_rate_per_s * (1.0 if on else 0.2)


@dataclasses.dataclass(frozen=True)
class _Envelope(Scenario):
    """The base constant rate under a looser bound: every candidate takes
    the thinning draw against a rate read once per edge."""

    name: str = "local-envelope"

    def rate_bound(self, edge, cfg):
        return cfg.arrival_rate_per_s * (2.0 + edge)


_LOCAL_SCENARIOS = {s.name: s for s in (_CoinQos(), _Pulsed(), _Envelope())}
PAPER_DEFAULT_CASES = list_scenarios() + sorted(_LOCAL_SCENARIOS)


def _paper_default_case(name):
    """The scenario and a config that keeps its trace a few hundred rows:
    the horizon shrinks for scenarios whose own rates are city-scale."""
    scn = _LOCAL_SCENARIOS.get(name) or get_scenario(name)
    c = cfg()
    bound = max(float(scn.rate_bound(e, c)) for e in range(4))
    if bound * c.horizon_ms / 1000.0 > 200.0:
        c = cfg(horizon_ms=200_000.0 / bound)
    return scn, c


@pytest.mark.parametrize("name", PAPER_DEFAULT_CASES)
def test_paper_default_columns_match_the_scalar_loop(name):
    """Every draw, in the same order: the columns equal the reference loop
    column for column, and both leave the generator in the same state."""
    scn, c = _paper_default_case(name)
    rng, ref_rng = np.random.default_rng(31), np.random.default_rng(31)
    cols = scn.generate_arrivals_columns(rng, 4, 3, c, rng_mode="paper-default")
    rows = _scalar_trace(scn, ref_rng, 4, 3, c)
    assert len(rows) > 20
    want = [np.array(col) for col in zip(*rows)]
    got = [cols.arrival_ms, cols.cover, cols.service, cols.A, cols.C, cols.size_bytes]
    for g, w, dtype in zip(got, want, [np.float64, np.int64, np.int64] + [np.float64] * 3):
        assert g.dtype == dtype
        np.testing.assert_array_equal(g, w.astype(dtype))
    assert rng.bit_generator.state == ref_rng.bit_generator.state


@pytest.mark.parametrize("name", PAPER_DEFAULT_CASES)
def test_paper_default_requests_are_the_columns(name):
    """``generate_arrivals`` is ``to_requests()`` of the columnar trace, and
    both are the reference loop's rows with rids in arrival order."""
    scn, c = _paper_default_case(name)
    cols = scn.generate_arrivals_columns(
        np.random.default_rng(17), 4, 3, c, rng_mode="paper-default"
    )
    reqs = scn.generate_arrivals(
        np.random.default_rng(17), 4, 3, c, rng_mode="paper-default"
    )
    rows = _scalar_trace(scn, np.random.default_rng(17), 4, 3, c)
    assert [req_tuple(r) for r in cols.to_requests()] == [req_tuple(r) for r in reqs]
    assert [req_tuple(r) for r in reqs] == [(i,) + row for i, row in enumerate(rows)]


N_DRAWS = 100_000


@pytest.mark.parametrize(
    "lo, hi", [(2e4, 1.2e5), (0.0, 1.0), (-3.5, 7.25), (1e-3, 1e9)]
)
def test_uniform_as_scaled_random_is_bit_identical(lo, hi):
    """``lo + (hi - lo) * random()`` is ``uniform(lo, hi)`` on the same
    generator state (NumPy computes ``low + range * next_double``)."""
    a, b = np.random.default_rng(2026), np.random.default_rng(2026)
    want = [a.uniform(lo, hi) for _ in range(N_DRAWS)]
    got = [lo + (hi - lo) * b.random() for _ in range(N_DRAWS)]
    assert got == want


@pytest.mark.parametrize("scale", [1000.0 / 2.7777777777777777, 1000.0 / 3.0, 1.0, 1e-3])
def test_exponential_as_scaled_standard_is_bit_identical(scale):
    """``scale * standard_exponential()`` is ``exponential(scale)`` on the
    same generator state."""
    a, b = np.random.default_rng(7), np.random.default_rng(7)
    want = [a.exponential(scale) for _ in range(N_DRAWS)]
    got = [scale * b.standard_exponential() for _ in range(N_DRAWS)]
    assert got == want


@pytest.mark.parametrize("frame_ms", [0.1, 3000.0])
def test_bucket_columns_uses_the_per_request_frame_index(frame_ms):
    """Arrivals on and next to every frame boundary land where
    ``bucket_arrivals`` puts them: the index is ``arrival_ms // frame_ms``,
    which for ``frame_ms=0.1`` puts ``1.0`` in frame 9, below ``10 * 0.1``."""
    n_frames = 40
    b = np.arange(1, n_frames + 2) * frame_ms
    t = np.sort(np.concatenate([np.nextafter(b, 0.0), b, np.nextafter(b, np.inf)]))
    n = t.size
    cols = RequestColumns(
        arrival_ms=t, cover=np.zeros(n, np.int64), service=np.arange(n, dtype=np.int64),
        A=np.zeros(n), C=np.zeros(n), size_bytes=np.zeros(n),
    )
    by_req = bucket_arrivals(cols.to_requests(), frame_ms, n_frames)
    by_col = bucket_columns(cols, frame_ms, n_frames)
    assert [[r.service for r in br] for br in by_req] == [
        bc.service.tolist() for bc in by_col
    ]


# ---------------------------------------------------------------------------
# Distributional parity: vectorized vs per-request, same law
# ---------------------------------------------------------------------------


def _counts_over_seeds(scn, c, mode, n_seeds, n_edge=2, n_services=2):
    return np.array(
        [
            len(scn.generate_arrivals(
                np.random.default_rng(s), n_edge, n_services, c, rng_mode=mode
            ))
            for s in range(n_seeds)
        ],
        np.float64,
    )


@pytest.mark.parametrize("scenario", SCENARIO_NAMES)
def test_vectorized_counts_match_per_request_in_expectation(scenario):
    """Both modes draw the same thinned Poisson process, so total counts over
    seeds must agree within Monte-Carlo error (5 sigma of the pooled mean)."""
    scn = get_scenario(scenario)
    c = cfg(horizon_ms=20_000.0)
    n_seeds = 24
    a = _counts_over_seeds(scn, c, "paper-default", n_seeds)
    b = _counts_over_seeds(scn, c, "vectorized", n_seeds)
    # Poisson totals: var == mean; compare seed-means with a 5-sigma band
    pooled = 0.5 * (a.mean() + b.mean())
    sigma = math.sqrt(2.0 * pooled / n_seeds)
    assert abs(a.mean() - b.mean()) < 5.0 * sigma, (a.mean(), b.mean(), sigma)


def test_vectorized_respects_time_varying_rate():
    """flash-crowd's hot edges must see ~burst_mult the traffic inside the
    burst window in *both* modes (the thinning is what's being vectorized)."""
    scn = get_scenario("flash-crowd")
    c = cfg(horizon_ms=50_000.0, arrival_rate_per_s=2.0)
    t_lo, t_hi = scn.burst_start_frac * c.horizon_ms, scn.burst_end_frac * c.horizon_ms
    for mode in RNG_MODES:
        in_burst = out_burst = 0
        for s in range(8):
            for r in scn.generate_arrivals(
                np.random.default_rng(s), 2, 2, c, rng_mode=mode
            ):
                if r.cover != 0:
                    continue  # edge 0 is hot (stride 2)
                if t_lo <= r.arrival_ms < t_hi:
                    in_burst += 1
                else:
                    out_burst += 1
        # burst window is 20% of the horizon at 10x rate -> in/out ~ 10 * (0.2/0.8)
        ratio = in_burst / max(out_burst, 1)
        assert 1.5 < ratio < 4.0, (mode, ratio)


def test_vectorized_qos_law_matches():
    """hetero-tiers' two-tier QoS mix must survive vectorization: deadlines
    take exactly the two tier values, accuracy means sit near the mix mean."""
    scn = get_scenario("hetero-tiers")
    c = cfg(horizon_ms=40_000.0)
    strict_c = c.delay_req_ms * scn.strict_deadline_mult
    lenient_c = c.delay_req_ms * scn.lenient_deadline_mult
    stats = {}
    for mode in RNG_MODES:
        reqs = [
            r
            for s in range(6)
            for r in scn.generate_arrivals(
                np.random.default_rng(s), 3, 2, c, rng_mode=mode
            )
        ]
        cs = {r.C for r in reqs}
        assert cs == {strict_c, lenient_c}, (mode, cs)
        frac_strict = np.mean([r.C == strict_c for r in reqs])
        assert abs(frac_strict - scn.strict_frac) < 0.05, (mode, frac_strict)
        stats[mode] = np.mean([r.A for r in reqs])
    assert abs(stats["vectorized"] - stats["paper-default"]) < 1.5, stats


# ---------------------------------------------------------------------------
# max_frame_arrivals envelope, both modes
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("mode", RNG_MODES)
@pytest.mark.parametrize("scenario", ["sustained-overload", "flash-crowd"])
def test_max_frame_arrivals_bounds_realized_buckets(scenario, mode):
    c = cfg()
    n_frames = int(np.ceil(c.horizon_ms / c.frame_ms))
    mx = max_frame_arrivals(scenario, 13, 4, 3, c, n_frames, rng_mode=mode)
    reqs = stream_trace(scenario, 13, 4, 3, c, rng_mode=mode)
    buckets = bucket_arrivals(reqs, c.frame_ms, n_frames)
    realized = max((len(b) for b in buckets), default=0)
    assert mx >= realized
    # the count-only pass must be exact, not just an upper bound — that is
    # what pins windowed == materialized padding
    assert mx == realized


# ---------------------------------------------------------------------------
# Chunk engine internals
# ---------------------------------------------------------------------------


def test_chunk_iterator_consumption_is_pull_independent():
    """Draining the chunk iterator all at once vs chunk-by-chunk with
    interruptions yields the same chunks (the RNG advance is internal)."""
    scn = get_scenario("diurnal")
    c = cfg()
    a = list(iter_edge_arrival_chunks(scn, np.random.default_rng(1), 0, 3, c, c.horizon_ms))
    it = iter_edge_arrival_chunks(scn, np.random.default_rng(1), 0, 3, c, c.horizon_ms)
    b = []
    while True:
        nxt = next(it, None)
        if nxt is None:
            break
        b.append(nxt)
    assert len(a) == len(b)
    for ca, cb in zip(a, b):
        for xa, xb in zip(ca, cb):
            np.testing.assert_array_equal(xa, xb)


def test_zero_rate_edge_yields_nothing():
    scn = get_scenario("paper-default")
    c = cfg(arrival_rate_per_s=0.0)
    assert scn.generate_arrivals(np.random.default_rng(0), 3, 2, c,
                                 rng_mode="vectorized") == []
    assert list(iter_edge_arrival_chunks(scn, np.random.default_rng(0), 0, 2, c,
                                         c.horizon_ms)) == []


def test_deep_subclass_scalar_override_is_honored_in_vectorized_mode():
    """A subclass of a *registered* scenario that overrides only the scalar
    hooks must not silently inherit the parent's batched law: the vectorized
    engine detects the deeper scalar override (MRO depth, not a one-level
    `is` check) and loops the scalar hook instead."""
    import dataclasses as dc

    from repro.core.scenarios import FlashCrowdScenario, HeteroTiersScenario

    @dc.dataclass(frozen=True)
    class FixedQosTiers(HeteroTiersScenario):
        # new scalar QoS law, no draw_qos_batch twin
        def draw_qos(self, rng, cfg):
            rng.random()  # consume like a tier draw would
            return 42.0, 4242.0

    c = cfg()
    reqs = FixedQosTiers().generate_arrivals(
        np.random.default_rng(0), 3, 2, c, rng_mode="vectorized"
    )
    assert reqs, "subclass scenario generated nothing"
    assert {r.A for r in reqs} == {42.0}
    assert {r.C for r in reqs} == {4242.0}

    @dc.dataclass(frozen=True)
    class NoBurstFlash(FlashCrowdScenario):
        # new scalar rate law (burst removed), no rate_batch twin
        def rate(self, edge, t_ms, cfg):
            return cfg.arrival_rate_per_s

    c = cfg(horizon_ms=30_000.0, arrival_rate_per_s=2.0)
    scn = NoBurstFlash()
    n = np.mean([
        len(scn.generate_arrivals(np.random.default_rng(s), 2, 2, c,
                                  rng_mode="vectorized"))
        for s in range(10)
    ])
    # the thinned process must follow the constant scalar rate (~120 total),
    # not the inherited 10x-burst batch law (~175)
    expect = 2.0 * 30.0 * 2
    assert abs(n - expect) < 4 * math.sqrt(expect), n


def test_vec_chunk_constant_is_frozen():
    """VEC_CHUNK is part of the vectorized trace's definition — changing it
    changes every vectorized trace, so treat it like a file format."""
    assert VEC_CHUNK == 512


# ---------------------------------------------------------------------------
# Property tests (hypothesis — optional in minimal environments; the guard
# keeps the rest of the module running where it is not installed)
# ---------------------------------------------------------------------------

try:
    from hypothesis import given, settings
    from hypothesis import strategies as st

    HAVE_HYPOTHESIS = True
except ImportError:  # pragma: no cover - exercised on minimal images
    HAVE_HYPOTHESIS = False

if HAVE_HYPOTHESIS:

    @settings(max_examples=15, deadline=None)
    @given(
        seed=st.integers(min_value=0, max_value=2**31 - 1),
        rate=st.floats(min_value=0.2, max_value=12.0),
        n_edge=st.integers(min_value=1, max_value=5),
    )
    def test_prop_vectorized_deterministic_and_in_horizon(seed, rate, n_edge):
        scn = get_scenario("paper-default")
        c = cfg(horizon_ms=6000.0, arrival_rate_per_s=rate)
        a = scn.generate_arrivals(np.random.default_rng(seed), n_edge, 2, c,
                                  rng_mode="vectorized")
        b = scn.generate_arrivals(np.random.default_rng(seed), n_edge, 2, c,
                                  rng_mode="vectorized")
        assert [req_tuple(r) for r in a] == [req_tuple(r) for r in b]
        assert all(0.0 <= r.arrival_ms < c.horizon_ms for r in a)
        assert all(1.0 <= r.A <= 99.0 for r in a)

    @settings(max_examples=10, deadline=None)
    @given(
        seed=st.integers(min_value=0, max_value=2**31 - 1),
        rate=st.floats(min_value=0.5, max_value=8.0),
    )
    def test_prop_max_frame_arrivals_is_exact_envelope(seed, rate):
        c = cfg(horizon_ms=9000.0, arrival_rate_per_s=rate)
        n_frames = int(np.ceil(c.horizon_ms / c.frame_ms))
        for mode in RNG_MODES:
            mx = max_frame_arrivals(
                "paper-default", seed, 3, 2, c, n_frames, rng_mode=mode
            )
            buckets = bucket_arrivals(
                stream_trace("paper-default", seed, 3, 2, c, rng_mode=mode),
                c.frame_ms, n_frames,
            )
            assert mx == max((len(b) for b in buckets), default=0)
else:

    @pytest.mark.skip(reason="hypothesis not installed")
    def test_prop_vectorized_deterministic_and_in_horizon():
        pass

    @pytest.mark.skip(reason="hypothesis not installed")
    def test_prop_max_frame_arrivals_is_exact_envelope():
        pass
