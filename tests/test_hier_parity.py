"""Three-way parity for the hierarchical device allocator.

``repro.core.aggregation`` ships the analytic class allocator three ways —
a NumPy f32 oracle (:func:`hier_cells_np`), a jitted XLA scan, and a fused
Pallas kernel — and the contract is **bitwise integer equality** of the
``(take, start)`` cell tensors across all three, mirroring the dense GUS
harness in ``tests/test_gus_parity.py``:

* scenario-captured class instances (generated frames, tiled duplicate
  blocks) agree across every backend;
* every padding bucket agrees, and zero-count padding rows never allocate
  or touch the budgets;
* tie frames, all-infeasible frames, and exact-capacity chunk edges hit
  the same branch on every backend (first-occurrence argmax, f32 floor
  division);
* ``hier_assign(exact=False)`` is a faithful chunk-list view of the cell
  tensors (never over-allocates, allocation-ordered);
* backend dispatch (``hier_backend_fn``) returns stable identities and
  honors ``REPRO_GUS_BACKEND``;
* fleet level: the device hierarchical path composes with admission
  control and link impairments, matching the dense fleet *exactly* on
  singleton-class scenarios (continuous QoS draws) and on contiguous
  duplicate classes with lossless class means — and XLA vs Pallas fleet
  runs are bit-identical end to end;
* per-member realized impairment accounting is pinned by a golden fixture
  (``tests/fixtures/hier_member_golden.npz``).
"""
from __future__ import annotations

import dataclasses
import math
import pathlib

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from repro.core import (  # noqa: E402
    CongestionConfig,
    EngineOptions,
    Scenario,
    SimConfig,
    aggregate_instance,
    demo_cluster_spec,
    generate_instance,
    get_scenario,
    hier_assign,
    hier_backend_fn,
    hier_cells,
    hier_cells_np,
    simulate_fleet,
)
from repro.core.impairments import (  # noqa: E402
    AdmissionConfig,
    BurstyLossLink,
    ImpairmentConfig,
    IntermittentLink,
)
from repro.core.instance import FlatInstance, GeneratorConfig  # noqa: E402

SPEC = demo_cluster_spec()
FIXTURES = pathlib.Path(__file__).parent / "fixtures"

SMALL = GeneratorConfig(n_requests=24, n_edge=4, n_cloud=1, n_services=6,
                        n_variants=4)

#: every implementation of the analytic allocator, by dispatch name
HIER_IMPLS = ("np", "xla", "pallas")

#: padding buckets exercised by the fleet path (``_pad_bucket``)
BUCKETS = (4, 8, 16, 32, 64, 128)


# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------

def _class_args(agg, gamma, eta, pad_to=None):
    """Sort class rows by ``first_idx`` (the order the fleet feeds the
    device allocator) and optionally pad with zero-count rows."""
    o = np.argsort(agg.first_idx, kind="stable")
    us, feas = agg.us[o], agg.feas[o]
    v, u = agg.v[o], agg.u[o]
    cover = agg.cover[o].astype(np.int32)
    count = agg.count[o].astype(np.int32)
    if pad_to is not None and pad_to > us.shape[0]:
        pad = pad_to - us.shape[0]
        zc = np.zeros((pad,) + us.shape[1:], us.dtype)
        us = np.concatenate([us, zc])
        feas = np.concatenate([feas, np.zeros_like(zc, bool)])
        v = np.concatenate([v, zc])
        u = np.concatenate([u, zc])
        cover = np.concatenate([cover, np.zeros(pad, np.int32)])
        count = np.concatenate([count, np.zeros(pad, np.int32)])
    return (us, feas, v, u, cover, count,
            np.asarray(gamma, np.float32), np.asarray(eta, np.float32))


def _run_impl(impl, args):
    if impl == "np":
        take, start = hier_cells_np(*args)
    else:
        take, start = hier_cells(*args, backend=impl)
    return np.asarray(take), np.asarray(start)


def three_way(args, label=""):
    """Assert bitwise (take, start) equality across all backends; return
    the oracle's tensors."""
    ref_take, ref_start = _run_impl("np", args)
    for impl in HIER_IMPLS[1:]:
        take, start = _run_impl(impl, args)
        np.testing.assert_array_equal(
            take, ref_take, err_msg=f"{label}: take np vs {impl}")
        np.testing.assert_array_equal(
            start, ref_start, err_msg=f"{label}: start np vs {impl}")
    return ref_take, ref_start


def tile_instance(inst: FlatInstance, k: int) -> FlatInstance:
    rep = lambda x: np.repeat(np.asarray(x), k, axis=0)  # noqa: E731
    return dataclasses.replace(
        inst,
        cover=rep(inst.cover), A=rep(inst.A), C=rep(inst.C),
        w_a=rep(inst.w_a), w_c=rep(inst.w_c),
        acc=rep(inst.acc), ctime=rep(inst.ctime), v=rep(inst.v),
        u=rep(inst.u), avail=rep(inst.avail),
    )


# ---------------------------------------------------------------------------
# allocator-level three-way parity
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("seed", range(6))
def test_generated_instances_three_way(seed):
    inst = generate_instance(seed, as_numpy=True)
    agg = aggregate_instance(inst)
    args = _class_args(agg, np.asarray(inst.gamma), np.asarray(inst.eta))
    take, _ = three_way(args, f"seed={seed}")
    per_class = take.sum(axis=(1, 2))
    assert np.all(per_class <= args[5])  # never over-allocates a class


@pytest.mark.parametrize("seed", range(3))
@pytest.mark.parametrize("k", [2, 5])
def test_duplicate_class_instances_three_way(seed, k):
    inst = tile_instance(generate_instance(seed, SMALL, as_numpy=True), k)
    agg = aggregate_instance(inst)
    args = _class_args(agg, np.asarray(inst.gamma), np.asarray(inst.eta))
    three_way(args, f"dup seed={seed} k={k}")


@pytest.mark.parametrize("bucket", BUCKETS)
def test_padding_buckets_three_way(bucket):
    cfg = dataclasses.replace(SMALL, n_requests=max(2, (3 * bucket) // 4))
    inst = generate_instance(1, cfg, as_numpy=True)
    agg = aggregate_instance(inst)
    assert 0 < agg.n_classes <= bucket
    gamma, eta = np.asarray(inst.gamma), np.asarray(inst.eta)
    bare = _class_args(agg, gamma, eta)
    padded = _class_args(agg, gamma, eta, pad_to=bucket)
    take_b, start_b = three_way(bare, f"bucket={bucket} bare")
    take_p, start_p = three_way(padded, f"bucket={bucket} padded")
    n_c = agg.n_classes
    # padding rows never allocate, never shift the real rows' result
    np.testing.assert_array_equal(take_p[:n_c], take_b)
    np.testing.assert_array_equal(start_p[:n_c], start_b)
    assert take_p[n_c:].sum() == 0 and start_p[n_c:].sum() == 0


def _degenerate(us, feas, v, u, cover, count, gamma, eta):
    return (
        np.asarray(us, np.float32), np.asarray(feas, bool),
        np.asarray(v, np.float32), np.asarray(u, np.float32),
        np.asarray(cover, np.int32), np.asarray(count, np.int32),
        np.asarray(gamma, np.float32), np.asarray(eta, np.float32),
    )


def test_tie_frames_pick_first_flat_cell():
    # constant utility everywhere: every backend must break ties at the
    # first occurrence on the flat j*L + l axis
    C, M, L = 3, 4, 2
    args = _degenerate(
        np.ones((C, M, L)), np.ones((C, M, L), bool),
        np.ones((C, M, L)), np.ones((C, M, L)),
        np.zeros(C), np.full(C, 2),
        np.full(M, 1e6), np.full(M, 1e6),
    )
    take, start = three_way(args, "ties")
    assert np.all(take[:, 0, 0] == 2)       # cell (0, 0) wins every tie
    assert take.sum() == 3 * 2
    np.testing.assert_array_equal(start, np.zeros_like(start))


def test_all_infeasible_and_zero_count_rows():
    C, M, L = 4, 3, 2
    feas = np.ones((C, M, L), bool)
    feas[1] = False                          # class 1: nowhere to go
    count = np.array([3, 3, 0, 3])           # class 2: padding row
    args = _degenerate(
        np.random.default_rng(0).uniform(0, 1, (C, M, L)), feas,
        np.ones((C, M, L)), np.ones((C, M, L)),
        np.zeros(C), count, np.full(M, 1e6), np.full(M, 1e6),
    )
    take, _ = three_way(args, "infeasible/zero-count")
    assert take[1].sum() == 0 and take[2].sum() == 0
    assert take[0].sum() == 3 and take[3].sum() == 3


def test_exact_capacity_chunk_edges():
    # gamma fits exactly 2 of 3 members at the only feasible local cell
    M, L = 2, 1
    us = np.array([[[1.0], [0.5]]])
    feas = np.array([[[True], [False]]])
    args = _degenerate(
        us, feas, np.ones((1, M, L)), np.zeros((1, M, L)),
        [0], [3], [2.0, 0.0], [1e6, 1e6],
    )
    take, _ = three_way(args, "gamma-bound")
    assert int(take[0, 0, 0]) == 2 and take.sum() == 2

    # eta binds an offload cell: floor(2.5 / 1.0) = 2 of 3 members ship
    feas = np.array([[[False], [True]]])
    args = _degenerate(
        us, feas, np.ones((1, M, L)),
        np.ones((1, M, L)), [0], [3], [1e6, 1e6], [2.5, 1e6],
    )
    take, _ = three_way(args, "eta-bound")
    assert int(take[0, 1, 0]) == 2 and take.sum() == 2


def test_budget_carries_across_classes():
    # two identical classes compete for gamma[0] = 3: first (by order)
    # takes 3, second is pushed to the worse cell
    M, L = 2, 1
    us = np.tile(np.array([[[1.0], [0.4]]]), (2, 1, 1))
    args = _degenerate(
        us, np.ones((2, M, L), bool), np.ones((2, M, L)),
        np.zeros((2, M, L)), [0, 0], [3, 2], [3.0, 1e6], [1e6, 1e6],
    )
    take, _ = three_way(args, "carry")
    assert int(take[0, 0, 0]) == 3
    assert int(take[1, 0, 0]) == 0 and int(take[1, 1, 0]) == 2


def test_hier_assign_analytic_is_cell_view():
    """``hier_assign(exact=False)`` must be exactly the chunk-list view of
    the cell tensors: same totals per (class, cell), allocation-ordered,
    never over-allocating."""
    inst = tile_instance(generate_instance(2, SMALL, as_numpy=True), 3)
    agg = aggregate_instance(inst)
    gamma, eta = np.asarray(inst.gamma), np.asarray(inst.eta)
    chunks = hier_assign(agg, gamma, eta, exact=False)
    take, _ = hier_cells_np(*_class_args(agg, gamma, eta))
    o = np.argsort(agg.first_idx, kind="stable")
    totals = np.zeros_like(take)
    taken = np.zeros(agg.n_classes, np.int64)
    rank = np.empty(agg.n_classes, np.int64)
    rank[o] = np.arange(agg.n_classes)
    for c, j, l, t in chunks:
        totals[rank[c], j, l] += t
        taken[c] += t
    np.testing.assert_array_equal(totals, take)
    assert np.all(taken <= agg.count)


def test_backend_dispatch_plumbing(monkeypatch):
    from repro.core.aggregation import _hier_cells_xla

    # stable identities per resolved backend — the fleet runner's compile
    # cache keys on them
    assert hier_backend_fn() is hier_backend_fn("xla")
    assert hier_backend_fn() is _hier_cells_xla
    assert hier_backend_fn("pallas") is hier_backend_fn("pallas")
    assert hier_backend_fn("pallas") is not hier_backend_fn("xla")
    # env default steers the None resolution, explicit still wins
    monkeypatch.setenv("REPRO_GUS_BACKEND", "pallas")
    assert hier_backend_fn() is hier_backend_fn("pallas")
    assert hier_backend_fn("xla") is _hier_cells_xla
    with pytest.raises(ValueError):
        hier_backend_fn("cuda-graphs")


# ---------------------------------------------------------------------------
# fleet-level parity: admission, impairments, backends
# ---------------------------------------------------------------------------

def fleet_cfg(**kw) -> SimConfig:
    base = dict(
        horizon_ms=12_000.0,
        arrival_rate_per_s=4.0,
        delay_req_ms=6000.0,
        acc_req_mean=50.0,
        acc_req_std=10.0,
        congestion=CongestionConfig(enabled=False),
    )
    base.update(kw)
    return SimConfig(**base)


def _pair(cfg, *, scenario="paper-default", spec=SPEC, n_rep=2, seed=0,
          backend=None):
    """(dense, hier) fleet runs of the same trace with metrics on."""
    dense = simulate_fleet(
        spec, cfg, policy="gus", scenario=scenario, n_rep=n_rep, seed=seed,
        options=EngineOptions(metrics=True),
    )
    hier = simulate_fleet(
        spec, cfg, policy="gus", scenario=scenario, n_rep=n_rep, seed=seed,
        options=EngineOptions(scheduler="hierarchical", metrics=True,
                              backend=backend),
    )
    return dense, hier


def _assert_fleet_match(dense, hier, *, us_rtol=1e-6):
    assert hier.n_requests == dense.n_requests
    assert hier.n_served == dense.n_served
    np.testing.assert_array_equal(
        np.asarray(hier.satisfied_per_rep), np.asarray(dense.satisfied_per_rep))
    np.testing.assert_allclose(
        np.asarray(hier.mean_us_per_rep), np.asarray(dense.mean_us_per_rep),
        rtol=us_rtol)
    da, ha = dense.metrics.aggregate(), hier.metrics.aggregate()
    for key in ("n_arrivals", "n_served", "n_satisfied", "n_shed", "n_refused"):
        assert ha[key] == da[key], (key, ha[key], da[key])


def test_admission_shed_matches_dense_on_singletons():
    """delay_req < frame: early arrivals are provably late and must shed.
    Congestion off makes admission a pure deadline check, so the
    class-level shed on singleton classes is bit-identical to the dense
    per-request shed."""
    cfg = fleet_cfg(delay_req_ms=2500.0,
                    admission=AdmissionConfig(enabled=True, shed=True))
    dense, hier = _pair(cfg)
    _assert_fleet_match(dense, hier)
    agg = hier.metrics.aggregate()
    assert agg["n_shed"] > 0                       # the regime actually sheds
    assert agg["n_shed"] < agg["n_arrivals"]       # ... but not everything


def test_admission_queue_cap_matches_dense_on_singletons():
    """queue_cap_mult=0 refuses every assignment on both paths — the
    degenerate regime that exercises the post-allocation refusal lane."""
    cfg = fleet_cfg(admission=AdmissionConfig(enabled=True,
                                              queue_cap_mult=0.0))
    dense, hier = _pair(cfg)
    _assert_fleet_match(dense, hier)
    agg = hier.metrics.aggregate()
    assert agg["n_refused"] > 0
    assert agg["n_satisfied"] == 0                 # nothing survives a 0-cap


def test_plain_singleton_fleet_is_bitwise():
    dense, hier = _pair(fleet_cfg())
    _assert_fleet_match(dense, hier)


# -- duplicate classes: a trace whose class means are lossless --------------

@dataclasses.dataclass(frozen=True)
class _FrameSnappedDup(Scenario):
    """Paper workload with every arrival snapped to its frame start and
    duplicated ``dup`` times.

    With ``acc_req_std=0`` and ``req_size_lo == req_size_hi`` every request
    that lands in one frame with the same (cover, service) is *identical*,
    so the class-mean representatives equal every member exactly — the
    lossless-duplicate regime where the hierarchical fleet must match the
    dense fleet bit for bit (given ample capacity, so the greedy never
    binds mid-class).
    """

    name: str = "frame-snapped-dup"
    dup: int = 3

    def generate_arrivals(self, rng, n_edge, n_services, cfg, rng_mode=None):
        base = super().generate_arrivals(
            rng, n_edge, n_services, cfg, rng_mode=rng_mode)
        out = []
        for r in base:
            snap = float(math.floor(r.arrival_ms / cfg.frame_ms) * cfg.frame_ms)
            for _ in range(self.dup):
                out.append(dataclasses.replace(r, arrival_ms=snap))
        out.sort(key=lambda r: r.arrival_ms)
        for i, r in enumerate(out):
            r.rid = i
        return out


def _ample_spec():
    """demo cluster with budgets scaled far past the offered load, so the
    allocation order (per-request vs per-class) can never matter."""
    return dataclasses.replace(
        SPEC,
        gamma_frame=np.asarray(SPEC.gamma_frame) * 200.0,
        eta_frame=np.asarray(SPEC.eta_frame) * 200.0,
    )


def _dup_cfg(**kw) -> SimConfig:
    base = dict(
        horizon_ms=12_000.0,
        arrival_rate_per_s=3.0,
        delay_req_ms=6000.0,
        acc_req_std=0.0,                 # exact class means
        req_size_lo=65_536.0,
        req_size_hi=65_536.0,            # exact class means
        congestion=CongestionConfig(enabled=False),
    )
    base.update(kw)
    return SimConfig(**base)


_IMPAIRED = ImpairmentConfig(
    enabled=True,
    link_profiles=(IntermittentLink(), BurstyLossLink()),
    seed=7,
)


def test_duplicate_classes_match_dense_bitwise():
    dense, hier = _pair(_dup_cfg(), scenario=_FrameSnappedDup(),
                        spec=_ample_spec())
    assert dense.n_requests % 3 == 0 and dense.n_requests > 0
    _assert_fleet_match(dense, hier)


def test_duplicate_classes_impaired_match_dense_bitwise():
    """Per-member realized link impairments: the deaggregated member
    accounting must reproduce the dense impaired simulator exactly on
    contiguous-duplicate classes."""
    cfg = _dup_cfg(delay_req_ms=3300.0, impairments=_IMPAIRED)
    dense, hier = _pair(cfg, scenario=_FrameSnappedDup(), spec=_ample_spec())
    _assert_fleet_match(dense, hier)
    # the impairments must actually bite for this to mean anything
    plain, _ = _pair(_dup_cfg(delay_req_ms=3300.0),
                     scenario=_FrameSnappedDup(), spec=_ample_spec())
    moved = (
        (np.asarray(dense.satisfied_per_rep)
         != np.asarray(plain.satisfied_per_rep)).any()
        or not np.allclose(np.asarray(dense.mean_us_per_rep),
                           np.asarray(plain.mean_us_per_rep))
    )
    assert moved, "impairment stream left the run untouched"


def test_fleet_xla_vs_pallas_bitwise():
    """The two device backends must produce bit-identical fleet results —
    admission, impairments, and congestion all on."""
    cfg = fleet_cfg(
        delay_req_ms=4000.0,
        admission=AdmissionConfig(enabled=True, shed=True),
        impairments=_IMPAIRED,
        congestion=CongestionConfig(enabled=True),
    )
    runs = {}
    for backend in ("xla", "pallas"):
        runs[backend] = simulate_fleet(
            SPEC, cfg, policy="gus", n_rep=2, seed=0,
            options=EngineOptions(scheduler="hierarchical", metrics=True,
                                  backend=backend),
        )
    x, p = runs["xla"], runs["pallas"]
    assert x.n_served == p.n_served
    np.testing.assert_array_equal(
        np.asarray(x.satisfied_per_rep), np.asarray(p.satisfied_per_rep))
    np.testing.assert_array_equal(
        np.asarray(x.mean_us_per_rep), np.asarray(p.mean_us_per_rep))
    np.testing.assert_array_equal(
        np.asarray(x.final_backlog_per_rep), np.asarray(p.final_backlog_per_rep))
    xa, pa = x.metrics.aggregate(), p.metrics.aggregate()
    for key in ("n_shed", "n_refused", "n_satisfied"):
        assert xa[key] == pa[key], key


def test_mega_city_with_admission_and_impairments():
    """The previously-impossible composition: city-scale hierarchical
    fleet with admission and impairments both enabled."""
    spec = demo_cluster_spec(n_edge=6, n_cloud=1, n_services=5, n_variants=10)
    cfg = SimConfig(
        horizon_ms=9_000.0,
        admission=AdmissionConfig(enabled=True, shed=True),
        impairments=_IMPAIRED,
    )
    scn = dataclasses.replace(get_scenario("mega-city"),
                              rate_per_edge_per_s=60.0)
    fr = simulate_fleet(
        spec, cfg, policy="gus", scenario=scn, n_rep=1, seed=0,
        options=EngineOptions(scheduler="hierarchical", window=1,
                              metrics=True),
    )
    assert fr.n_requests > 0
    assert np.isfinite(np.asarray(fr.satisfied_per_rep)).all()
    for k, v in fr.metrics.aggregate().items():
        assert np.isfinite(np.asarray(v, np.float64)).all(), k


# ---------------------------------------------------------------------------
# golden fixture: per-member realized impairment accounting
# ---------------------------------------------------------------------------

def golden_run():
    """The pinned run: impaired duplicate-class hierarchical fleet.

    Shared with ``tests/fixtures/make_hier_golden.py`` (which loads this
    module by path), so the fixture and the test can never run different
    configurations.  The deadline sits close to the frame length, so the
    impairment stream's latency spikes actually decide satisfaction — the
    fixture pins a non-trivial per-member outcome profile.
    """
    return simulate_fleet(
        _ample_spec(), _dup_cfg(delay_req_ms=3300.0, impairments=_IMPAIRED),
        policy="gus", scenario=_FrameSnappedDup(), n_rep=2, seed=0,
        options=EngineOptions(scheduler="hierarchical"),
    )


def test_member_jitter_golden_fixture():
    """Pin the impaired duplicate-class hier fleet against a committed
    fixture (regenerate with ``PYTHONPATH=src python
    tests/fixtures/make_hier_golden.py``) so silent drift in the
    per-member deaggregation accounting fails loudly."""
    path = FIXTURES / "hier_member_golden.npz"
    if not path.exists():
        pytest.fail(f"missing fixture {path}; regenerate with "
                    "`PYTHONPATH=src python tests/fixtures/make_hier_golden.py`")
    fr = golden_run()
    g = np.load(path)
    assert int(g["n_requests"]) == fr.n_requests
    assert int(g["n_served"]) == fr.n_served
    np.testing.assert_array_equal(
        g["satisfied_per_rep"], np.asarray(fr.satisfied_per_rep))
    np.testing.assert_allclose(
        g["mean_us_per_rep"], np.asarray(fr.mean_us_per_rep), rtol=1e-6)


def test_class_chunks_carry_budgets_across_grid_steps(monkeypatch):
    """A frame whose classes span several grid steps (and a padded tail)
    allocates exactly like the oracle: the budget carry survives from one
    class chunk to the next and restarts for the next frame."""
    from repro.kernels import hier_pallas

    monkeypatch.setattr(hier_pallas, "CLASS_CHUNK", 8)
    frames = []
    for seed in (0, 1):
        inst = tile_instance(generate_instance(seed, SMALL, as_numpy=True), 2)
        agg = aggregate_instance(inst)
        frames.append(_class_args(agg, np.asarray(inst.gamma),
                                  np.asarray(inst.eta), pad_to=37))
    batch = [np.stack(xs) for xs in zip(*frames)]
    take, start = hier_pallas.hier_cells_pallas(*batch, interpret=True)
    for b, args in enumerate(frames):
        want_take, want_start = hier_cells_np(*args)
        assert want_take.sum() > 0
        np.testing.assert_array_equal(np.asarray(take[b]), want_take)
        np.testing.assert_array_equal(np.asarray(start[b]), want_start)


def test_fit_count_is_largest_fitting_multiple():
    """The chunk count every backend shares is ``max{t : f32(t * cost) <=
    budget}``, on exact multiples (where a chip's inexact divide floors one
    low) and on arbitrary budgets alike."""
    from repro.core.aggregation import _fit_count_np
    from repro.kernels.hier_pallas import fit_count

    rng = np.random.default_rng(0)
    cost = rng.uniform(0.5, 3000.0, 4000).astype(np.float32)
    k = rng.integers(1, 64, 4000).astype(np.float32)
    budgets = np.concatenate([k * cost, rng.uniform(0, 20000, 4000).astype(np.float32)])
    costs = np.concatenate([cost, cost])
    got = np.asarray(jax.jit(fit_count)(budgets, costs))
    for b, c, g in zip(budgets, costs, got):
        t = np.floor(np.float64(b) / np.float64(c)) + 1
        while np.float32(np.float32(t) * c) > b:
            t -= 1
        assert g == t == _fit_count_np(b, c), (b, c, g, t)
