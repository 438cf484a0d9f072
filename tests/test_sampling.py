"""Cohort sampler: purity, unbiasedness, uniform super-cohort routing,
and the per-client state matrix (billing parity vs the per-client
VersionCache dict oracle)."""

import numpy as np

from billing_oracle import VersionCache
from repro.core.client_state import ClientStateMatrix
from repro.core.sampling import (CohortSampler, draw_without_replacement,
                                 round_rng)


# ---------------------------------------------------------------------------
# draw_without_replacement
# ---------------------------------------------------------------------------

def test_draw_is_sorted_unique_in_range():
    for n, k in ((10, 10), (100, 30), (10_000, 50), (7, 0)):
        ids = draw_without_replacement(round_rng(0, 0), n, k)
        assert ids.shape == (k,) and ids.dtype == np.int64
        assert (np.diff(ids) > 0).all()          # sorted, no repeats
        assert (ids >= 0).all() and (ids < n).all()


def test_draw_rejects_bad_k():
    with np.testing.assert_raises(ValueError):
        draw_without_replacement(round_rng(0, 0), 10, 11)
    with np.testing.assert_raises(ValueError):
        draw_without_replacement(round_rng(0, 0), 10, -1)


def test_rejection_path_is_unbiased_chi_square():
    """The sparse (batched-rejection) path must be uniform over ids — an
    order-dependent dedupe bug would skew the marginal.  Chi-square on
    the pooled selection counts, normal-approximation threshold (no
    scipy): stat ~ chi2(df) => mean df, var 2df; 5 sigma is a ~1e-6
    false-positive gate."""
    n, k, rounds = 500, 20, 4000      # 4k << n: always the sparse path
    counts = np.zeros(n)
    for r in range(rounds):
        ids = draw_without_replacement(round_rng(123, r), n, k)
        counts[ids] += 1
    expected = rounds * k / n
    stat = float(((counts - expected) ** 2 / expected).sum())
    df = n - 1
    assert stat < df + 5 * np.sqrt(2 * df), (stat, df)


# ---------------------------------------------------------------------------
# CohortSampler
# ---------------------------------------------------------------------------

def test_plan_is_pure_in_seed_and_round():
    s = CohortSampler(n_devices=100, n_simple=50, participation=0.1, seed=9)
    a, b = s.plan(5), s.plan(5)
    np.testing.assert_array_equal(a.simple_ids, b.simple_ids)
    np.testing.assert_array_equal(a.complex_ids, b.complex_ids)
    # call order is irrelevant (no sequential stream): a second sampler
    # visiting rounds backwards draws the same plans
    s2 = CohortSampler(n_devices=100, n_simple=50, participation=0.1, seed=9)
    for r in (7, 3, 5):
        np.testing.assert_array_equal(s2.plan(r).simple_ids,
                                      s.plan(r).simple_ids)
    # different rounds / seeds give different cohorts
    assert not np.array_equal(s.plan(0).simple_ids, s.plan(1).simple_ids) \
        or not np.array_equal(s.plan(0).complex_ids, s.plan(1).complex_ids)


def test_stratified_capacities_match_trainer_rule():
    s = CohortSampler(n_devices=100, n_simple=50, participation=0.1, seed=0)
    assert (s.cap_simple, s.cap_complex) == (5, 5)
    assert s.plan(0).all_real
    # tiny populations floor at 1 per arch (the old trainer's rule)
    s = CohortSampler(n_devices=4, n_simple=2, participation=0.01, seed=0)
    assert (s.cap_simple, s.cap_complex) == (1, 1)


def test_uniform_plan_routes_and_pads():
    s = CohortSampler(n_devices=100, n_simple=50, participation=0.1,
                      seed=11, uniform=True)
    assert s.k_super == 10
    for r in range(20):
        p = s.plan(r)
        # realized split sums to the super-cohort size
        assert p.n_real_simple + p.n_real_complex == s.k_super
        # routing: real simple slots < n_simple, real complex slots >=
        assert (p.simple_ids[p.simple_real] < 50).all()
        assert (p.complex_ids[p.complex_real] >= 50).all()
        # real ids are distinct clients; pad slots wrap real ids
        rid = p.real_ids()
        assert np.unique(rid).size == rid.size
        assert np.isin(p.simple_ids[~p.simple_real],
                       np.concatenate([p.simple_ids[p.simple_real],
                                       [0]])).all()


def test_uniform_participation_is_unbiased_chi_square():
    """The paper's protocol: every client equally likely per round,
    regardless of architecture.  Chi-square over participation counts
    accumulated in the client-state matrix."""
    n, rounds = 200, 3000
    s = CohortSampler(n_devices=n, n_simple=100, participation=0.05,
                      seed=42, uniform=True)
    m = ClientStateMatrix(n)
    for r in range(rounds):
        m.record_round(s.plan(r).real_ids(), r)
    counts = m.column("participation")
    expected = rounds * s.k_super / n
    stat = float(((counts - expected) ** 2 / expected).sum())
    df = n - 1
    assert stat < df + 5 * np.sqrt(2 * df), (stat, df)


def test_uniform_equals_stratified_at_full_participation():
    """At participation=1.0 both modes enumerate the whole population:
    the bit-parity hook for the mode switch."""
    kw = dict(n_devices=20, n_simple=8, participation=1.0, seed=5)
    s, u = CohortSampler(**kw), CohortSampler(uniform=True, **kw)
    for r in range(4):
        a, b = s.plan(r), u.plan(r)
        assert b.all_real
        np.testing.assert_array_equal(a.simple_ids, b.simple_ids)
        np.testing.assert_array_equal(a.complex_ids, b.complex_ids)


def test_state_dict_validation():
    s = CohortSampler(n_devices=100, n_simple=50, participation=0.1, seed=1)
    s.validate_state(s.state_dict())         # self-consistent
    s.validate_state(None)                   # pre-sampler checkpoint
    s.validate_state({})
    bad = dict(s.state_dict(), seed=2)
    with np.testing.assert_raises(ValueError):
        s.validate_state(bad)


# ---------------------------------------------------------------------------
# ClientStateMatrix
# ---------------------------------------------------------------------------

def test_record_round_and_histogram():
    m = ClientStateMatrix(10)
    m.record_round(np.array([1, 2, 3]), 0)
    m.record_round(np.array([2, 3, 4]), 1)
    assert m.tracked_clients() == 4
    assert m.participation_histogram() == {"0": 6, "1": 2, "2": 2}
    np.testing.assert_array_equal(m.column("last_round")[[1, 2, 4]],
                                  [0.0, 1.0, 1.0])
    assert m.column("last_round")[0] == -1.0     # never participated


def test_billing_parity_vs_version_cache():
    """The vectorized tag-compare must bill byte-for-byte like the
    per-client VersionCache dict oracle on identical fetch sequences —
    including hit/miss tallies (the telemetry deltas)."""
    m = ClientStateMatrix(64)
    vc = VersionCache()
    rng = np.random.default_rng(3)
    hits = misses = 0
    for r in range(50):
        ids = rng.choice(64, size=12, replace=False)
        tags = rng.integers(0, 5, size=12)
        ref = sum(vc.bill(int(c), float(t), 37.0)
                  for c, t in zip(ids, tags))
        got, h, mi = m.bill_downloads(ids, tags.astype(float), 37.0)
        assert got == ref
        hits += h
        misses += mi
    assert (hits, misses) == (vc.hits, vc.misses)


def test_billing_reset_forgets_versions():
    m = ClientStateMatrix(8)
    ids = np.arange(4)
    billed, _, _ = m.bill_downloads(ids, np.zeros(4), 10.0)
    assert billed == 40.0
    billed, _, _ = m.bill_downloads(ids, np.zeros(4), 10.0)
    assert billed == 0.0                         # all cached
    m.reset_version_tags()
    billed, _, _ = m.bill_downloads(ids, np.zeros(4), 10.0)
    assert billed == 40.0                        # history wiped


def test_load_matches_columns_by_name():
    m = ClientStateMatrix(5)
    m.record_round(np.array([0, 1]), 3)
    # a checkpoint written under a REORDERED schema restores by name
    cols = list(reversed(m.columns))
    payload = m.array[:, ::-1].copy()
    m2 = ClientStateMatrix(5)
    m2.load(payload, cols)
    np.testing.assert_array_equal(m2.array, m.array)
    with np.testing.assert_raises(ValueError):
        m2.load(payload, cols[:-1])              # width mismatch
    with np.testing.assert_raises(ValueError):
        ClientStateMatrix(6).load(payload, cols)  # size mismatch


def test_gather_scatter_roundtrip():
    m = ClientStateMatrix(6)
    ids = np.array([1, 4, m.sentinel])           # sentinel row is scratch
    rows = m.gather(ids)
    rows[:, 0] = 9.0
    m.scatter(ids, rows)
    np.testing.assert_array_equal(m.column("participation")[[1, 4]],
                                  [9.0, 9.0])
    assert m.tracked_clients() == 2              # sentinel masked out
