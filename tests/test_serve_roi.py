"""ROI-query service: range decomposition properties, extraction
bit-identity against the dense cube, and the full serving fault matrix
(DESIGN.md §11).

Three layers, matching serve/roi.py and serve/service.py:

1. **Decomposition properties** (hypothesis): roi_to_ranges is exactly
   the intersecting block set (nothing missing, nothing extra), sorted,
   disjoint, minimal — and on aligned power-of-two ROIs hilbert needs
   at most (cubes: exactly 1 vs e²) as many ranges as row-major.
2. **Extraction exactness**: extract_roi over a ResidentPipeline's block
   store is bit-identical to slicing the unblockized cube, across
   ordering × boundary × channel count.
3. **Fault matrix**: every injected serving fault (failed fetch,
   bit-flipped payload, cache poison, deadline pressure, overload)
   surfaces as a typed QueryResult — recovered, degraded with an exact
   ``missing_ranges`` manifest, rejected, or error. Never a hang, never
   a silently wrong payload.

Plus the thread-safety satellite (the ops row-plan LRU hammered from a
pool) and the benchmark-model consistency
row the CI diff gate pins.
"""

import threading
from concurrent.futures import ThreadPoolExecutor

import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # container lacks hypothesis; deterministic fallback
    from _hypothesis_fallback import given, settings, st

from repro.core.boundary import mixed
from repro.core.orderings import block_index_3d
from repro.launch.faults import ServeFaultPlan, initial_state
from repro.serve import (QUERY_STATUSES, ROI, FetchError, QueryResult,
                         StencilQueryService, StoreLayout, extract_roi,
                         merge_blocks_to_ranges, ranges_to_blocks, roi_model,
                         roi_to_ranges)

KINDS = ("row_major", "column_major", "morton", "hilbert")
MS = (8, 16, 32)


# ---------------------------------------------------------------------------
# 1. roi_to_ranges decomposition properties
# ---------------------------------------------------------------------------

def _brute_blocks(layout: StoreLayout, roi: ROI) -> set:
    """Independent oracle: curve indices of every block whose T³ extent
    intersects the ROI, by scanning the whole block grid."""
    T, nt = layout.T, layout.nt
    out = set()
    for bk in range(nt):
        for bi in range(nt):
            for bj in range(nt):
                b = (bk, bi, bj)
                if all(c * T < h and (c + 1) * T > l
                       for c, l, h in zip(b, roi.lo, roi.hi)):
                    out.add(int(block_index_3d(layout.kind, bk, bi, bj, nt)))
    return out


@settings(max_examples=60, deadline=None)
@given(st.data())
def test_roi_to_ranges_exact_sorted_disjoint_minimal(data):
    """Union of ranges == intersecting block set; ranges are sorted,
    pairwise disjoint and non-adjacent (minimal), across all orderings
    and M ∈ {8, 16, 32}."""
    M = MS[data.draw(st.integers(0, len(MS) - 1))]
    kind = KINDS[data.draw(st.integers(0, len(KINDS) - 1))]
    lo = tuple(data.draw(st.integers(0, M - 1)) for _ in range(3))
    hi = tuple(data.draw(st.integers(l + 1, M)) for l in lo)
    layout = StoreLayout(M=M, T=4, kind=kind)
    roi = ROI(lo, hi)

    ranges = roi_to_ranges(layout, roi)
    assert all(a < b for a, b in ranges)
    for (_, b0), (a1, _) in zip(ranges, ranges[1:]):
        assert b0 < a1  # sorted + disjoint + non-adjacent == minimal
    assert set(ranges_to_blocks(ranges).tolist()) == _brute_blocks(layout, roi)


@settings(max_examples=40, deadline=None)
@given(st.data())
def test_aligned_pow2_cube_is_one_hierarchical_range(data):
    """An aligned 2^a-block cube is one octree subtree: exactly ONE
    contiguous range under hilbert/morton, exactly e² ranges under
    row-major (e < nt) — so hilbert ≤ row-major always, strictly
    whenever the cube is a proper subcube."""
    M = MS[data.draw(st.integers(0, len(MS) - 1))]
    T = 4
    nt = M // T
    a = data.draw(st.integers(0, nt.bit_length() - 1))
    e = 2 ** a  # cube edge, blocks
    pos = tuple(data.draw(st.integers(0, nt // e - 1)) * e for _ in range(3))
    roi = ROI(tuple(p * T for p in pos), tuple((p + e) * T for p in pos))

    counts = {k: len(roi_to_ranges(StoreLayout(M=M, T=T, kind=k), roi))
              for k in KINDS}
    assert counts["hilbert"] == 1 and counts["morton"] == 1
    assert counts["row_major"] == (e * e if e < nt else 1)
    assert counts["hilbert"] <= counts["row_major"]
    if e < nt and e > 1:
        assert counts["hilbert"] < counts["row_major"]


def test_merge_blocks_to_ranges_edge_cases():
    assert merge_blocks_to_ranges(np.array([])) == []
    assert merge_blocks_to_ranges(np.array([3])) == [(3, 4)]
    assert merge_blocks_to_ranges(np.array([5, 3, 4, 9, 3])) == [(3, 6), (9, 10)]
    assert ranges_to_blocks([]).size == 0
    np.testing.assert_array_equal(ranges_to_blocks([(1, 3), (7, 8)]), [1, 2, 7])


def test_roi_and_layout_validation():
    with pytest.raises(ValueError):
        ROI((0, 0, 0), (0, 4, 4))  # empty axis
    with pytest.raises(ValueError):
        ROI((0, 0), (4, 4))  # not 3-D
    with pytest.raises(ValueError):
        StoreLayout(M=10, T=4)  # T does not tile M
    with pytest.raises(ValueError):
        roi_to_ranges(StoreLayout(M=8, T=4), ROI((0, 0, 0), (9, 4, 4)))
    with pytest.raises(ValueError):
        QueryResult(status="bogus", roi=ROI((0, 0, 0), (1, 1, 1)))


def test_roi_model_accounting():
    lay = StoreLayout(M=16, T=4, kind="hilbert", channels=2)
    m = roi_model(lay, ROI((0, 0, 0), (8, 8, 8)))
    assert m["blocks_touched"] == 8 and m["ranges"] == 1
    assert m["bytes_read"] == 8 * 2 * 64 * 4
    assert m["payload_bytes"] == 2 * 512 * 4
    assert m["utilization"] == 1.0
    # unaligned box pays for whole blocks: utilization < 1
    m2 = roi_model(lay, ROI((1, 1, 1), (9, 9, 9)))
    assert m2["blocks_touched"] == 27 and m2["utilization"] < 1.0


# ---------------------------------------------------------------------------
# 2. extract_roi bit-identity vs the dense cube (ordering × boundary × C)
# ---------------------------------------------------------------------------

def _rois_for(M):
    return [ROI((0, 0, 0), (M, M, M)),             # whole cube
            ROI((0, 0, 0), (M // 2,) * 3),         # aligned octant
            ROI((1, 2, 3), (M - 3, M - 1, M)),     # unaligned box
            ROI((M - 1, 0, M // 2), (M, 1, M // 2 + 1))]  # single element line


@pytest.mark.parametrize("kind", KINDS)
@pytest.mark.parametrize("rule,bc", [
    ("gol", "periodic"), ("gol", "neumann0"),
    ("wave", "periodic"), ("wave", mixed(k="neumann0")),
])
def test_extract_roi_bit_identical_to_dense_slice(kind, rule, bc):
    import jax.numpy as jnp

    from repro.stencil import ResidentPipeline

    M, T = 8, 4
    pipe = ResidentPipeline(M=M, T=T, rule=rule, bc=bc, kind=kind)
    cube = np.asarray(pipe.run(jnp.asarray(initial_state(rule, M, seed=1)), 2))
    store = np.asarray(pipe.to_blocks(jnp.asarray(cube)))
    layout = StoreLayout.from_pipeline(pipe)
    for roi in _rois_for(M):
        got = extract_roi(store, layout, roi)
        sl = tuple(slice(l, h) for l, h in zip(roi.lo, roi.hi))
        np.testing.assert_array_equal(got, cube[(Ellipsis,) + sl])


def test_extract_roi_skip_blocks_nan_fill():
    lay = StoreLayout(M=8, T=4, kind="hilbert")
    store = np.random.default_rng(0).standard_normal(
        (lay.nb, 4, 4, 4)).astype(np.float32)
    roi = ROI((0, 0, 0), (8, 4, 4))
    ranges = roi_to_ranges(lay, roi)
    skip = [int(ranges_to_blocks(ranges)[0])]
    out = extract_roi(store, lay, roi, ranges=ranges, skip_blocks=skip)
    assert np.isnan(out).sum() == 64  # exactly one block's footprint
    full = extract_roi(store, lay, roi)
    mask = ~np.isnan(out)
    np.testing.assert_array_equal(out[mask], full[mask])


# ---------------------------------------------------------------------------
# 3. the serving fault matrix
# ---------------------------------------------------------------------------

class FakeClock:
    """Injectable monotonic clock; ``sleep`` advances it (no real wait)."""

    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


def _service(kind="hilbert", M=16, T=4, C=1, **kw):
    rng = np.random.default_rng(7)
    lay = StoreLayout(M=M, T=T, kind=kind, channels=C)
    shape = ((lay.nb, T, T, T) if C == 1
             else (C, lay.nb, T, T, T))
    store = rng.standard_normal(shape).astype(np.float32)
    kw.setdefault("backoff_s", 1e-4)
    return StencilQueryService(store=store, layout=lay, **kw), store, lay


OCTANT = ROI((0, 0, 0), (8, 8, 8))       # 1 hilbert range at M=16/T=4
MULTI = ROI((0, 0, 0), (16, 8, 8))       # 8 row-major ranges


@pytest.mark.parametrize("C", [1, 2])
def test_query_ok_bit_identical(C):
    svc, store, lay = _service(C=C)
    r = svc.query(OCTANT)
    assert r.status == "ok" and r.complete and r.missing_ranges == ()
    assert r.payload.shape == ((8, 8, 8) if C == 1 else (2, 8, 8, 8))
    np.testing.assert_array_equal(r.payload, extract_roi(store, lay, OCTANT))
    assert len(r.ranges) == 1 and r.fetch_calls == 1  # contiguity economics


def test_cache_hits_and_disabled_cache():
    svc, _, lay = _service()
    r1 = svc.query(OCTANT)
    r2 = svc.query(OCTANT)
    assert r1.cache_misses == 8 and r1.fetch_calls == 1
    assert r2.cache_hits == 8 and r2.cache_misses == 0 and r2.fetch_calls == 0
    np.testing.assert_array_equal(r1.payload, r2.payload)
    assert svc.stats()["cached_blocks"] == 8

    svc0, _, _ = _service(cache_blocks=0)
    svc0.query(OCTANT)
    r = svc0.query(OCTANT)
    assert r.cache_hits == 0 and r.fetch_calls == 1  # every query refetches
    assert svc0.stats()["cached_blocks"] == 0


def test_cache_poison_quarantined_and_refetched():
    svc, store, lay = _service()
    svc.query(OCTANT)
    b = int(ranges_to_blocks(roi_to_ranges(lay, OCTANT))[0])
    assert svc.poison_cache(b)
    r = svc.query(OCTANT)
    assert r.status == "ok" and r.quarantined == 1
    assert r.cache_hits == 7 and r.cache_misses == 1  # only the bad block
    np.testing.assert_array_equal(r.payload, extract_roi(store, lay, OCTANT))
    assert svc.stats()["quarantined"] == 1
    # the quarantined block was re-fetched and re-cached clean
    r3 = svc.query(OCTANT)
    assert r3.cache_hits == 8 and r3.quarantined == 0


def test_transient_fetch_failures_recover():
    svc, store, lay = _service(max_retries=2)
    plan = ServeFaultPlan(fail_first=2)
    svc.fetch = plan.wrap_fetch(svc.fetch)
    r = svc.query(OCTANT)
    assert r.status == "ok" and r.retries == 2 and r.fetch_calls == 3
    np.testing.assert_array_equal(r.payload, extract_roi(store, lay, OCTANT))


def test_exhausted_retries_all_missing_is_error():
    svc, _, _ = _service(max_retries=2)
    plan = ServeFaultPlan(fail_first=99)
    svc.fetch = plan.wrap_fetch(svc.fetch)
    r = svc.query(OCTANT)
    assert r.status == "error" and not r.complete and r.payload is None
    assert r.missing_ranges == tuple(r.ranges)
    assert "injected fetch failure" in r.error


def test_exhausted_retries_partial_is_degraded_with_manifest():
    svc, store, lay = _service(kind="row_major", max_retries=2)
    plan = ServeFaultPlan(fail_first=3)  # kills exactly the first range
    svc.fetch = plan.wrap_fetch(svc.fetch)
    r = svc.query(MULTI)
    assert r.status == "degraded" and not r.complete
    assert len(r.ranges) == 8 and r.missing_ranges == (r.ranges[0],)
    # missing footprint is NaN; delivered footprint is bit-identical
    miss = np.isnan(r.payload)
    assert miss.sum() == (r.ranges[0][1] - r.ranges[0][0]) * 4 ** 3
    want = extract_roi(store, lay, MULTI)
    np.testing.assert_array_equal(r.payload[~miss], want[~miss])
    assert svc.stats()["degraded"] == 1


def test_bitflipped_fetch_caught_by_manifest_and_retried():
    svc, store, lay = _service(max_retries=2)
    plan = ServeFaultPlan(bitflip_first=1)
    svc.fetch = plan.wrap_fetch(svc.fetch)
    r = svc.query(OCTANT)
    assert r.status == "ok" and r.integrity_failures >= 1 and r.retries >= 1
    np.testing.assert_array_equal(r.payload, extract_roi(store, lay, OCTANT))


def test_bitflip_every_fetch_never_serves_wrong_bytes():
    svc, _, _ = _service(max_retries=1)
    plan = ServeFaultPlan(bitflip_first=99)
    svc.fetch = plan.wrap_fetch(svc.fetch)
    r = svc.query(OCTANT)
    assert r.status == "error" and r.payload is None  # typed, not corrupt
    assert "integrity failure" in r.error


def test_deadline_pressure_degrades_with_fake_clock():
    clock = FakeClock()
    svc, store, lay = _service(kind="row_major", clock=clock,
                               sleep=clock.advance, deadline_s=0.5)
    plan = ServeFaultPlan(slow_first=99, slow_s=0.2)
    svc.fetch = plan.wrap_fetch(svc.fetch, sleep=clock.advance)
    r = svc.query(MULTI)
    assert r.status == "degraded" and r.missing_ranges
    assert "deadline" in r.error
    assert r.elapsed_s >= 0.5  # but it returned — no hang
    # the two ranges that landed before the deadline are exact
    miss = np.isnan(r.payload)
    want = extract_roi(store, lay, MULTI)
    np.testing.assert_array_equal(r.payload[~miss], want[~miss])
    # a fresh unhurried query on the same (now slow-free) service is ok
    plan.slow_first = 0
    assert svc.query(MULTI).status == "ok"


def test_admission_control_sheds_typed_rejections():
    svc, _, _ = _service(max_in_flight=2, cache_blocks=0)
    base = svc.fetch
    entered = threading.Semaphore(0)
    release = threading.Event()

    def gated(a, b):
        entered.release()
        assert release.wait(10)
        return base(a, b)

    svc.fetch = gated
    with ThreadPoolExecutor(max_workers=2) as ex:
        futs = [ex.submit(svc.query, OCTANT, deadline_s=30) for _ in range(2)]
        assert entered.acquire(timeout=10) and entered.acquire(timeout=10)
        shed = [svc.query(OCTANT) for _ in range(4)]  # budget is full
        release.set()
        held = [f.result(timeout=30) for f in futs]
    assert [r.status for r in shed] == ["rejected"] * 4
    assert all(r.payload is None and "admission" in r.error for r in shed)
    assert [r.status for r in held] == ["ok", "ok"]
    assert svc.stats()["shed"] == 4 and svc.stats()["in_flight"] == 0


def test_query_batch_order_preserving_and_typed():
    svc, store, lay = _service()
    rois = [OCTANT, ROI((8, 8, 8), (16, 16, 16)), ROI((1, 2, 3), (5, 9, 13)),
            ROI((0, 0, 0), (16, 16, 16))]
    results = svc.query_batch(rois)
    assert [r.roi for r in results] == rois
    assert all(r.status in QUERY_STATUSES for r in results)
    assert all(r.status == "ok" for r in results)
    for roi, r in zip(rois, results):
        np.testing.assert_array_equal(r.payload, extract_roi(store, lay, roi))


def test_fault_plan_composes_under_batch():
    """Transient failures + one bitflip injected into a concurrent batch:
    every outcome typed, every delivered byte exact."""
    svc, store, lay = _service(max_retries=3)
    plan = ServeFaultPlan(fail_first=2, bitflip_first=1)
    svc.fetch = plan.wrap_fetch(svc.fetch)
    rois = [OCTANT, ROI((8, 0, 0), (16, 8, 8)), ROI((0, 8, 0), (8, 16, 8))]
    results = svc.query_batch(rois)
    assert all(r.status == "ok" for r in results)
    assert sum(r.retries for r in results) >= 3
    for roi, r in zip(rois, results):
        np.testing.assert_array_equal(r.payload, extract_roi(store, lay, roi))


def test_short_read_is_a_typed_fetch_error():
    svc, _, _ = _service(max_retries=0)
    svc.fetch = lambda a, b: np.zeros((1, 1, 4, 4, 4), np.float32)
    r = svc.query(OCTANT)
    assert r.status == "error" and "short read" in r.error


def test_fetch_error_is_runtime_error():
    assert issubclass(FetchError, RuntimeError)


# ---------------------------------------------------------------------------
# satellites: thread-safe LRU caches under the serving pool
# ---------------------------------------------------------------------------

def test_row_plan_thread_safe_under_hammer():
    from repro.kernels import ops

    rng = np.random.default_rng(3)
    idxs = [np.sort(rng.choice(4096, 256, replace=False)) for _ in range(16)]
    refs = [ops._row_plan(i, 64) for i in idxs]  # uncached ground truth
    errs = []

    def worker(t):
        try:
            for i in range(100):
                j = (t + i) % len(idxs)
                rows, pos = ops._row_plan(idxs[j], 64,
                                          plan_key=("tsafe", j))
                np.testing.assert_array_equal(rows, refs[j][0])
                np.testing.assert_array_equal(pos, refs[j][1])
        except Exception as e:  # pragma: no cover - failure path
            errs.append(e)

    threads = [threading.Thread(target=worker, args=(t,)) for t in range(8)]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    assert errs == []


# ---------------------------------------------------------------------------
# satellite: the benchmarked ROI suite matches the model, hilbert strict
# ---------------------------------------------------------------------------

def test_benchmark_rows_match_model_and_hilbert_strictly_beats_row():
    from benchmarks.roi import ORDERINGS, roi_suite

    T = 8
    for M in (32, 64):
        for name, roi in roi_suite(M):
            counts = {k: roi_model(StoreLayout(M=M, T=T, kind=k), roi)
                      for k in ORDERINGS}
            # the acceptance criterion: strict on every benchmarked row
            assert counts["hilbert"]["ranges"] < counts["row_major"]["ranges"], \
                (M, name, counts)
            # geometry keys are curve-independent
            for k in ORDERINGS:
                assert counts[k]["blocks_touched"] == \
                    counts["hilbert"]["blocks_touched"]
                assert counts[k]["bytes_read"] == counts["hilbert"]["bytes_read"]


def test_benchmark_derived_strings_reproduce_model():
    from benchmarks import roi as bench

    for name, _us, derived in bench.rows(sizes=(32,)):
        # name: roi/extract_M{M}_T{T}_{kind}_{roi_name}
        tail = name.split("/", 1)[1][len("extract_"):]
        m_s, t_s, rest = tail.split("_", 2)
        kind = next(k for k in bench.ORDERINGS if rest.startswith(k))
        roi_name = rest[len(kind) + 1:]
        lay = StoreLayout(M=int(m_s[1:]), T=int(t_s[1:]), kind=kind)
        roi = dict(bench.roi_suite(lay.M))[roi_name]
        m = roi_model(lay, roi)
        d = dict(p.split("=") for p in derived.split(";"))
        assert int(d["roi_ranges"]) == m["ranges"]
        assert int(d["roi_blocks"]) == m["blocks_touched"]
        assert int(d["roi_bytes_read"]) == m["bytes_read"]
        assert int(d["roi_payload_bytes"]) == m["payload_bytes"]
        assert abs(float(d["utilization"]) - m["utilization"]) < 1e-3
