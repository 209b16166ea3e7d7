"""Fault injection + fault-tolerant shard recovery (core.faults).

The chaos contract (ISSUE 9): every scheduled failure — worker crash,
stalled chunk loads, gather-transport drop, torn cache write — is
reproducible in-process through :class:`FaultInjector`; a resilient
cluster recovers orphaned shards **bitwise-equal** to the no-fault run
(same rows, same kernels, same merge order) across fault × W × index
space; when the retry budget or a request deadline is exhausted the
round degrades to partial coverage instead of raising; and no accepted
serve request is ever dropped or left unresolved.
"""

import threading
import time

import numpy as np
import pytest

from repro.core.embedding_cache import EmbeddingCache
from repro.core.evaluator import IVFSearchSpace
from repro.core.fair_sharding import FairSharder, ShardAborted
from repro.core.faults import (Fault, FaultInjector, InjectedCrash,
                               InjectedTransportDrop, SearchOutcome,
                               WorkerHealth, full_coverage)
from repro.core.serving import ServeFrontend, ServeTimeoutError
from repro.core.sharded_search import ResidentRows, ShardedSearchDriver
from repro.launch.distributed import SimulatedCluster
from repro.training.fault_tolerance import resilient_loop

pytestmark = pytest.mark.faults


# -- fixtures -----------------------------------------------------------------


N_DOCS, DIM, N_Q, K = 200, 16, 6, 5
# cluster edges for the IVF-shaped search space: shard cuts snap here
IVF_EDGES = np.array([0, 40, 80, 120, 160, 200], np.int64)


@pytest.fixture()
def synth():
    rng = np.random.default_rng(11)
    q = rng.normal(size=(N_Q, DIM)).astype(np.float32)
    docs = rng.normal(size=(N_DOCS, DIM)).astype(np.float32)
    return q, docs


def _load_from(docs):
    return lambda lo, hi: docs[lo:hi]


def _space(index_impl):
    """The driver's sized ``n_docs`` argument: a plain int for a flat
    scan, an :class:`IVFSearchSpace` (cluster-edge boundaries) for the
    IVF path — dead-worker repartitions must re-snap to these edges."""
    if index_impl == "flat":
        return N_DOCS
    return IVFSearchSpace(N_DOCS, IVF_EDGES)


def _oracle(q, docs, space):
    driver = ShardedSearchDriver(score_impl="numpy", chunk_size=16)
    return driver.search(q, space, _load_from(docs), K)


def _run_cluster(q, docs, space, w, injector, *, deadline_s=None,
                 round_deadline_s=0.15, max_retries=2, backoff_s=0.01,
                 searches=1):
    """W resilient drivers, one shared injector; returns the per-rank
    outs of the last search plus the cluster (for health inspection)."""
    cluster = SimulatedCluster(w, resilient=True)
    drivers = [ShardedSearchDriver(
        n_workers=w, worker_index=rank, sharder=cluster.sharder,
        gather=cluster.gather, score_impl="numpy", chunk_size=16,
        fault_injector=injector, round_deadline_s=round_deadline_s,
        max_shard_retries=max_retries, retry_backoff_s=backoff_s)
        for rank in range(w)]
    outs = None
    for _ in range(searches):
        outs = cluster.run(lambda rank: drivers[rank].search(
            q, space, _load_from(docs), K, deadline_s=deadline_s))
    return outs, cluster


# -- FaultInjector ------------------------------------------------------------


def test_fault_validation():
    with pytest.raises(ValueError):
        Fault(kind="meteor")
    with pytest.raises(ValueError):
        Fault(kind="crash", phase="orbit")
    with pytest.raises(ValueError):
        Fault(kind="torn_write", point="nowhere")


def test_injector_fires_once_and_logs():
    inj = FaultInjector([Fault(kind="crash", worker=1, round=0)])
    inj.on_chunk(0, 0, 0)                   # wrong worker: no fire
    inj.on_chunk(1, 1, 0)                   # wrong round: no fire
    with pytest.raises(InjectedCrash):
        inj.on_chunk(1, 0, 0)
    inj.on_chunk(1, 0, 0)                   # one-shot: spent
    assert inj.fired == [("crash", 1, 0, "load")]


def test_injector_repeat_fires_every_match():
    inj = FaultInjector([Fault(kind="crash", repeat=True)])
    for _ in range(3):
        with pytest.raises(InjectedCrash):
            inj.on_chunk(0, 0, 0)
    assert len(inj.fired) == 3


def test_injector_stall_sleeps_instead_of_raising():
    inj = FaultInjector([Fault(kind="stall", stall_s=0.1)])
    t0 = time.monotonic()
    inj.on_chunk(0, 0, 0)
    assert time.monotonic() - t0 >= 0.09


def test_injector_gather_drop():
    inj = FaultInjector([Fault(kind="drop", worker=2, phase="gather")])
    inj.on_gather(0, 0)
    with pytest.raises(InjectedTransportDrop):
        inj.on_gather(2, 0)


def test_from_seed_is_deterministic():
    a = FaultInjector.from_seed(7, n_workers=4, n_faults=3)
    b = FaultInjector.from_seed(7, n_workers=4, n_faults=3)
    assert a.faults == b.faults
    assert all(f.kind in ("crash", "stall", "drop") for f in a.faults)
    assert all(f.worker in range(4) for f in a.faults)
    c = FaultInjector.from_seed(8, n_workers=4, n_faults=3)
    assert a.faults != c.faults


def test_search_outcome_unpacks_like_a_tuple():
    v, i = np.zeros((2, 3)), np.ones((2, 3), np.int64)
    out = SearchOutcome((v, i), coverage=full_coverage(2))
    a, b = out
    assert a is v and b is i
    assert not out.degraded
    np.testing.assert_array_equal(out.coverage, [1.0, 1.0])


# -- the chaos matrix: fault × W × index space --------------------------------


def _fault_for(kind):
    if kind == "drop":
        return Fault(kind="drop", worker=1, round=0, phase="gather")
    return Fault(kind=kind, worker=1, round=0, phase="load", stall_s=1.0)


@pytest.mark.parametrize("index_impl", ("flat", "ivf"))
@pytest.mark.parametrize("w", (2, 4))
@pytest.mark.parametrize("kind", ("crash", "stall", "drop"))
def test_recovery_is_bitwise_equal_to_no_fault_run(synth, kind, w,
                                                   index_impl):
    """One worker crashes / stalls past the round deadline / loses its
    gather send: survivors rescore the orphaned shard and the merged
    positions are bitwise-equal to the no-fault W=1 oracle, with full
    coverage on every rank."""
    q, docs = synth
    space = _space(index_impl)
    ref_vals, ref_pos = _oracle(q, docs, space)
    inj = FaultInjector([_fault_for(kind)])
    outs, _ = _run_cluster(q, docs, space, w, inj)
    assert inj.fired, f"{kind} fault never fired"
    for out in outs:
        vals, pos = out
        np.testing.assert_array_equal(pos, ref_pos)
        np.testing.assert_allclose(vals, ref_vals, rtol=1e-5)
        np.testing.assert_array_equal(out.coverage, full_coverage(N_Q))
        assert not out.degraded


@pytest.mark.parametrize("index_impl", ("flat", "ivf"))
def test_round_after_crash_repartitions_over_survivors(synth, index_impl):
    """The round *after* a crash: the dead rank gets an exact-zero share
    (bounds re-snapped to cluster edges on the IVF space) and survivors
    still reproduce the oracle."""
    q, docs = synth
    space = _space(index_impl)
    ref_vals, ref_pos = _oracle(q, docs, space)
    inj = FaultInjector([Fault(kind="crash", worker=1, round=0)])
    outs, cluster = _run_cluster(q, docs, space, 4, inj, searches=2)
    assert cluster.health.is_dead(1)
    bounds = cluster.sharder.bounds(
        N_DOCS, IVF_EDGES if index_impl == "ivf" else None)
    lo, hi = bounds[1]
    assert lo == hi, f"dead worker kept a non-empty shard {bounds[1]}"
    if index_impl == "ivf":
        for b in {b for lo_hi in bounds for b in lo_hi}:
            assert b in IVF_EDGES, f"cut {b} not on a cluster edge"
    for out in outs:
        vals, pos = out
        np.testing.assert_array_equal(pos, ref_pos)
        np.testing.assert_array_equal(out.coverage, full_coverage(N_Q))


def test_retry_budget_exhaustion_degrades_with_partial_coverage(synth):
    """Every rescue attempt crashes too: past max_shard_retries the
    round resolves partial — identical on every rank, coverage < 1,
    degraded set — instead of raising."""
    q, docs = synth
    inj = FaultInjector([
        Fault(kind="crash", worker=1, round=0, phase="load"),
        Fault(kind="crash", round=0, phase="retry", repeat=True)])
    outs, _ = _run_cluster(q, docs, N_DOCS, 2, inj, max_retries=1)
    ref = outs[0]
    for out in outs:
        assert out.degraded
        assert (np.asarray(out.coverage) < 1.0).all()
        np.testing.assert_allclose(out.coverage, 0.5)
        np.testing.assert_array_equal(out[1], ref[1])
    # the half that survived is still exact: every returned position
    # comes from worker 0's shard and matches the flat oracle's ranking
    # restricted to that shard
    lo, hi = 0, N_DOCS // 2
    full = q @ docs[lo:hi].T
    oracle_pos = np.argsort(-full, axis=1, kind="stable")[:, :K]
    np.testing.assert_array_equal(ref[1], oracle_pos + lo)


def test_request_deadline_degrades_instead_of_blocking(synth):
    """A crash whose rescuer is itself stalled: waiters hit the request
    deadline and resolve partial NOW (coverage = the shards that did
    arrive) instead of waiting out the stalled recovery."""
    q, docs = synth
    inj = FaultInjector([
        Fault(kind="crash", worker=1, round=0, phase="load"),
        Fault(kind="stall", round=0, phase="retry", stall_s=2.0,
              repeat=True)])
    t0 = time.monotonic()
    outs, _ = _run_cluster(q, docs, N_DOCS, 4, inj, deadline_s=0.4,
                           round_deadline_s=0.05)
    for out in outs:
        assert out.degraded
        assert (np.asarray(out.coverage) < 1.0).all()
        np.testing.assert_array_equal(out[1], outs[0][1])
    # the partial merge resolved near the deadline, not after the stall
    # (cluster.run still joins the stalled rescuer thread afterwards)
    assert time.monotonic() - t0 < 10.0


def test_no_survivor_left_degrades_to_reporting_ranks(synth):
    """Both of a W=2 cluster's recovery paths dead-end (the only
    survivor's rescue crashes repeatedly): partial result, no hang."""
    q, docs = synth
    inj = FaultInjector([
        Fault(kind="crash", worker=0, round=0, phase="load"),
        Fault(kind="crash", round=0, phase="retry", repeat=True)])
    outs, _ = _run_cluster(q, docs, N_DOCS, 2, inj, max_retries=0)
    assert outs[0].degraded
    np.testing.assert_allclose(outs[0].coverage, 0.5)


def test_crash_before_the_sibling_acquires_keeps_the_round_partition(synth):
    """Rank 1 acquires round 0 only once rank 0 has crashed and been
    marked dead: it must still get round 0's partition (so rank 0's
    shard is orphaned, its rescue crashes, coverage 0.5), not a
    partition that already leaves the dead rank out."""
    q, docs = synth
    inj = FaultInjector([
        Fault(kind="crash", worker=0, round=0, phase="load"),
        Fault(kind="crash", round=0, phase="retry", repeat=True)])
    cluster = SimulatedCluster(2, resilient=True)
    drivers = [ShardedSearchDriver(
        n_workers=2, worker_index=rank, sharder=cluster.sharder,
        gather=cluster.gather, score_impl="numpy", chunk_size=16,
        fault_injector=inj, round_deadline_s=30.0, max_shard_retries=0)
        for rank in range(2)]

    def work(rank):
        if rank == 1:
            t_end = time.monotonic() + 30.0
            while (not cluster.health.is_dead(0)
                   and time.monotonic() < t_end):
                time.sleep(0.005)
            assert cluster.health.is_dead(0)
        return drivers[rank].search(q, N_DOCS, _load_from(docs), K)

    outs = cluster.run(work)
    assert outs[1].degraded
    np.testing.assert_allclose(outs[1].coverage, 0.5)


# -- the resident executor's chunk fault point --------------------------------


class _NoLoadRows(ResidentRows):
    """A device-resident corpus whose ``(lo, hi)`` loader must never run."""

    __slots__ = ()

    def __call__(self, lo, hi):
        raise AssertionError(f"resident corpus loaded as chunk [{lo}, {hi})")


class _RecordingInjector(FaultInjector):
    def __init__(self, faults, events):
        super().__init__(faults)
        self.events = events

    def on_chunk(self, worker, round_no, chunk_index, phase="load"):
        self.events.append(("chunk", chunk_index))
        super().on_chunk(worker, round_no, chunk_index, phase)


def test_resident_chunk_fault_point_fires_per_chunk_before_its_scan(
        synth, monkeypatch):
    """200 rows in chunks of 16, S=4: chunks 0..12 each pass the fault
    point once, in order, and every group of four passes it before the
    in-place scan that reads it is dispatched."""
    from repro.kernels import ops
    q, docs = synth
    events: list = []
    scan = ops.superchunk_update

    def recording_scan(*args, **kw):
        events.append(("scan", [int(o) for o in args[4]]))
        return scan(*args, **kw)

    monkeypatch.setattr(ops, "superchunk_update", recording_scan)
    driver = ShardedSearchDriver(score_impl="jax", chunk_size=16,
                                 superchunk_size=4,
                                 fault_injector=_RecordingInjector([],
                                                                   events))
    _, pos = driver.search(q, N_DOCS, _NoLoadRows(docs, 16), K)
    assert driver.stats["executor"] == "resident"
    expected = []
    for g in range(0, 13, 4):
        group = list(range(g, min(g + 4, 13)))
        expected += [("chunk", ci) for ci in group]
        expected.append(("scan", [16 * ci for ci in group]
                         + [0] * (4 - len(group))))
    assert events == expected
    np.testing.assert_array_equal(pos, _oracle(q, docs, N_DOCS)[1])


@pytest.mark.parametrize("w", (2, 3))
def test_resident_recovery_is_bitwise_equal_to_no_fault_round(synth, w):
    """A worker crashes after its first in-place scan (chunk 4 of its
    shard): a survivor rescans the orphaned shard through the same
    resident path and every rank's merged round equals the no-fault
    round bit for bit, scores included, with full coverage."""
    q, docs = synth

    def cluster_round(injector):
        cluster = SimulatedCluster(w, resilient=True)
        drivers = [ShardedSearchDriver(
            n_workers=w, worker_index=rank, sharder=cluster.sharder,
            gather=cluster.gather, score_impl="jax", chunk_size=16,
            superchunk_size=4, fault_injector=injector,
            round_deadline_s=0.15, retry_backoff_s=0.01)
            for rank in range(w)]
        src = _NoLoadRows(docs, 16)
        outs = cluster.run(lambda rank: drivers[rank].search(q, N_DOCS,
                                                             src, K))
        return outs, drivers

    clean, _ = cluster_round(FaultInjector([]))
    inj = FaultInjector([Fault(kind="crash", worker=1, round=0,
                               phase="load", chunk=4)])
    outs, drivers = cluster_round(inj)
    assert inj.fired == [("crash", 1, 0, "load")]
    assert drivers[0].stats["executor"] == "resident"
    ref_pos = _oracle(q, docs, N_DOCS)[1]
    for out, ref in zip(outs, clean):
        np.testing.assert_array_equal(out[1], ref[1])
        np.testing.assert_array_equal(out[0], ref[0])
        np.testing.assert_array_equal(out[1], ref_pos)
        np.testing.assert_array_equal(out.coverage, full_coverage(N_Q))
        assert not out.degraded


# -- FairSharder: diagnostics + dead-worker bookkeeping -----------------------


def test_acquire_timeout_raises_with_diagnostics():
    s = FairSharder(2)
    s.ACQUIRE_TIMEOUT_S = 0.1               # instance override
    r0, _ = s.acquire(0, 100)
    assert r0 == 0
    s.update(0, 50, 1.0, round_no=0)
    with pytest.raises(ShardAborted) as ei:
        s.acquire(0, 100)                   # round 1 blocks on worker 1
    msg = str(ei.value)
    assert "round 0" in msg and "workers [1]" in msg
    assert "no round committed yet" in msg


def test_abort_releases_waiters_with_diagnostics():
    s = FairSharder(2)
    s.acquire(0, 100)
    errs = []

    def blocked():
        try:
            s.acquire(0, 100)
        except ShardAborted as e:
            errs.append(e)

    t = threading.Thread(target=blocked)
    t.start()
    time.sleep(0.05)
    boom = RuntimeError("worker 1 exploded")
    s.abort(boom)
    t.join(timeout=5)
    assert not t.is_alive()
    (err,) = errs
    assert "aborted while worker 0 waited for round 1" in str(err)
    assert "pending" in str(err)
    assert err.__cause__ is boom


def test_death_mid_round_leaves_the_round_partition_alone():
    s = FairSharder(2)
    r0, b0 = s.acquire(0, 100)
    s.mark_dead(0)
    r1, b1 = s.acquire(1, 100)
    assert r0 == r1 == 0 and b1 == b0 == [(0, 50), (50, 100)]
    s.update(1, 50, 1.0, round_no=0)        # round 0 commits without 0
    r, bounds = s.acquire(1, 100)
    assert r == 1 and bounds == [(0, 0), (0, 100)]


def test_mark_dead_zeroes_share_and_unblocks_round():
    s = FairSharder(4)
    for w in range(4):
        s.acquire(w, 100)
    for w in (0, 2, 3):
        s.update(w, 25, 1.0, round_no=0)
    s.mark_dead(1)                          # round 0 commits without it
    r, bounds = s.acquire(0, 100)
    assert r == 1
    lo, hi = bounds[1]
    assert lo == hi
    assert sum(b - a for a, b in bounds) == 100


def test_absolve_is_noop_for_committed_rounds():
    s = FairSharder(2)
    s.acquire(0, 10), s.acquire(1, 10)
    s.update(0, 5, 1.0, round_no=0)
    s.update(1, 5, 1.0, round_no=0)
    before = s.throughput.copy()
    s.absolve(0, 0)                         # round 0 already committed
    s.absolve(1, 5)                         # future round: buffered only
    np.testing.assert_array_equal(s.throughput, before)


def test_all_dead_shares_raise():
    s = FairSharder(2)
    s.mark_dead(0)
    s.mark_dead(1)
    with pytest.raises(ShardAborted, match="all 2 workers are dead"):
        s.shares(100)


# -- serve frontend: abandoned / expired / never-dropped ----------------------


def _echo_backend(delay=0.0):
    def run(texts, topk):
        if delay:
            time.sleep(delay)
        qnum = np.asarray([int(t[1:]) for t in texts])
        ids = qnum[:, None] * 100 + np.arange(topk)[None, :]
        return ids, ids.astype(np.float32)

    return run


def test_search_timeout_abandons_request():
    """A timed-out blocking search resolves its Future with
    ServeTimeoutError (never left unresolved) and coalescing skips the
    abandoned request instead of scoring it."""
    release = threading.Event()

    def gated(texts, topk):
        release.wait(5.0)
        return _echo_backend()(texts, topk)

    with ServeFrontend(gated, topk=2, max_batch=8, max_wait_ms=1) as fe:
        blocker = fe.submit("q1")           # occupies the dispatcher
        time.sleep(0.05)
        with pytest.raises(ServeTimeoutError):
            fe.search("q2", timeout=0.05)
        assert fe.stats["abandoned"] == 1
        release.set()
        blocker.result(timeout=10)
        # the abandoned request's Future is resolved, not dangling
        after = fe.submit("q3").result(timeout=10)
        np.testing.assert_array_equal(after[0][:, 0], [300])
    assert fe.stats["completed"] == 2       # q1 + q3, never q2


def test_deadline_ms_expires_queued_request_degraded_empty():
    release = threading.Event()

    def gated(texts, topk):
        release.wait(5.0)
        return _echo_backend()(texts, topk)

    with ServeFrontend(gated, topk=3, max_batch=8, max_wait_ms=1) as fe:
        fe.submit("q1")                     # occupies the dispatcher
        time.sleep(0.05)
        doomed = fe.submit(["q2", "q4"], deadline_ms=10.0)
        time.sleep(0.1)                     # deadline lapses in queue
        release.set()
        out = doomed.result(timeout=10)
        ids, scores = out
        assert out.degraded
        np.testing.assert_array_equal(out.coverage, [0.0, 0.0])
        np.testing.assert_array_equal(ids, -np.ones((2, 3)))
        assert np.all(np.isneginf(scores))
    assert fe.stats["expired"] == 1


def test_no_accepted_request_left_unresolved_under_mixed_deadlines():
    """The no-lost-request property: every accepted Future resolves —
    a real result, a degraded-empty expiry, or ServeTimeoutError —
    none dangle."""
    with ServeFrontend(_echo_backend(delay=0.02), topk=2, max_batch=4,
                       max_wait_ms=1) as fe:
        futs = []
        for i in range(12):
            ddl = 1.0 if i % 3 == 0 else None   # some effectively-instant
            futs.append(fe.submit(f"q{i}", deadline_ms=ddl))
        resolved = 0
        for f in futs:
            try:
                f.result(timeout=10)
                resolved += 1
            except ServeTimeoutError:
                resolved += 1
        assert resolved == len(futs)
    st = fe.stats
    assert st["completed"] + st["expired"] == st["accepted"]


def test_deadline_ms_validation():
    with ServeFrontend(_echo_backend(), topk=2, max_batch=4,
                       max_wait_ms=1) as fe:
        with pytest.raises(ValueError):
            fe.submit("q1", deadline_ms=0)
        with pytest.raises(ValueError):
            fe.submit("q1", deadline_ms=-5)


# -- WorkerHealth + the shared Heartbeat --------------------------------------


def test_heartbeat_requires_path_or_sink():
    from repro.training.fault_tolerance import Heartbeat
    with pytest.raises(ValueError):
        Heartbeat()


def test_heartbeat_feeds_worker_health_staleness():
    """One Heartbeat implementation serves training (file sink) and
    serving (WorkerHealth sink): a beating worker never goes stale, a
    silent one does."""
    health = WorkerHealth(2, stale_after_s=0.2)
    with health.heartbeat(0, interval=0.05):
        time.sleep(0.35)
        assert not health.failed(0)         # beats keep it fresh
        assert health.failed(1)             # silent since construction
    assert health.live() == [0, 1]          # stale != dead
    health.mark_dead(1)
    assert health.is_dead(1)
    assert health.dead == {1}
    assert health.live() == [0]
    assert health.failed(1)


def test_heartbeat_file_sink_still_writes(tmp_path):
    from repro.training.fault_tolerance import Heartbeat
    import json
    path = str(tmp_path / "hb.json")
    with Heartbeat(path, interval=10.0) as hb:
        hb.update(42)
    payload = json.load(open(path))
    assert payload["step"] == 42 and "time" in payload


# -- resilient_loop (training retry loop, previously uncovered) ---------------


def test_resilient_loop_completes_without_failures():
    seen = []
    end = resilient_loop(seen.append, 0, 5, on_failure=lambda e: 0)
    assert end == 5 and seen == [0, 1, 2, 3, 4]


def test_resilient_loop_restores_and_resumes():
    calls, failed = [], []

    def step(i):
        calls.append(i)
        if i == 2 and not failed:
            raise RuntimeError("transient")

    def on_failure(e):
        failed.append(e)
        return 1                            # "restore" to step 1

    end = resilient_loop(step, 0, 4, on_failure)
    assert end == 4
    assert calls == [0, 1, 2, 1, 2, 3]      # resumed from the restore
    assert len(failed) == 1


def test_resilient_loop_gives_up_after_max_consecutive_failures():
    def step(i):
        raise RuntimeError("persistent")

    with pytest.raises(RuntimeError, match="persistent"):
        resilient_loop(step, 0, 3, on_failure=lambda e: 0,
                       max_failures=2)


def test_resilient_loop_does_not_swallow_interrupts():
    def step(i):
        raise KeyboardInterrupt

    with pytest.raises(KeyboardInterrupt):
        resilient_loop(step, 0, 3, on_failure=lambda e: 0)


# -- EmbeddingCache torn writes through the injector --------------------------


def _fill(cache, n, seed=0, prefix="d"):
    rng = np.random.default_rng(seed)
    vecs = rng.normal(size=(n, cache.dim)).astype(np.float32)
    ids = [f"{prefix}{i}" for i in range(n)]
    cache.cache_records(ids, vecs)
    return ids, vecs


def test_torn_write_mid_append_recovers_to_committed_state(tmp_path):
    """Crash between the vector payload and the id-index append: the
    reopened cache trusts meta['n'], truncates the torn payload bytes,
    and the next append lands with correct row alignment."""
    cache = EmbeddingCache(str(tmp_path / "c"), dim=8)
    ids, vecs = _fill(cache, 10)
    cache.fault_injector = FaultInjector(
        [Fault(kind="torn_write", phase="cache", point="payload")])
    with pytest.raises(InjectedCrash):
        _fill(cache, 4, seed=1, prefix="x")
    assert cache.fault_injector.fired == [
        ("torn_write", None, None, "cache:payload")]
    # torn on disk: payload grew, id index did not
    import os
    vec_bytes = os.path.getsize(tmp_path / "c" / "vectors.bin")
    ids_bytes = os.path.getsize(tmp_path / "c" / "ids.bin")
    assert vec_bytes == 14 * 8 * cache.dtype.itemsize
    assert ids_bytes == 10 * 8                    # id append never ran

    reopened = EmbeddingCache(str(tmp_path / "c"), dim=8)
    assert len(reopened) == 10
    np.testing.assert_allclose(reopened.get(ids), vecs, atol=1e-2)
    ids2, vecs2 = _fill(reopened, 4, seed=2, prefix="y")
    assert len(reopened) == 14
    np.testing.assert_allclose(reopened.get(ids2), vecs2, atol=1e-2)
    np.testing.assert_allclose(reopened.get(ids), vecs, atol=1e-2)


def test_torn_write_before_meta_commit_recovers(tmp_path):
    """Crash after both payload appends but before the atomic meta.json
    replace: the rows exist on disk but were never committed — the
    reopened cache ignores and truncates them."""
    cache = EmbeddingCache(str(tmp_path / "c"), dim=8)
    ids, vecs = _fill(cache, 6)
    cache.fault_injector = FaultInjector(
        [Fault(kind="torn_write", phase="cache", point="meta")])
    with pytest.raises(InjectedCrash):
        _fill(cache, 3, seed=1, prefix="x")

    reopened = EmbeddingCache(str(tmp_path / "c"), dim=8)
    assert len(reopened) == 6
    assert not reopened.has([f"x{i}" for i in range(3)]).any()
    ids2, vecs2 = _fill(reopened, 3, seed=2, prefix="y")
    assert len(reopened) == 9
    np.testing.assert_allclose(reopened.get(ids2), vecs2, atol=1e-2)


# -- compaction chaos: crash/stall at every compaction injection point --------


def _mutated_cache(tmp_path, layout):
    """A cache with superseded rows and tombstones — real work for the
    compactor — plus (for ``layout="ivf"``) the cluster-sorted
    permutation compaction should lay the live rows out in."""
    from repro.index.ivf import cluster_order
    cache = EmbeddingCache(str(tmp_path / "c"), dim=8)
    _fill(cache, 24)
    cache.delete_records(["d3", "d10"])
    cache.cache_records(["d5"], np.full((1, 8), 2.0, np.float32))
    order = None
    if layout == "ivf":
        snap = cache.snapshot()
        order = cluster_order(
            lambda lo, hi: snap.get_range(lo, hi).astype(np.float32),
            snap.n_live, 4, train_steps=4, train_batch=8)
        snap.close()
    return cache, order


def _live_view(cache):
    """(ids, vectors) of the live set, sorted by id — layout-independent
    content equality across compaction/reopen."""
    snap = cache.snapshot()
    order = np.argsort(snap.ids)
    ids = snap.ids[order].copy()
    vecs = snap.get_rows(order).copy()
    snap.close()
    return ids, vecs


@pytest.mark.parametrize("w", (1, 2))
@pytest.mark.parametrize("layout", ("flat", "ivf"))
@pytest.mark.parametrize("point", ("compact_payload", "compact_meta",
                                   "compact_swap"))
def test_compaction_crash_reopens_to_one_generation(tmp_path, point,
                                                    layout, w):
    """Crash at every compaction injection point: reopen lands on
    exactly the pre- or post-compaction generation (one epoch's payload
    files on disk, never a torn hybrid), zero committed records are
    lost, and a W-worker search over the reopened cache matches the
    flat-scan oracle."""
    import os
    cache, order = _mutated_cache(tmp_path, layout)
    gen0 = cache.generation
    want_ids, want_vecs = _live_view(cache)
    cache.fault_injector = FaultInjector(
        [Fault(kind="torn_write", phase="cache", point=point)])
    with pytest.raises(InjectedCrash):
        cache.compact(order=order)
    assert cache.fault_injector.fired == [
        ("torn_write", None, None, f"cache:{point}")]

    reopened = EmbeddingCache(str(tmp_path / "c"), dim=8)
    # a single consistent generation: pre-compaction for the payload /
    # meta crashes, post-compaction once the meta swap landed
    want_epoch = 1 if point == "compact_swap" else 0
    assert reopened.epoch == want_epoch
    assert reopened.generation == gen0
    # exactly one epoch's payload files remain (strays swept on open)
    names = sorted(os.listdir(tmp_path / "c"))
    vec_files = [f for f in names if f.startswith("vectors")]
    want_vec = "vectors.bin" if want_epoch == 0 else "vectors.e1.bin"
    assert vec_files == [want_vec], names
    # zero lost committed records
    got_ids, got_vecs = _live_view(reopened)
    np.testing.assert_array_equal(got_ids, want_ids)
    np.testing.assert_array_equal(got_vecs, want_vecs)

    # the reopened cache serves a W-worker search bitwise-matching the
    # single-worker oracle over the same snapshot
    snap = reopened.snapshot()
    docs = snap.get_range(0, snap.n_live).astype(np.float32)
    snap.close()
    rng = np.random.default_rng(3)
    q = rng.normal(size=(4, 8)).astype(np.float32)
    ref_vals, ref_pos = ShardedSearchDriver(
        score_impl="numpy", chunk_size=16).search(
            q, len(docs), _load_from(docs), K)
    if w == 1:
        outs = [ShardedSearchDriver(score_impl="numpy", chunk_size=8)
                .search(q, len(docs), _load_from(docs), K)]
    else:
        cluster = SimulatedCluster(w)
        drivers = [ShardedSearchDriver(
            n_workers=w, worker_index=rank, sharder=cluster.sharder,
            gather=cluster.gather, score_impl="numpy", chunk_size=8)
            for rank in range(w)]
        outs = cluster.run(lambda rank: drivers[rank].search(
            q, len(docs), _load_from(docs), K))
    for vals, pos in outs:
        np.testing.assert_array_equal(pos, ref_pos)
        np.testing.assert_array_equal(vals, ref_vals)


@pytest.mark.parametrize("point", ("compact_payload", "compact_meta",
                                   "compact_swap"))
def test_compaction_stall_keeps_pinned_readers_serving(tmp_path, point):
    """A stalled disk mid-compaction must not block pinned readers:
    snapshot reads resolve through the frozen (rows, mmap) pair without
    taking the writer lock, so they stream bit-identical rows all the
    way through the stall."""
    cache, _ = _mutated_cache(tmp_path, "flat")
    cache.fault_injector = FaultInjector(
        [Fault(kind="stall", phase="cache", point=point, stall_s=0.3)])
    snap = cache.snapshot()
    first = snap.get_range(0, snap.n_live).copy()
    reads = []
    stop = threading.Event()

    def reader():
        while not stop.is_set():
            reads.append(snap.get_range(0, snap.n_live).copy())
            time.sleep(0.01)

    t = threading.Thread(target=reader)
    t.start()
    try:
        t0 = time.monotonic()
        stats = cache.compact()
        dt = time.monotonic() - t0
    finally:
        stop.set()
        t.join()
    assert dt >= 0.29, dt                 # the stall really fired
    assert stats["epoch"] == 1
    assert len(reads) >= 10               # readers ran during the stall
    for r in reads:
        np.testing.assert_array_equal(r, first)
    # the pin still serves the retired epoch after compaction completes
    np.testing.assert_array_equal(snap.get_range(0, snap.n_live), first)
    snap.close()
