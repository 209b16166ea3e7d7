"""Fair sharding: throughput-weighted shard sizes (paper §3.5).

Mixing devices with different throughput (or pods with stragglers) stalls
the fast ones under equal sharding.  ``FairSharder`` keeps an EMA of
per-worker throughput and splits each round's items proportionally, so all
workers finish together.  Also used for straggler mitigation: a slow
worker's share shrinks on the next round.

The EMA commits **per round**: ``update`` buffers observations and only
folds them into the EMA once every worker has reported the round.  Shard
bounds therefore stay frozen while a round is in flight — essential when
one sharder instance is shared by W workers (``SimulatedCluster``,
``ShardedSearchDriver``) that partition at different wall-clock times;
an immediately-applied EMA would hand late-partitioning workers
*different* bounds than early ones, silently overlapping or dropping
corpus slices.

On a real cluster each process holds its own replica and only observes
its own rank, so the search driver exchanges observations through the
gather transport (``ProcessAllGather.exchange_observations``) — every
replica then commits the identical complete round and all processes
keep computing identical bounds.
"""

from __future__ import annotations

import threading
import time

import numpy as np


class GenerationMismatch(RuntimeError):
    """Raised by :meth:`FairSharder.acquire` when this worker's pinned
    corpus generation disagrees with the round's agreed generation (the
    first acquirer's key wins).  The round is *not* consumed: the caller
    re-prepares its corpus at :attr:`agreed` (e.g.
    ``cache.snapshot(agreed)``) and re-acquires the same round."""

    def __init__(self, round_no: int, agreed, mine):
        super().__init__(
            f"round {round_no}: this worker is pinned to generation "
            f"{mine} but the round agreed on {agreed}; re-prepare at "
            f"the agreed generation and re-acquire")
        self.round_no = round_no
        self.agreed = agreed
        self.mine = mine


class ShardAborted(RuntimeError):
    """A sibling worker died mid-round (or a round wait timed out); this
    worker's wait was released.  Secondary casualty — cluster runners
    filter it in favor of the original error (like
    ``threading.BrokenBarrierError``).  The message carries real
    diagnostics: how many rounds committed and which workers the
    blocking round is still waiting on."""


class FairSharder:
    # acquire_bounds gives up after this long waiting for the previous
    # round to commit — a missing sibling report means a worker died
    ACQUIRE_TIMEOUT_S = 300.0

    def __init__(self, n_workers: int, alpha: float = 0.5,
                 min_share: float = 0.01):
        self.n = n_workers
        self.alpha = alpha
        self.min_share = min_share
        self.throughput = np.ones(n_workers, np.float64)
        # round-buffered observations, keyed per round:
        # round -> {worker: items/s} (None = reported with no timing
        # signal: an empty shard, or an absolved/recovered worker)
        self._pending: dict[int, dict[int, float | None]] = {}
        self._lock = threading.Lock()
        self._cv = threading.Condition(self._lock)
        self._committed = 0                  # rounds folded into the EMA
        self._issued = [0] * n_workers       # rounds begun, per worker
        # round -> agreed corpus generation key (first acquirer wins)
        self._round_gen: dict[int, object] = {}
        # round -> (corpus key, bounds) of its first acquirer: a worker
        # marked dead mid-round must not change the round's partition
        # for the siblings that acquire it later
        self._round_bounds: dict[int, tuple] = {}
        self._abort_exc: BaseException | None = None
        self._dead: set[int] = set()

    def shares(self, total_items: int) -> list[int]:
        """Split ``total_items`` proportionally to throughput.

        Invariants: shares are non-negative and sum to ``total_items``
        exactly; since ``frac`` is normalized, the floor() pass leaves a
        remainder in ``[0, n]`` (``n`` only reachable through float
        round-off in the normalization) which goes to the fastest
        workers, one item each.  ``total_items < n`` is legal: most
        floors are 0 and the remainder pass hands single items to the
        fastest workers, leaving the rest with empty (contiguous)
        bounds.

        Workers reported dead (:meth:`mark_dead`) get an exact-zero
        share — ``min_share`` applies to *live* workers only — so the
        next round's partition covers the corpus with survivors alone.
        """
        assert total_items >= 0, total_items
        with self._lock:
            w = np.maximum(self.throughput, 1e-9).copy()
            dead = set(self._dead)
        if len(dead) >= self.n:
            raise ShardAborted(
                f"all {self.n} workers are dead; no survivor left to "
                f"shard {total_items} items across")
        live = np.array([wk not in dead for wk in range(self.n)])
        w[~live] = 0.0
        frac = np.zeros(self.n, np.float64)
        lf = np.maximum(w[live] / w[live].sum(), self.min_share)
        frac[live] = lf / lf.sum()
        sizes = np.floor(frac * total_items).astype(int)
        rem = int(total_items - sizes.sum())
        # a remainder beyond n means frac was not normalized — the old
        # `order[i % n]` round-robin would silently paper over that
        assert 0 <= rem <= self.n, (
            f"floor remainder {rem} outside [0, {self.n}] "
            f"(total_items={total_items}, frac sum={frac.sum()!r})")
        live_order = [int(i) for i in np.argsort(-w, kind="stable")
                      if live[i]]
        for i in range(rem):
            sizes[live_order[i % len(live_order)]] += 1
        return sizes.tolist()

    def bounds(self, total_items: int,
               boundaries=None) -> list[tuple[int, int]]:
        """Contiguous ``[lo, hi)`` per worker covering ``total_items``.

        ``boundaries`` (optional, sorted, starting at 0 and ending at
        ``total_items``) restricts where cuts may land: each
        proportional cut point snaps to the nearest allowed boundary.
        The IVF search space passes its cluster edges here so every
        worker's shard is a run of *whole* clusters — shards stay
        contiguous permutation slices instead of slivers of every
        cluster.  Snapped cuts are forced monotone, so shards still
        partition ``[0, total_items)`` exactly (a slow worker may end
        up with an empty shard when its share is smaller than the
        cluster granularity).
        """
        sizes = self.shares(total_items)
        ends = np.cumsum(sizes)
        if boundaries is not None and total_items > 0:
            bnd = np.asarray(boundaries, np.int64)
            # snap each interior cut to the nearest cluster edge;
            # maximum.accumulate keeps the cut sequence monotone
            idx = np.searchsorted(bnd, ends[:-1])
            idx = np.clip(idx, 1, len(bnd) - 1)
            below = bnd[idx - 1]
            above = bnd[idx]
            snapped = np.where(ends[:-1] - below <= above - ends[:-1],
                               below, above)
            snapped = np.maximum.accumulate(snapped)
            ends = np.concatenate([snapped, ends[-1:]])
        starts = np.concatenate([[0], ends[:-1]])
        return list(zip(starts.tolist(), ends.tolist()))

    def _round_diagnostics(self) -> str:
        """Lock held.  Which round is blocking and who hasn't reported."""
        bucket = self._pending.get(self._committed, {})
        missing = [wk for wk in range(self.n)
                   if wk not in self._dead and wk not in bucket]
        parts = [f"rounds 0..{self._committed - 1} committed"
                 if self._committed else "no round committed yet",
                 f"round {self._committed} still pending reports from "
                 f"workers {missing}"]
        if self._dead:
            parts.append(f"dead workers: {sorted(self._dead)}")
        return "; ".join(parts)

    def acquire(self, worker: int, total_items: int, boundaries=None,
                generation=None) -> tuple[int, list[tuple[int, int]]]:
        """Round-versioned partition: ``(round_no, bounds)``.

        A worker's r-th call blocks until rounds ``0..r-1`` have all
        committed, so every worker reads the *same* EMA state for the
        same logical round.  The plain ``bounds()`` read is only safe
        when something else already orders rounds across workers (the
        sync path's gather barrier); with ``search_async`` a fast
        worker's report can commit a round *between* two workers'
        partition reads for the next one, silently splitting the corpus
        two different ways in a single round.

        Never blocks when rounds are already ordered (sync path, or
        ``n == 1``) — the wait condition is satisfied on entry.

        Every acquirer of one round over the same corpus gets the
        partition the first acquirer got, even when a worker is marked
        dead in between: its shard is then orphaned and recovered inside
        the round, and only the next round leaves it out.

        The returned ``round_no`` is the sharder-global round this
        partition belongs to — the key the fault-tolerant gather and
        round-tagged :meth:`update` use, and stable even when the caller
        constructs a fresh driver per round (the serve cluster backend).

        ``generation`` (optional, any comparable key — the cache's
        ``(generation, epoch)``) makes the round *generation-agreed*:
        the first acquirer's key becomes the round's generation, and a
        later acquirer pinned to a different one gets
        :class:`GenerationMismatch` without consuming the round — it
        re-prepares at the agreed key and re-acquires, so all W workers
        of a round provably score the same corpus snapshot even while a
        writer mutates the cache between rounds.
        """
        with self._cv:
            r = self._issued[worker]
            self._issued[worker] += 1
            deadline = time.monotonic() + self.ACQUIRE_TIMEOUT_S
            while self._committed < r and self._abort_exc is None:
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    raise ShardAborted(
                        f"worker {worker} waited "
                        f"{self.ACQUIRE_TIMEOUT_S}s for round {r - 1} "
                        f"to commit: {self._round_diagnostics()}")
                self._cv.wait(remaining)
            if self._abort_exc is not None:
                raise ShardAborted(
                    f"sharder aborted while worker {worker} waited for "
                    f"round {r}: {self._round_diagnostics()}"
                ) from self._abort_exc
            if generation is not None:
                agreed = self._round_gen.setdefault(r, generation)
                if agreed != generation:
                    # roll the issue back: the round was not consumed —
                    # the caller re-acquires it at the agreed generation
                    self._issued[worker] -= 1
                    raise GenerationMismatch(r, agreed, generation)
        # round r cannot commit (and move the EMA) until THIS worker
        # reports it, which happens only after the caller scores the
        # slice these bounds describe; the first acquirer's bounds for
        # this corpus stand for the whole round
        bounds = self.bounds(total_items, boundaries)
        key = (total_items, None if boundaries is None
               else tuple(np.asarray(boundaries).tolist()))
        with self._cv:
            if r >= self._committed:
                pinned = self._round_bounds.setdefault(r, (key, bounds))
                if pinned[0] == key:
                    bounds = pinned[1]
        return r, list(bounds)

    def acquire_bounds(self, worker: int, total_items: int,
                       boundaries=None) -> list[tuple[int, int]]:
        """:meth:`acquire` without the round number (legacy callers)."""
        return self.acquire(worker, total_items, boundaries)[1]

    def abort(self, exc: BaseException | None = None) -> None:
        """Release workers blocked in :meth:`acquire` when a sibling
        dies mid-round (mirrors the gather transports' abort)."""
        with self._cv:
            self._abort_exc = exc if exc is not None else RuntimeError(
                "aborted")
            self._cv.notify_all()

    def mark_dead(self, worker: int) -> None:
        """Remove ``worker`` from the cluster: it gets exact-zero shares
        from now on (see :meth:`shares`) and rounds stop waiting for its
        reports — any round blocked solely on it commits immediately.
        Unlike :meth:`abort`, survivors keep running."""
        with self._cv:
            self._dead.add(worker)
            self._try_commit_locked()
            self._cv.notify_all()

    def absolve(self, worker: int, round_no: int) -> None:
        """Count ``worker`` as having reported ``round_no`` without a
        throughput observation — used when its shard was recovered by a
        survivor (or given up) so the round can commit without it.  A
        no-op for already-committed rounds."""
        with self._cv:
            if round_no < self._committed:
                return
            self._pending.setdefault(round_no, {}).setdefault(worker,
                                                              None)
            self._try_commit_locked()

    def update(self, worker: int, items: int, seconds: float,
               round_no: int | None = None):
        """Report one worker's round observation.

        The observation is buffered per round; once every *live* worker
        has reported (or been absolved for) the oldest uncommitted
        round, its observations fold into the EMA atomically and the
        round commits.  (With ``n == 1`` this is an immediate update.)
        A worker with an empty shard reports ``items == 0`` and counts
        toward round completion without moving its EMA.

        ``round_no`` tags the observation with the round it belongs to
        (from :meth:`acquire`).  Without it, the report lands on the
        earliest uncommitted round this worker hasn't reported — the
        pre-fault-tolerance behavior.  Reports for already-committed
        rounds (a stalled straggler finishing after its shard was
        recovered) are dropped.
        """
        with self._cv:
            if round_no is None:
                round_no = self._committed
                while worker in self._pending.get(round_no, {}):
                    round_no += 1
            if round_no < self._committed:
                return                      # recovered behind its back
            bucket = self._pending.setdefault(round_no, {})
            if items > 0 and seconds > 0:
                bucket[worker] = items / seconds
            else:
                bucket.setdefault(worker, None)
            self._try_commit_locked()

    def _try_commit_locked(self) -> None:
        """Commit every leading round whose live workers all reported."""
        while True:
            needed = [wk for wk in range(self.n) if wk not in self._dead]
            if not needed:
                return                      # cluster fully dead
            bucket = self._pending.get(self._committed)
            if bucket is None or any(wk not in bucket for wk in needed):
                return
            for wk, obs in bucket.items():
                if obs is not None and wk not in self._dead:
                    self.throughput[wk] = (
                        self.alpha * obs
                        + (1 - self.alpha) * self.throughput[wk])
            del self._pending[self._committed]
            self._round_gen.pop(self._committed, None)
            self._round_bounds.pop(self._committed, None)
            self._committed += 1
            self._cv.notify_all()
