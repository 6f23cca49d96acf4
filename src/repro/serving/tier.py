"""Production KGE serving tier: continuous query batching over replicated,
federation-versioned embedding tables.

Four mechanisms, composed:

**Continuous request batching** — ``submit_rank``/``submit_topk`` enqueue
validated requests; ``step()`` coalesces the FIFO head into one query batch
(same kind, same top-k bucket), pads the batch extent to a power-of-two
bucket and slices filters from the precomputed pow-2-width ``FilterPack``,
so steady-state traffic hits a FIXED set of compiled programs — the tick
engine's signature-bucket idiom applied to queries. Batches dispatch
asynchronously (``serving.engine.side_counts_dispatch`` — device out, no host
sync) and results are collected by non-blocking ``jax.Array.is_ready``
polling, so new batches launch while old ones execute.

**Health-aware replica routing** — the active ``TableVersion`` is staged
onto a ring of replica devices (``core.distributed.replica_devices``:
consecutive mesh devices from the owner's sticky home, so replica 0 is the
device the federation already keeps the accepted tables resident on). Each
batch routes to the healthy replica with the fewest in-flight batches,
tie-broken by lifetime dispatch count (so equal-load traffic spreads
instead of piling onto the lowest slot); per-replica accounting lives in
``Replica.inflight``/``dispatched``/``ewma_s``. A batch whose collection
fails (device error, injected crash, poisoned output) does NOT fail its
requests: it re-dispatches up to ``retry_limit`` times to a different
replica, on the SAME pinned ``TableVersion`` — a retried batch is
bit-identical to one that succeeded first try. ``breaker_fails``
consecutive failures open a circuit breaker: the replica leaves the
routing pool and is re-admitted by timed probe (one trial batch every
``probe_after`` tier-wide dispatches — the serving mirror of the
federation's quarantine-with-timed-release). With ``hedge_after=`` set,
the oldest stuck batch is hedged to a second replica; the first result
wins, bit-identical either way since both replicas hold the same
``TableVersion``.

**Admission control and shedding** — ``max_queue=`` bounds the submit
queue with an explicit ``TierOverloadError`` reject at submit; a
per-request ``deadline=`` (seconds of queue budget) sheds expired requests
at coalesce time into a terminal ``shed`` state distinct from ``failed``.
Every submitted request deterministically resolves to exactly one of
served / shed / failed — ``run_until_drained`` asserts
``served + shed + failed == submitted`` at every drain point.

**Version hot-swap** — ``publish(params)`` builds an immutable
``TableVersion`` (non-finite bitmask computed once), stages it onto every
replica device (a ``device_put`` where the params live elsewhere, then the
entity table laid out once in the layout the rank and top-k programs read,
``stats["staged"]``), and atomically flips the active pointer between
batches. In-flight batches hold a reference to the version they
were dispatched on and finish (and retry) there — no traffic pause, no
failed requests. Because a hot-swap can land between submit-time
validation and dispatch, ``_dispatch`` re-checks every request against the
non-finite bitmask of the version the batch is actually pinned to.
``attach(scheduler, owner)`` subscribes the tier to the federation's
accept hook so every accepted tick update republishes. ``warm_buckets=``
pre-traces the configured query buckets against the freshly staged tables
on every replica at publish time, so the first post-swap batch (and the
first batch ever) pays no compile: programs specialize on shape, not
version, so each ``(kind, bucket, replica)`` signature warms exactly once
per process.

**Observability** — the serving thread's work is marked with
``jax.profiler.TraceAnnotation`` leaf spans, which never overlap and cost
about a microsecond each with no profiler active: ``repro.serve.submit``
(validation and enqueue), ``.coalesce`` (popping the batch, shedding
included), ``.assemble`` (re-validation, concatenation, filter rows,
padding), ``.put`` (``device_put`` of the batch), ``.launch`` (the program
dispatch), ``.collect`` (outputs to host, scattered into requests) and
``.wait`` (polling while the dispatch-ahead window is full). ``put``,
``launch`` and ``collect`` carry the launch ``seq`` that a served
request's ``batch`` names. ``stats`` counts why each batched coalesce
stopped: ``cut_kind``, ``cut_full``, ``cut_empty``.

``serve_impl="direct"`` (``REPRO_SERVE_IMPL``) disables coalescing — one
dispatch per request, the baseline ``bench_serving.py`` measures batching
against. ``REPRO_SERVE_REPLICAS`` sizes the replica ring.
``serve_faults=`` / ``REPRO_SERVE_FAULTS`` arm the seeded chaos layer
(``core.faults.ServeFaultPlan``) — off by default, keeping the query fast
path bit-identical to the faults-free tier.
"""
from __future__ import annotations

import threading
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Deque, Dict, List, Optional, Tuple

import jax
import numpy as np
from jax.profiler import TraceAnnotation

from repro.core.distributed import replica_devices
from repro.core.faults import ServeFault, ServeFaultError, ServeFaultPlan
from repro.kernels.dispatch import (
    resolve_serve_faults,
    resolve_serve_impl,
    resolve_serve_replicas,
)
from repro.serving.engine import (
    _served_counts_jit,
    _streaming_topk_jit,
    side_counts_dispatch,
)
from repro.serving.tables import FilterPack, TableVersion, check_id_range


def _pow2_at_least(n: int, floor: int = 1) -> int:
    n = max(int(n), int(floor), 1)
    return 1 << (n - 1).bit_length()


class TierOverloadError(RuntimeError):
    """Submit-time admission reject: the tier's queue is at ``max_queue``.
    Raised BEFORE the request enters the system — rejected requests are
    counted in ``stats["rejected"]`` and never become ``QueryRequest``s,
    so they do not participate in the served/shed/failed accounting."""


@dataclass
class QueryRequest:
    """One submitted query batch-of-rows; ``result`` lands asynchronously."""

    rid: int
    kind: str                      # "rank" | "topk"
    h: np.ndarray
    r: np.ndarray
    t: Optional[np.ndarray] = None  # rank only
    k: int = 0                      # topk only
    #: seconds of queue budget from submit; expired requests are shed at
    #: coalesce time (never dispatched). ``None`` = wait forever.
    deadline: Optional[float] = None
    # perf_counter: latency math (finished_at - submitted_at) must be
    # monotonic; time.time() jumps with NTP/clock adjustments
    submitted_at: float = field(default_factory=time.perf_counter)
    #: when its batch was first launched (retries and hedges keep it):
    #: ``dispatched_at - submitted_at`` is its wait in the FIFO,
    #: ``finished_at - dispatched_at`` its time from dispatch to result
    dispatched_at: Optional[float] = None
    finished_at: Optional[float] = None
    batch: Optional[int] = None     # launch seq that served it
    version: Optional[int] = None   # table version that served it
    result: object = None
    error: Optional[Exception] = None
    shed: bool = False
    done: bool = False

    @property
    def latency(self) -> Optional[float]:
        if self.finished_at is None:
            return None
        return self.finished_at - self.submitted_at

    @property
    def state(self) -> str:
        """``pending`` | ``served`` | ``shed`` | ``failed`` — every request
        terminates in exactly one of the last three."""
        if not self.done:
            return "pending"
        if self.shed:
            return "shed"
        return "failed" if self.error is not None else "served"


class Replica:
    """One device holding the serving tables; load = in-flight batches.

    Health state drives the circuit breaker: ``fails`` counts CONSECUTIVE
    batch failures (any success resets it), ``healthy=False`` removes the
    replica from the routing pool, and ``probe_at`` is the tier-wide launch
    sequence number at which it earns one probe batch (re-admission on
    probe success — the federation quarantine's timed release, with the
    launch counter as the clock so tests are scheduling-deterministic).
    ``ewma_s`` tracks smoothed batch latency for observability and hedging
    diagnostics."""

    def __init__(self, slot: int, device):
        self.slot = slot
        self.device = device
        self.inflight = 0    # currently executing batches
        self.dispatched = 0  # lifetime batch count (routing observability)
        self.fails = 0       # consecutive failures (breaker input)
        self.healthy = True
        self.probe_at: Optional[int] = None  # launch seq of next probe
        self.ewma_s: Optional[float] = None  # smoothed batch latency

    def __repr__(self):  # pragma: no cover - debugging aid
        return (
            f"Replica({self.slot}, {self.device}, inflight={self.inflight}, "
            f"{'healthy' if self.healthy else 'UNHEALTHY'})"
        )


@dataclass
class _InFlight:
    """A dispatched batch: device outputs + how to scatter them back."""

    kind: str
    out: Tuple                      # device arrays
    segs: List[Tuple[QueryRequest, int, int]]  # (request, offset, rows)
    nq: int                         # real (unpadded) query rows
    tv: TableVersion                # version the batch was dispatched on
    replica: Replica
    host_in: Tuple = ()             # padded host arrays (retry/hedge re-launch)
    kb: int = 0                     # topk k bucket
    seq: int = 0                    # tier-wide launch sequence number
    attempts: int = 0               # re-dispatches already consumed
    fault: Optional[ServeFault] = None
    dispatched_at: float = 0.0
    hedge: Optional["_InFlight"] = None

    def ready(self) -> bool:
        # an injected straggle suppresses readiness for its simulated delay
        # (the device results exist — polling just pretends they don't)
        if (self.fault is not None and self.fault.kind == "straggle"
                and time.perf_counter() - self.dispatched_at
                < self.fault.delay):
            return False
        return all(x.is_ready() for x in self.out)


class KGEServingTier:
    """Continuously-batched, replicated, hot-swappable KGE query serving.

    The public surface is asynchronous: ``submit_rank(h, r, t)`` /
    ``submit_topk(h, r, k=)`` return a ``QueryRequest`` immediately
    (validation errors raise at submit; ``TierOverloadError`` rejects at
    ``max_queue``); ``step()`` advances the admission loop one batch;
    ``run_until_drained()`` pumps until every request is done. Results:
    ``req.result`` is the (B,) rank array, or an ``(ids, scores)`` pair for
    top-k — bit-identical to a per-call ``KGECandidateRanker`` on the same
    table version, regardless of retries or hedging.
    """

    def __init__(self, params, model, known_triples=None, *, owner: Optional[str] = None,
                 block_e: int = 2048, rank_impl: Optional[str] = None,
                 serve_impl: Optional[str] = None, replicas: Optional[int] = None,
                 home_slot: int = 0, devices=None, max_batch: int = 64,
                 min_bucket: int = 8, max_inflight: Optional[int] = None,
                 filters: Optional[FilterPack] = None,
                 warm_buckets: Optional[List[Tuple]] = None,
                 serve_faults=None, retry_limit: int = 1,
                 breaker_fails: int = 3, probe_after: int = 8,
                 hedge_after: Optional[float] = None,
                 max_queue: Optional[int] = None):
        self.model = model
        self.owner = owner
        self.block_e = block_e
        self.rank_impl = rank_impl
        self.serve_impl = resolve_serve_impl(serve_impl)
        self.max_batch = int(max_batch)
        self.min_bucket = int(min_bucket)
        self.filters = (
            filters if filters is not None
            else FilterPack(known_triples, model.num_entities)
        )
        devs = replica_devices(home_slot, resolve_serve_replicas(replicas),
                               devices)
        self.replicas = [Replica(i, d) for i, d in enumerate(devs)]
        #: dispatch-ahead depth: two batches per replica keeps every device
        #: busy while the host assembles the next batch, without unbounded
        #: queue growth on the devices
        self.max_inflight = (
            2 * len(self.replicas) if max_inflight is None else int(max_inflight)
        )
        #: resilience knobs — all inert on the failure-free fast path
        plan = resolve_serve_faults(serve_faults)
        if isinstance(plan, str):
            plan = ServeFaultPlan.parse(plan)
        self.fault_plan: Optional[ServeFaultPlan] = plan
        self.fault_counts: Dict[str, int] = {}
        self.retry_limit = int(retry_limit)
        self.breaker_fails = int(breaker_fails)
        self.probe_after = int(probe_after)
        self.hedge_after = hedge_after
        self.max_queue = None if max_queue is None else int(max_queue)
        self.queue: Deque[QueryRequest] = deque()
        self.inflight: Deque[_InFlight] = deque()
        #: hedge/primary losers still executing on device: reaped only to
        #: release their replica's in-flight slot, outputs discarded
        self._zombies: List[_InFlight] = []
        self.stats: Dict[str, int] = {
            "submitted": 0, "served": 0, "failed": 0, "shed": 0,
            "rejected": 0, "retried": 0, "hedged": 0,
            "breaker_open": 0, "breaker_close": 0,
            "batches": 0, "published": 0, "publish_errors": 0,
            "warmed": 0,
            # entity tables staged: one per (version, replica device)
            "staged": 0,
            # why a batched coalesce stopped growing, one count per batch:
            # the next request was of the other kind or top-k bucket, would
            # pass max_batch, or the queue ran out
            "cut_kind": 0, "cut_full": 0, "cut_empty": 0,
        }
        #: bucket specs to pre-trace at publish: ("rank", rows) or
        #: ("topk", rows, k). Rows/k are rounded to the same pow-2 buckets
        #: the admission loop pads to, so a warmed spec covers every real
        #: batch that lands in its bucket.
        self.warm_buckets: List[Tuple] = list(warm_buckets or [])
        for spec in self.warm_buckets:
            if (not spec or spec[0] not in ("rank", "topk")
                    or len(spec) != (2 if spec[0] == "rank" else 3)):
                raise ValueError(
                    f"warm bucket {spec!r}: expected ('rank', rows) or "
                    f"('topk', rows, k)"
                )
        #: (kind, bucket_rows, k_bucket, replica_slot) signatures already
        #: traced — programs specialize on shape not version, so each
        #: signature warms once per process, not once per publish
        self._warmed: set = set()
        self._next_rid = 0
        #: monotone launch sequence number: one per device dispatch
        #: (primary, retry, or hedge) — the fault plan's draw clock and the
        #: breaker's probe clock
        self._seq = 0
        #: serializes publish() against itself (the federation thread) —
        #: the serving loop only ever READS the active pointer, once per
        #: batch, so the flip is atomic by assignment
        self._publish_lock = threading.Lock()
        self._active: Optional[TableVersion] = None
        self.publish(params, version=0)
        self.stats["published"] = 0  # the constructor's own staging isn't a flip

    # ------------------------------------------------------------ publish
    @property
    def version(self) -> int:
        return self._active.version

    def publish(self, params, *, version: Optional[int] = None) -> TableVersion:
        """Publish a new table version and atomically make it active.

        Builds the immutable ``TableVersion`` (one on-device finiteness
        reduction per table), stages it onto every replica device — the
        params committed there (zero-copy where they already are: the
        owner's sticky home) and the entity table staged once for the
        serving programs — then flips the active pointer. Batches
        dispatched before the flip complete on the old version; batches
        dispatched after serve the new one. No pause."""
        with self._publish_lock:
            v = (
                (self._active.version + 1 if self._active is not None else 0)
                if version is None else int(version)
            )
            tv = TableVersion(params, self.model, self.filters,
                              version=v, owner=self.owner,
                              block_e=self.block_e)
            for rep in self.replicas:
                tv.on(rep.device)
            self.stats["staged"] += tv.staged
            self._warm(tv)
            self._active = tv
            self.stats["published"] += 1
            return tv

    def _warm(self, tv: TableVersion) -> None:
        """Pre-trace the configured query buckets against ``tv``'s staged
        tables on every replica, with zero-id dummy queries. Tracing (and
        the compile it triggers) is synchronous, so by the time ``publish``
        flips the active pointer every warmed ``(kind, bucket, replica)``
        program is resident in the jit caches and the first post-swap batch
        dispatches without compiling. Dummy results are dropped on the
        floor — no stats, no inflight accounting."""
        if not self.warm_buckets:
            return
        for rep in self.replicas:
            ptab = tv.on(rep.device)
            for spec in self.warm_buckets:
                kind = spec[0]
                rows = _pow2_at_least(
                    spec[1],
                    self.min_bucket if self.serve_impl == "batched" else 1,
                )
                kb = (
                    min(_pow2_at_least(spec[2]), self.model.num_entities)
                    if kind == "topk" else 0
                )
                sig = (kind, rows, kb, rep.slot)
                if sig in self._warmed:
                    continue
                h = np.zeros(rows, dtype=np.int64)
                r = np.zeros(rows, dtype=np.int64)
                filt = self.filters.rows_for(h, r)
                if kind == "rank":
                    t = np.zeros(rows, dtype=np.int64)
                    filt = np.concatenate(
                        [t[:, None].astype(np.int32), filt], axis=1
                    )
                    dh, dr, dt, df = jax.device_put(
                        (h, r, t, filt), rep.device
                    )
                    side_counts_dispatch(
                        ptab, self.model, dh, dr, dt, df,
                        entities=tv.entities, block_e=self.block_e,
                        impl=self.rank_impl,
                    )
                else:
                    dh, dr, df = jax.device_put((h, r, filt), rep.device)
                    _streaming_topk_jit(
                        ptab, self.model, dh, dr, df, k=kb,
                        entities=tv.entities, block_e=self.block_e,
                    )
                self._warmed.add(sig)
                self.stats["warmed"] += 1

    def attach(self, sched, owner: str) -> "KGEServingTier":
        """Subscribe to a ``FederationScheduler``'s accept hook: every
        accepted update for ``owner`` republishes the serving tables (the
        version hot-swap path), starting from the owner's current params.
        Publish failures are counted, never propagated — a serving-side
        problem must not abort a federation tick."""
        if owner not in sched.trainers:
            raise ValueError(f"unknown owner {owner!r}")
        self.owner = owner

        def _on_accept(name, tick, params):
            if name != owner:
                return
            try:
                self.publish(params)
            except Exception:
                self.stats["publish_errors"] += 1

        sched.add_accept_listener(_on_accept)
        self.publish(dict(sched.trainers[owner].params))
        return self

    @classmethod
    def for_owner(cls, sched, owner: str, **kw) -> "KGEServingTier":
        """A tier serving ``owner``'s tables out of a federation: filters
        from the owner's full triple set (train ∪ valid ∪ test — the
        standard Filter-mode universe), tables from the owner's trainer,
        home slot from the scheduler's sticky placement when the batched
        tick engine has one, and the accept hook attached."""
        tr = sched.trainers[owner]
        kg = sched.kgs[owner]
        known = np.concatenate([kg.train, kg.valid, kg.test])
        engine = getattr(sched, "_tick_engine", None)
        if engine is not None and "home_slot" not in kw:
            kw["home_slot"] = engine.placement.slot(owner)
        tier = cls(tr.params, tr.model, known, owner=owner, **kw)
        tier.attach(sched, owner)
        return tier

    # ------------------------------------------------------------- submit
    def _admit(self) -> None:
        """Admission control, cheapest check first: a full queue rejects at
        submit, explicitly, before any validation work is spent."""
        if self.max_queue is not None and len(self.queue) >= self.max_queue:
            self.stats["rejected"] += 1
            raise TierOverloadError(
                f"queue at max_queue={self.max_queue}; request rejected "
                f"at submit"
            )

    def _submit(self, req: QueryRequest) -> QueryRequest:
        self.stats["submitted"] += 1
        self.queue.append(req)
        return req

    def submit_rank(self, h, r, t, *, deadline: Optional[float] = None
                    ) -> QueryRequest:
        """Queue a filtered-rank query batch; returns immediately.
        ``deadline`` is this request's queue budget in seconds — expired
        requests are shed at coalesce time instead of dispatched."""
        with TraceAnnotation("repro.serve.submit", rid=self._next_rid):
            self._admit()
            tv = self._active
            h = check_id_range("head entity", h, self.model.num_entities)
            t = check_id_range("tail entity", t, self.model.num_entities)
            r = check_id_range("relation", r, self.model.num_relations)
            tv.check_finite("entity", tv.ent_bad, h)
            tv.check_finite("relation", tv.rel_bad, r)
            rid = self._next_rid
            self._next_rid += 1
            return self._submit(
                QueryRequest(rid, "rank", h, r, t, deadline=deadline)
            )

    def submit_topk(self, h, r, *, k: int = 10,
                    deadline: Optional[float] = None) -> QueryRequest:
        """Queue a top-k candidate query batch; returns immediately."""
        with TraceAnnotation("repro.serve.submit", rid=self._next_rid):
            self._admit()
            tv = self._active
            h = check_id_range("head entity", h, self.model.num_entities)
            r = check_id_range("relation", r, self.model.num_relations)
            if not 1 <= k <= self.model.num_entities:
                raise ValueError(
                    f"k must be in [1, {self.model.num_entities}], got {k}"
                )
            tv.check_finite("entity", tv.ent_bad, h)
            tv.check_finite("relation", tv.rel_bad, r)
            rid = self._next_rid
            self._next_rid += 1
            return self._submit(
                QueryRequest(rid, "topk", h, r, k=int(k), deadline=deadline)
            )

    # ------------------------------------------------------ admission loop
    def _shed(self, req: QueryRequest, now: float) -> None:
        """Terminal ``shed`` state: the deadline expired while queued. The
        request was never dispatched — distinct from ``failed`` (dispatched
        but unservable) by contract."""
        req.shed = True
        req.done = True
        req.finished_at = now
        self.stats["shed"] += 1

    @staticmethod
    def _expired(req: QueryRequest, now: float) -> bool:
        return (req.deadline is not None
                and now - req.submitted_at > req.deadline)

    def _coalesce(self) -> List[QueryRequest]:
        """Pop the FIFO head's batchable prefix: same kind (and same top-k
        bucket), up to ``max_batch`` query rows, counting in ``stats`` why
        the batch stopped (``cut_kind`` / ``cut_full`` / ``cut_empty``).
        Deadline-expired requests are shed (popped, never dispatched) as
        they surface. ``direct`` mode takes one request — the per-call
        baseline."""
        now = time.perf_counter()
        while self.queue and self._expired(self.queue[0], now):
            self._shed(self.queue.popleft(), now)
        if not self.queue:
            return []
        head = self.queue[0]
        take = [self.queue.popleft()]
        if self.serve_impl == "direct":
            return take
        rows = len(head.h)
        kb = _pow2_at_least(head.k) if head.kind == "topk" else 0
        cut = "cut_empty"
        while self.queue:
            nxt = self.queue[0]
            if self._expired(nxt, now):
                self._shed(self.queue.popleft(), now)
                continue
            if nxt.kind != head.kind or (
                    head.kind == "topk" and _pow2_at_least(nxt.k) != kb):
                cut = "cut_kind"
                break
            if rows + len(nxt.h) > self.max_batch:
                cut = "cut_full"
                break
            take.append(self.queue.popleft())
            rows += len(nxt.h)
        self.stats[cut] += 1
        return take

    def _pad(self, arrs: List[np.ndarray], nq: int) -> List[np.ndarray]:
        """Pad batch extent to a pow-2 bucket by repeating row 0 — padded
        rows compute (and are discarded), keeping the compiled-program set
        fixed across every traffic mix."""
        nb = _pow2_at_least(nq, self.min_bucket if self.serve_impl == "batched"
                            else 1)
        if nb == nq:
            return arrs
        return [
            np.concatenate([a, np.repeat(a[:1], nb - nq, axis=0)], axis=0)
            for a in arrs
        ]

    # ------------------------------------------------------------- routing
    def _eligible(self) -> List[Replica]:
        """The routing pool: healthy replicas, plus UNHEALTHY replicas whose
        probe is due (the breaker's half-open state). If the breaker has
        opened on EVERY replica and no probe is due, the whole ring is the
        pool — the tier must keep serving with whatever it has."""
        pool = [rp for rp in self.replicas if rp.healthy]
        pool += [
            rp for rp in self.replicas
            if not rp.healthy and rp.probe_at is not None
            and self._seq >= rp.probe_at
        ]
        return pool or list(self.replicas)

    def _pick_replica(self, exclude: Tuple[Replica, ...] = ()) -> Replica:
        """Least-loaded healthy replica, tie-broken by lifetime dispatch
        count BEFORE slot — at equal in-flight load, traffic alternates
        across the ring instead of skewing onto the lowest slot. ``exclude``
        steers retries/hedges away from the replica that just failed (the
        exclusion is dropped if it would empty the pool — a single-replica
        tier still retries, on the only device it has)."""
        pool = [rp for rp in self._eligible() if rp not in exclude]
        if not pool:
            # honoring the exclusion beats honoring the breaker: a retry or
            # hedge steered off a bad replica may land on an unhealthy one
            # (a forced probe) rather than go back where it just failed
            pool = [rp for rp in self.replicas if rp not in exclude]
        if not pool:
            pool = self._eligible()
        rp = min(pool, key=lambda rp: (rp.inflight, rp.dispatched, rp.slot))
        if not rp.healthy:
            # half-open: this pick IS the probe — push the next probe out so
            # exactly one trial batch is in flight per probe window
            rp.probe_at = self._seq + self.probe_after
        return rp

    def _note_failure(self, rep: Replica) -> None:
        rep.fails += 1
        if rep.healthy and rep.fails >= self.breaker_fails:
            rep.healthy = False
            rep.probe_at = self._seq + self.probe_after
            self.stats["breaker_open"] += 1
        elif not rep.healthy:
            rep.probe_at = self._seq + self.probe_after

    def _note_success(self, rep: Replica, latency_s: float) -> None:
        rep.fails = 0
        if not rep.healthy:
            rep.healthy = True
            rep.probe_at = None
            self.stats["breaker_close"] += 1
        rep.ewma_s = (
            latency_s if rep.ewma_s is None
            else 0.8 * rep.ewma_s + 0.2 * latency_s
        )

    # ------------------------------------------------------------ dispatch
    def _revalidate(self, reqs: List[QueryRequest], tv: TableVersion
                    ) -> List[QueryRequest]:
        """Re-check finiteness against the version the batch is actually
        pinned to: submit-time validation ran against ``_active`` as of
        submit, and a hot-swap in between could otherwise serve rows that
        are non-finite in the dispatch version. O(B) bitmask lookups —
        requests touching bad rows fail here (terminal, with the same
        refusal semantics as submit) instead of serving garbage."""
        ok: List[QueryRequest] = []
        now: Optional[float] = None
        for q in reqs:
            bad = bool(tv.ent_bad[q.h].any()) or bool(tv.rel_bad[q.r].any())
            if not bad and q.kind == "rank":
                bad = bool(tv.ent_bad[q.t].any())
            if bad:
                if now is None:
                    now = time.perf_counter()
                q.error = ValueError(
                    f"non-finite query embedding in dispatch version "
                    f"{tv.version} (hot-swap between submit and dispatch)"
                )
                q.done = True
                q.finished_at = now
                self.stats["failed"] += 1
            else:
                ok.append(q)
        return ok

    def _dispatch(self, reqs: List[QueryRequest]) -> int:
        tv = self._active  # ONE read: the batch is pinned to this version
        kind = reqs[0].kind
        with TraceAnnotation("repro.serve.assemble", kind=kind) as span:
            reqs = self._revalidate(reqs, tv)
            if not reqs:
                return 0
            h = np.concatenate([q.h for q in reqs])
            r = np.concatenate([q.r for q in reqs])
            nq = len(h)
            span.set_metadata(rows=nq)
            segs, off = [], 0
            for q in reqs:
                segs.append((q, off, len(q.h)))
                off += len(q.h)
            if kind == "rank":
                t = np.concatenate([q.t for q in reqs])
                filt = np.concatenate(
                    [t[:, None].astype(np.int32), self.filters.rows_for(h, r)],
                    axis=1,
                )
                host_in = tuple(self._pad([h, r, t, filt], nq))
                kb = 0
            else:
                kb = min(_pow2_at_least(reqs[0].k), self.model.num_entities)
                filt = self.filters.rows_for(h, r)
                host_in = tuple(self._pad([h, r, filt], nq))
        self.stats["batches"] += 1
        fl = self._launch(kind, host_in, segs, nq, tv, kb)
        for q in reqs:
            q.dispatched_at = fl.dispatched_at
        return nq

    def _launch(self, kind: str, host_in: Tuple, segs, nq: int,
                tv: TableVersion, kb: int, *, attempts: int = 0,
                exclude: Tuple[Replica, ...] = (),
                hedge_of: Optional[_InFlight] = None) -> _InFlight:
        """One device dispatch of an assembled batch (primary, retry, or
        hedge — each consumes a fresh launch sequence number, so the fault
        plan draws independently per attempt)."""
        rep = self._pick_replica(exclude=exclude)
        seq = self._seq
        self._seq += 1
        fault = None
        if self.fault_plan is not None:
            fault = self.fault_plan.draw(seq, rep.slot)
            if fault is not None:
                self.fault_counts[fault.kind] = (
                    self.fault_counts.get(fault.kind, 0) + 1
                )
        with TraceAnnotation("repro.serve.put", seq=seq):
            dev_in = jax.device_put(host_in, rep.device)
        with TraceAnnotation("repro.serve.launch", seq=seq, replica=rep.slot):
            ptab = tv.on(rep.device)
            if kind == "rank":
                dh, dr, dt, df = dev_in
                counts = side_counts_dispatch(
                    ptab, self.model, dh, dr, dt, df, entities=tv.entities,
                    block_e=self.block_e, impl=self.rank_impl,
                )
                out: Tuple = (counts,)
            else:
                dh, dr, df = dev_in
                out = _streaming_topk_jit(
                    ptab, self.model, dh, dr, df, k=kb, entities=tv.entities,
                    block_e=self.block_e,
                )
        rep.inflight += 1
        rep.dispatched += 1
        fl = _InFlight(
            kind, out, segs, nq, tv, rep, host_in=host_in, kb=kb, seq=seq,
            attempts=attempts, fault=fault,
            dispatched_at=time.perf_counter(),
        )
        if hedge_of is None:
            self.inflight.append(fl)
        return fl

    def _hedge_due(self, b: _InFlight) -> bool:
        """``b`` has been in flight longer than ``hedge_after`` seconds, is
        not hedged yet, and a second replica exists to hedge onto."""
        return (
            self.hedge_after is not None and b.hedge is None
            and time.perf_counter() - b.dispatched_at >= self.hedge_after
            and not b.ready()
            and any(rp is not b.replica for rp in self.replicas)
        )

    def _maybe_hedge(self) -> None:
        """Hedged dispatch of the oldest stuck batch: if the FIFO head has
        been in flight longer than ``hedge_after`` seconds, launch a
        duplicate on a DIFFERENT replica and let the first result win —
        bit-identical either way, since both replicas hold the batch's
        pinned ``TableVersion``."""
        if not self.inflight or not self._hedge_due(self.inflight[0]):
            return
        b = self.inflight[0]
        b.hedge = self._launch(
            b.kind, b.host_in, b.segs, b.nq, b.tv, b.kb,
            attempts=b.attempts, exclude=(b.replica,), hedge_of=b,
        )
        self.stats["hedged"] += 1

    # ------------------------------------------------------------- collect
    def _output_bad(self, kind: str, host: List[np.ndarray]) -> bool:
        """Armed-only output screen: a sane rank batch has finite,
        non-negative counts; a sane top-k batch has finite scores. Anything
        else is a poisoned (or genuinely broken) replica output and must
        route through the retry path, not reach a caller."""
        if kind == "rank":
            c = host[0]
            if c.dtype.kind == "f" and not np.isfinite(c).all():
                return True
            return bool((c < 0).any())
        # top-k scores: finite, or -inf where a filtered slot padded the
        # candidate set — NaN/+inf means a damaged replica output
        vals = host[0]
        return not bool(np.all(np.isfinite(vals) | np.isneginf(vals)))

    def _poison(self, kind: str, host: List[np.ndarray], fault: ServeFault
                ) -> List[np.ndarray]:
        """Apply an injected ``poison`` to collected outputs: rank counts go
        impossibly negative, top-k scores go NaN — damage the armed screen
        is specified to catch."""
        host = [np.array(x, copy=True) for x in host]
        n = min(max(1, fault.rows), host[0].shape[0])
        if kind == "rank":
            host[0][:n] = -(10 ** 6)
        else:
            host[0][:n] = np.nan
        return host

    def _collect(self, src: _InFlight, kind: str) -> List[np.ndarray]:
        """Materialize one launch's outputs on host, surfacing injected
        crashes, applying injected poison, and screening the result when
        the fault layer is armed. Raises on anything unservable."""
        if src.fault is not None and src.fault.kind == "crash":
            raise ServeFaultError("crash", src.seq, src.replica.slot)
        host = [np.asarray(x) for x in src.out]
        if src.fault is not None and src.fault.kind == "poison":
            host = self._poison(kind, host, src.fault)
        if self.fault_plan is not None and self._output_bad(kind, host):
            raise ServeFaultError("poison", src.seq, src.replica.slot)
        return host

    def _finish_batch(self, b: _InFlight) -> None:
        """Resolve one batch: consume the first usable result (primary or
        hedge), zombie the loser, and on total failure either re-dispatch
        to a different replica (failure isolation — the batch's requests
        survive) or, past ``retry_limit``, fail its requests."""
        with TraceAnnotation("repro.serve.collect", seq=b.seq):
            failed = self._settle(b)
        if failed is not None:
            self.stats["retried"] += 1
            self._launch(b.kind, b.host_in, b.segs, b.nq, b.tv, b.kb,
                         attempts=b.attempts + 1, exclude=failed)

    def _settle(self, b: _InFlight) -> Optional[Tuple[Replica, ...]]:
        """Collect ``b``'s outputs and scatter them into its requests, or
        fail them; returns the replicas that failed it when a retry is
        due instead."""
        sources = (
            [b] if b.hedge is None
            else ([b, b.hedge] if b.ready() else [b.hedge, b])
        )
        host = None
        used = None
        err: Optional[Exception] = None
        spent: List[_InFlight] = []
        for src in sources:
            try:
                host = self._collect(src, b.kind)
                used = src
                break
            except Exception as ex:  # device-side failure: isolate to batch
                err = ex
                src.replica.inflight -= 1
                self._note_failure(src.replica)
                spent.append(src)
        if host is None:
            if b.attempts < self.retry_limit:
                return tuple(s.replica for s in spent)
            now = time.perf_counter()
            for q, _, _ in b.segs:
                q.error, q.done, q.finished_at = err, True, now
            self.stats["failed"] += len(b.segs)
            return None
        now = time.perf_counter()
        used.replica.inflight -= 1
        self._note_success(used.replica, now - used.dispatched_at)
        for src in sources:
            if src is not used and src not in spent:
                self._zombies.append(src)  # race loser: reaped for its slot
        for q, off, n in b.segs:
            if b.kind == "rank":
                q.result = host[0][off:off + n] + 1
            else:
                vals, ids = host
                q.result = (ids[off:off + n, :q.k], vals[off:off + n, :q.k])
            q.version = b.tv.version
            q.batch = used.seq
            q.finished_at = now
            q.done = True
        self.stats["served"] += len(b.segs)
        return None

    def _reap_zombies(self) -> None:
        if not self._zombies:
            return
        keep = []
        for z in self._zombies:
            # raw readiness — a zombie's simulated straggle delay is moot,
            # only its replica's in-flight slot matters now
            if all(x.is_ready() for x in z.out):
                z.replica.inflight -= 1
            else:
                keep.append(z)
        self._zombies = keep

    def _batch_ready(self, b: _InFlight) -> bool:
        return b.ready() or (b.hedge is not None and b.hedge.ready())

    def _reap(self, *, block: bool = False) -> int:
        """Collect completed batches; with ``block`` wait for the oldest
        (the admission loop calls this when the dispatch-ahead window is
        full), then keep draining whatever else already finished. The
        blocking wait polls (instead of blocking inside ``np.asarray``) so
        simulated straggles are honored and the hedge trigger keeps
        firing."""
        done = 0
        self._reap_zombies()
        while self.inflight:
            head = self.inflight[0]
            if not self._batch_ready(head):
                if not block:
                    break
                self._maybe_hedge()
                with TraceAnnotation("repro.serve.wait"):
                    while not (self._batch_ready(head)
                               or self._hedge_due(head)):
                        time.sleep(2e-4)
                continue
            block = False
            b = self.inflight.popleft()
            self._finish_batch(b)
            self._reap_zombies()
            done += len(b.segs)
        return done

    # -------------------------------------------------------- driving loop
    def step(self) -> int:
        """One admission-loop tick: collect finished batches, hedge the
        oldest stuck one, then dispatch (at most) one coalesced batch.
        Returns the query rows dispatched."""
        self._reap()
        self._maybe_hedge()
        if not self.queue:
            return 0
        while len(self.inflight) >= self.max_inflight:
            self._reap(block=True)
        with TraceAnnotation("repro.serve.coalesce") as span:
            reqs = self._coalesce()
            span.set_metadata(rows=sum(len(q.h) for q in reqs))
        if not reqs:
            return 0  # everything at the head was shed
        return self._dispatch(reqs)

    def run_until_drained(self, *, max_steps: int = 1_000_000) -> None:
        for _ in range(max_steps):
            if not self.queue and not self.inflight:
                if self._zombies:
                    self._reap_zombies()
                    if self._zombies:
                        time.sleep(2e-4)
                    continue
                self._check_accounting()
                return
            if self.queue:
                self.step()
            else:
                self._reap(block=True)
        raise RuntimeError("serving tier failed to drain")

    def _check_accounting(self) -> None:
        """The resolution invariant, asserted at every drain point: every
        submitted request terminates in exactly one of served/shed/failed
        (rejected requests never entered)."""
        s = self.stats
        if s["served"] + s["shed"] + s["failed"] != s["submitted"]:
            raise RuntimeError(
                f"serving accounting broken: served={s['served']} + "
                f"shed={s['shed']} + failed={s['failed']} != "
                f"submitted={s['submitted']}"
            )

    # ------------------------------------------------------- observability
    def replica_load(self) -> List[Tuple[int, int]]:
        """[(slot, lifetime batches)] — the routing spread."""
        return [(rp.slot, rp.dispatched) for rp in self.replicas]

    def health(self) -> List[Dict]:
        """Per-replica health snapshot: breaker state, consecutive-failure
        count, smoothed latency, and routing counters."""
        return [
            {
                "slot": rp.slot, "healthy": rp.healthy, "fails": rp.fails,
                "inflight": rp.inflight, "dispatched": rp.dispatched,
                "ewma_ms": None if rp.ewma_s is None else rp.ewma_s * 1e3,
                "probe_at": rp.probe_at,
            }
            for rp in self.replicas
        ]


def serving_program_cache_size() -> int:
    """Number of compiled serving-program specializations (rank counts +
    top-k). The retrace-pin test asserts this stays flat across
    steady-state traffic of ANY mix of batch sizes within the bucket set —
    continuous batching is only a win if padded buckets actually stop
    recompilation."""
    return _served_counts_jit._cache_size() + _streaming_topk_jit._cache_size()
