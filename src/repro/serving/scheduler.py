"""Continuous-batching scheduler over the paged SECDED KV cache.

The fixed-batch engine (`ServingEngine.generate`) serves one rectangular
batch: every request the same prompt length, every request decoded for the
same number of tokens, lanes idle once their request is done. This module
serves a *stream* of variable-length requests instead (DESIGN.md §11):

  * a fixed number of batch *lanes* decode in lock-step, each lane at its own
    sequence position (models/lm.py per-lane `pos` vectors);
  * requests are admitted FCFS into free lanes when the page arena has room
    for their prompt (plus one decode page);
  * each lane's KV is committed token-by-token into SECDED pages
    (core/kvpages.py); pages are allocated on demand as a request crosses a
    page boundary;
  * under page pressure the *youngest* running request is preempted
    (recompute-style: pages freed, request re-queued at the front; on
    re-admission its prompt plus already-generated tokens are re-prefilled),
    so the oldest requests always make progress;
  * every ``scrub_interval`` steps the arena injects the current `kv`-rail
    interval faults, all live pages are scrubbed-on-read (corrected planes
    written back, per-page counters attributed to the owning request), and
    lane caches are refreshed from the corrected payload. The interval's
    aggregate counters optionally drive the `kv` rail of a
    MultiRailController — the cache voltage walks independently of the
    weight rails.

Scheduling is pure host logic; all device work goes through the jit'd
helpers from serving/steps.py and the arena methods, with fixed shapes so
nothing retraces across steps (prefill/commit trace once per distinct
prompt length).
"""

from __future__ import annotations

import dataclasses
import time
from collections import deque

import numpy as np

from repro.obs import profile as obs_profile
from repro.core.kvpages import (
    KVGeometry,
    KVPageArena,
    PageAllocator,
    PrefixTrie,
    SharedPageDEDError,
    dedup_page_table,
)
from repro.core.controller import reader_weighted_stats
from repro.core.telemetry import FaultStats

# Decode programs run at a batch padded to a multiple of DECODE_ROWS. XLA
# picks fusions (and with them where bf16 intermediates are rounded) per
# program shape, so one request's greedy rollout is bit-reproducible only
# across programs of the same batch: a 4-lane serve and a 1-row `generate`
# agree because both decode 8 rows. Padding rows are idle lanes.
DECODE_ROWS = 8


def decode_rows(n: int) -> int:
    """Rows of the decode batch that holds ``n`` lanes."""
    return -(-n // DECODE_ROWS) * DECODE_ROWS


@dataclasses.dataclass(frozen=True)
class Request:
    """One serving request: a prompt and a greedy-decode budget."""

    rid: int
    prompt: np.ndarray  # (s0,) int32
    max_new_tokens: int


#: Public name of the request protocol type (`repro.serving.ServeRequest`):
#: the consolidated serving API exports the dataclass under the name the
#: engine/scheduler docs use; `Request` remains for existing call sites.
ServeRequest = Request


@dataclasses.dataclass
class RequestState:
    req: Request
    status: str = "waiting"  # waiting | running | finished
    lane: int = -1
    admit_seq: int = -1  # admission order; preemption evicts the youngest
    pages: list = dataclasses.field(default_factory=list)
    tokens: list = dataclasses.field(default_factory=list)  # generated so far
    stats: FaultStats = dataclasses.field(default_factory=FaultStats)
    # state-store telemetry of the lane while it served this request
    state_stats: FaultStats = dataclasses.field(default_factory=FaultStats)
    preemptions: int = 0
    shared_tokens: int = 0  # leading tokens served from trie-shared pages
    # flight-recorder bookkeeping (step-clock values; -1 = never/not traced)
    admit_step: int = -1  # clock at FIRST admission (re-admissions keep it)
    first_token_step: int = -1
    finish_step: int = -1
    # host ns since the stream started, stamped only inside a profiler
    # session (ContinuousBatchingScheduler.stamp_ns); -1 otherwise
    admit_ns: int = -1
    first_token_ns: int = -1

    @property
    def rid(self) -> int:
        return self.req.rid

    @property
    def stored(self) -> int:
        """Tokens whose KV lives in pages: prompt + fed decode tokens.

        The freshest generated token is produced *before* its KV is written
        (it is stored when fed to the next decode step), hence the -1.
        """
        return len(self.req.prompt) + max(len(self.tokens) - 1, 0)

    @property
    def resume_seq(self) -> np.ndarray:
        """Token sequence a (re-)admission prefills: prompt + all generated
        tokens except the last (whose KV the next decode step will write)."""
        gen = np.asarray(self.tokens[:-1], np.int32)
        return np.concatenate([self.req.prompt.astype(np.int32), gen])

    @property
    def done(self) -> bool:
        return len(self.tokens) >= self.req.max_new_tokens


@dataclasses.dataclass
class ServeReport:
    """Outcome of one `serve_stream` run."""

    outputs: dict  # rid -> (max_new_tokens,) np.int32 generated tokens
    request_stats: dict  # rid -> FaultStats (scrub-on-read telemetry)
    kv_stats: FaultStats  # aggregate cache telemetry
    steps: int  # batched decode steps executed
    preemptions: int
    kv_voltages: list  # kv rail trajectory (one entry per scrub interval)
    arena: KVPageArena
    pages_free_at_end: int  # == arena.n_pages unless the allocator leaked
    prefix_hit_tokens: int = 0  # prompt tokens served from shared pages
    spec_dispatches: int = 0  # speculative verify blocks executed
    spec_emitted: int = 0  # tokens emitted by speculative blocks
    # aggregate SECDED state-store telemetry (`ssm` domain; models with
    # recurrent state, core/statestore.py), one decode step a read
    state_stats: FaultStats = dataclasses.field(default_factory=FaultStats)


def normalize_requests(requests) -> list:
    """(prompt, max_new_tokens) pairs -> Request objects with stream-order
    rids (pre-built Requests pass through untouched)."""
    return [
        r
        if isinstance(r, Request)
        else Request(i, np.asarray(r[0], np.int32), int(r[1]))
        for i, r in enumerate(requests)
    ]


def partition_requests(requests, n_shards: int) -> list:
    """Round-robin the stream across ``n_shards`` data-parallel replicas.

    Round-robin by arrival index keeps each replica's queue in global FCFS
    order (admission inside a replica stays FCFS), and a 1-shard mesh gets
    the whole stream in order — the serve path's bit-identity anchor.
    """
    assert n_shards >= 1, n_shards
    parts: list = [[] for _ in range(n_shards)]
    for i, r in enumerate(requests):
        parts[i % n_shards].append(r)
    return parts


@dataclasses.dataclass
class MeshServeReport:
    """Merged outcome of one data-parallel mesh serve (DESIGN.md §13).

    Per-shard ServeReports stay intact in ``by_shard`` — the per-chip DED
    counters and kv-rail trajectories are the whole point of the mesh
    telemetry — while the merged views answer the single-stream questions
    (which tokens came back, what did the cache see in aggregate).
    """

    by_shard: list  # ServeReport per reliability shard
    outputs: dict  # rid -> generated tokens, merged across shards
    request_stats: dict  # rid -> FaultStats, merged across shards
    kv_stats: FaultStats  # cross-shard aggregate cache telemetry
    shard_of: dict  # rid -> shard that served it
    steps: int  # total decode dispatch steps across shards
    preemptions: int

    @property
    def kv_stats_by_shard(self) -> list:
        """Per-chip cache telemetry, shard-tagged (never collapsed)."""
        return [
            dataclasses.replace(r.kv_stats, shard=s)
            for s, r in enumerate(self.by_shard)
        ]

    @property
    def kv_voltages_by_shard(self) -> list:
        return [list(r.kv_voltages) for r in self.by_shard]

    @classmethod
    def merge(cls, reports) -> "MeshServeReport":
        reports = list(reports)
        outputs, request_stats, shard_of = {}, {}, {}
        for s, r in enumerate(reports):
            for rid, toks in r.outputs.items():
                assert rid not in outputs, f"request {rid} served twice"
                outputs[rid] = toks
                shard_of[rid] = s
            request_stats.update(r.request_stats)
        return cls(
            by_shard=reports,
            outputs=outputs,
            request_stats=request_stats,
            kv_stats=FaultStats.summed(r.kv_stats for r in reports),
            shard_of=shard_of,
            steps=sum(r.steps for r in reports),
            preemptions=sum(r.preemptions for r in reports),
        )


class ContinuousBatchingScheduler:
    """Host-side lane + page bookkeeping (admit / grow / preempt / retire)."""

    def __init__(
        self,
        requests,
        n_lanes: int,
        alloc: PageAllocator,
        geom: KVGeometry,
        arena: KVPageArena | None = None,
        trie: PrefixTrie | None = None,
        recorder=None,
    ):
        self.waiting = deque(RequestState(r) for r in requests)
        self.lanes: list = [None] * n_lanes
        self.alloc = alloc
        self.geom = geom
        self.arena = arena  # needed to wipe recycled pages before reuse
        self.trie = trie  # prefix-sharing radix tree (None = private pages)
        self.recorder = recorder  # optional obs.TraceRecorder
        self.shard = arena.shard if arena is not None else -1
        self.finished: dict = {}
        self.preemptions = 0
        self._admit_counter = 0
        self.fresh_pages: list = []  # allocated since last wipe drain
        self._t0_ns = time.perf_counter_ns() if obs_profile.enabled() else None

    def stamp_ns(self) -> int:
        """Host ns since the stream started; read only while a profiler
        session runs that was already running at the start (-1 otherwise),
        so the clock is never read on the untraced path."""
        if self._t0_ns is None or not obs_profile.enabled():
            return -1
        return time.perf_counter_ns() - self._t0_ns

    def _alloc(self, owner):
        """Page for ``owner``; recycles the dirty list when the clean free
        list runs dry, then evicts sole-referenced trie leaves (LRU) before
        giving up — cached prefixes yield to live requests, preemption is
        the last resort. Every allocation is recorded in ``fresh_pages`` —
        the serve loop zero-wipes the batch before anything commits to it
        (once the arena has faulted, even 'clean'-list pages hold stale
        words: tick() injects into the whole arena, allocated or not)."""
        page = self.alloc.alloc(owner)
        if page is None and self.trie is not None and not self.alloc.dirty_pages:
            self.trie.evict_lru(1)
        if page is None and self.alloc.dirty_pages:
            self.alloc.recycle()
            page = self.alloc.alloc(owner)
        if page is not None:
            self.fresh_pages.append(page)
        return page

    def drain_fresh_pages(self) -> None:
        """Wipe pages allocated since the last drain (no-op pre-fault: an
        arena that never ticked below the guardband is zero/valid-data only,
        and scrub of a previous owner's *valid* words is clean by identity)."""
        if self.fresh_pages and self.arena is not None and self.arena.faulted:
            self.arena.zero_pages(np.asarray(self.fresh_pages, np.int32))
        self.fresh_pages.clear()

    @property
    def running(self) -> list:
        return [st for st in self.lanes if st is not None]

    @property
    def unfinished(self) -> bool:
        return bool(self.waiting) or any(self.lanes)

    def _free_lane(self):
        for i, st in enumerate(self.lanes):
            if st is None:
                return i
        return None

    def admit(self):
        """Admit waiting requests FCFS while lanes + pages allow; yields the
        admitted (lane, state, resume_seq) triples (pages pre-allocated to
        cover the prefilled sequence plus the first decode token).

        With a prefix trie, the longest cached full-page prefix of the
        sequence is *shared* (refcounted) instead of allocated: the state's
        ``shared_tokens`` records how deep, ``pages`` starts with the shared
        pages, and only the private suffix needs fresh allocations (trie
        leaves are LRU-evicted under pressure before admission stalls).
        """
        while self.waiting:
            lane = self._free_lane()
            if lane is None:
                break
            st = self.waiting[0]
            seq = st.resume_seq
            shared: list = []
            if self.trie is not None:
                shared = self.trie.lookup(seq)
                for p in shared:
                    self.alloc.share(p, st.rid)
            need = self.geom.pages_for(len(seq) + 1) - len(shared)
            if need > self.alloc.free_pages and self.trie is not None:
                # cached-but-unreferenced prefixes yield to the admission
                # (the just-shared pages are pinned by st.rid's reference)
                self.trie.evict_lru(need - self.alloc.free_pages)
            if need > self.alloc.free_pages:
                if shared:
                    self.alloc.free(shared, st.rid)  # undo; retry next round
                break
            self.waiting.popleft()
            st.pages = shared + [self._alloc(st.rid) for _ in range(need)]
            st.shared_tokens = len(shared) * self.geom.page_tokens
            st.status, st.lane = "running", lane
            st.admit_seq = self._admit_counter
            self._admit_counter += 1
            self.lanes[lane] = st
            if st.admit_ns < 0:
                st.admit_ns = self.stamp_ns()
            rec = self.recorder
            if rec:
                if st.admit_step < 0:
                    st.admit_step = rec.step
                rec.emit(
                    "admit", request_id=st.rid, shard=self.shard, lane=lane,
                    prompt_len=len(seq), shared_tokens=st.shared_tokens,
                )
                rec.metrics.counter("serve.admissions").inc()
                if shared:
                    rec.emit(
                        "prefix_hit", request_id=st.rid, shard=self.shard,
                        tokens=st.shared_tokens, pages=len(shared),
                    )
            yield lane, st, seq

    def ensure_pages(self, st: RequestState, until: int | None = None) -> bool:
        """Guarantee pages exist for positions up to ``until`` (default: the
        position the next decode step writes); preempts younger requests
        under pressure. False if ``st`` itself had to be preempted (i.e. it
        is the youngest and the arena is full)."""
        until = st.stored if until is None else until
        added = 0
        while until // self.geom.page_tokens >= len(st.pages):
            page = self._alloc(st.rid)
            if page is not None:
                st.pages.append(page)
                added += 1
                continue
            victim = max(self.running, key=lambda s: s.admit_seq)
            self.preempt(victim)
            if victim is st:
                return False
        if added and self.recorder:
            self.recorder.emit(
                "page_grow", request_id=st.rid, shard=self.shard,
                pages_added=added, pages_total=len(st.pages),
            )
        return True

    def preempt(self, st: RequestState) -> None:
        """Recompute-style preemption: drop pages, re-queue at the front."""
        if self.recorder:
            self.recorder.emit(
                "preempt", request_id=st.rid, shard=self.shard, lane=st.lane,
                pages_freed=len(st.pages), preemptions=st.preemptions + 1,
            )
        self.alloc.free(st.pages, st.rid)
        self.lanes[st.lane] = None
        st.pages, st.lane, st.admit_seq = [], -1, -1
        st.shared_tokens = 0
        st.status = "waiting"
        st.preemptions += 1
        self.preemptions += 1
        self.waiting.appendleft(st)

    def retire(self, st: RequestState) -> None:
        with obs_profile.span(
            "serve.retire",
            request_id=st.rid,
            admit_ns=st.admit_ns,
            first_token_ns=st.first_token_ns,
            done_ns=self.stamp_ns,
        ):
            rec = self.recorder
            if rec:
                st.finish_step = rec.step
                lat = rec.step - st.admit_step if st.admit_step >= 0 else 0
                rec.emit(
                    "retire", request_id=st.rid, shard=self.shard,
                    tokens=len(st.tokens), latency_steps=lat,
                    first_token_step=st.first_token_step,
                    preemptions=st.preemptions,
                )
                rec.metrics.histogram("request.latency_steps").observe(lat)
                if st.first_token_step >= 0 and st.admit_step >= 0:
                    rec.metrics.histogram("request.first_token_steps").observe(
                        st.first_token_step - st.admit_step
                    )
            self.alloc.free(st.pages, st.rid)
            self.lanes[st.lane] = None
            st.pages, st.lane = [], -1
            st.shared_tokens = 0
            st.status = "finished"
            self.finished[st.rid] = st


def _block_steps(want: int, max_block: int, scrub_left: int | None) -> int:
    """A decode block's steps: ``want`` cut to ``max_block`` and to the
    steps left before the next scrub, bucketed down to a power of two (few
    scan shapes)."""
    k = max(1, min(want, max_block))
    if scrub_left is not None:
        k = max(1, min(k, scrub_left))
    return 1 << (k.bit_length() - 1)


def serve_stream(
    params,
    cfg,
    helpers,
    arena: KVPageArena,
    requests,
    *,
    n_lanes: int,
    max_len: int,
    scrub_interval: int = 1,
    max_block: int = 16,
    kv_controller=None,
    init_cache_fn=None,
    helpers_factory=None,
    share_prefix: bool = False,
    speculative: int = 0,
    draft_params=None,
    draft_cfg=None,
    recorder=None,
    scrub_overlap: bool | None = None,
) -> ServeReport:
    """Drive a request stream to completion over the paged cache.

    ``helpers`` comes from serving/steps.make_paged_helpers (any
    ``DecodeBlockHelpers``-shaped mapping works); ``kv_controller``
    is an optional UndervoltController fed the per-interval scrub telemetry —
    its output voltage is applied to the arena (the `kv` rail walk). When the
    controller escalates its ECC scheme (core/controller.py EscalationPolicy),
    the arena is re-encoded under the stronger code and ``helpers_factory``
    (codec name -> helpers, see serving/steps.HelpersFactory) supplies a
    commit path matching the new check-plane geometry. Without a factory
    there is no way to apply a stronger code to the live arena, so
    escalation is *suppressed* around each controller update (and the
    caller's policy restored afterwards) — the controller must never advance
    its codec state past the protection actually in force (it would
    mis-report and double-escalate).

    Decode runs in *blocks* of up to ``max_block`` steps lowered to one
    scanned dispatch (multi-step scheduling): the block size is the largest
    power of two that no active lane's remaining budget — and no pending
    scrub deadline — cuts short, so blocks never decode wasted tokens and
    the scrub cadence stays exact. ``max_block=1`` recovers the one-dispatch-
    per-token loop (what the preemption tests pin down).

    ``share_prefix`` turns on the prefix-sharing trie (DESIGN.md §16):
    identical full-page prompt prefixes map to the same physical pages
    (refcounted; divergence is copy-on-write by construction since only
    complete, immutable prompt pages are shared), admission scrubs the
    shared pages *once* and chunk-prefills only the private suffix, and the
    interval scrub deduplicates shared pages — physically each is scrubbed
    once (that is the power/throughput win) while the DED telemetry fed to
    the kv controller stays *reader-weighted*: a detected-uncorrectable on
    a page with N readers is N correlated request failures, so it counts N
    times against the physical word count and the escalation ladder trips
    earlier (scrub-aware sharing).

    ``speculative=K`` (with ``draft_params``/``draft_cfg``) drafts K-1
    tokens per dispatch with the draft model (dense, reliable-memory lane
    caches — the *target* cache is what lives in undervolted pages) and
    verifies all K positions with one chunked target forward; only accepted
    tokens' page commits land (rejected rows steer to the scratch page), so
    the emitted stream is exactly the greedy rollout.

    ``scrub_overlap`` (DESIGN.md §18) moves the interval scrub off the
    decode critical path: tick + scrub-on-read + cache refresh are
    dispatched as usual (device-side dependencies keep the refresh ordered
    before the next decode block), but the counter harvest — the
    ``np.asarray`` host sync plus all stats/controller/recorder work — is
    deferred until just before the *next* interval's tick (and stream end),
    so the decode blocks in between overlap the scrub instead of waiting
    for it. Bit-identity is structural: the controller's rail move from
    interval N's counters lands before interval N+1's injection exactly as
    in the serialized path, per-lane attribution is captured at dispatch
    time (preemption between intervals can't skew it), and the device
    work is the same launches in the same order — planes, counters, tokens
    and rail walks are byte-identical (tested). ``None`` (auto) overlaps
    except when codec escalation is live (``kv_controller.escalation`` with
    a ``helpers_factory``): escalation rebinds the commit path mid-stream,
    which must stay synchronous with the scrub that flushed the arena, so
    those streams auto-demote to the serialized path.
    """
    import jax
    import jax.numpy as jnp

    from repro.configs import shapes
    from repro.core import statestore
    from repro.models import lm
    from repro.serving import steps as steps_mod

    geom = arena.geom
    requests = normalize_requests(requests)
    has_state = shapes.has_state_layers(cfg)
    for r in requests:
        total = len(r.prompt) + r.max_new_tokens
        assert total <= max_len, (r.rid, total, max_len)
        assert geom.pages_for(total) <= arena.n_pages, (
            f"request {r.rid} needs {geom.pages_for(total)} pages, "
            f"arena has {arena.n_pages}"
        )
        assert r.max_new_tokens >= 1 and len(r.prompt) >= 1

    init_cache_fn = init_cache_fn or (lambda b: lm.init_cache(cfg, b, max_len))
    alloc = PageAllocator(arena.n_pages)
    rec = recorder
    trie = (
        PrefixTrie(
            alloc, geom.page_tokens, recorder=rec, shard=arena.shard
        )
        if share_prefix
        else None
    )
    sched = ContinuousBatchingScheduler(
        requests, n_lanes, alloc, geom, arena=arena, trie=trie, recorder=rec
    )
    if rec:
        rec.emit(
            "serve_begin", shard=arena.shard, n_requests=len(requests),
            n_lanes=n_lanes, scrub_interval=scrub_interval,
            share_prefix=bool(share_prefix), speculative=int(speculative),
            voltage=float(arena.voltage), codec=arena.codec_name,
        )
    spec_k = int(speculative)
    n_rows = decode_rows(n_lanes)
    if spec_k >= 2:
        assert draft_params is not None and draft_cfg is not None, (
            "speculative decode needs draft_params + draft_cfg"
        )
        assert helpers.get("spec_multistep") is not None, (
            "helpers were built without a draft config (spec_multistep)"
        )
        draft_prefill = jax.jit(steps_mod.make_prefill_step(draft_cfg))
        dcache = lm.init_cache(draft_cfg, n_rows, max_len)
    else:
        draft_prefill, dcache = None, None
    cache = init_cache_fn(n_rows)
    state_stats = FaultStats()
    if has_state:  # lane slots of the state store: SECDED planes only
        cache = statestore.seal(cache, cfg)
        state_words = statestore.words_per_lane(cfg)
    k_cap = _block_steps(max_block, max_block, scrub_interval or None)  # largest block
    cur_tok = np.zeros(n_rows, np.int32)
    pos_v = np.zeros(n_rows, np.int32)
    steps = 0
    since_scrub = 0
    kv_voltages: list = []
    prefix_hit_tokens = 0
    spec_dispatches = 0
    spec_emitted = 0

    overlap = scrub_overlap
    if overlap is None:
        # Auto-demotion (see docstring): live codec escalation must rebind
        # the commit path synchronously with the scrub that flushed it.
        overlap = not (
            kv_controller is not None
            and helpers_factory is not None
            and getattr(kv_controller, "escalation", None) is not None
        )
    pending_scrub = None  # deferred interval harvest (overlap mode)

    @obs_profile.spanned("serve.scrub_dispatch")
    def _dispatch_scrub():
        """Interval scrub device work: tick, scrub-on-read, cache refresh —
        all async dispatch, no host sync. Returns the capture the deferred
        harvest needs: the device counters plus dispatch-time attribution
        (the (state, n_pages) pairs and dedup rows as of THIS interval —
        preemption or retirement before the harvest must not skew them)."""
        nonlocal cache
        arena.tick()
        # Table width tracks the *live* page maximum (power-of-two
        # bucketed so the jit shape set stays logarithmic), not worst-
        # case stream capacity: the scrub pass scales with pages that
        # actually hold tokens, and scratch filler rows are pure waste.
        live_max = max(len(st.pages) for st in sched.running)
        p_cols = 1 << max(live_max - 1, 0).bit_length()
        table = np.full((n_lanes, p_cols), arena.scratch_page, np.int32)
        n_tok = np.zeros(n_lanes, np.int32)
        lanes_cap: list = []
        for i, st in enumerate(sched.lanes):
            if st is None:
                lanes_cap.append(None)
                continue
            table[i, : len(st.pages)] = st.pages
            n_tok[i] = st.stored  # already counts the token committed above
            lanes_cap.append((st, len(st.pages)))
        if trie is None:
            payload, cnt = arena.scrub_pages_async(table.reshape(-1))
            cache = helpers["refresh"](
                cache,
                payload.reshape(n_lanes, -1, geom.token_f32),
                jnp.asarray(n_tok),
            )
            cap = {"mode": "private", "cnt": cnt, "p_cols": p_cols}
        else:
            # Prefix sharing: scrub each unique live page ONCE (that is
            # the physical work and the arena.stats truth), then fan the
            # corrected payload out to every reader's lane cache.
            upad, rows, n_u = dedup_page_table(table, arena.scratch_page)
            payload_u, cnt = arena.scrub_pages_async(upad)
            cache = helpers["refresh"](
                cache,
                payload_u[jnp.asarray(rows.reshape(-1))].reshape(
                    n_lanes, -1, geom.token_f32
                ),
                jnp.asarray(n_tok),
            )
            cap = {"mode": "shared", "cnt": cnt, "rows": rows, "n_u": n_u}
        cap["lanes"] = lanes_cap
        # Gauge values describe the interval being scrubbed, so snapshot
        # them now — at harvest time the scheduler has moved on.
        cap["gauges"] = (
            sched.alloc.free_pages, len(sched.waiting), len(sched.running)
        )
        return cap

    @obs_profile.spanned("serve.scrub_harvest")
    def _harvest_scrub(cap):
        """The deferred half of the interval scrub: the one host sync plus
        all stats / controller / recorder work, bit-identical to running
        inline (same counters, same reduction order, same rail move)."""
        nonlocal helpers
        with obs_profile.span("serve.scrub_sync"):
            cnt = np.asarray(cap["cnt"])
        interval = FaultStats()  # reader-weighted attribution
        if cap["mode"] == "private":
            cnt = cnt.reshape(n_lanes, cap["p_cols"], 8)
            for i, lc in enumerate(cap["lanes"]):
                if lc is None:
                    continue
                st, n_p = lc
                rows_c = cnt[i, :n_p]
                rs = FaultStats.from_counters(
                    rows_c.sum(axis=0), words=n_p * geom.words_per_page
                )
                st.stats.accumulate(rs)
                interval.accumulate(rs)
            # without sharing every live page has one reader: the
            # reader-weighted view IS the physical view
            physical = interval
            arena.stats.accumulate(interval)
        else:
            rows, n_u = cap["rows"], cap["n_u"]
            for i, lc in enumerate(cap["lanes"]):
                if lc is None:
                    continue
                st, n_p = lc
                rs = FaultStats.from_counters(
                    cnt[rows[i, :n_p]].sum(axis=0),
                    words=n_p * geom.words_per_page,
                )
                st.stats.accumulate(rs)
                interval.accumulate(rs)
            physical = FaultStats.from_counters(
                cnt[:n_u].sum(axis=0),
                words=n_u * geom.words_per_page,
                shard=arena.shard,
            )
            arena.stats.accumulate(physical)
        if kv_controller is not None and not kv_controller.locked:
            # See docstring: without a factory a stronger code cannot be
            # applied to the live arena, so escalation is suppressed for
            # this update only (the caller's policy is left intact).
            saved_policy = kv_controller.escalation
            if helpers_factory is None:
                kv_controller.escalation = None
            try:
                # Scrub-aware sharing: reader-weighted counters over the
                # *physical* word population — a DED on an N-reader page
                # counts N times, so ded_rate amplifies with fan-out and
                # the escalation ladder trips earlier than it would for
                # private pages (core/controller.reader_weighted_stats).
                arena.set_voltage(
                    kv_controller.update(
                        reader_weighted_stats(interval, physical)
                    )
                )
            finally:
                kv_controller.escalation = saved_policy
            change = kv_controller.pop_codec_change()
            if change and rec:
                rec.emit(
                    "kv_codec_change", shard=arena.shard, domain="kv",
                    codec=change,
                )
            if change:
                # Escalate right after the scrub above flushed every
                # correctable fault: the arena re-encodes under the
                # stronger code and the commit path switches with it.
                # (A change can only arrive when a factory exists —
                # escalation was suppressed above otherwise. Escalation-
                # capable streams run serialized — see scrub_overlap — so
                # this runs at the same point the inline path would.)
                shared_now = None
                if trie is not None:
                    shared_now = sorted(
                        set(sched.alloc.shared_pages()) | set(trie.pages())
                    )
                try:
                    arena.change_codec(change, shared_pages=shared_now)
                except SharedPageDEDError as err:
                    # Refuse-and-copy: a latched DED on a shared page
                    # must not be re-sealed for N readers. Drop the
                    # trie's claim on the poisoned prefixes, preempt
                    # every running reader (recompute *is* the copy —
                    # fresh pages, re-prefilled KV), then re-protect.
                    trie.evict_pages(err.pages)
                    bad = set(err.pages)
                    preempted = 0
                    for st in list(sched.running):
                        if bad & set(st.pages):
                            sched.preempt(st)
                            preempted += 1
                    arena.change_codec(change)
                    if rec:
                        rec.emit(
                            "shared_ded_recovery", shard=arena.shard,
                            domain="kv", pages=len(err.pages),
                            preempted=preempted,
                        )
                helpers = helpers_factory(change)
        if rec:
            rec.emit(
                "kv_scrub", shard=arena.shard, domain="kv",
                interval=len(kv_voltages), voltage=float(arena.voltage),
                codec=arena.codec_name, corrected=physical.corrected,
                detected=physical.detected, silent=physical.silent,
                words=physical.words,
            )
            m = rec.metrics
            lbl = {"shard": arena.shard} if arena.shard >= 0 else {}
            m.observe_fault_stats("kv.scrub", physical, **lbl)
            free_pages, queue_depth, lanes_active = cap["gauges"]
            for gname, val in (
                ("kv.pages_free", free_pages),
                ("sched.queue_depth", queue_depth),
                ("sched.lanes_active", lanes_active),
            ):
                m.gauge(gname, **lbl).set(val)
                rec.emit(
                    "gauge", shard=arena.shard, name=gname, value=val
                )
        kv_voltages.append(arena.voltage)


    def _prefill_group(s0, sh, grp):
        """One admission group of equal (prompt length, shared prefix):
        prefill (or chunk-prefill past the shared pages), commit the
        prompts' KV to pages and load each row into its lane."""
        nonlocal cache, dcache, prefix_hit_tokens
        m = len(grp)
        pf_rows = steps_mod.program_rows(cfg, m, n_rows)
        with obs_profile.span(
            "serve.prefill_group", m=m, prompt_len=s0, shared_tokens=sh
        ):
            cachem = init_cache_fn(pf_rows)
            seqs = np.stack([seq for _, _, seq in grp])
            if pf_rows > m:
                seqs = np.concatenate([seqs, np.repeat(seqs[-1:], pf_rows - m, axis=0)])
            if sh:
                # Prefix hit: refresh the shared pages' payload into the
                # batch cache (scrub-on-read — each *unique* page once, its
                # counters attributed to every reader), then chunk-prefill
                # only the private suffix at pos0 = sh.
                n_sp = sh // geom.page_tokens
                ptab = np.stack([st.pages[:n_sp] for _, st, _ in grp])
                upad, rows, n_u = dedup_page_table(ptab, arena.scratch_page)
                payload_u, cnt_u = arena.scrub_pages(upad)
                payload = jnp.asarray(payload_u)[
                    jnp.asarray(rows.reshape(-1))
                ].reshape(m, sh, geom.token_f32)
                cachem = helpers["refresh"](
                    cachem, payload, jnp.full((m,), sh, jnp.int32)
                )
                tokm, cachem = helpers["chunk"](
                    params,
                    jnp.asarray(seqs[:, sh:]),
                    cachem,
                    jnp.full((m,), sh, jnp.int32),
                )
                payload_sfx = helpers["extract_span"](cachem, start=sh, stop=s0)
                tok_idx = np.arange(sh, s0)
                # physical telemetry once; per-reader attribution below
                arena.stats.accumulate(
                    FaultStats.from_counters(
                        cnt_u[:n_u].sum(axis=0),
                        words=n_u * geom.words_per_page,
                        shard=arena.shard,
                    )
                )
                for r, (_, st, _) in zip(rows, grp):
                    st.stats.accumulate(
                        FaultStats.from_counters(
                            cnt_u[r].sum(axis=0),
                            words=n_sp * geom.words_per_page,
                        )
                    )
                prefix_hit_tokens += sh * m
            else:
                tokm, cachem = helpers["prefill"](params, jnp.asarray(seqs), cachem)
                payload_sfx = helpers["extract_range"](cachem, s0=s0)
                tok_idx = np.arange(s0)
            page_ids = np.stack(
                [
                    [st.pages[t // geom.page_tokens] for t in tok_idx]
                    for _, st, _ in grp
                ]
                + [[arena.scratch_page] * len(tok_idx)] * (pf_rows - m)
            )
            arena.commit_tokens(
                payload_sfx.reshape(page_ids.size, -1),
                page_ids.reshape(-1),
                np.tile(tok_idx % geom.page_tokens, len(page_ids)),
            )
            if trie is not None:
                # register the prompts' complete pages (partial tail pages
                # stay private — that is what makes divergence CoW-free)
                for _, st, seq in grp:
                    trie.insert(seq, st.pages[: len(seq) // geom.page_tokens])
            if draft_prefill is not None:
                dcachem = lm.init_cache(draft_cfg, m, max_len)
                _, dcachem = draft_prefill(draft_params, jnp.asarray(seqs), dcachem)
            if has_state:
                # the prefill's final state, encoded into the admitted lanes'
                # slots (a slot's previous request is overwritten here)
                lanes = np.full(pf_rows, n_rows, np.int32)  # padding rows: dropped
                lanes[:m] = [lane for lane, _, _ in grp]
                with obs_profile.span("state.commit", lanes=m, words=m * state_words):
                    cache = helpers["commit_state"](cache, cachem, jnp.asarray(lanes))
            with obs_profile.span("serve.prefill_sync"):
                tok_host = np.asarray(tokm).reshape(-1)
            for row, (lane, st, _) in enumerate(grp):
                cache = helpers["load_lane"](cache, cachem, row, lane)
                if draft_prefill is not None:
                    dcache = helpers["load_lane"](dcache, dcachem, row, lane)
                if not st.tokens:  # fresh admission: keep the prefill's token
                    st.tokens = [int(tok_host[row])]
                    st.first_token_ns = sched.stamp_ns()
                    if rec and st.first_token_step < 0:
                        st.first_token_step = rec.step
                if st.done:  # budget met by the prefill token alone
                    sched.retire(st)
                    continue
                cur_tok[lane] = st.tokens[-1]
                pos_v[lane] = s0

    def _decode_block() -> bool:
        """One decode block: its size, the pages it grows into, its dispatch
        and the tokens it books. False if page growth left no lane."""
        nonlocal cache, dcache, steps, since_scrub, spec_dispatches, spec_emitted
        with obs_profile.span("serve.decode_block") as block:
            # -- block size: no lane's budget, and no scrub deadline, overrun -
            running = sched.running
            k = _block_steps(
                min(st.req.max_new_tokens - len(st.tokens) for st in running),
                max_block,
                scrub_interval - since_scrub if scrub_interval else None,
            )

            # -- page growth for the whole block; preempt on pressure -------
            with obs_profile.span("serve.page_growth") as growth:
                for st in list(running):
                    if st.status == "running":  # an earlier growth may evict it
                        sched.ensure_pages(st, until=st.stored + k - 1)
                growth.set_metadata(pages_added=len(sched.fresh_pages))
                active = [i for i, st in enumerate(sched.lanes) if st is not None]
                if active:
                    sched.drain_fresh_pages()  # wipe before the block commits
            block.set_metadata(k=k, lanes_active=len(active))
            if not active:
                return False

            # -- k decode steps + per-token page commits in one dispatch ----
            rows_k = steps_mod.program_rows(cfg, k, k_cap)
            page_ids = np.full((rows_k, n_rows), arena.scratch_page, np.int32)
            slots = np.zeros((rows_k, n_rows), np.int32)
            for i in active:
                st = sched.lanes[i]
                for j in range(k):
                    t = pos_v[i] + j
                    page_ids[j, i] = st.pages[t // geom.page_tokens]
                    slots[j, i] = t % geom.page_tokens
            if spec_k >= 2 and k >= 2:
                # Draft k-1 tokens, verify all k in one chunked target
                # forward; page commits land only for the accepted prefix
                # (rejected rows steer to the scratch page in the dispatch).
                kk = min(k, spec_k)
                greedy, n_emit, cache, dcache, arena.lo, arena.hi, arena.parity = (
                    helpers["spec_multistep"](
                        params,
                        draft_params,
                        jnp.asarray(cur_tok[:, None]),
                        cache,
                        dcache,
                        arena.lo,
                        arena.hi,
                        arena.parity,
                        jnp.asarray(pos_v),
                        jnp.asarray(page_ids[:kk]),
                        jnp.asarray(slots[:kk]),
                        k=kk,
                        scratch_page=arena.scratch_page,
                    )
                )
                with obs_profile.span("serve.block_sync"):
                    greedy_host = np.asarray(greedy)
                    n_host = np.asarray(n_emit)
                steps += 1
                spec_dispatches += 1
                adv = max((int(n_host[i]) for i in active), default=0)
                if rec:
                    # clock first so same-dispatch retires see the post-block step
                    rec.advance(max(adv, 1))
                    rec.emit(
                        "spec_block", shard=arena.shard, k=kk,
                        lanes=len(active),
                        emitted=int(sum(n_host[i] for i in active)),
                        slots=kk * len(active),
                    )
                    rec.metrics.counter("spec.slots").inc(kk * len(active))
                    rec.metrics.counter("spec.emitted").inc(
                        int(sum(n_host[i] for i in active))
                    )
                for i in active:
                    st = sched.lanes[i]
                    n = int(n_host[i])
                    st.tokens.extend(int(t) for t in greedy_host[i, :n])
                    spec_emitted += n
                    cur_tok[i] = st.tokens[-1]
                    pos_v[i] += n
                    if st.done:
                        sched.retire(st)
                since_scrub += adv
            else:
                state_args = ()  # the live lanes and the block's size
                if has_state:
                    live_v = np.zeros(n_rows, np.int32)
                    live_v[active] = 1
                    state_args = (jnp.asarray(live_v), jnp.int32(k))
                toks, cache, arena.lo, arena.hi, arena.parity, *counts = helpers["multistep"](
                    params,
                    jnp.asarray(cur_tok[:, None]),
                    cache,
                    arena.lo,
                    arena.hi,
                    arena.parity,
                    jnp.asarray(pos_v),
                    jnp.asarray(page_ids),
                    jnp.asarray(slots),
                    *state_args,
                )
                with obs_profile.span("serve.block_sync"):
                    if has_state:
                        toks_host, counts = jax.device_get((toks, counts))
                        toks_host = toks_host[:k]
                    else:
                        toks_host = np.asarray(toks)
                if has_state:
                    block_stats = FaultStats()
                    for i in active:
                        rs = FaultStats.from_counters(
                            np.pad(counts[0][i], (0, 5)), words=int(counts[0][i].sum())
                        )
                        sched.lanes[i].state_stats.accumulate(rs)
                        block_stats.accumulate(rs)
                    state_stats.accumulate(block_stats)
                    block.set_metadata(
                        state_corrected=block_stats.corrected,
                        state_detected=block_stats.detected,
                    )
                steps += k
                since_scrub += k
                if rec:
                    rec.advance(k)  # the deterministic clock IS decode progress
                for i in active:
                    st = sched.lanes[i]
                    st.tokens.extend(int(t) for t in toks_host[:, i])
                    cur_tok[i] = st.tokens[-1]
                    pos_v[i] += k
                    if st.done:
                        sched.retire(st)
        return True

    with obs_profile.span("serve.stream", requests=len(requests), lanes=n_lanes):
        while sched.unfinished:
            # -- admission: batch same-shape prefills, commit the prompts' KV
            admitted = list(sched.admit())
            sched.drain_fresh_pages()  # wipe before the prompt commits below
            if admitted:
                with obs_profile.span("serve.admit", admitted=len(admitted)):
                    groups: dict = {}
                    for lane, st, seq in admitted:
                        groups.setdefault((len(seq), st.shared_tokens), []).append(
                            (lane, st, seq)
                        )
                    for (s0, sh), grp in groups.items():
                        _prefill_group(s0, sh, grp)

            if not sched.running:
                if not sched.unfinished:
                    break
                assert sched.waiting, "deadlock: no lanes active and queue empty"
                continue  # freed pages let admission proceed next iteration
            if not _decode_block():
                continue

            # -- scrub interval: inject at the kv rail, scrub-on-read, refresh
            if scrub_interval and since_scrub >= scrub_interval:
                since_scrub = 0
            else:
                continue
            # Off-critical-path scrub (§18): interval N's counters are
            # harvested immediately before interval N+1's tick, so the
            # controller's rail move still lands before the next injection —
            # exactly where the serialized path puts it — while the decode
            # blocks in between overlapped interval N's scrub device work.
            if pending_scrub is not None:
                _harvest_scrub(pending_scrub)
                pending_scrub = None
            if sched.running:
                cap = _dispatch_scrub()
                if overlap:
                    pending_scrub = cap
                else:
                    _harvest_scrub(cap)

        if pending_scrub is not None:
            # Stream drained with a scrub in flight: harvest before teardown
            # so the report's stats/voltages match the serialized path.
            _harvest_scrub(pending_scrub)
            pending_scrub = None

    if trie is not None:
        # Serve teardown: the prefix cache has no meaning past this stream,
        # so release every trie reference before the free-page accounting
        # (pages_free_at_end must see the arena fully reclaimed).
        trie.drain()
        sched.alloc.recycle()
    outputs = {
        rid: np.asarray(st.tokens, np.int32) for rid, st in sched.finished.items()
    }
    if rec:
        rec.emit(
            "serve_end", shard=arena.shard, steps=steps,
            preemptions=sched.preemptions, finished=len(outputs),
        )
        lbl = {"shard": arena.shard} if arena.shard >= 0 else {}
        rec.metrics.counter("serve.steps", **lbl).inc(steps)
        rec.metrics.counter("serve.preemptions", **lbl).inc(sched.preemptions)
        rec.metrics.counter("serve.prefix_hit_tokens", **lbl).inc(
            prefix_hit_tokens
        )
    return ServeReport(
        outputs=outputs,
        request_stats={rid: st.stats for rid, st in sched.finished.items()},
        kv_stats=arena.stats,
        steps=steps,
        preemptions=sched.preemptions,
        kv_voltages=kv_voltages,
        arena=arena,
        pages_free_at_end=sched.alloc.free_pages,
        prefix_hit_tokens=prefix_hit_tokens,
        spec_dispatches=spec_dispatches,
        spec_emitted=spec_emitted,
        state_stats=state_stats,
    )
