"""Paged, SECDED-protected KV-cache arena (DESIGN.md §11).

The weight arena (core/planestore.py) made the *static* model state live in
undervolted ECC memory; this module does the same for the *dynamic* state —
the KV cache — so the paper's power saving applies to serving, where the
cache dominates on-chip memory traffic. The `kv` voltage domain introduced
with the multi-rail work (configs/shapes.MEMORY_DOMAINS) is backed here with
real storage for the first time.

Layout
  * The arena is a flat word store of ``n_pages`` fixed-size pages (plus one
    scratch page masked writes land on). A page holds ``page_tokens`` tokens;
    one token's payload is every attention layer's K and V row for that
    position, bitcast f32 -> uint32 and packed two words per SECDED(72,64)
    codeword: lo/hi uint32 planes + a uint8 parity plane, exactly the word
    geometry of the weight path.
  * `PageAllocator` hands out pages with single-owner bookkeeping; the
    continuous-batching scheduler (serving/scheduler.py) allocates one page
    per ``page_tokens`` positions per request and frees them on retire or
    preemption.
  * Writes encode (kernels/ops.encode); reads slice each page out of the
    flat planes as one contiguous ``words_per_page`` window addressed by its
    start offset (`_gather_pages`), travel through the scrub-on-read kernel
    (kernels/paged_gather.py) which corrects single-bit faults and emits
    per-page (clean, corrected, detected) counters, and the corrected pages
    are written back window by window (`_scatter_pages`).
  * `tick()` injects one interval's undervolting faults at the current `kv`
    rail voltage. Unlike the weight store — which keeps clean planes and
    re-derives the faulty view per voltage — the cache is mutable, so faults
    are XORed *into* the stored planes and persist until a scrub corrects
    them or a write overwrites the cell; each interval draws a fresh mask
    (key folded with the interval counter), modelling fault accumulation on
    a live memory rather than a voltage re-materialisation.

At nominal voltage no mask is ever non-zero and encode->decode is the
identity on the bitcast payload, so the paged read path is bit-identical to
a dense cache (tested in tests/test_kvpaged.py).
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro import codes
from repro.core import scenario
from repro.core.faultsim import _device_chunk_masks_jit
from repro.core.telemetry import FaultStats
from repro.core.voltage import PlatformProfile
from repro.kernels import ops as kops
from repro.kernels import paged_gather
from repro.obs import profile as obs_profile

PAGE_TOKENS = 8  # default page size (tokens); 2^k keeps slot math cheap


def dedup_page_table(table, scratch_page: int):
    """Deduplicate a page-id table for a single scrub pass (DESIGN.md §16).

    Under prefix sharing the same physical page appears in several readers'
    tables; scrubbing it once per reader would double-charge its counters
    and waste the scrub bandwidth the sharing exists to save (see
    kernels/paged_gather.py on the duplicate-row contract). Returns
    ``(upad, rows, n_unique)``:

      * ``upad``    — the unique non-scratch page ids ascending, padded with
        ``scratch_page`` to the next power of two (bounds the jit retrace
        set exactly like the scheduler's lane tables); when ``table``
        contains scratch entries at least one scratch slot is guaranteed so
        they never alias a real page's row.
      * ``rows``    — int32 of ``table``'s shape mapping every entry to its
        row in ``upad`` (scratch entries map to a scratch slot).
      * ``n_unique``— count of real (non-scratch) pages: ``upad[:n_unique]``
        rows of the scrub counters are the physical-telemetry rows.
    """
    table = np.asarray(table, np.int32)
    flat = table.reshape(-1)
    real = flat[flat != scratch_page]
    uniq = np.unique(real)
    n_u = len(uniq)
    has_scratch = len(real) != len(flat)
    target = 1 << max(n_u + int(has_scratch) - 1, 0).bit_length()
    upad = np.concatenate(
        [uniq, np.full(max(target, 1) - n_u, scratch_page, np.int32)]
    ).astype(np.int32)
    rows = np.where(
        flat == scratch_page, n_u, np.searchsorted(uniq, flat)
    ).astype(np.int32)
    return upad, rows.reshape(table.shape), n_u


@dataclasses.dataclass(frozen=True)
class KVGeometry:
    """Word-level geometry of one model's paged KV cache."""

    attn_positions: tuple[int, ...]  # period positions with an attn mixer
    n_groups: int
    n_kv_heads: int
    head_dim: int
    page_tokens: int = PAGE_TOKENS

    @classmethod
    def from_config(cls, cfg, page_tokens: int = PAGE_TOKENS) -> "KVGeometry":
        attn = tuple(
            j for j in range(cfg.period) if cfg.layer_kind(j)["mixer"] == "attn"
        )
        assert attn, f"{cfg.name}: no attention layers to page"
        return cls(attn, cfg.n_groups, cfg.n_kv_heads, cfg.hd, int(page_tokens))

    @property
    def token_f32(self) -> int:
        """f32 values per token: K and V rows of every attention layer."""
        return 2 * len(self.attn_positions) * self.n_groups * self.n_kv_heads * self.head_dim

    @property
    def token_words(self) -> int:
        """64-bit SECDED codewords per token (two f32 per codeword)."""
        return self.token_f32 // 2

    @property
    def words_per_page(self) -> int:
        return self.page_tokens * self.token_words

    def pages_for(self, n_tokens: int) -> int:
        return -(-int(n_tokens) // self.page_tokens)


class PageAllocator:
    """Free-list page allocator with refcounted-owner bookkeeping.

    Owners are opaque hashables (request ids, or the prefix trie's sentinel).
    A page starts single-owner via ``alloc``; additional readers attach with
    ``share`` (prefix sharing, DESIGN.md §16) and each reader drops only its
    own reference with ``free`` — the page goes dirty only when the *last*
    reference drops, so no page is ever recycled out from under a reader.
    The double-alloc / foreign-free asserts are the invariants the
    hypothesis tests drive.

    Freed pages land on a *dirty* list, not the free list: they still hold
    the previous owner's words and re-enter circulation via ``recycle()``.
    Note that sitting on the *free* list is no guarantee of cleanliness
    either — ``KVPageArena.tick`` injects faults into every arena word,
    allocated or not — so the serving loop zero-wipes *newly allocated*
    pages (in one batched scatter, and only once the arena has ever
    faulted) before any commit touches them: stale words and latent DED
    events from a page's past are never attributed to its next owner.
    """

    def __init__(self, n_pages: int):
        self.n_pages = int(n_pages)
        self._free = list(range(self.n_pages - 1, -1, -1))  # pop() -> page 0 first
        self._dirty: list[int] = []
        self._owners: dict[int, set] = {}

    @property
    def free_pages(self) -> int:
        """Pages allocatable without preemption (clean + recyclable)."""
        return len(self._free) + len(self._dirty)

    @property
    def dirty_pages(self) -> int:
        return len(self._dirty)

    @property
    def used_pages(self) -> int:
        return self.n_pages - self.free_pages

    def owner_of(self, page: int):
        """Sole owner of a single-reader page; a frozenset for shared pages;
        None for unallocated pages."""
        owners = self._owners.get(page)
        if not owners:
            return None
        if len(owners) == 1:
            return next(iter(owners))
        return frozenset(owners)

    def refcount(self, page: int) -> int:
        return len(self._owners.get(page, ()))

    def is_shared(self, page: int) -> bool:
        return self.refcount(page) > 1

    def shared_pages(self) -> list[int]:
        """Live pages with more than one reader, ascending."""
        return sorted(p for p, o in self._owners.items() if len(o) > 1)

    def alloc(self, owner) -> int | None:
        """One *clean* page for ``owner``; None if the clean list is empty
        (the caller recycles the dirty list, evicts trie leaves, or
        preempts)."""
        if not self._free:
            return None
        page = self._free.pop()
        assert page not in self._owners, f"page {page} double-allocated"
        self._owners[page] = {owner}
        return page

    def share(self, page: int, owner) -> None:
        """Attach ``owner`` as an additional reader of a live page."""
        owners = self._owners.get(page)
        assert owners, f"page {page} shared while unallocated"
        assert owner not in owners, f"page {page} already referenced by {owner!r}"
        owners.add(owner)

    def free(self, pages, owner) -> None:
        """Drop ``owner``'s reference on each page; a page goes dirty only
        when its last reference drops (never freed with refcount > 0)."""
        for page in pages:
            owners = self._owners.get(page)
            assert owners is not None and owner in owners, (
                f"page {page} freed by {owner!r} but owned by "
                f"{self.owner_of(page)!r}"
            )
            owners.discard(owner)
            if owners:
                continue  # surviving readers keep the page live
            del self._owners[page]
            self._dirty.append(page)

    def recycle(self) -> list:
        """Move the dirty list to the free list; returns the batch (the
        serving loop's allocation-time wipe handles the zeroing)."""
        batch, self._dirty = self._dirty, []
        self._free.extend(batch)
        return batch


class _TrieNode:
    __slots__ = ("key", "page", "parent", "children", "stamp")

    def __init__(self, key, page, parent):
        self.key = key        # tuple of page_tokens token ids (None at root)
        self.page = page      # physical page id (None at root)
        self.parent = parent
        self.children: dict[tuple, "_TrieNode"] = {}
        self.stamp = 0        # LRU clock of the last lookup/insert touch


class PrefixTrie:
    """Radix tree over *full-page* token prefixes (DESIGN.md §16).

    Each edge is one page's worth of token ids (``page_tokens`` of them), so
    a node at depth d names a d·page_tokens-token prefix and carries the one
    physical page storing that chunk's KV rows. The trie itself holds a
    reference on every registered page (sentinel owner), so a prefix stays
    cached after its last reader retires; capacity pressure evicts
    sole-referenced leaves in LRU order before the scheduler resorts to
    preemption. Only *complete* pages are ever registered — a request's
    partial tail page is private by construction, which is what makes
    divergence copy-on-write: the shared prefix is immutable, every writer
    appends into pages it exclusively owns.
    """

    OWNER = "<prefix-trie>"

    def __init__(
        self,
        alloc: PageAllocator,
        page_tokens: int,
        recorder=None,
        shard: int = -1,
    ):
        self.alloc = alloc
        self.page_tokens = int(page_tokens)
        self._root = _TrieNode(None, None, None)
        self._by_page: dict[int, _TrieNode] = {}
        self._clock = 0
        # Optional flight recorder (obs.TraceRecorder): registrations and
        # evictions land as trie_insert / trie_evict events (DESIGN.md §17).
        self.recorder = recorder
        self.shard = int(shard)

    def __len__(self) -> int:
        return len(self._by_page)

    def _chunks(self, tokens) -> list[tuple]:
        pt = self.page_tokens
        toks = [int(t) for t in tokens]
        return [
            tuple(toks[i : i + pt]) for i in range(0, len(toks) - pt + 1, pt)
        ]

    def lookup(self, tokens) -> list[int]:
        """Pages of the longest cached full-page prefix of ``tokens``,
        capped at len(tokens)-1 so at least one suffix token is always left
        to prefill (the decode step needs a current token)."""
        if len(tokens) < 2:
            return []
        max_pages = (len(tokens) - 1) // self.page_tokens
        node, pages = self._root, []
        self._clock += 1
        for key in self._chunks(tokens)[:max_pages]:
            child = node.children.get(key)
            if child is None:
                break
            child.stamp = self._clock
            pages.append(child.page)
            node = child
        return pages

    def insert(self, tokens, pages) -> None:
        """Register ``pages`` as the full-page chunks of ``tokens``.

        ``pages`` must cover exactly the leading len(pages) full-page chunks
        (the caller passes a request's committed prompt pages). Chunks
        already present are stamped; new chunks take a trie reference via
        ``alloc.share`` so the page outlives its writer.
        """
        chunks = self._chunks(tokens)
        assert len(pages) <= len(chunks), "pages beyond full-page prefix"
        node = self._root
        self._clock += 1
        fresh = 0
        for key, page in zip(chunks, pages):
            child = node.children.get(key)
            if child is None:
                self.alloc.share(page, self.OWNER)
                child = _TrieNode(key, int(page), node)
                node.children[key] = child
                self._by_page[child.page] = child
                fresh += 1
            child.stamp = self._clock
            node = child
        if fresh and self.recorder:
            self.recorder.emit("trie_insert", shard=self.shard, pages=fresh)

    def _drop(self, node: _TrieNode) -> None:
        del node.parent.children[node.key]
        del self._by_page[node.page]
        self.alloc.free([node.page], self.OWNER)

    def evict_lru(self, n: int = 1) -> list[int]:
        """Drop up to ``n`` sole-referenced leaves, least recently touched
        first. Returns the pages released to the dirty list (the caller
        recycles). Leaves still shared with running readers are skipped —
        eviction never invalidates a reader."""
        freed = []
        while len(freed) < n:
            victims = [
                nd for nd in self._by_page.values()
                if not nd.children and self.alloc.refcount(nd.page) == 1
            ]
            if not victims:
                break
            victim = min(victims, key=lambda nd: nd.stamp)
            freed.append(victim.page)
            self._drop(victim)
        if freed and self.recorder:
            self.recorder.emit(
                "trie_evict", shard=self.shard, pages=len(freed), reason="lru"
            )
        return freed

    def pages(self) -> list[int]:
        """Every page the trie currently holds a reference on (sorted)."""
        return sorted(self._by_page)

    def evict_pages(self, pages) -> list[int]:
        """Forcibly drop the trie's reference on ``pages`` and every
        descendant chunk (a child's prefix is unreachable without its
        parent). Used when codec escalation refuses to re-protect shared
        pages: the trie reference goes away, surviving readers keep the
        page live until preemption recomputes them. Returns the pages whose
        trie reference was dropped."""
        dropped = []
        for page in pages:
            node = self._by_page.get(int(page))
            if node is None:
                continue
            stack = [node]
            while stack:
                nd = stack.pop()
                stack.extend(nd.children.values())
                if nd.page in self._by_page:
                    dropped.append(nd.page)
                    self._drop(nd)
        if dropped and self.recorder:
            self.recorder.emit(
                "trie_evict", shard=self.shard, pages=len(dropped),
                reason="forced",
            )
        return dropped

    def drain(self) -> list[int]:
        """Release every trie reference (serve teardown): afterwards the
        allocator's pages_free_at_end bookkeeping sees no cached prefixes."""
        pages = list(self._by_page)
        for page in pages:
            node = self._by_page.get(page)
            if node is not None and node.page in self._by_page:
                del node.parent.children[node.key]
                del self._by_page[node.page]
                self.alloc.free([node.page], self.OWNER)
        self._root.children.clear()
        return pages


class SharedPageDEDError(RuntimeError):
    """Raised when ``KVPageArena.change_codec`` finds a latched
    detected-uncorrectable word on a page with multiple readers: re-encoding
    would seal the corruption as apparently-clean data for every reader at
    once (the correlated-failure regime of DESIGN.md §14). Carries the
    offending pages so the scheduler can evict/preempt and recompute."""

    def __init__(self, pages, codec: str):
        self.pages = tuple(int(p) for p in pages)
        self.codec = str(codec)
        super().__init__(
            f"codec change to {self.codec!r} refused: latched DED on shared "
            f"pages {list(self.pages)}"
        )


# ---------------------------------------------------------------------------
# jit'd arena primitives (module-level so tracing is shared across arenas)
# ---------------------------------------------------------------------------
def _payload_to_planes(payload, codec: str = "secded72"):
    """(N, token_f32) f32 -> lo/hi (N, token_words) uint32 + check plane.

    Check bits come from the codec's ``encode_jnp`` — the same fold the
    Pallas encode kernel runs — called as plain jnp inside the already-jit'd
    commit: the per-token write path is XLA-fused with the extract/scatter
    around it instead of paying a kernel launch per decode step.
    Bit-identical to `kernels/ops.encode` (it is the same function).
    """
    c = codes.get(codec)
    u = jax.lax.bitcast_convert_type(payload.astype(jnp.float32), jnp.uint32)
    lo, hi = u[:, 0::2], u[:, 1::2]
    return lo, hi, c.encode_jnp(lo, hi).astype(jnp.dtype(c.check_dtype))


def _planes_to_payload(lo, hi):
    """Inverse of `_payload_to_planes` (parity is not part of the payload)."""
    u = jnp.stack([lo, hi], axis=-1).reshape(lo.shape[0], -1)
    return jax.lax.bitcast_convert_type(u, jnp.float32)


@jax.jit
def _xor_into(plane, mask):
    return plane ^ mask


# A flat plane addressed by page: one int32 start offset ``page * W`` per
# page and a contiguous W-word window, so no per-word index exists.
_PAGE_WINDOW = jax.lax.GatherDimensionNumbers(
    offset_dims=(1,), collapsed_slice_dims=(), start_index_map=(0,)
)
_PAGE_UPDATE = jax.lax.ScatterDimensionNumbers(
    update_window_dims=(1,), inserted_window_dims=(), scatter_dims_to_operand_dims=(0,)
)


def _gather_pages(plane, page_ids, words_per_page):
    """(n_words,) flat plane, (P,) page ids -> (P, words_per_page) rows."""
    return jax.lax.gather(
        plane,
        (page_ids * words_per_page)[:, None],
        _PAGE_WINDOW,
        slice_sizes=(words_per_page,),
        mode=jax.lax.GatherScatterMode.PROMISE_IN_BOUNDS,
    )


def _scatter_pages(plane, page_ids, rows, words_per_page):
    """Write (P, words_per_page) rows back over their pages of the flat
    plane. Repeated ids (idle lanes' scratch page) carry identical rows, so
    the order in which duplicates land does not matter."""
    return jax.lax.scatter(
        plane,
        (page_ids * words_per_page)[:, None],
        rows,
        _PAGE_UPDATE,
        mode=jax.lax.GatherScatterMode.PROMISE_IN_BOUNDS,
    )


@functools.partial(jax.jit, static_argnames=("words_per_page",))
def _zero_pages(lo, hi, par, page_ids, *, words_per_page):
    """All-zero data and check bits over every page of ``page_ids``."""
    return tuple(
        _scatter_pages(
            p, page_ids, jnp.zeros((page_ids.shape[0], words_per_page), p.dtype), words_per_page
        )
        for p in (lo, hi, par)
    )


@functools.partial(
    jax.jit, static_argnames=("token_words", "words_per_page", "codec")
)
def _commit_tokens(
    lo, hi, par, payload, page_ids, slots, *, token_words, words_per_page,
    codec: str = "secded72",
):
    """Encode token payload rows and scatter them into the arena planes."""
    rlo, rhi, rpar = _payload_to_planes(payload, codec)
    base = page_ids * words_per_page + slots * token_words
    idx = base[:, None] + jnp.arange(token_words, dtype=jnp.int32)[None, :]
    return lo.at[idx].set(rlo), hi.at[idx].set(rhi), par.at[idx].set(rpar)


@functools.partial(
    jax.jit, static_argnames=("words_per_page", "codec", "interpret")
)
def _scrub_rows(lo, hi, par, page_ids, *, words_per_page, codec, interpret):
    """Gather page rows, scrub-on-read, write corrected planes back.

    Each page moves as one contiguous ``words_per_page`` window addressed by
    its start offset (`_gather_pages` / `_scatter_pages`), so the (P, W)
    rows the kernel reads are sliced, not gathered word by word. Returns
    ``(lo, hi, par, olo (P, W), ohi (P, W), counters (P, 8))``."""
    olo, ohi, opar, cnt = paged_gather.gather_scrub_pages(
        *(_gather_pages(p, page_ids, words_per_page) for p in (lo, hi, par)),
        codec=codec,
        interpret=interpret,
    )
    lo, hi, par = (
        _scatter_pages(p, page_ids, o, words_per_page)
        for p, o in ((lo, olo), (hi, ohi), (par, opar))
    )
    return lo, hi, par, olo, ohi, cnt


class KVPageArena:
    """The paged KV store: flat SECDED planes + rail state + fault model.

    ``n_pages`` real pages plus one scratch row (index ``n_pages``) that
    masked/inactive writes are steered to; the scratch row is never read.
    """

    def __init__(
        self,
        geom: KVGeometry,
        profile: PlatformProfile,
        n_pages: int,
        seed: int = 0,
        ecc: bool = True,
        codec: str = "secded72",
        shard: int = 0,
        env=None,
    ):
        self.geom = geom
        # Environment scenario (DESIGN.md §14): the burst shape and the
        # aging-drift clock live here; the flux multiplier is expected to
        # arrive *in the profile* (scenario.EnvironmentProfile.scale_profile
        # — the engine's store-derived kv profile is already scaled), so a
        # store-fed arena never double-scales.
        self.env = scenario.resolve(env)
        burst = self.env.burst if self.env else None
        self._burst = burst if (burst is not None and burst.enabled) else None
        self.profile = profile
        self.n_pages = int(n_pages)
        self.ecc = bool(ecc)
        self.seed = int(seed)
        self.codec_name = str(codec)
        self.codec = codes.get(self.codec_name)
        # Mesh shard identity (DESIGN.md §13): replica ``shard``'s arena is
        # its own silicon, so its interval draws come from a shard-folded
        # key — the same fold the shard_map'd weight path applies via
        # lax.axis_index. Shard 0 keeps the historical stream bit-for-bit.
        self.shard = int(shard)
        w = geom.words_per_page
        self.n_words = self.n_pages * w  # real (non-scratch) words
        total = (self.n_pages + 1) * w
        self._total_words = total
        self.lo = jnp.zeros((total,), jnp.uint32)
        self.hi = jnp.zeros((total,), jnp.uint32)
        # all-zero data has all-zero check bits in every registered linear
        # code: the empty arena is clean
        self.parity = jnp.zeros((total,), jnp.dtype(self.codec.check_dtype))
        self.voltage = float(profile.v_nom)
        self._key = jax.random.PRNGKey(self.seed ^ 0xCACE)
        if self.shard:
            self._key = jax.random.fold_in(self._key, self.shard)
        self._interval = 0
        self.faulted = False  # True once any tick() injected a mask
        self.stats = FaultStats()  # cumulative scrub-on-read telemetry

    @property
    def scratch_page(self) -> int:
        return self.n_pages

    # -- rail ---------------------------------------------------------------
    def set_voltage(self, v: float) -> None:
        self.voltage = float(v)

    def change_codec(self, codec: str, shared_pages=None) -> None:
        """Re-protect the live arena under another registered code (the `kv`
        rail's escalation path): the check plane is re-encoded from the
        current page contents through the new encoder — exactly what a
        hardware re-protection sweep would write, so faults the *old* code
        had not yet corrected are re-sealed as (apparent) clean data. Call
        right after a scrub interval so correctable faults were flushed
        first; the scheduler does.

        ``shared_pages`` (page ids with more than one reader) are scrubbed
        under the *old* code immediately before the switch — a single-owner
        page re-sealing a latent fault hurts one request, but a shared page
        would silently re-protect another reader's corrupted data, so if the
        flush scrub leaves a latched DED on any shared page the change is
        refused with :class:`SharedPageDEDError` (arena untouched) and the
        scheduler must evict/preempt those readers first.
        """
        if codec == self.codec_name:
            return
        ids = np.asarray(
            [] if shared_pages is None else list(shared_pages), np.int32
        )
        if ids.size:
            _, cnt = self.scrub_pages(ids)
            self.stats.accumulate(
                FaultStats.from_counters(
                    cnt.sum(axis=0),
                    words=int(ids.size) * self.geom.words_per_page,
                    shard=self.shard,
                )
            )
            detected = cnt[:, 2]  # COUNTER_FIELDS index of "detected"
            if detected.any():
                raise SharedPageDEDError(ids[detected > 0].tolist(), codec)
        self.codec_name = str(codec)
        self.codec = codes.get(self.codec_name)
        self.parity = kops.encode(self.lo, self.hi, codec=self.codec_name)

    def tick(self) -> None:
        """Inject one interval's faults at the current rail voltage.

        Fresh draw per interval (key folded with the interval counter): a
        live memory keeps accumulating faults while undervolted, it does not
        re-materialise them per voltage like the read-only weight arena.
        Inside the guardband the rate is exactly 0 and this is a no-op.
        With an environment set, the interval counter doubles as the aging
        clock — this chip's rate drifts by its deterministic per-shard
        multiplier as the soak progresses — and the masks carry the
        environment's correlated burst shape.
        """
        self._interval += 1
        rate = self.profile.fault_rate(self.voltage)
        if rate <= 0.0:
            return
        rate *= scenario.aging_multiplier(
            self.shard, self._interval, self.env, self.seed
        )
        key = jax.random.fold_in(self._key, self._interval)
        self.faulted = True
        mlo, mhi, mpar = obs_profile.call(
            "kv.inject_masks",
            _device_chunk_masks_jit(),
            key, self._total_words, jnp.float32(rate),
            jnp.float32(self.profile.row_sigma), n_check=self.codec.n_check,
            burst=self._burst,
        )
        self.lo = _xor_into(self.lo, mlo)
        self.hi = _xor_into(self.hi, mhi)
        self.parity = _xor_into(self.parity, mpar)
        if not self.ecc:
            # No-ECC baseline: check bits track the faulty data, the read-
            # path decoder becomes a pass-through and faults flow into
            # attention.
            self.parity = kops.encode(self.lo, self.hi, codec=self.codec_name)

    # -- data path ----------------------------------------------------------
    def zero_pages(self, page_ids) -> None:
        """Clear freshly allocated pages (all-zero data + parity is a valid
        clean codeword). Without this, a page re-allocated to a new request
        would expose the previous owner's stale — possibly faulty — words to
        the new owner's scrub, polluting its DED accounting and the canary."""
        ids = jnp.asarray(page_ids, jnp.int32).reshape(-1)
        if ids.size == 0:
            return
        self.lo, self.hi, self.parity = _zero_pages(
            self.lo, self.hi, self.parity, ids,
            words_per_page=self.geom.words_per_page,
        )

    def commit_tokens(self, payload, page_ids, slots) -> None:
        """Write one token per row: payload (N, token_f32) f32, page_ids and
        slots (N,) int32 (slot = position within the page). Rows steered to
        the scratch page are don't-cares (inactive lanes)."""
        with obs_profile.span("kv.commit_tokens", rows=payload.shape[0]):
            self.lo, self.hi, self.parity = _commit_tokens(
                self.lo,
                self.hi,
                self.parity,
                payload,
                jnp.asarray(page_ids, jnp.int32),
                jnp.asarray(slots, jnp.int32),
                token_words=self.geom.token_words,
                words_per_page=self.geom.words_per_page,
                codec=self.codec_name,
            )

    def scrub_pages_async(self, page_ids):
        """Asynchronously dispatched scrub-on-read of ``page_ids`` (any
        shape, flattened): commits the corrected planes (scrub write-back)
        and returns (payload (P, page_tokens, token_f32) f32 device array,
        counters (P, 8) int32 DEVICE array) with no host sync — the caller
        defers the counter harvest (``np.asarray``) past whatever decode
        work it wants the scrub to overlap (DESIGN.md §18). Its span counts
        the table's entries (``pages``) and those that are not the scratch
        page (``live_pages``): the scrub gathers every entry alike."""
        ids = jnp.asarray(page_ids, jnp.int32).reshape(-1)
        with obs_profile.span(
            "kv.paged_gather_scrub",
            pages=ids.shape[0],
            live_pages=lambda: int(
                np.count_nonzero(np.asarray(page_ids) != self.scratch_page)
            ),
        ):
            self.lo, self.hi, self.parity, olo, ohi, cnt = _scrub_rows(
                self.lo,
                self.hi,
                self.parity,
                ids,
                words_per_page=self.geom.words_per_page,
                codec=self.codec_name,
                interpret=kops.use_interpret(),
            )
        payload = _planes_to_payload(
            olo.reshape(-1, self.geom.token_words),
            ohi.reshape(-1, self.geom.token_words),
        ).reshape(ids.shape[0], self.geom.page_tokens, self.geom.token_f32)
        return payload, cnt

    def scrub_pages(self, page_ids):
        """Scrub-on-read of ``page_ids`` (any shape, flattened): returns
        (payload (P, page_tokens, token_f32) f32, counters (P, 8) np.int32)
        and commits the corrected planes (scrub write-back)."""
        payload, cnt = self.scrub_pages_async(page_ids)
        return payload, np.asarray(cnt)
