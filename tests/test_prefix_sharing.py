"""Prefix-sharing copy-on-write KV pages + speculative decode (DESIGN.md §16).

The invariants driven here are the ones the refcounted allocator and the
prefix trie assert internally:

  * no page is ever freed (recycled) while it still has readers,
  * copy-on-write never mutates a shared page — codec escalation *refuses*
    to re-encode a shared page with a latched DED,
  * trie lookup returns exactly the longest cached full-page prefix,
  * preemption-recompute under sharing reproduces the private-serve tokens,

plus the two end-to-end acceptance properties: a shared-prefix serve is
bit-identical to the private serve at nominal voltage, and speculative
decode emits exactly the greedy rollout no matter how bad the draft is.
"""

import dataclasses

import numpy as np
import pytest

import jax

from _hypothesis_compat import given, settings, st
from repro.configs import get_smoke_config
from repro.core import voltage as vmod
from repro.core.kvpages import (
    KVGeometry,
    KVPageArena,
    PageAllocator,
    PrefixTrie,
    SharedPageDEDError,
    dedup_page_table,
)
from repro.models import lm
from repro.serving import (
    CanaryConfig,
    FaultModelConfig,
    ProtectionConfig,
    RailsConfig,
    ReliabilityConfig,
    ReliabilityConfigError,
    ServingEngine,
)


# ---------------------------------------------------------------------------
# refcounted allocator
# ---------------------------------------------------------------------------


def test_allocator_share_free_refcounts():
    alloc = PageAllocator(3)
    p = alloc.alloc("a")
    alloc.share(p, "b")
    assert alloc.refcount(p) == 2 and alloc.is_shared(p)
    assert alloc.shared_pages() == [p]
    assert alloc.owner_of(p) == frozenset({"a", "b"})
    alloc.free([p], "a")
    # surviving reader keeps the page live: not dirty, not recyclable
    assert alloc.refcount(p) == 1 and alloc.dirty_pages == 0
    assert alloc.owner_of(p) == "b"
    alloc.free([p], "b")
    assert alloc.dirty_pages == 1 and alloc.refcount(p) == 0
    with pytest.raises(AssertionError):
        alloc.share(p, "c")  # share of an unallocated page
    q = alloc.alloc("a")
    alloc.share(q, "b")
    with pytest.raises(AssertionError):
        alloc.share(q, "b")  # double reference by the same owner
    with pytest.raises(AssertionError):
        alloc.free([q], "c")  # foreign free


@settings(max_examples=40, deadline=None)
@given(seed=st.integers(0, 1000))
def test_allocator_no_page_recycled_with_readers(seed):
    """Model check vs a reference refcount map: under random alloc / share /
    free traffic a page reaches the dirty list exactly when its last
    reference drops, and never before."""
    rng = np.random.default_rng(seed)
    alloc = PageAllocator(8)
    refs: dict[int, set] = {}
    owners = ["r%d" % i for i in range(5)]
    for _ in range(120):
        op = rng.integers(0, 3)
        if op == 0:
            who = owners[rng.integers(0, len(owners))]
            page = alloc.alloc(who)
            if page is None:
                alloc.recycle()
                continue
            assert page not in refs, "allocator handed out a live page"
            refs[page] = {who}
        elif op == 1 and refs:
            page = list(refs)[rng.integers(0, len(refs))]
            candidates = [o for o in owners if o not in refs[page]]
            if not candidates:
                continue
            who = candidates[rng.integers(0, len(candidates))]
            alloc.share(page, who)
            refs[page].add(who)
        elif op == 2 and refs:
            page = list(refs)[rng.integers(0, len(refs))]
            who = list(refs[page])[rng.integers(0, len(refs[page]))]
            before_dirty = alloc.dirty_pages
            alloc.free([page], who)
            refs[page].discard(who)
            if refs[page]:
                # freed with surviving readers: must NOT have gone dirty
                assert alloc.dirty_pages == before_dirty
                assert alloc.refcount(page) == len(refs[page])
            else:
                assert alloc.dirty_pages == before_dirty + 1
                del refs[page]
    for page, expect in refs.items():
        assert alloc.refcount(page) == len(expect)
    assert alloc.used_pages == len(refs)


# ---------------------------------------------------------------------------
# prefix trie
# ---------------------------------------------------------------------------


def _trie(pt=4, n_pages=16):
    alloc = PageAllocator(n_pages)
    return PrefixTrie(alloc, pt), alloc


@settings(max_examples=40, deadline=None)
@given(
    seed=st.integers(0, 500),
    pt=st.sampled_from([2, 4]),
    n_common=st.integers(0, 12),
    n_tail=st.integers(1, 8),
)
def test_trie_lookup_is_longest_common_fullpage_prefix(seed, pt, n_common, n_tail):
    """Insert one sequence, look up a probe sharing exactly ``n_common``
    leading tokens: the hit must cover min(n_common, len(probe)-1) // pt
    pages — the longest *full-page* common prefix, never more, capped so at
    least one probe token is left to prefill."""
    rng = np.random.default_rng(seed)
    trie, alloc = _trie(pt, n_pages=32)
    base = rng.integers(0, 97, size=6 * pt).astype(np.int32)
    pages = [alloc.alloc("writer") for _ in range(6)]
    trie.insert(base, pages)
    probe = np.concatenate(
        [base[:n_common], 100 + rng.integers(0, 50, size=n_tail).astype(np.int32)]
    )
    hit = trie.lookup(probe)
    want = min(n_common, len(probe) - 1) // pt if len(probe) >= 2 else 0
    assert hit == pages[:want]
    # every hit page gained no reference from lookup alone
    for p in pages:
        assert alloc.refcount(p) == 2  # writer + trie


def test_trie_insert_shares_and_drain_releases():
    trie, alloc = _trie(pt=2, n_pages=8)
    toks = np.arange(6, dtype=np.int32)
    pages = [alloc.alloc("w") for _ in range(3)]
    trie.insert(toks, pages)
    assert len(trie) == 3 and trie.pages() == sorted(pages)
    for p in pages:
        assert alloc.is_shared(p)
    # the writer retires; the trie reference keeps every page live
    alloc.free(pages, "w")
    assert alloc.dirty_pages == 0
    # re-inserting the same prefix only stamps (no double reference)
    trie.insert(toks, pages)
    assert all(alloc.refcount(p) == 1 for p in pages)
    assert trie.drain() == pages
    assert alloc.dirty_pages == 3 and len(trie) == 0


def test_trie_evict_lru_skips_shared_leaves():
    trie, alloc = _trie(pt=2, n_pages=8)
    a = np.asarray([1, 2, 3, 4], np.int32)
    b = np.asarray([1, 2, 9, 9], np.int32)
    pa = [alloc.alloc("wa") for _ in range(2)]
    trie.insert(a, pa)
    pb_tail = alloc.alloc("wb")
    trie.insert(b, [pa[0], pb_tail])
    alloc.free([pa[1]], "wa")  # leaf [3,4] now sole-referenced by the trie
    # leaf [9,9] still has its writer attached: eviction must skip it, and
    # the shared interior node [1,2] is not a leaf at all
    freed = trie.evict_lru(3)
    assert freed == [pa[1]]
    assert sorted(trie.pages()) == sorted([pa[0], pb_tail])


# ---------------------------------------------------------------------------
# copy-on-write: codec escalation refuses shared pages with latched DED
# ---------------------------------------------------------------------------


def _committed_arena(page_tokens=2, n_pages=3):
    cfg = get_smoke_config("qwen3-0.6b")
    geom = KVGeometry.from_config(cfg, page_tokens)
    arena = KVPageArena(geom, vmod.PLATFORMS["vc707"], n_pages)
    rng = np.random.default_rng(0)
    n_tok = geom.page_tokens * n_pages
    payload = rng.standard_normal((n_tok, geom.token_f32)).astype(np.float32)
    pages = np.repeat(np.arange(n_pages), geom.page_tokens)
    slots = np.tile(np.arange(geom.page_tokens), n_pages)
    arena.commit_tokens(payload, pages, slots)
    return arena, geom


def test_change_codec_refuses_shared_page_with_latched_ded():
    """Regression for the correlated-failure hazard: re-encoding a shared
    page with an uncorrectable word would seal the corruption as clean data
    for every reader. The change must refuse (arena untouched), name the
    offending pages, and succeed once the shared set shrinks."""
    arena, geom = _committed_arena()
    w = geom.words_per_page
    # double-bit (uncorrectable) fault in page 1; page 0 stays clean
    arena.hi = arena.hi.at[w + 3].set(arena.hi[w + 3] ^ np.uint32(0b11))
    with pytest.raises(SharedPageDEDError) as ei:
        arena.change_codec("ileave88", shared_pages=[0, 1])
    assert ei.value.pages == (1,) and ei.value.codec == "ileave88"
    assert arena.codec_name == "secded72"  # untouched
    # the DED is still latched (visible), not sealed
    _, cnt = arena.scrub_pages([1])
    assert cnt[0, 2] == 1
    # once page 1 is no longer shared (evicted + readers preempted), the
    # sweep proceeds: the clean shared page re-encodes fine
    arena.change_codec("ileave88", shared_pages=[0])
    assert arena.codec_name == "ileave88"
    _, cnt = arena.scrub_pages([0])
    assert cnt[0, 1] == 0 and cnt[0, 2] == 0


def test_change_codec_clean_shared_pages_pass():
    arena, _ = _committed_arena()
    arena.change_codec("dected79", shared_pages=[0, 1, 2])
    assert arena.codec_name == "dected79"
    _, cnt = arena.scrub_pages(np.arange(arena.n_pages))
    assert cnt[:, 1].sum() == 0 and cnt[:, 2].sum() == 0


# ---------------------------------------------------------------------------
# dedup_page_table
# ---------------------------------------------------------------------------


@settings(max_examples=40, deadline=None)
@given(seed=st.integers(0, 500), m=st.integers(1, 5), k=st.integers(1, 6))
def test_dedup_page_table_roundtrip(seed, m, k):
    rng = np.random.default_rng(seed)
    scratch = 64
    table = rng.integers(0, 12, size=(m, k)).astype(np.int32)
    table[rng.random(table.shape) < 0.3] = scratch
    upad, rows, n_u = dedup_page_table(table, scratch)
    # every non-scratch entry maps back to itself; scratch maps to the pad
    got = upad[rows.reshape(-1)].reshape(table.shape)
    np.testing.assert_array_equal(got, table)
    assert n_u == len(np.unique(table[table != scratch]))
    assert (upad[n_u:] == scratch).all()
    # pow2-padded, and a scratch slot exists whenever the table needs one
    assert len(upad) & (len(upad) - 1) == 0
    if (table == scratch).any():
        assert (upad[rows.reshape(-1)[table.reshape(-1) == scratch]] == scratch).all()


# ---------------------------------------------------------------------------
# end-to-end: shared serve bit-identity, preemption-recompute, speculative
# ---------------------------------------------------------------------------

_STATE = {}


def _shared_state():
    if not _STATE:
        cfg = get_smoke_config("qwen3-0.6b")
        params = lm.init_params(cfg, jax.random.PRNGKey(0))
        rng = np.random.default_rng(0)
        prefix = rng.integers(0, cfg.vocab, size=(16,)).astype(np.int32)
        reqs = [
            (
                np.concatenate(
                    [prefix, rng.integers(0, cfg.vocab, size=(3,)).astype(np.int32)]
                ),
                6,
            )
            for _ in range(6)
        ]
        eng = ServingEngine(cfg, params, rel=None, max_len=48)
        _STATE["v"] = (cfg, params, reqs, eng)
    return _STATE["v"]


def test_shared_serve_bit_identical_to_private_at_nominal():
    """The §16 acceptance property: with a shared-heavy stream the trie path
    must change *nothing* observable at nominal voltage — same tokens, same
    (zero) fault counters, same kv rail trajectory — while actually hitting
    the trie and returning every page to the pool."""
    cfg, params, reqs, eng = _shared_state()
    private = eng.serve(reqs, n_lanes=2, scrub_interval=2)
    shared = eng.serve(reqs, n_lanes=2, scrub_interval=2, share_prefix=True)
    assert sorted(shared.outputs) == sorted(private.outputs)
    for rid in private.outputs:
        np.testing.assert_array_equal(shared.outputs[rid], private.outputs[rid])
    # the trie was actually exercised: 2 full pages (16 tokens) per later
    # admission; the first 2 lanes prefill privately
    assert shared.prefix_hit_tokens == 16 * (len(reqs) - 2)
    assert private.prefix_hit_tokens == 0
    # nominal voltage: scrubs run and observe zero faults on both paths
    assert shared.kv_stats.corrected == 0 and shared.kv_stats.detected == 0
    assert private.kv_stats.corrected == 0 and private.kv_stats.detected == 0
    assert shared.kv_stats.words > 0
    assert shared.kv_voltages == private.kv_voltages
    # teardown drained the trie: no page leaked behind a cached prefix
    assert shared.pages_free_at_end == shared.arena.n_pages


@settings(max_examples=6, deadline=None)
@given(n_pages_extra=st.integers(0, 3), seed=st.integers(0, 3))
def test_preemption_recompute_under_sharing(n_pages_extra, seed):
    """Page pressure with the trie on: cached prefixes yield (LRU eviction),
    the youngest reader preempts and recomputes — and the emitted tokens
    still match the roomy private serve exactly."""
    cfg, params, base_reqs, eng = _shared_state()
    rng = np.random.default_rng(seed)
    reqs = [base_reqs[i] for i in rng.permutation(len(base_reqs))]
    pt = 8
    geom = KVGeometry.from_config(cfg, pt)
    longest = max(geom.pages_for(len(p) + n) for p, n in reqs)
    tight = eng.serve(
        reqs,
        n_lanes=2,
        page_tokens=pt,
        n_pages=longest + n_pages_extra,
        scrub_interval=2,
        share_prefix=True,
    )
    roomy = eng.serve(reqs, n_lanes=2, page_tokens=pt, scrub_interval=2)
    for rid, toks in roomy.outputs.items():
        np.testing.assert_array_equal(tight.outputs[rid], toks)
    assert tight.pages_free_at_end == tight.arena.n_pages


def test_speculative_emits_exactly_greedy_rollout():
    """Accepted-prefix property: with the target as its own draft every
    block fully accepts; with a garbage draft almost nothing does — either
    way the emitted stream is exactly the plain greedy serve."""
    cfg, params, reqs, eng = _shared_state()
    plain = eng.serve(reqs, n_lanes=2, scrub_interval=2)
    good = eng.serve(
        reqs, n_lanes=2, scrub_interval=2,
        speculative=4, draft_params=params, draft_cfg=cfg,
    )
    bad_params = lm.init_params(cfg, jax.random.PRNGKey(7))
    bad = eng.serve(
        reqs, n_lanes=2, scrub_interval=2,
        speculative=4, draft_params=bad_params, draft_cfg=cfg,
    )
    for rid in plain.outputs:
        np.testing.assert_array_equal(good.outputs[rid], plain.outputs[rid])
        np.testing.assert_array_equal(bad.outputs[rid], plain.outputs[rid])
    assert good.spec_dispatches > 0 and bad.spec_dispatches > 0
    # a perfect draft accepts more per dispatch than a garbage one, and
    # strictly more than the 1 token/dispatch a rejected block falls back to
    assert good.spec_emitted / good.spec_dispatches > 2.0
    assert (
        good.spec_emitted / good.spec_dispatches
        >= bad.spec_emitted / bad.spec_dispatches
    )


def test_speculative_composes_with_prefix_sharing():
    cfg, params, reqs, eng = _shared_state()
    plain = eng.serve(reqs, n_lanes=2, scrub_interval=2)
    spec = eng.serve(
        reqs, n_lanes=2, scrub_interval=2, share_prefix=True,
        speculative=3, draft_params=params, draft_cfg=cfg,
    )
    for rid in plain.outputs:
        np.testing.assert_array_equal(spec.outputs[rid], plain.outputs[rid])
    assert spec.prefix_hit_tokens > 0 and spec.spec_dispatches > 0
    assert spec.pages_free_at_end == spec.arena.n_pages


# ---------------------------------------------------------------------------
# ReliabilityConfig: one grouped surface
# ---------------------------------------------------------------------------

# the flat keywords the grouped sub-configs replaced, and two dead options
_REMOVED_KEYWORDS = [
    (ReliabilityConfig, name)
    for name in (
        "mask_source", "batched", "environment", "drift",
        "multi_rail", "rail_policy", "rail_spread", "controller_step_v",
        "controller_start_v", "adaptive_rails",
        "codecs", "escalation", "protect", "protect_embed",
        "canary_prompts", "canary_tokens", "divergence_slo", "paranoid",
        "fuse",
    )
] + [(FaultModelConfig, "batched"), (ProtectionConfig, "protect")]


def test_flat_keywords_raise_type_error():
    for cls, name in _REMOVED_KEYWORDS:
        with pytest.raises(TypeError, match=name):
            cls(**{name: True})
        assert not hasattr(cls(), name), (cls.__name__, name)


def test_subconfigs_default_to_instances():
    # never None: engine code reads the groups unguarded
    rel = ReliabilityConfig()
    assert rel.fault_model == FaultModelConfig()
    assert rel.rails == RailsConfig()
    assert rel.protection == ProtectionConfig()
    assert rel.canary == CanaryConfig()
    assert ReliabilityConfig() == rel
    assert not rel.embed_protected and rel.escalation_policy is None
    assert [f.name for f in dataclasses.fields(rel)] == [
        "platform", "ecc", "voltage", "mode", "seed",
        "fault_model", "rails", "protection", "canary",
    ]
    assert [f.name for f in dataclasses.fields(FaultModelConfig)] == [
        "mask_source", "environment", "drift",
    ]
    assert [f.name for f in dataclasses.fields(ProtectionConfig)] == [
        "codecs", "escalation", "embed",
    ]


def test_replace_roundtrips_through_subconfigs():
    rel = ReliabilityConfig(
        mode="inline", rails=RailsConfig(multi_rail=True), seed=3
    )
    moved = dataclasses.replace(rel, rails=dataclasses.replace(rel.rails, start_v=0.6))
    assert moved.rails.start_v == 0.6 and moved.rails.multi_rail
    assert moved != rel
    # exactly that one field differs
    assert dataclasses.replace(moved.rails, start_v=None) == rel.rails
    for f in dataclasses.fields(rel):
        if f.name != "rails":
            assert getattr(moved, f.name) == getattr(rel, f.name), f.name
    back = dataclasses.replace(moved, rails=dataclasses.replace(moved.rails, start_v=None))
    assert back == rel


def test_validate_raises_typed_errors():
    with pytest.raises(ReliabilityConfigError, match="mode"):
        ReliabilityConfig(mode="nope").validate()
    with pytest.raises(ReliabilityConfigError, match="platform"):
        ReliabilityConfig(platform="nope").validate()
    with pytest.raises(ReliabilityConfigError, match="rail"):
        ReliabilityConfig(
            rails=RailsConfig(policy="sideways"), mode="inline"
        ).validate()
    with pytest.raises(ReliabilityConfigError):
        # per-domain codec dict needs the multi-rail domain partition
        ReliabilityConfig(
            mode="inline", protection=ProtectionConfig(codecs={"mlp": "dected79"})
        ).validate()
    # the typed error IS both historical exception types
    assert issubclass(ReliabilityConfigError, ValueError)
    assert issubclass(ReliabilityConfigError, AssertionError)
    # a valid config returns itself for chaining
    ok = ReliabilityConfig(mode="inline", rails=RailsConfig(multi_rail=True))
    assert ok.validate() is ok


@pytest.mark.parametrize(
    "protection",
    [
        ProtectionConfig(codecs={"kv": "dected79"}),
        ProtectionConfig(escalation=("secded72", "dected79")),
    ],
    ids=["codecs", "escalation"],
)
def test_validate_rejects_lut_codec_on_compiled_lane(protection, monkeypatch):
    """The dense-LUT DEC-TED decoder has no Mosaic lowering: on the compiled
    lane the config is refused up front (naming the kernel limit) instead of
    crashing at the first escalation mid-serve. The interpret lane keeps
    accepting it."""
    from repro.kernels import backend

    rel = ReliabilityConfig(
        mode="inline", rails=RailsConfig(multi_rail=True), protection=protection
    )
    assert rel.validate() is rel  # this host's interpret lane
    monkeypatch.setattr(backend, "resolve", lambda: "compiled")
    with pytest.raises(ReliabilityConfigError, match="dense-LUT gather"):
        rel.validate()
    # gather-free ladders stay valid on the compiled lane
    ok = ReliabilityConfig(
        mode="inline",
        rails=RailsConfig(multi_rail=True),
        protection=ProtectionConfig(escalation=("parity65", "secded72", "ileave88")),
    )
    assert ok.validate() is ok
