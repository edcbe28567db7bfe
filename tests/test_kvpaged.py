"""Paged SECDED KV cache + continuous batching (DESIGN.md §11).

Pins down the tentpole contracts:
  * paged serve at nominal voltage is bit-identical to the dense decode loop
    on the same batch composition, with the scrub-on-read path exercised
    every step;
  * per-request outputs are independent of lane count, page pressure, and
    preemption (greedy decode is deterministic; recompute preemption must
    reproduce the same tokens) — hypothesis-driven;
  * the page allocator never double-books and never leaks;
  * per-page DED counters account injected single/double-bit faults exactly
    and feed the `kv` rail so it walks independently of the weight rails.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from _hypothesis_compat import given, settings, st
from repro.configs import get_smoke_config
from repro.configs.shapes import supports_paged_kv
from repro.core import voltage as vmod
from repro.core.kvpages import KVGeometry, KVPageArena, PageAllocator
from repro.models import lm
from repro.serving.engine import RailsConfig, ReliabilityConfig, ServingEngine


@pytest.fixture(scope="module")
def setup():
    cfg = get_smoke_config("qwen3-0.6b")
    params = lm.init_params(cfg, jax.random.PRNGKey(0))
    prompts = np.random.default_rng(0).integers(0, cfg.vocab, (4, 8)).astype(np.int32)
    return cfg, params, prompts


@pytest.fixture(scope="module")
def engine(setup):
    cfg, params, _ = setup
    return ServingEngine(cfg, params, rel=None, max_len=48)


def test_supports_paged_kv_applicability():
    assert supports_paged_kv(get_smoke_config("qwen3-0.6b"))
    assert not supports_paged_kv(get_smoke_config("rwkv6-3b"))
    assert not supports_paged_kv(get_smoke_config("mixtral-8x22b"))  # SWA


def test_paged_bit_identical_to_dense_at_nominal(setup, engine):
    """Same batch composition, scrub-on-read every step: tokens must match
    the dense decode loop bit-for-bit."""
    cfg, params, prompts = setup
    ref = engine.generate(prompts, n_tokens=12)
    rep = engine.serve(
        [(prompts[i], 12) for i in range(4)], n_lanes=4, scrub_interval=1
    )
    out = np.stack([rep.outputs[i] for i in range(4)])
    np.testing.assert_array_equal(ref, out)
    # every word that crossed the read path decoded clean
    s = rep.kv_stats
    assert s.words > 0 and s.clean == s.words
    assert s.corrected == 0 and s.detected == 0


def test_paged_scrub_cadence_is_bit_stable(setup, engine):
    """The page round-trip is the identity at nominal: any scrub cadence
    (including none) and any block size produce identical tokens."""
    cfg, params, prompts = setup
    reqs = [(prompts[i][: 4 + i], 4 + 3 * i) for i in range(4)]
    ref = engine.serve(reqs, n_lanes=2, scrub_interval=0).outputs
    for scrub, block in ((1, 1), (3, 4), (7, 16)):
        out = engine.serve(
            reqs, n_lanes=2, scrub_interval=scrub, max_block=block
        ).outputs
        for rid, toks in ref.items():
            np.testing.assert_array_equal(toks, out[rid], err_msg=f"{scrub}/{block}")


def test_paged_matches_dense_single_request(setup, engine):
    """Each request's stream output equals its own dense batch-of-1 rollout,
    even with mixed lengths and lane reuse."""
    cfg, params, prompts = setup
    reqs = [(prompts[i][: 4 + 2 * i], 5 + 3 * i) for i in range(4)]
    rep = engine.serve(reqs, n_lanes=2, page_tokens=4, n_pages=8, scrub_interval=2)
    assert rep.preemptions >= 1  # tight arena: page pressure actually bit
    for i, (p, n) in enumerate(reqs):
        ref = engine.generate(p[None], n_tokens=n)[0]
        np.testing.assert_array_equal(ref, rep.outputs[i])


def test_refresh_covers_payload_lanes_only(setup):
    """The lane cache holds the decode batch padded to DECODE_ROWS; an
    interval refresh writes the scrubbed payload into the real lanes'
    valid positions and leaves the padding rows as they are."""
    from repro.serving import steps
    from repro.serving.scheduler import decode_rows

    cfg, _, _ = setup
    geom = KVGeometry.from_config(cfg)
    rows = decode_rows(3)
    assert rows == 8 and decode_rows(8) == 8 and decode_rows(9) == 16
    rng = np.random.default_rng(0)
    cache = jax.tree.map(
        lambda c: jnp.asarray(rng.standard_normal(c.shape), c.dtype),
        lm.init_cache(cfg, rows, 16),
    )
    payload = jnp.asarray(rng.standard_normal((3, 16, geom.token_f32)), jnp.float32)
    n_tok = jnp.asarray([16, 5, 0], jnp.int32)
    out = steps._refresh_cache(cache, payload, n_tok, geom=geom)
    for c, o in zip(jax.tree.leaves(cache), jax.tree.leaves(out)):
        np.testing.assert_array_equal(np.asarray(o[:, 2:]), np.asarray(c[:, 2:]))
        np.testing.assert_array_equal(np.asarray(o[:, 1, 5:]), np.asarray(c[:, 1, 5:]))
    dt = jax.tree.leaves(cache)[0].dtype
    want = np.asarray(payload.astype(dt).astype(jnp.float32))
    got = np.asarray(steps._extract_span(out, start=0, stop=16, geom=geom))
    np.testing.assert_array_equal(got[0], want[0])
    np.testing.assert_array_equal(got[1, :5], want[1, :5])


@settings(max_examples=12, deadline=None)
@given(
    n_lanes=st.integers(1, 4),
    n_pages=st.integers(4, 24),
    page_tokens=st.sampled_from([2, 4, 8]),
    seed=st.integers(0, 6),
)
def test_scheduler_invariants_under_pressure(n_lanes, n_pages, page_tokens, seed):
    """Admission/eviction/preemption invariants: every request completes with
    exactly its budget, outputs are independent of page pressure, and the
    allocator ends the run with every page back in the free pool."""
    cfg, params, prompts, engine = _shared_state()
    rng = np.random.default_rng(seed)
    reqs = [
        (
            prompts[rng.integers(0, 4)][: int(rng.integers(3, 9))],
            int(rng.integers(1, 10)),
        )
        for _ in range(int(rng.integers(2, 7)))
    ]
    longest = max(-(-(len(p) + n) // page_tokens) for p, n in reqs)
    n_pages = max(n_pages, longest)  # below this the stream cannot be served
    rep = engine.serve(
        reqs,
        n_lanes=n_lanes,
        page_tokens=page_tokens,
        n_pages=n_pages,
        scrub_interval=2,
        max_block=4,
    )
    assert sorted(rep.outputs) == list(range(len(reqs)))
    for i, (p, n) in enumerate(reqs):
        assert len(rep.outputs[i]) == n
    # page accounting: every page back in the pool (nothing leaked; the
    # allocator's own asserts catch double-alloc/foreign-free during the run)
    assert rep.pages_free_at_end == rep.arena.n_pages
    # outputs independent of pressure: a roomy arena gives identical tokens
    roomy = engine.serve(
        reqs, n_lanes=n_lanes, page_tokens=page_tokens, scrub_interval=2,
        max_block=4,
    )
    for rid, toks in roomy.outputs.items():
        np.testing.assert_array_equal(toks, rep.outputs[rid])


_STATE = {}


def _shared_state():
    """Module-scope state for the hypothesis test (fixtures can't be given)."""
    if not _STATE:
        cfg = get_smoke_config("qwen3-0.6b")
        params = lm.init_params(cfg, jax.random.PRNGKey(0))
        prompts = (
            np.random.default_rng(0).integers(0, cfg.vocab, (4, 8)).astype(np.int32)
        )
        _STATE["v"] = (cfg, params, prompts, ServingEngine(cfg, params, rel=None, max_len=48))
    return _STATE["v"]


def test_allocator_invariants():
    alloc = PageAllocator(4)
    a = alloc.alloc("a")
    b = alloc.alloc("b")
    assert a != b and alloc.used_pages == 2
    with pytest.raises(AssertionError):
        alloc.free([a], "b")  # foreign free
    alloc.free([a], "a")
    assert alloc.dirty_pages == 1 and alloc.free_pages == 3
    # freed pages are not reusable until recycled (they need a zero-wipe)
    got = {alloc.alloc("c") for _ in range(2)}
    assert alloc.alloc("d") is None and a not in got
    assert alloc.recycle() == [a]
    assert alloc.alloc("d") == a


def _mk_arena(page_tokens=2, n_pages=3, codec="secded72"):
    cfg = get_smoke_config("qwen3-0.6b")
    geom = KVGeometry.from_config(cfg, page_tokens)
    return KVPageArena(geom, vmod.PLATFORMS["vc707"], n_pages, codec=codec), geom


def test_per_page_counters_single_and_double_bit():
    """Scrub-on-read accounting: a 1-bit fault corrects (and the payload
    round-trips clean), a 2-bit fault raises DED in exactly its page's
    counter row, and the corrected planes are written back (second read is
    clean)."""
    arena, geom = _mk_arena()
    rng = np.random.default_rng(1)
    n_tok = geom.page_tokens * arena.n_pages
    payload = jnp.asarray(
        rng.standard_normal((n_tok, geom.token_f32)).astype(np.float32)
    )
    pages = np.repeat(np.arange(arena.n_pages), geom.page_tokens)
    slots = np.tile(np.arange(geom.page_tokens), arena.n_pages)
    arena.commit_tokens(payload, pages, slots)

    w = geom.words_per_page
    # single-bit fault in page 0, double-bit fault in one word of page 2
    arena.lo = arena.lo.at[3].set(arena.lo[3] ^ np.uint32(1 << 7))
    arena.hi = arena.hi.at[2 * w + 5].set(arena.hi[2 * w + 5] ^ np.uint32(0b101))

    out, cnt = arena.scrub_pages(np.arange(arena.n_pages))
    assert cnt.shape == (arena.n_pages, 8)
    assert cnt[0, 1] == 1 and cnt[0, 2] == 0  # corrected, in page 0 only
    assert cnt[2, 2] == 1 and cnt[2, 1] == 0  # detected, in page 2 only
    assert cnt[1, 1] == 0 and cnt[1, 2] == 0
    assert (cnt[:, 0] + cnt[:, 1] + cnt[:, 2] == w).all()
    # corrected payload round-trips the committed values everywhere except
    # the uncorrectable word: word 5 of page 2 is token 0's f32 lane 11
    # (codeword j holds f32 lanes 2j / 2j+1; both flips hit the hi lane)
    got = np.asarray(out).reshape(n_tok, geom.token_f32)
    ref = np.asarray(payload)
    bad = np.flatnonzero(got != ref)
    assert set(bad) == {(2 * geom.page_tokens) * geom.token_f32 + 11}
    # scrub write-back: single-bit fault is gone, DED stays latched
    _, cnt2 = arena.scrub_pages(np.arange(arena.n_pages))
    assert cnt2[0, 1] == 0 and cnt2[0, 0] == w
    assert cnt2[2, 2] == 1


def test_fresh_page_wipe_clears_accumulated_free_page_faults():
    """tick() faults the whole arena, allocated or not: a page that sat free
    through many undervolt intervals accumulates faults (possibly latched
    DED) that must never be attributed to its next owner. The allocation-
    time zero-wipe (scheduler.drain_fresh_pages) guarantees a wiped page
    scrubs fully clean."""
    arena, geom = _mk_arena(page_tokens=2, n_pages=3)
    arena.set_voltage(0.54)  # crash-adjacent: ~2% of words fault per interval
    for _ in range(10):
        arena.tick()
    assert arena.faulted
    # without the wipe, the never-written page is not clean (the repro)
    _, cnt = arena.scrub_pages([1])
    assert cnt[0, 1] + cnt[0, 2] > 0
    arena.zero_pages([2])
    _, cnt2 = arena.scrub_pages([2])
    assert cnt2[0, 0] == geom.words_per_page
    assert cnt2[0, 1] == 0 and cnt2[0, 2] == 0


def _word_index_scrub(lo, hi, par, page_ids, *, words_per_page, codec):
    """The scrub as it addressed pages through one int32 index per word: the
    reference the page-window form must match bit for bit."""
    from repro.kernels import ops, paged_gather

    idx = page_ids[:, None] * words_per_page + jnp.arange(words_per_page, dtype=jnp.int32)
    olo, ohi, opar, cnt = paged_gather.gather_scrub_pages(
        lo[idx], hi[idx], par[idx], codec=codec, interpret=ops.use_interpret()
    )
    return lo.at[idx].set(olo), hi.at[idx].set(ohi), par.at[idx].set(opar), olo, ohi, cnt


@pytest.mark.parametrize("table", ["unsorted_scratch", "dedup"])
@pytest.mark.parametrize("codec", ["secded72", "dected79", "ileave88"])
def test_page_window_scrub_matches_word_index_form(codec, table):
    """`_scrub_rows` slices whole pages by their start offsets; its planes,
    payload planes and counters equal the per-word-index formulation's on an
    arena with single-, double- and triple-bit flips, one of them in the
    scratch page that idle lanes repeat."""
    from repro.core.kvpages import _scrub_rows, dedup_page_table
    from repro.kernels import ops

    arena, geom = _mk_arena(page_tokens=2, n_pages=6, codec=codec)
    rng = np.random.default_rng(7)
    n_tok = geom.page_tokens * arena.n_pages
    arena.commit_tokens(
        jnp.asarray(rng.standard_normal((n_tok, geom.token_f32)).astype(np.float32)),
        np.repeat(np.arange(arena.n_pages), geom.page_tokens),
        np.tile(np.arange(geom.page_tokens), arena.n_pages),
    )
    w, s = geom.words_per_page, arena.scratch_page
    lo, hi, par = (np.asarray(p).copy() for p in (arena.lo, arena.hi, arena.parity))
    lo[0 * w + 3] ^= np.uint32(1 << 7)  # single
    hi[2 * w + 5] ^= np.uint32(0b101)  # double
    lo[4 * w + 1] ^= np.uint32(0b10011)  # triple
    par[3 * w + 2] ^= par.dtype.type(1)  # check bit
    hi[s * w + 4] ^= np.uint32(1 << 30)  # the scratch page
    ids = np.array([4, s, 0, 3, s, s, 2, 5], np.int32)
    if table == "dedup":
        ids = dedup_page_table(np.array([[4, 2, s], [2, 0, s], [5, 4, 3]]), s)[0]
    ids = jnp.asarray(ids)
    planes = tuple(jnp.asarray(p) for p in (lo, hi, par))
    want = _word_index_scrub(*planes, ids, words_per_page=w, codec=codec)
    got = _scrub_rows(
        *planes, ids, words_per_page=w, codec=codec, interpret=ops.use_interpret()
    )
    assert np.asarray(want[5])[:, 1:3].sum() > 0  # the flips were seen
    for name, a, b in zip(("lo", "hi", "par", "olo", "ohi", "cnt"), want, got):
        assert a.shape == b.shape and a.dtype == b.dtype, name
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b), err_msg=name)


def _word_index_commit(lo, hi, par, payload, page_ids, slots, *, token_words,
                       words_per_page, codec):
    """The commit as it addressed tokens through one int32 index per word:
    the reference the token-window form must match bit for bit."""
    from repro.core.kvpages import _payload_to_planes

    rlo, rhi, rpar = _payload_to_planes(payload, codec)
    base = page_ids * words_per_page + slots * token_words
    idx = base[:, None] + jnp.arange(token_words, dtype=jnp.int32)[None, :]
    return lo.at[idx].set(rlo), hi.at[idx].set(rhi), par.at[idx].set(rpar)


def _commit_rows(case, n_pages, page_tokens, s):
    """(page_ids, slots) of one commit, laid out as its caller lays them."""
    if case == "one_row":
        return [2], [1]
    if case == "decode_idle_lanes":  # idle lanes steered to the scratch page
        return [0, s, 3, s, s, 1], [2, 0, 1, 0, 0, 3]
    if case == "prompt_across_pages":  # 11 tokens over pages 3, 1, 4
        t = np.arange(11)
        return np.array([3, 1, 4])[t // page_tokens], t % page_tokens
    if case == "prompt_group_padding":  # 2 prompts of 6 and 2 padding rows
        t = np.arange(6)
        table = [[0, 2], [4, 1], [s, s], [s, s]]
        pages = np.stack([np.array(row)[t // page_tokens] for row in table])
        return pages.reshape(-1), np.tile(t % page_tokens, len(table))
    assert case == "last_page_last_slot"
    return [0, n_pages - 1], [0, page_tokens - 1]


@pytest.mark.parametrize(
    "case",
    ["one_row", "decode_idle_lanes", "prompt_across_pages", "prompt_group_padding",
     "last_page_last_slot"],
)
@pytest.mark.parametrize("codec", ["secded72", "dected79"])
def test_token_window_commit_matches_word_index_form(codec, case):
    """`_commit_tokens` writes each token as one ``token_words`` window at
    its start offset; outside the scratch page its planes equal the
    per-word-index formulation's, over an arena whose untouched words hold
    earlier data."""
    from repro.core.kvpages import _commit_tokens

    arena, geom = _mk_arena(page_tokens=4, n_pages=5, codec=codec)
    rng = np.random.default_rng(11)
    n_tok = geom.page_tokens * (arena.n_pages + 1)
    arena.commit_tokens(
        jnp.asarray(rng.standard_normal((n_tok, geom.token_f32)).astype(np.float32)),
        np.repeat(np.arange(arena.n_pages + 1), geom.page_tokens),
        np.tile(np.arange(geom.page_tokens), arena.n_pages + 1),
    )
    pages, slots = _commit_rows(case, arena.n_pages, geom.page_tokens, arena.scratch_page)
    pages, slots = jnp.asarray(pages, jnp.int32), jnp.asarray(slots, jnp.int32)
    payload = jnp.asarray(
        rng.standard_normal((pages.shape[0], geom.token_f32)).astype(np.float32)
    )
    planes = (arena.lo, arena.hi, arena.parity)
    kw = dict(token_words=geom.token_words, words_per_page=geom.words_per_page, codec=codec)
    want = _word_index_commit(*planes, payload, pages, slots, **kw)
    got = _commit_tokens(*planes, payload, pages, slots, **kw)
    real = arena.n_words  # the scratch page follows the real pages
    for name, before, a, b in zip(("lo", "hi", "par"), planes, want, got):
        assert a.shape == b.shape and a.dtype == b.dtype, name
        np.testing.assert_array_equal(np.asarray(a)[:real], np.asarray(b)[:real], err_msg=name)
        if not (pages == arena.scratch_page).any():
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b), err_msg=name)
    changed = np.asarray(want[0])[:real] != np.asarray(planes[0])[:real]
    assert changed.any()  # the commit overwrote earlier data


def test_kv_rail_walks_independently_of_weight_rails(setup):
    cfg, params, prompts = setup
    eng = ServingEngine(
        cfg, params,
        rel=ReliabilityConfig(
            platform="vc707", ecc=True, voltage=1.0, mode="inline",
            rails=RailsConfig(multi_rail=True, start_v=0.60),
        ),
        max_len=48,
    )
    w_volts, _ = eng.autotune_voltage()
    w_locked = {d: c.voltage for d, c in eng.controller.rails.items()}
    reqs = [(prompts[i % 4], 16) for i in range(6)]
    rep = eng.serve(reqs, n_lanes=3, scrub_interval=1, walk_kv=True, kv_voltage=0.60)
    kv = eng.controller.rails["kv"]
    # the kv canary saw real DED telemetry from the page arena and locked...
    assert kv.locked and rep.kv_stats.detected > 0
    assert kv.voltage >= vmod.PLATFORMS["vc707"].v_crash
    # ...while no weight rail moved
    for d, v in w_locked.items():
        assert eng.controller.rails[d].voltage == v
    # the kv domain now carries real words in the power accounting
    words = eng._store.words_by_domain()
    assert words.get("kv", 0) == rep.arena.n_words
    assert "kv" in eng.power_report()["rails"]
    # a later uniform weight-rail step must not drop the kv rail from the
    # power accounting (its words stay in the denominator either way)
    eng.set_voltage(0.60)
    assert eng.rails["kv"] == rep.arena.voltage
    assert "kv" in eng.power_report()["bram_w_by_domain"]


def test_undervolted_kv_cache_corrects_and_serves(setup, engine):
    """Moderate undervolt on the cache only: ECC corrects every single-bit
    fault on the live stream and the outputs stay usable (the weights are
    clean, so any token drift comes from cache faults alone)."""
    cfg, params, prompts = setup
    reqs = [(prompts[i], 12) for i in range(4)]
    ref = engine.serve(reqs, n_lanes=4, scrub_interval=1).outputs
    rep = engine.serve(reqs, n_lanes=4, scrub_interval=1, kv_voltage=0.58)
    assert rep.kv_stats.corrected > 0
    agree = np.mean(
        [np.mean(rep.outputs[i] == ref[i]) for i in range(4)]
    )
    assert agree > 0.9
