"""Serving step functions: prefill, single-token decode (greedy), and the
paged-cache lane helpers for continuous batching.

`serve_step` is what decode_32k / long_500k dry-run cells lower: one new token
against a seq_len-deep KV cache (or SSM state), returning the sampled token
and the updated cache. Cache buffers are donated so the compiled step updates
in place.

`make_paged_helpers` builds the jit'd glue between the dense per-lane decode
cache and the SECDED page arena (core/kvpages.py): extract one token's K/V
payload per lane, load a prefilled batch-of-1 cache into a lane, and refresh
lane caches from scrubbed page payloads. The payload layout (per token: for
each attention period position, K then V, each (groups, kv_heads, head_dim)
C-order) is defined *only* here — extract and refresh are exact inverses.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Optional, Protocol, runtime_checkable

import jax
import jax.numpy as jnp

from repro.configs import shapes
from repro.core import statestore
from repro.core.kvpages import KVGeometry
from repro.models import lm
from repro.models.base import ModelConfig
from repro.obs import profile as obs_profile


def _jit_named(name: str, fn, static_argnames=(), **bound):
    """``jax.jit`` of ``fn`` with ``bound`` keywords fixed, compiled as the
    program ``jit_<name>``: a bare ``functools.partial`` has no name, and
    every such program would show up as ``jit__unknown`` in a trace."""
    f = functools.partial(fn, **bound)
    f.__name__ = f.__qualname__ = name
    return jax.jit(f, static_argnames=static_argnames)


def make_prefill_step(cfg: ModelConfig):
    def prefill_step(params, tokens, cache, img=None):
        logits, cache = lm.prefill(params, tokens, cfg, cache, img=img)
        next_tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return next_tok, cache

    return prefill_step


def _extract_tokens(cache, idx, *, geom: KVGeometry):
    """Per-lane token payload: cache tree + (L,) positions -> (L, token_f32)."""
    parts = []
    for j in geom.attn_positions:
        for name in ("k", "v"):
            c = cache[f"p{j}"][name]  # (g, L, S, H, D)
            sel = jnp.take_along_axis(
                c, idx.reshape(1, -1, 1, 1, 1).astype(jnp.int32), axis=2
            )  # (g, L, 1, H, D)
            parts.append(jnp.moveaxis(sel[:, :, 0], 0, 1).reshape(idx.shape[0], -1))
    return jnp.concatenate(parts, axis=1).astype(jnp.float32)


def _extract_span(cachem, *, start: int, stop: int, geom: KVGeometry):
    """Window payload: batch-of-m cache -> (m, stop-start, token_f32) for
    cache positions start..stop-1 (prefix sharing commits only the private
    suffix — the shared pages already hold positions 0..start-1)."""
    parts = []
    span = stop - start
    for j in geom.attn_positions:
        for name in ("k", "v"):
            c = cachem[f"p{j}"][name]  # (g, m, S, H, D)
            m = c.shape[1]
            sel = jnp.moveaxis(c[:, :, start:stop], 0, 2)  # (m, span, g, H, D)
            parts.append(sel.reshape(m, span, -1))
    return jnp.concatenate(parts, axis=2).astype(jnp.float32)


def _extract_range(cachem, *, s0: int, geom: KVGeometry):
    """Prompt payload: batch-of-m cache -> (m, s0, token_f32), tokens 0..s0-1."""
    return _extract_span(cachem, start=0, stop=s0, geom=geom)


def _refresh_cache(cache, payload, n_tok, *, geom: KVGeometry):
    """Scatter scrubbed page payloads back into the lane caches.

    payload: (L, T, token_f32) decoded tokens in position order (T >= the
    cache depth S is sliced; T < S leaves the tail untouched); n_tok: (L,)
    valid-token counts — positions >= n_tok keep their cache bits. The
    payload covers cache lanes 0..L-1; lanes beyond (the decode batch's
    padding rows) keep theirs.
    """
    length, t_total, _ = payload.shape
    out = {k: dict(v) for k, v in cache.items()}
    off = 0
    for j in geom.attn_positions:
        for name in ("k", "v"):
            c = cache[f"p{j}"][name][:, :length]  # (g, L, S, H, D)
            g, _, s, h, d = c.shape
            t = min(t_total, s)
            sz = g * h * d
            part = payload[:, :t, off : off + sz].reshape(length, t, g, h, d)
            part = jnp.moveaxis(part, 2, 0).astype(c.dtype)  # (g, L, t, H, D)
            valid = (jnp.arange(t)[None, :] < n_tok[:, None])[None, :, :, None, None]
            out[f"p{j}"][name] = cache[f"p{j}"][name].at[:, :length, :t].set(
                jnp.where(valid, part, c[:, :, :t])
            )
            off += sz
    return out


def _load_lane(cache, cachem, src_row, lane, *, skip=()):
    """Copy row ``src_row`` of a prefilled batch-of-m cache into ``lane``.
    Entries named in ``skip`` are left as they are: a state layer's lane
    slot holds SECDED planes, which ``statestore.commit`` writes."""
    copy = lambda c, cm: jax.lax.dynamic_update_slice_in_dim(
        c, jax.lax.dynamic_slice_in_dim(cm.astype(c.dtype), src_row, 1, 1), lane, 1
    )
    return {
        k: cache[k] if k in skip else jax.tree_util.tree_map(copy, cache[k], cachem[k])
        for k in sorted(cache)
    }


def program_rows(cfg: ModelConfig, rows: int, cap: int) -> int:
    """Rows a prefill group of ``rows`` prompts, or a decode block of
    ``rows`` steps, is compiled at. An all-attention model compiles each
    size. A model with state layers, whose unrolled hybrid period makes
    every program costly to compile, runs each size at ``cap``: a prefill
    group at the decode rows (the last prompt repeated, its commits to the
    scratch page), a decode block in the largest block's program
    (``_multistep`` loops over the first ``rows``)."""
    return cap if shapes.has_state_layers(cfg) else rows


def _multistep(
    params, tok, cache, lo, hi, par, pos0, page_ids, slots, live=None, n_steps=None,
    *, cfg, geom, codec="secded72",
):
    """Decode ``k`` tokens per lane in one dispatch (multi-step scheduling).

    The continuous-batching loop pays Python dispatch per token where the
    fixed-batch loop pays one `lax.scan`; this rolls a *block* of k decode
    steps — decode, extract the written token's KV, commit it to the page
    arena — into one scanned program. page_ids/slots: (k, L) per-step page
    targets (precomputed on host; inactive lanes point at the scratch page).
    ``live`` (L,): the lanes holding a request, given where the model keeps
    recurrent state in the SECDED state store (core/statestore.py). Such a
    model compiles one program for every block size: page_ids/slots then
    hold the largest block's rows, and a loop runs the first ``n_steps`` ()
    of them (its unrolled hybrid period makes each program costly to
    compile).

    Returns (tokens (k, L), cache, lo, hi, par), and with ``live`` also the
    block's (L, 3) clean / corrected / detected state-word counts; rows of
    ``tokens`` past ``n_steps`` are zero.
    """
    from repro.core.kvpages import _commit_tokens

    if live is not None:
        cache = statestore.arm(cache, live, cfg)

    def body(carry, xs):
        tok, cache, lo, hi, par, pos = carry
        pids, slts = xs
        logits, cache = lm.decode_step(params, tok, cfg, cache, pos)
        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)[:, None]
        payload = _extract_tokens(cache, pos, geom=geom)
        lo, hi, par = _commit_tokens(
            lo, hi, par, payload, pids, slts,
            token_words=geom.token_words,
            words_per_page=geom.words_per_page,
            codec=codec,
        )
        return (nxt, cache, lo, hi, par, pos + 1), nxt[:, 0]

    if live is None:
        (tok, cache, lo, hi, par, _), toks = jax.lax.scan(
            body, (tok, cache, lo, hi, par, pos0), (page_ids, slots)
        )
        return toks, cache, lo, hi, par

    def step(i, carry):
        state, toks = carry
        state, nxt = body(state, (page_ids[i], slots[i]))
        return state, toks.at[i].set(nxt)

    (tok, cache, lo, hi, par, _), toks = jax.lax.fori_loop(
        0, n_steps, step,
        ((tok, cache, lo, hi, par, pos0), jnp.zeros(page_ids.shape, jnp.int32)),
    )
    cache, counts = statestore.harvest(cache, cfg)
    return toks, cache, lo, hi, par, counts


def _chunk_prefill(params, tokens, cache, pos0, *, cfg):
    """Chunked prefill of ``tokens`` (m, s) at per-lane cache position
    ``pos0`` (m,): the prefix-sharing admission path — the shared prefix is
    already in the cache (refreshed from its pages), only the private
    suffix runs through the model. Returns (next_tok (m,), cache)."""
    logits, cache = lm.chunk_step(params, tokens, cfg, cache, pos0)
    return jnp.argmax(logits, axis=-1).astype(jnp.int32), cache


def _spec_multistep(
    params, dparams, tok, cache, dcache, lo, hi, par, pos0, page_ids, slots,
    *, cfg, dcfg, geom, codec="secded72", k, scratch_page,
):
    """Draft k-1 tokens with the draft model, verify all k positions with
    the target model in ONE chunk dispatch, commit pages only for accepted
    tokens (DESIGN.md §16).

    tok: (L, 1) current token; cache/dcache: target/draft lane caches;
    pos0: (L,) position of ``tok``; page_ids/slots: (k, L) host page
    targets for positions pos0..pos0+k-1 (inactive lanes already point at
    the scratch page).

    Greedy acceptance: the target's chunk logits give greedy[:, i] =
    argmax P(. | t0, d1..d_i); draft d_{i+1} is accepted iff it equals
    greedy[:, i], and ``n_emit = 1 + #accepted-prefix`` in [1, k] — so the
    emitted tokens greedy[:, :n_emit] are exactly the tokens step-by-step
    greedy decode would have produced, regardless of draft quality (the
    accepted-prefix property, tested). Rejected drafts' K/V rows stay in
    the dense lane cache beyond the valid length (masked by every later
    attention and overwritten before they are ever attended) and their
    page commits are steered to the scratch row.

    Returns (greedy (L, k), n_emit (L,), cache, dcache, lo, hi, par).
    """
    from repro.core.kvpages import _commit_tokens

    length = tok.shape[0]

    def draft_body(carry, _):
        t, dc, p = carry
        logits, dc = lm.decode_step(dparams, t, dcfg, dc, p)
        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)[:, None]
        return (nxt, dc, p + 1), nxt[:, 0]

    if k > 1:
        # length=k, not k-1: the k-th step's sampled token is discarded but
        # its decode writes tokens_v[:, k-1]'s K/V into the draft cache —
        # otherwise full acceptance leaves a hole at pos0+k-1 that the next
        # block's draft would attend as garbage (hurting acceptance, never
        # correctness: the target verifies regardless).
        (_, dcache, _), drafts = jax.lax.scan(
            draft_body, (tok, dcache, pos0), None, length=k
        )
        tokens_v = jnp.concatenate([tok, drafts[:-1].T], axis=1)  # (L, k)
    else:
        tokens_v = tok  # degenerate k=1: plain single-step decode via chunk
    full, cache = lm.chunk_logits(params, tokens_v, cfg, cache, pos0)
    greedy = jnp.argmax(full, axis=-1).astype(jnp.int32)  # (L, k)
    if k > 1:
        match = (tokens_v[:, 1:] == greedy[:, :-1]).astype(jnp.int32)
        n_emit = 1 + jnp.sum(jnp.cumprod(match, axis=1), axis=1)  # (L,)
    else:
        n_emit = jnp.ones((length,), jnp.int32)

    # Commit positions pos0+i only where i < n_emit: the committed rows are
    # exactly the block's accepted sequence [t0, accepted drafts] — the same
    # resume_seq prefix the non-speculative path commits.
    payloads = jax.vmap(
        lambda i: _extract_tokens(cache, pos0 + i, geom=geom)
    )(jnp.arange(k))  # (k, L, F)
    accept = jnp.arange(k)[:, None] < n_emit[None, :]
    commit_ids = jnp.where(accept, page_ids, scratch_page)
    lo, hi, par = _commit_tokens(
        lo, hi, par,
        payloads.reshape(k * length, -1),
        commit_ids.reshape(-1),
        slots.reshape(-1),
        token_words=geom.token_words,
        words_per_page=geom.words_per_page,
        codec=codec,
    )
    return greedy, n_emit, cache, dcache, lo, hi, par


@runtime_checkable
class DecodeBlockHelpers(Protocol):
    """The decode-block helper contract the continuous-batching scheduler
    consumes (DESIGN.md §11/§16). ``make_paged_helpers`` is the canonical
    producer; anything item-accessible with these keys satisfies it."""

    def __getitem__(self, name: str) -> Callable: ...


@dataclasses.dataclass(frozen=True)
class PagedHelpers:
    """jit'd continuous-batching helpers sharing one payload layout.

    Attribute and ``helpers["name"]`` access are both supported — the
    scheduler historically indexed a plain dict and external factories may
    still return one (see :class:`DecodeBlockHelpers`).

      prefill(params, tokens (m,s), cachem)       -> (next_tok (m,), cachem)
      multistep(params, tok, cache, lo, hi, par,
                pos (L,), page_ids (k,L), slots)  -> (toks (k,L), cache, planes)
      extract_range(cachem, s0=s)                 -> (m, s, token_f32) payload
      extract_span(cachem, start=a, stop=b)       -> (m, b-a, token_f32)
      load_lane(cache, cachem, src_row, lane)     -> cache
      refresh(cache, payload (L,T,F), n_tok (L,)) -> cache
      chunk(params, tokens (m,s), cachem, pos0)   -> (next_tok (m,), cachem)
      commit_state(cache, cachem, lanes (m,))     -> cache (state models only:
                the prefill's final state encoded into the lanes' slots)
      spec_multistep(params, dparams, tok, cache, dcache, lo, hi, par,
                pos (L,), page_ids (k,L), slots, k=, scratch_page=)
                -> (greedy (L,k), n_emit (L,), cache, dcache, planes)

    Single-step decode is multistep with k=1 (one (1, L) page row); the
    per-token extract lives inside the multistep scan body. ``codec`` is
    the SECDED-family codec the commit path encodes with — rebuild the
    helpers (via the engine's helpers factory) when the kv rail escalates.
    """

    codec: str
    prefill: Callable
    multistep: Callable
    extract_range: Callable
    extract_span: Callable
    load_lane: Callable
    refresh: Callable
    chunk: Callable
    spec_multistep: Optional[Callable] = None
    commit_state: Optional[Callable] = None

    def __getitem__(self, name: str) -> Callable:
        fn = getattr(self, name)
        if fn is None:
            raise KeyError(name)
        return fn

    def get(self, name: str, default: Any = None) -> Any:
        return getattr(self, name, default) or default


@runtime_checkable
class HelpersFactory(Protocol):
    """codec name -> decode-block helpers, called by the scheduler when the
    kv rail's escalation ladder changes the arena's codec mid-serve."""

    def __call__(self, codec: str) -> DecodeBlockHelpers: ...


def make_paged_helpers(
    cfg: ModelConfig,
    geom: KVGeometry,
    codec: str = "secded72",
    draft_cfg: ModelConfig | None = None,
) -> PagedHelpers:
    """Build the jit'd :class:`PagedHelpers` bundle for one (config,
    geometry, codec) triple. ``draft_cfg`` enables ``spec_multistep`` (the
    draft model's decode runs inside the same scanned dispatch)."""
    spanned = obs_profile.spanned
    spec = None
    if draft_cfg is not None:
        spec = spanned("decode.spec_multistep")(
            _jit_named(
                "spec_multistep",
                _spec_multistep,
                static_argnames=("k", "scratch_page"),
                cfg=cfg, dcfg=draft_cfg, geom=geom, codec=codec,
            )
        )
    state = shapes.has_state_layers(cfg)
    skip = tuple(f"p{j}" for j in statestore.positions(cfg))
    return PagedHelpers(
        codec=codec,
        commit_state=(
            _jit_named("commit_state", statestore.commit, cfg=cfg) if state else None
        ),
        prefill=spanned("decode.prefill")(jax.jit(make_prefill_step(cfg))),
        multistep=spanned("decode.multistep")(
            _jit_named("multistep", _multistep, cfg=cfg, geom=geom, codec=codec)
        ),
        extract_range=_jit_named(
            "extract_range", _extract_range, static_argnames=("s0",), geom=geom
        ),
        extract_span=_jit_named(
            "extract_span",
            _extract_span,
            static_argnames=("start", "stop"),
            geom=geom,
        ),
        load_lane=_jit_named("_load_lane", _load_lane, skip=skip),
        refresh=_jit_named("refresh", _refresh_cache, geom=geom),
        chunk=spanned("decode.chunk_prefill")(
            _jit_named("chunk_prefill", _chunk_prefill, cfg=cfg)
        ),
        spec_multistep=spec,
    )


def make_serve_step(cfg: ModelConfig):
    def serve_step(params, tokens, cache, pos, img=None):
        logits, cache = lm.decode_step(params, tokens, cfg, cache, pos, img=img)
        next_tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        if cfg.n_codebooks:
            next_tok = next_tok[:, :, None]  # (B, K, 1)
        else:
            next_tok = next_tok[:, None]  # (B, 1)
        return next_tok, cache

    return serve_step
