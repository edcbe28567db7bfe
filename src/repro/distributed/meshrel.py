"""Mesh-sharded reliability layer (DESIGN.md §13).

The reliability stack from DESIGN.md §9–§12 — the PlaneStore arena, the paged
KV cache, the fused inject+scrub and scrub-on-read kernels, the multi-rail
controller — was single-device: one chip, one fault population, one rail set.
At production scale every replica/shard is its own chip with its own silicon
(MoRS models per-SRAM fault-map variation; the MLP undervolting follow-up
measures per-board V_min spread), so this module makes the layer mesh-native:

  * the flat word arenas are partitioned across the mesh's *reliability
    shard axes* (the batch super-axis — each data-parallel replica is one
    chip whose rails move together; TP inside a replica shares the board);
  * the fused inject+scrub and paged scrub-on-read kernels run under
    ``shard_map``: every shard generates its own ``DeviceFaultField`` masks
    with ``collectives.shard_key`` (``jax.lax.axis_index`` folded into the
    PRNG key), so shards draw independent fault populations — shard 0 keeps
    the unsharded key, the bit-identity anchor for the 1-device mesh;
  * per-shard (n_shards, n_domains, 8) counter blocks come back with NO
    collective inside the step: the per-interval scrub is collective-free,
    and the single cross-shard counter reduction (``fold_counters``, or
    ``make_rail_step(..., with_psum=True)`` for the historical in-step
    ``collectives.psum_counters``) is hoisted out so a soak of N intervals
    pays one reduction instead of N. Both rail policies stay fed: `uniform`
    (one schedule, worst-shard canary via the folded view) and `per_shard`
    (each shard walks its own V_min).

Collective traffic per rail *soak*: one counter reduction of
n_domains x 128 int32 lanes — independent of arena size AND of the number
of intervals in the soak (this is what fixed the d8-below-d4 words/sec dip
in BENCH_mesh.json: at 8 forced host devices the per-interval psum dispatch
dominated the tiny per-shard scrub slices). The plane data itself never
crosses shards (each chip scrubs its own words); the CPU serving engine
additionally gathers the faulty planes to one device because its decode
path is single-device (a real TP mesh would consume them sharded in place).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from repro import codes
from repro.core.faultsim import _check_dtype, _device_chunk_masks
from repro.distributed import collectives
from repro.distributed.sharding import reliability_axes, reliability_shards
from repro.kernels import ops as kops
from repro.obs import profile as obs_profile

__all__ = [
    "arena_sharding",
    "fold_counters",
    "make_kv_scrub_step",
    "make_rail_step",
    "pad_to_shards",
    "reliability_axes",
    "reliability_shards",
    "schedule_rates",
    "shard_devices",
]


@jax.jit
def fold_counters(per_shard):
    """The hoisted once-per-soak counter reduction: sum an
    (n_shards, ...) per-shard counter block over the shard axis on device.
    Replaces the per-interval in-step psum — call it once after a soak (or
    whenever a worst-shard/fleet view is actually needed), not per step."""
    return jnp.sum(per_shard, axis=0)


def _axes_spec(axes) -> P:
    return P(axes[0] if len(axes) == 1 else tuple(axes))


def arena_sharding(mesh: Mesh) -> NamedSharding:
    """NamedSharding partitioning a flat (n_words,) arena over the
    reliability shard axes (word count must be a multiple of the shard
    count — ``pad_to_shards`` arranges that)."""
    return NamedSharding(mesh, _axes_spec(reliability_axes(mesh)))


def shard_devices(mesh: Mesh) -> list:
    """The chip of every reliability shard, in shard order: the (first)
    device holding shard ``s``'s slice of an ``arena_sharding`` array. A
    serving replica runs where its shard's words live."""
    n = reliability_shards(mesh)
    out = [None] * n
    for dev, idx in arena_sharding(mesh).devices_indices_map((n,)).items():
        s = idx[0].start or 0
        if out[s] is None or dev.id < out[s].id:
            out[s] = dev
    return out


def pad_to_shards(n: int, n_shards: int) -> int:
    """Padded word count: the smallest multiple of ``n_shards`` >= n."""
    return -(-n // n_shards) * n_shards


def _chunked_shard_masks(key, local_n, rates_w, sigma, n_check, chunk_words, burst=None):
    """Per-shard flip masks over ``local_n`` flat words, chunked exactly like
    ``DeviceFaultField.masks_for_rates`` (fold_in per chunk index) so the
    1-shard mesh reproduces the unsharded device stream bit-for-bit —
    including under a ``burst`` profile, whose auxiliary draws fold off the
    same per-chunk key (DESIGN.md §14)."""
    los, his, pars = [], [], []
    for ci, start in enumerate(range(0, local_n, chunk_words)):
        m = min(chunk_words, local_n - start)
        lo, hi, par = _device_chunk_masks(
            jax.random.fold_in(key, ci), m, rates_w[start : start + m],
            sigma, n_check=n_check, burst=burst,
        )
        los.append(lo)
        his.append(hi)
        pars.append(par)
    if not los:
        z32 = jnp.zeros((0,), jnp.uint32)
        return z32, z32, jnp.zeros((0,), jnp.dtype(_check_dtype(n_check)))
    if len(los) == 1:
        return los[0], his[0], pars[0]
    return jnp.concatenate(los), jnp.concatenate(his), jnp.concatenate(pars)


@functools.lru_cache(maxsize=None)
def make_rail_step(
    mesh: Mesh,
    local_words: int,
    n_domains: int,
    codec: str,
    seed: int,
    row_sigma: float,
    reencode: bool = False,
    chunk_words: int = 1 << 18,
    burst=None,
    with_psum: bool = False,
):
    """Build the shard_map'd fused inject+scrub step for one codec group.

    Returns a jitted callable
        fn(lo, hi, check, dom, rates) ->
            (faulty_lo, faulty_hi, faulty_check,
             per_shard_counters (n_shards, n_domains, 8))
    where the planes are flat (n_shards * local_words,) arrays sharded over
    the mesh's reliability axes, ``dom`` the per-word domain index (spill
    index ``n_domains`` for pad words), and ``rates`` an
    (n_shards, n_domains + 1) per-(shard, domain) fault-rate table (spill
    column 0.0). Every shard draws its masks from its own stream
    (collectives.shard_key).

    The step itself is collective-free: the per-shard counter block comes
    back sharded and any cross-shard view is the caller's one-per-soak
    ``fold_counters`` call. ``with_psum=True`` restores the historical
    in-step ``collectives.psum_counters`` aggregate as a fifth output
    (``(n_domains, 8)`` replicated) for callers that genuinely need the
    fleet view every interval.

    ``burst`` (a hashable scenario.BurstProfile, static under the cache)
    turns the per-shard draws into correlated multi-bit upsets; environment
    flux and per-shard aging drift arrive through the rate table itself
    (schedule_rates), so the compiled step is reused across a whole soak.
    """
    axes = reliability_axes(mesh)
    codec_obj = codes.get(codec)
    base_key = jax.random.PRNGKey(seed ^ 0xECC)
    sigma = jnp.float32(row_sigma)
    spec = _axes_spec(axes)

    def body(lo, hi, check, dom, rates):
        key = collectives.shard_key(base_key, axes)
        rates_w = rates[0][dom]  # (local_words,) per-word fault rate
        mlo, mhi, mpar = _chunked_shard_masks(
            key, local_words, rates_w, sigma, codec_obj.n_check, chunk_words,
            burst=burst,
        )
        flo, fhi, fpar, cnt = kops.inject_scrub_domains(
            lo, hi, check, mlo, mhi, mpar, dom, n_domains,
            codec=codec, reencode=reencode,
        )
        if with_psum:
            agg = collectives.psum_counters(cnt, axes)
            return flo, fhi, fpar, cnt[None], agg
        return flo, fhi, fpar, cnt[None]

    out_specs = (spec, spec, spec, spec) + ((P(),) if with_psum else ())
    fn = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(spec, spec, spec, spec, spec),
        out_specs=out_specs,
        check_vma=False,
    )
    # counters come back already sliced to the 8 telemetry lanes:
    # kops.inject_scrub_domains drops the lane padding and the spill row
    jitted = jax.jit(fn)

    def step(*args):
        return obs_profile.call("mesh.rail_step", jitted, *args)

    return step


@functools.lru_cache(maxsize=None)
def make_kv_scrub_step(
    mesh: Mesh,
    words_per_page: int,
    local_words: int,
    table_cols: int,
    codec: str = "secded72",
    with_payload: bool = True,
):
    """Shard_map'd paged scrub-on-read over per-replica KV arenas.

    The planes are the ``n_shards`` replicas' arenas stacked flat
    ((n_shards * local_words,), sharded over the reliability axes); ``table``
    is one (table_cols,) page-id row per shard (scratch-page filler for
    unused columns, ids local to the replica's arena). Each shard gathers
    its own rows, runs the scrub-on-read kernel, writes corrected planes
    back, and contributes its (table_cols, 8) counter rows; no plane word
    ever crosses a shard boundary. Returns a jitted callable
        fn(lo, hi, par, table) -> (lo, hi, par, payload_lo, payload_hi,
                                   counters (n_shards, table_cols, 8))
    ``with_payload=False`` drops the two payload outputs (callable returns
    (lo, hi, par, counters)): a scrub-only soak — the background scrubber
    and the BENCH_mesh throughput record — needs corrected planes and
    counters but never reads the gathered payload, and skipping it removes
    2 * table_cols * words_per_page words of per-step output traffic.
    """
    from repro.core.kvpages import _scrub_rows

    axes = reliability_axes(mesh)
    spec = _axes_spec(axes)
    interpret = kops.use_interpret()

    def body(lo, hi, par, table):
        lo, hi, par, olo, ohi, cnt = _scrub_rows(
            lo, hi, par, table[0],
            words_per_page=words_per_page, codec=codec, interpret=interpret,
        )
        payload = (olo[None], ohi[None]) if with_payload else ()
        return (lo, hi, par, *payload, cnt[None])

    n_out = 6 if with_payload else 4
    fn = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(spec, spec, spec, spec),
        out_specs=(spec,) * n_out,
        check_vma=False,
    )
    jitted = jax.jit(fn)

    def step(*args):
        return obs_profile.call("mesh.kv_scrub_step", jitted, *args)

    return step


# ---------------------------------------------------------------------------
# Host-side helpers for the per-(shard, domain) rail schedule
# ---------------------------------------------------------------------------
def schedule_rates(
    schedule, domains, profiles, n_shards: int, shard_multipliers=None
) -> np.ndarray:
    """(n_shards, n_domains + 1) fault-rate table for a rail schedule.

    ``schedule``: one {domain: voltage} dict (uniform across shards) or a
    sequence of ``n_shards`` of them (per-shard rails). ``profiles`` maps
    domain -> PlatformProfile. The trailing spill column is rate 0 — pad
    words never fault and never count. ``shard_multipliers`` (length
    n_shards, optional) scales each chip's whole rate row — the per-shard
    aging-drift hook (core/scenario.aging_multiplier); None or all-ones is
    bit-identical to the unscaled table.
    """
    if isinstance(schedule, dict):
        schedule = [schedule] * n_shards
    schedule = list(schedule)
    assert len(schedule) == n_shards, (len(schedule), n_shards)
    rates = np.zeros((n_shards, len(domains) + 1), np.float32)
    for s, volts in enumerate(schedule):
        missing = set(domains) - set(volts)
        assert not missing, f"shard {s} rails missing domains: {sorted(missing)}"
        for i, d in enumerate(domains):
            rates[s, i] = profiles[d].fault_rate(float(volts[d]))
    if shard_multipliers is not None:
        mult = np.asarray(shard_multipliers, np.float32)
        assert mult.shape == (n_shards,), (mult.shape, n_shards)
        # the spill column is 0.0 and stays 0.0 under any multiplier
        rates *= mult[:, None]
    return rates
