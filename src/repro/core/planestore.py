"""Batched plane store: every EccWeight plane of a model in one flat arena.

The per-leaf undervolting loop launched 2-3 kernels *per weight matrix* per
voltage step and synced a per-leaf status array back to the host each time.
The store concatenates all (lo, hi, check) planes into flat (n_words,)
arenas at protect time, keeps a leaf -> [offset, offset+size) slice index,
and makes a voltage step one fused ``inject_scrub`` launch per *codec
group* with a single counter block crossing to host (DESIGN.md §9/§12).

Mask sources:
  * "host"   — the NumPy FaultField oracle, one field per leaf keyed exactly
    like the historical per-leaf path (``leaf_seed``), so the batched step is
    bit-identical to the per-leaf reference (tested);
  * "device" — one DeviceFaultField per codec group: counter-based
    jax.random, masks never exist in host memory (statistically equivalent,
    FIP holds).

Codecs (DESIGN.md §12): every memory domain selects a registered ECC scheme
(``codecs`` maps domain -> codec name; default everything on the built-in
``secded72``). Slots sharing a codec form one *group* with its own
concatenated planes and one fused kernel launch per voltage step — the
uniform-SECDED default is exactly one group whose planes alias the master
arrays, so the historical single-launch behaviour (and its bit patterns) is
unchanged. ``set_domain_codec`` re-encodes a domain under a stronger code at
runtime — the controller escalation path.

Async dispatch + double buffering (DESIGN.md §18): every voltage step also
has a ``*_async`` form that dispatches the fused launches and returns
immediately with a ``PendingFaultStats`` — the ``np.asarray(counters)``
host sync (the only serialization point) is deferred to ``harvest()``, so
decode work dispatched after the step overlaps the scrub. On compiled
backends each codec group's planes rotate through a depth-2 buffer ring and
the launch donates the two-steps-stale faulty planes back to XLA
(``donate_argnums``), making the steady-state soak allocation-free; the
interpret/CPU lane skips donation (unsupported there) but keeps the same
dispatch order, so both lanes are bit-identical to the serial path.
"""

from __future__ import annotations

import dataclasses
import functools
import zlib
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro import codes
from repro.core import scenario
from repro.core.faultsim import DeviceFaultField, FaultField
from repro.core.telemetry import DomainFaultStats, FaultStats
from repro.core.voltage import PlatformProfile
from repro.codes import DEFAULT_CODEC
from repro.kernels import backend as kbackend
from repro.kernels import ops as kops


def leaf_seed(base_seed: int, key: str) -> int:
    """Per-leaf fault-field seed; must stay stable across refactors — the
    fault pattern is a property of (silicon sample, rail), i.e. (seed, leaf)."""
    return (base_seed * 0x9E3779B1 + zlib.crc32(key.encode())) & 0x7FFFFFFF


@dataclasses.dataclass
class PendingFaultStats:
    """Deferred telemetry from an asynchronously dispatched voltage step.

    Holds the per-group device counter blocks of a ``set_voltage_async`` /
    ``set_rails_async`` / ``set_rails_sharded_async`` dispatch. The planes
    are already usable (JAX async dispatch); ``harvest()`` performs the one
    host sync the synchronous method would have done inline and returns
    exactly the stats object it would have returned — same counters,
    same reduction, same denominators (tested bit-identical).
    """

    counters: list
    finish: Any  # callable(list[np.ndarray]) -> FaultStats-family object

    def harvest(self):
        return self.finish([np.asarray(c) for c in self.counters])


# Double-buffer donation (DESIGN.md §18): the stale faulty planes handed
# back to XLA are matched to the step's outputs by shape/dtype
# (input-output aliasing), so on the compiled lane (TPU) the steady-state
# soak rotates two plane buffers instead of allocating a third every step.
# The CPU doesn't honor donation — it takes the plain launch with identical
# math.
def _donation_supported() -> bool:
    return kbackend.compiled_available()


@functools.partial(
    jax.jit,
    static_argnames=("codec", "reencode"),
    donate_argnums=(6, 7, 8),
    keep_unused=True,
)
def _fused_step_donated(
    lo, hi, check, mlo, mhi, mpar, stale_lo, stale_hi, stale_check,
    *, codec, reencode,
):
    # The stale planes contribute storage, not values: the kernel math is
    # exactly kops.inject_scrub.
    del stale_lo, stale_hi, stale_check
    return kops.inject_scrub(
        lo, hi, check, mlo, mhi, mpar, codec=codec, reencode=reencode
    )


@functools.partial(
    jax.jit,
    static_argnames=("codec", "reencode", "n_domains"),
    donate_argnums=(7, 8, 9),
    keep_unused=True,
)
def _fused_domains_step_donated(
    lo, hi, check, mlo, mhi, mpar, dom_ids, stale_lo, stale_hi, stale_check,
    *, n_domains, codec, reencode,
):
    del stale_lo, stale_hi, stale_check
    return kops.inject_scrub_domains(
        lo, hi, check, mlo, mhi, mpar, dom_ids, n_domains,
        codec=codec, reencode=reencode,
    )


@dataclasses.dataclass(frozen=True)
class Slot:
    """Arena placement of one EccWeight leaf's planes."""

    key: str
    offset: int
    size: int
    shape: tuple
    domain: str = "all"


@dataclasses.dataclass
class _CodecGroup:
    """Slots sharing one ECC scheme: one fused launch per voltage step."""

    name: str
    codec: Any  # codes.Codec
    slot_ids: tuple  # indices into store.slots, arena order
    offsets: tuple  # per-slot word offset inside the group arena
    n_words: int
    lo: Any  # (n_words,) uint32 clean data
    hi: Any
    check: Any  # (n_words,) codec check dtype
    dom_ids: Any  # (n_words,) jnp int32 (store-global domain indices)
    dom_ids_np: np.ndarray
    device_field: DeviceFaultField
    sharded: Any = None  # _ShardedGroup when the store is mesh-sharded


@dataclasses.dataclass
class _ShardedGroup:
    """Mesh-partitioned view of one codec group (DESIGN.md §13).

    The group planes padded to a shard multiple and placed with the arena
    NamedSharding: each reliability shard (chip) owns ``local_words``
    contiguous words and draws their faults from its own per-shard stream
    inside the shard_map'd rail step.
    """

    seed: int  # the group's device-stream seed (shard 0 reproduces it)
    local_words: int
    pad: int
    lo: Any  # (n_shards * local_words,) uint32, sharded
    hi: Any
    check: Any
    dom: Any  # (n_shards * local_words,) int32, spill index on pad words


class PlaneStore:
    """Flat arena over a sequence of EccWeight leaves (clean planes, device).

    With a ``domain_key`` classifier the arena is partitioned into named
    memory domains (DESIGN.md §10): every slot belongs to one domain, and
    ``set_rails`` drives a separate rail voltage per domain through one fused
    inject+scrub launch (per codec group) with per-domain counter rows.
    ``profiles`` optionally gives each domain its own PlatformProfile
    (MoRS-style per-instance fault behaviour); rails without a dedicated
    profile use ``platform``. ``codecs`` maps domains to registered ECC
    schemes (str for all domains, dict for per-domain choices).
    """

    def __init__(
        self,
        leaves,
        keys,
        platform: PlatformProfile,
        seed: int = 0,
        mask_source: str = "host",
        domain_key=None,
        profiles=None,
        codecs=None,
        mesh=None,
        env=None,
    ):
        assert mask_source in ("host", "device"), mask_source
        assert len(leaves) == len(set(keys)), "leaf keys must be unique"
        self.platform = platform
        self.seed = int(seed)
        self.mask_source = mask_source
        self.mesh = mesh
        # Environment scenario (DESIGN.md §14): name or EnvironmentProfile.
        # Flux multiplier enters through domain_profile (so every rate
        # consumer sees the scaled curve), the burst shape through the fault
        # fields, aging drift through set_rails_sharded's per-shard rate
        # multipliers. env=None is the historical store bit-for-bit.
        self.env = scenario.resolve(env)
        self._soak = 0  # sharded scrub intervals stepped (the drift clock)
        # A disabled burst shape normalizes to None so the fault fields (and
        # the make_rail_step cache) take the historical path exactly.
        burst = self.env.burst if self.env else None
        self._burst = burst if (burst is not None and burst.enabled) else None
        if mesh is not None:
            # Mesh-sharded arena (DESIGN.md §13): masks must be generated
            # inside shard_map from per-shard streams — the host oracle has
            # no shard identity.
            assert mask_source == "device", "sharded arenas need device masks"
        self._profiles = dict(profiles or {})
        self._external_words: dict[str, int] = {}
        self._external_shard_words: dict[int, dict[str, int]] = {}
        self._external_codecs: dict[str, str] = {}
        classify = domain_key if domain_key is not None else (lambda _k: "all")
        slots, off = [], 0
        los, his, pars = [], [], []
        for key, leaf in zip(keys, leaves):
            size = int(leaf.lo.size)
            slots.append(
                Slot(key, off, size, tuple(leaf.lo.shape), str(classify(key)))
            )
            los.append(leaf.lo.reshape(-1))
            his.append(leaf.hi.reshape(-1))
            pars.append(leaf.parity.reshape(-1))
            off += size
        # The arena owns the clean plane data; keep only plane-free leaf
        # metadata (scale/k/n/fuse) so the store doesn't hold a second full
        # copy of every plane.
        self._leaves = [
            dataclasses.replace(leaf, lo=None, hi=None, parity=None)
            for leaf in leaves
        ]
        self.slots = tuple(slots)
        self.n_words = off
        if los:
            self.lo = jnp.concatenate(los)
            self.hi = jnp.concatenate(his)
            self.parity = jnp.concatenate(pars)  # SECDED check bits, as packed
        else:
            self.lo = jnp.zeros((0,), jnp.uint32)
            self.hi = jnp.zeros((0,), jnp.uint32)
            self.parity = jnp.zeros((0,), jnp.uint8)
        # Domain order: first appearance in arena order (stable across runs
        # for a fixed leaf ordering); this is the counter row order.
        self.domains = tuple(dict.fromkeys(s.domain for s in self.slots))
        self._dom_index = {d: i for i, d in enumerate(self.domains)}
        dom_ids = np.zeros(self.n_words, np.int32)
        for s in self.slots:
            dom_ids[s.offset : s.offset + s.size] = self._dom_index[s.domain]
        self._dom_ids_np = dom_ids
        self._dom_ids = jnp.asarray(dom_ids) if self.n_words else jnp.zeros((0,), jnp.int32)
        # Per-domain codec choices (default: the built-in SECDED everywhere).
        if codecs is None:
            codecs = {}
        elif isinstance(codecs, str):
            codecs = {d: codecs for d in self.domains}
        self._codecs = {d: str(codecs.get(d, DEFAULT_CODEC)) for d in self.domains}
        for name in self._codecs.values():
            codes.get(name)  # fail fast on unknown codecs
        self._build_groups()

    # -- codec groups --------------------------------------------------------
    def codec_of(self, domain: str) -> str:
        return self._codecs.get(domain, DEFAULT_CODEC)

    def _build_groups(self) -> None:
        """(Re)build the per-codec sub-arenas from the master clean planes.

        The uniform-default case — every domain on one codec — produces a
        single group whose planes alias the master arrays (no copy, no
        re-encode for SECDED), keeping the historical memory footprint,
        launch count, and bit patterns.
        """
        by_codec: dict[str, list[int]] = {}
        for si, s in enumerate(self.slots):
            by_codec.setdefault(self.codec_of(s.domain), []).append(si)
        single = len(by_codec) == 1
        groups = []
        for cname, slot_ids in by_codec.items():
            codec = codes.get(cname)
            offsets, off = [], 0
            for si in slot_ids:
                offsets.append(off)
                off += self.slots[si].size
            if single:
                lo, hi = self.lo, self.hi
                dom_np = self._dom_ids_np
                dom = self._dom_ids
                dseed = self.seed
            else:
                sel = np.concatenate(
                    [
                        np.arange(
                            self.slots[si].offset,
                            self.slots[si].offset + self.slots[si].size,
                        )
                        for si in slot_ids
                    ]
                )
                idx = jnp.asarray(sel)
                lo, hi = self.lo[idx], self.hi[idx]
                dom_np = self._dom_ids_np[sel]
                dom = jnp.asarray(dom_np)
                # A stable, codec-keyed stream: regrouping must not change
                # the masks of groups whose membership did not change.
                dseed = (self.seed ^ zlib.crc32(cname.encode())) & 0x7FFFFFFF
            if cname == DEFAULT_CODEC and single:
                check = self.parity  # the leaves arrived SECDED-encoded
            else:
                check = kops.encode(lo, hi, codec=cname) if off else jnp.zeros(
                    (0,), jnp.dtype(codec.check_dtype)
                )
            groups.append(
                _CodecGroup(
                    name=cname,
                    codec=codec,
                    slot_ids=tuple(slot_ids),
                    offsets=tuple(offsets),
                    n_words=off,
                    lo=lo,
                    hi=hi,
                    check=check,
                    dom_ids=dom,
                    dom_ids_np=dom_np,
                    device_field=DeviceFaultField(
                        self.env.scale_profile(self.platform)
                        if self.env
                        else self.platform,
                        off,
                        seed=dseed,
                        n_check=codec.n_check,
                        burst=self._burst,
                    ),
                )
            )
        self._groups = groups
        # Depth-2 plane buffer ring per codec group (§18): regrouping (codec
        # escalation) changes plane geometry, so stale buffers are dropped.
        self._plane_hist: dict[str, list] = {}
        if self.mesh is not None:
            self._build_sharded_groups()
        # Per-leaf host oracle fields, keyed like the historical per-leaf
        # path; the check-bitplane count follows the slot's codec.
        self._host_fields = {}
        for g in self._groups:
            for si in g.slot_ids:
                s = self.slots[si]
                self._host_fields[s.key] = FaultField(
                    self.domain_profile(s.domain),
                    s.size,
                    seed=leaf_seed(self.seed, s.key),
                    n_check=g.codec.n_check,
                    burst=self._burst,
                )

    # -- mesh sharding (DESIGN.md §13) ---------------------------------------
    @property
    def n_shards(self) -> int:
        """Reliability shard (chip) count; 0 when the store is unsharded."""
        if self.mesh is None:
            return 0
        from repro.distributed.sharding import reliability_shards

        return reliability_shards(self.mesh)

    def _build_sharded_groups(self) -> None:
        """Partition every codec group's planes across the mesh.

        Word ``w`` of a group lands on shard ``w // local_words``; pad words
        (zero data, spill domain index) fill the tail so every shard owns the
        same word count. A 1-shard mesh adds no padding and shard 0 keeps the
        group's device-stream seed, so the sharded step is bit-identical to
        the unsharded device path (tested in tests/test_meshrel.py).
        """
        from repro.distributed import meshrel

        n_shards = self.n_shards
        sigmas = {self.domain_profile(d).row_sigma for d in self.domains}
        assert len(sigmas) <= 1, (
            "sharded arenas share one row-weakness field per chip; "
            f"got sigmas {sorted(sigmas)}"
        )
        sharding = meshrel.arena_sharding(self.mesh)
        spill = len(self.domains)
        self._shard_words = [dict.fromkeys(self.domains, 0) for _ in range(n_shards)]
        for g in self._groups:
            padded = meshrel.pad_to_shards(g.n_words, n_shards)
            pad = padded - g.n_words
            dom_np = np.concatenate(
                [g.dom_ids_np, np.full(pad, spill, np.int32)]
            ) if pad else g.dom_ids_np
            local = padded // n_shards if n_shards else 0
            for s in range(n_shards):
                counts = np.bincount(
                    dom_np[s * local : (s + 1) * local], minlength=spill + 1
                )
                for i, d in enumerate(self.domains):
                    self._shard_words[s][d] += int(counts[i])

            def padded_plane(x, dtype=None):
                x = jnp.asarray(x)
                if pad:
                    x = jnp.concatenate([x, jnp.zeros((pad,), x.dtype)])
                return jax.device_put(x, sharding)

            g.sharded = _ShardedGroup(
                seed=g.device_field.seed,
                local_words=local,
                pad=pad,
                lo=padded_plane(g.lo),
                hi=padded_plane(g.hi),
                check=padded_plane(g.check),
                dom=jax.device_put(jnp.asarray(dom_np), sharding),
            )

    def shard_words_by_domain(self) -> list:
        """Per-shard {domain: words} (power weighting + per-shard telemetry
        denominators), arena slices plus shard-registered external domains."""
        assert self.mesh is not None
        out = []
        for s in range(self.n_shards):
            d = dict(self._shard_words[s])
            for dom, w in self._external_shard_words.get(s, {}).items():
                d[dom] = d.get(dom, 0) + w
            out.append(d)
        return out

    def _normalize_schedule(self, schedule) -> list:
        """One {domain: voltage} dict per shard from any accepted form:
        a single dict (uniform), a sequence of per-shard dicts, or a dict
        whose values are per-shard sequences."""
        n = self.n_shards
        if isinstance(schedule, dict):
            if any(np.ndim(v) for v in schedule.values()):
                for d, v in schedule.items():
                    assert np.ndim(v) == 0 or np.size(v) == n, (
                        f"domain {d!r}: {np.size(v)} voltages for {n} shards"
                    )
                per = []
                for s in range(n):
                    per.append(
                        {
                            d: float(np.asarray(v).reshape(-1)[s])
                            if np.ndim(v)
                            else float(v)
                            for d, v in schedule.items()
                        }
                    )
                return per
            # independent dicts: a caller adjusting one shard's entry must
            # not silently retune every chip
            return [dict(schedule) for _ in range(n)]
        schedule = [dict(s) for s in schedule]
        assert len(schedule) == n, (len(schedule), n)
        return schedule

    def set_rails_sharded_async(self, schedule, ecc: bool = True):
        """Asynchronously dispatched ``set_rails_sharded``: the collective-
        free shard_map'd launches (meshrel.make_rail_step) go out per codec
        group and the (n_shards, n_domains, 8) per-shard counter blocks stay
        on device until ``pending.harvest()`` — a soak of N intervals pays
        one counter sync instead of N (DESIGN.md §18)."""
        from repro.core.telemetry import ShardFaultStats
        from repro.distributed import meshrel

        assert self.mesh is not None, "set_rails_sharded needs a mesh"
        schedule = self._normalize_schedule(schedule)
        n_shards = self.n_shards
        if self.n_words == 0:
            empty = ShardFaultStats(
                [DomainFaultStats(shard=s) for s in range(n_shards)]
            )
            return list(self._leaves), PendingFaultStats([], lambda _c: empty)
        profiles = {d: self.domain_profile(d) for d in self.domains}
        sigma = next(iter({p.row_sigma for p in profiles.values()}))
        # One scrub interval per rail step: the aging clock. At env=None or
        # drift_sigma=0 every multiplier is exactly 1.0 and the table is the
        # historical one bit-for-bit.
        self._soak += 1
        mult = (
            np.array(
                [
                    scenario.aging_multiplier(s, self._soak, self.env, self.seed)
                    for s in range(n_shards)
                ],
                np.float32,
            )
            if self.env is not None
            else None
        )
        rates = meshrel.schedule_rates(
            schedule, self.domains, profiles, n_shards, shard_multipliers=mult
        )
        counters, planes = [], {}
        # Faulty planes are assembled on shard 0's chip; the engine copies
        # the assembled weights to every replica's own chip before serving
        # (a TP mesh would consume them sharded in place instead).
        home = meshrel.shard_devices(self.mesh)[0]
        for g in self._groups:
            sg = g.sharded
            step = meshrel.make_rail_step(
                self.mesh, sg.local_words, len(self.domains), g.name,
                sg.seed, float(sigma), reencode=not ecc,
                burst=self._burst,
            )
            flo, fhi, fpar, per_shard = step(
                sg.lo, sg.hi, sg.check, sg.dom, jnp.asarray(rates)
            )
            counters.append(per_shard)
            planes[g.name] = tuple(
                jax.device_put(x, home) for x in (flo, fhi, fpar)
            )

        def finish(host_counters):
            total = np.zeros((n_shards, len(self.domains), 8), np.int64)
            for c in host_counters:
                total += c
            return ShardFaultStats.from_counter_blocks(
                total, self.domains, self.shard_words_by_domain()
            )

        return self._slice_leaves(planes), PendingFaultStats(counters, finish)

    def set_rails_sharded(self, schedule, ecc: bool = True):
        """Per-(shard, domain) voltage step across the whole mesh.

        One collective-free shard_map'd fused inject+scrub launch per codec
        group: every shard injects its own fault population at its own rails
        and tallies its own counter rows; only the (n_shards, n_domains, 8)
        counter block crosses to host (any fleet aggregate is the caller's
        one-per-soak ``meshrel.fold_counters``). Returns
        (faulty_leaves, ShardFaultStats). A uniform schedule on a 1-shard
        mesh is bit-identical to ``set_rails`` with device masks.
        """
        leaves, pending = self.set_rails_sharded_async(schedule, ecc=ecc)
        return leaves, pending.harvest()

    def set_domain_codec(self, domain: str, codec_name: str) -> None:
        """Re-protect ``domain`` under another registered code (the
        controller escalation path). Check planes are re-encoded from the
        clean master data; fault fields follow the new bitplane geometry.
        Other domains' groups are rebuilt with identical membership, seeds
        and geometry, so their mask streams are unchanged."""
        codes.get(codec_name)  # validate early
        assert domain in self.domains, (domain, self.domains)
        if self.codec_of(domain) == codec_name:
            return
        self._codecs[domain] = str(codec_name)
        self._build_groups()

    def codecs_by_domain(self) -> dict:
        out = {d: self.codec_of(d) for d in self.domains}
        out.update(self._external_codecs)
        return out

    def check_bits_by_domain(self) -> dict:
        """Check bits per 64-bit word for every domain (power weighting)."""
        return {d: codes.get(c).n_check for d, c in self.codecs_by_domain().items()}

    # -- domains -------------------------------------------------------------
    def domain_profile(self, domain: str) -> PlatformProfile:
        """The domain's fault curve, env-flux-scaled when an environment is
        set — every rate consumer (host fields, device rate vectors, the
        sharded rate tables, the engine's controllers) sees one curve."""
        prof = self._profiles.get(domain, self.platform)
        return self.env.scale_profile(prof) if self.env else prof

    def register_domain_words(
        self, domain: str, words: int, codec: str = DEFAULT_CODEC,
        shard: int | None = None,
    ) -> None:
        """Account storage that lives *outside* the weight arena — e.g. the
        paged KV cache (core/kvpages.py) — under a named domain.

        External domains join ``words_by_domain`` (power weighting, telemetry
        denominators) but not the arena's counter rows: their planes are not
        part of this store's fused inject+scrub launch, they carry their own
        fault machinery and report telemetry separately. ``codec`` records
        the external store's scheme for the redundancy-cost power weighting.
        ``shard`` attributes the words to one reliability shard's chip (mesh
        stores: each replica's KV arena is its own silicon); None registers
        them store-wide (the unsharded path).
        """
        if shard is None:
            self._external_words[str(domain)] = int(words)
        else:
            self._external_shard_words.setdefault(int(shard), {})[str(domain)] = (
                int(words)
            )
        self._external_codecs[str(domain)] = str(codec)

    def words_by_domain(self) -> dict:
        """Word count per domain (power weighting + telemetry denominators),
        arena slots plus any registered external domains (shard-registered
        externals contribute their cross-shard sum)."""
        counts = dict.fromkeys(self.domains, 0)
        for s in self.slots:
            counts[s.domain] += s.size
        for d, w in self._external_words.items():
            counts[d] = counts.get(d, 0) + w
        for per in self._external_shard_words.values():
            for d, w in per.items():
                counts[d] = counts.get(d, 0) + w
        return counts

    # -- masks ---------------------------------------------------------------
    def _group_host_masks(self, g: _CodecGroup, volts: dict):
        """Concatenated per-leaf oracle masks for one group (bit-identical to
        the per-leaf path: same fields, same seeds, same order)."""
        mlos, mhis, mpars = [], [], []
        for si in g.slot_ids:
            s = self.slots[si]
            mk = self._host_fields[s.key].masks(volts[s.domain])
            mlos.append(mk.lo)
            mhis.append(mk.hi)
            mpars.append(mk.parity)
        cat = lambda xs, dt: (
            jnp.asarray(np.concatenate(xs)) if xs else jnp.zeros((0,), dt)
        )
        return (
            cat(mlos, jnp.uint32),
            cat(mhis, jnp.uint32),
            cat(mpars, jnp.dtype(g.codec.check_dtype)),
        )

    def _group_rates(self, g: _CodecGroup, volts: dict) -> np.ndarray:
        """Per-word fault rate vector for a {domain: voltage} schedule."""
        rates = np.zeros(g.n_words, np.float32)
        for d, i in self._dom_index.items():
            rates[g.dom_ids_np == i] = self.domain_profile(d).fault_rate(
                float(volts[d])
            )
        return rates

    def _group_masks(self, g: _CodecGroup, v):
        volts = v if isinstance(v, dict) else {d: v for d in self.domains}
        if self.mask_source == "device":
            # Per-domain profiles make the rate a function of the word's
            # domain even under a scalar rail, so route through the rate
            # vector (the host path gets this for free from its per-leaf
            # fields); profile-less stores keep the scalar fast path.
            if isinstance(v, dict) or self._profiles:
                return g.device_field.masks_for_rates(self._group_rates(g, volts))
            return g.device_field.masks(v)
        return self._group_host_masks(g, volts)

    # Legacy single-group helpers (kept for the uniform-codec arena).
    def host_masks(self, v):
        assert len(self._groups) == 1, "host_masks is a single-group helper"
        volts = v if isinstance(v, dict) else {d: v for d in self.domains}
        return self._group_host_masks(self._groups[0], volts)

    def masks(self, v):
        assert len(self._groups) == 1, "masks is a single-group helper"
        return self._group_masks(self._groups[0], v)

    # -- the batched voltage step --------------------------------------------
    def _stale_planes(self, name: str):
        """Pop the two-steps-old faulty planes for donation (None until the
        ring has depth 2, or off compiled backends)."""
        if not _donation_supported():
            return None
        hist = self._plane_hist.setdefault(name, [])
        return hist.pop(0) if len(hist) >= 2 else None

    def _retire_planes(self, name: str, planes) -> None:
        hist = self._plane_hist.setdefault(name, [])
        hist.append(planes)
        del hist[:-2]

    def _fused_group_step(self, g: _CodecGroup, mlo, mhi, mpar, *,
                          reencode: bool, domains: bool):
        """One fused inject+scrub launch for a codec group, donating the
        stale buffer ring slot on compiled backends (§18)."""
        stale = self._stale_planes(g.name)
        if domains:
            if stale is not None:
                out = _fused_domains_step_donated(
                    g.lo, g.hi, g.check, mlo, mhi, mpar, g.dom_ids, *stale,
                    n_domains=len(self.domains), codec=g.name,
                    reencode=reencode,
                )
            else:
                out = kops.inject_scrub_domains(
                    g.lo, g.hi, g.check, mlo, mhi, mpar,
                    g.dom_ids, len(self.domains), codec=g.name,
                    reencode=reencode,
                )
        elif stale is not None:
            out = _fused_step_donated(
                g.lo, g.hi, g.check, mlo, mhi, mpar, *stale,
                codec=g.name, reencode=reencode,
            )
        else:
            out = kops.inject_scrub(
                g.lo, g.hi, g.check, mlo, mhi, mpar,
                codec=g.name, reencode=reencode,
            )
        self._retire_planes(g.name, out[:3])
        return out

    def set_voltage_async(self, v: float, ecc: bool = True):
        """Asynchronously dispatched ``set_voltage``: the fused launches go
        out, nothing syncs to host. Returns (faulty_leaves,
        PendingFaultStats) immediately — the leaves are usable right away
        (async dispatch) and ``pending.harvest()`` is the one deferred
        counter sync, so decode work dispatched in between overlaps the
        scrub instead of serializing behind it (DESIGN.md §18).

        Donation contract: on compiled backends the launch donates the
        group's two-steps-stale faulty planes; callers must not hold plane
        references across two or more voltage steps.
        """
        assert self.mesh is None, "mesh-sharded stores step via set_rails_sharded"
        if self.n_words == 0:
            return list(self._leaves), PendingFaultStats(
                [], lambda _c: FaultStats()
            )
        counters, planes = [], {}
        for g in self._groups:
            mlo, mhi, mpar = self._group_masks(g, v)
            flo, fhi, fpar, cnt = self._fused_group_step(
                g, mlo, mhi, mpar, reencode=not ecc, domains=False
            )
            counters.append(cnt)
            planes[g.name] = (flo, fhi, fpar)

        def finish(host_counters, n_words=self.n_words):
            total = np.zeros(8, np.int64)
            for c in host_counters:
                total += c
            return FaultStats.from_counters(total, words=n_words)

        return self._slice_leaves(planes), PendingFaultStats(counters, finish)

    def set_voltage(self, v: float, ecc: bool = True):
        """One fused inject+scrub launch per codec group for the whole store.

        Returns (faulty_leaves, FaultStats). faulty_leaves are the input
        EccWeight leaves with lo/hi/parity replaced by arena slices at rail
        voltage ``v`` (scale/k/n/fuse untouched).
        """
        leaves, pending = self.set_voltage_async(v, ecc=ecc)
        return leaves, pending.harvest()

    def set_rails_async(self, volts: dict, ecc: bool = True):
        """Asynchronously dispatched ``set_rails`` (same deferred-harvest
        and donation contract as ``set_voltage_async``)."""
        assert self.mesh is None, "mesh-sharded stores step via set_rails_sharded"
        missing = set(self.domains) - set(volts)
        assert not missing, f"rails missing for domains: {sorted(missing)}"
        if self.n_words == 0:
            return list(self._leaves), PendingFaultStats(
                [], lambda _c: DomainFaultStats()
            )
        counters, planes = [], {}
        for g in self._groups:
            mlo, mhi, mpar = self._group_masks(g, dict(volts))
            flo, fhi, fpar, cnt = self._fused_group_step(
                g, mlo, mhi, mpar, reencode=not ecc, domains=True
            )
            counters.append(cnt)
            planes[g.name] = (flo, fhi, fpar)

        def finish(host_counters):
            total = np.zeros((len(self.domains), 8), np.int64)
            for c in host_counters:
                total += c
            return FaultStats.from_counter_matrix(
                total, self.domains, self.words_by_domain()
            )

        return self._slice_leaves(planes), PendingFaultStats(counters, finish)

    def set_rails(self, volts: dict, ecc: bool = True):
        """One fused inject+scrub launch per codec group with a separate rail
        per domain.

        ``volts`` maps every domain name to its rail voltage. Returns
        (faulty_leaves, DomainFaultStats) — one counter row per domain
        crosses to host. A uniform schedule is bit-identical to
        ``set_voltage`` (same fields/streams, same kernel math; tested).
        """
        leaves, pending = self.set_rails_async(volts, ecc=ecc)
        return leaves, pending.harvest()

    def _slice_leaves(self, planes: dict):
        """Reassemble per-leaf EccWeight views from per-group faulty planes."""
        out: list = [None] * len(self.slots)
        for g in self._groups:
            flo, fhi, fpar = planes[g.name]
            for si, off in zip(g.slot_ids, g.offsets):
                s = self.slots[si]
                out[si] = dataclasses.replace(
                    self._leaves[si],
                    lo=flo[off : off + s.size].reshape(s.shape),
                    hi=fhi[off : off + s.size].reshape(s.shape),
                    parity=fpar[off : off + s.size].reshape(s.shape),
                )
        return out
