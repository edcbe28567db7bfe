"""Batched serving engine with ECC-protected weights under an undervolted rail.

The paper's §IV evaluation as a service. The served layout is
``mode="inline"``: the attention, MLP and SSM weight matrices (and the
embedding, on multi-rail engines) are replaced by int8 SECDED planes
(`kops.EccWeight`) held in one plane arena (`core/planestore.PlaneStore`), and
every forward pass reads them through the fused decode-matmul kernel
(DESIGN.md §2). A voltage step is one fused inject+scrub launch over the
arena; the DED-canary controllers consume its telemetry between rounds and
walk each rail down until the first detected-uncorrectable event. The KV
cache is served from SECDED pages (`serve`). Power comes from the calibrated
Table-I model.

``mode="domain"`` (the config default) keeps the raw weight bits in an
`EccMemoryDomain` ("BRAM") and refreshes a decoded view per voltage; no
benchmark cell serves it.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro import codes
from repro.configs import shapes
from repro.core import (
    EscalationPolicy,
    MeshRailController,
    MultiRailController,
    UndervoltController,
    scenario,
    voltage as vmod,
)
from repro.core import statestore
from repro.core.kvpages import PAGE_TOKENS, KVGeometry, KVPageArena
from repro.core.memory import EccMemoryDomain
from repro.core.planestore import PlaneStore
from repro.core.telemetry import DomainFaultStats, FaultStats, ShardFaultStats
from repro.distributed import meshrel
from repro.kernels import backend as kbackend
from repro.kernels import ops as kops
from repro.models import lm
from repro.models.base import ModelConfig
from repro.obs import profile as obs_profile
from repro.serving import scheduler as sched
from repro.serving import steps as serve_steps


class ReliabilityConfigError(ValueError, AssertionError):
    """An invalid reliability-config combination.

    Subclasses ``ValueError`` (the typed contract ``validate()`` documents)
    *and* ``AssertionError`` (what the historical inline ``assert`` guards
    raised, and what existing callers catch)."""


@dataclasses.dataclass(frozen=True)
class FaultModelConfig:
    """How faults are generated and applied (DESIGN.md §7/§14)."""

    # "host": NumPy FaultField masks, one stream per leaf (planestore.leaf_seed);
    # "device": counter-based jax.random masks, never materialised on host
    mask_source: str = "host"
    # Environment scenario: None (historical i.i.d. stream, bit-for-bit), a
    # name from scenario.ENVIRONMENTS ("consumer" / "avionics" / "space"), or
    # an EnvironmentProfile. Scales every domain's fault flux, shapes the
    # masks into correlated multi-bit bursts, and drifts each mesh shard's
    # rate over the soak.
    environment: Any = None
    # Override the environment's aging-drift sigma (scenario.resolve); a bare
    # drift with environment=None gets the neutral 1x-flux burst-free env.
    drift: float | None = None


@dataclasses.dataclass(frozen=True)
class RailsConfig:
    """Voltage-rail topology and controller tuning (DESIGN.md §10/§13)."""

    # partition the plane arena into memory domains (configs/shapes.domain_of),
    # each with its own closed-loop rail
    multi_rail: bool = False
    # mesh engines: "uniform" locks one schedule at the worst shard's first
    # DED; "per_shard" walks every chip to its own V_min
    policy: str = "uniform"
    # >0: per-domain fault-curve variation (lognormal sigma) modelling
    # block-to-block differences (arXiv:2005.04737 / MoRS); 0: shared curve
    spread: float = 0.0
    step_v: float = 0.01
    # warm-start voltage for the canary search (None -> v_nom); the
    # guardband [v_min, v_nom] is fault-free by definition, so starting at
    # its edge saves ~40 no-op rounds without changing the lock point
    start_v: float | None = None
    # locked rails re-trip under drift: retreat another backoff step instead
    # of holding (core/controller.py `adaptive`)
    adaptive: bool = False


@dataclasses.dataclass(frozen=True)
class ProtectionConfig:
    """What is protected and under which ECC schemes (DESIGN.md §12)."""

    # a registered codec name for every domain, or a {domain: name} mapping
    # (unnamed domains keep the built-in secded72; the dict form needs
    # multi_rail)
    codecs: Any = None
    # DED-canary escalation ladder (multi-rail only): an EscalationPolicy or
    # a tuple of codec names weakest -> strongest. On a DED trip a rail steps
    # up its code instead of retreating; the redundancy cost lands in
    # power_report.
    escalation: Any = None
    # include the embedding table in the protected arena (None -> multi_rail:
    # single-rail engines keep the attn/mlp-only protected set)
    embed: bool | None = None


@dataclasses.dataclass(frozen=True)
class CanaryConfig:
    """DED/accuracy canary behavior (DESIGN.md §15)."""

    # >0 reserves this many fixed canary prompts; each autotune round
    # greedy-decodes them against a cached clean-nominal reference rollout
    # and feeds the divergence score (1 - mean matched-prefix fraction,
    # [0, 1]) to the controller alongside the DED counters. Inline mode only.
    prompts: int = 0
    # decoded continuation length per canary prompt (prompt length is
    # core/campaign.CANARY_PROMPT_LEN)
    tokens: int = 12
    # canary divergence scores above this trip the rail (escalate if a ladder
    # step remains, else back off + lock) even when the DED counters are
    # clean; None records but never trips
    divergence_slo: float | None = None
    # also treat SILENT (ground-truth-only) events as canary trips
    paranoid: bool = False


@dataclasses.dataclass(frozen=True)
class ReliabilityConfig:
    """Reliability knobs for a ServingEngine.

    Five ungrouped scalars plus four grouped sub-configs — ``fault_model``
    (:class:`FaultModelConfig`), ``rails`` (:class:`RailsConfig`),
    ``protection`` (:class:`ProtectionConfig`) and ``canary``
    (:class:`CanaryConfig`), each defaulting to its own defaults, never
    None. ``validate()`` — called by ``ServingEngine.__init__`` — raises
    :class:`ReliabilityConfigError` (a ``ValueError``) on contradictory
    combinations.
    """

    platform: str = "vc707"
    ecc: bool = True
    voltage: float | None = None  # None -> nominal
    mode: str = "domain"  # domain | inline
    seed: int = 0
    fault_model: FaultModelConfig = dataclasses.field(default_factory=FaultModelConfig)
    rails: RailsConfig = dataclasses.field(default_factory=RailsConfig)
    protection: ProtectionConfig = dataclasses.field(default_factory=ProtectionConfig)
    canary: CanaryConfig = dataclasses.field(default_factory=CanaryConfig)

    def validate(self, *, mesh=None) -> "ReliabilityConfig":
        """Reject contradictory combinations with a typed error.

        Raises :class:`ReliabilityConfigError` (a ``ValueError``) and returns
        ``self`` so ``rel.validate()`` chains. ``mesh`` enables the extra
        mesh-engine constraints (DESIGN.md §13)."""

        def _require(cond: bool, msg: str):
            if not cond:
                raise ReliabilityConfigError(msg)

        _require(
            self.mode in ("domain", "inline"),
            f"mode must be 'domain' or 'inline', got {self.mode!r}",
        )
        _require(
            self.platform in vmod.PLATFORMS,
            f"unknown platform {self.platform!r}",
        )
        rails, prot = self.rails, self.protection
        _require(
            rails.policy in ("uniform", "per_shard"),
            f"rails.policy must be 'uniform' or 'per_shard', got "
            f"{rails.policy!r}",
        )
        if self.mode == "domain":
            _require(
                prot.codecs in (None, "secded72"),
                "domain mode stores raw bits behind the built-in SECDED; "
                "codec selection needs mode='inline'",
            )
        else:
            _require(
                rails.multi_rail
                or prot.codecs is None
                or isinstance(prot.codecs, str),
                "per-domain codec dicts need multi_rail=True",
            )
        if kbackend.resolve() == "compiled":
            names = set(shapes.domain_codecs(prot.codecs).values())
            if prot.escalation is not None:
                names |= set(self.escalation_policy.ladder)
            lut = sorted(n for n in names if codes.get(n).lut_input_arrays())
            _require(
                not lut,
                f"codec(s) {lut} resolve syndromes with a dense-LUT gather "
                "(codes/base.py Codec.classify_jnp: jnp.take over a "
                "2**n_check table), which Mosaic cannot lower on the "
                "compiled TPU lane ('Only 2D gather is supported'); choose "
                "gather-free codecs (parity65, secded72, ileave88) in "
                "`codecs` and the escalation ladder",
            )
        if mesh is not None:
            _require(
                rails.multi_rail and self.mode == "inline",
                "mesh engines drive the multi-rail plane arena",
            )
            _require(
                self.fault_model.mask_source == "device",
                "mesh engines need device masks (per-shard streams live "
                "inside shard_map)",
            )
            _require(
                rails.policy == "uniform" or prot.escalation is None,
                "per-shard codec escalation needs per-shard plane groups; "
                "use rails.policy='uniform' with an escalation ladder",
            )
        return self

    @property
    def embed_protected(self) -> bool:
        embed = self.protection.embed
        return self.rails.multi_rail if embed is None else embed

    @property
    def environment_profile(self):
        return scenario.resolve(
            self.fault_model.environment, drift=self.fault_model.drift
        )

    @property
    def escalation_policy(self) -> EscalationPolicy | None:
        escalation = self.protection.escalation
        if escalation is None or isinstance(escalation, EscalationPolicy):
            return escalation
        return EscalationPolicy(ladder=tuple(escalation))


def _decode_gather_table(ew: kops.EccWeight, codec: str = "secded72") -> jnp.ndarray:
    """ECC-read an EccWeight back to a dequantized float (K, N) table.

    Gather-read tables (the embedding) cannot go through the fused
    decode-matmul kernel; their ECC read happens when the rail moves, exactly
    like domain mode's refresh — at nominal voltage this is the identity on
    the quantized values. Weight leaves protected by a non-SECDED codec take
    the same path: the fused matmul kernel reads Hsiao planes only, so
    stronger codes pay a decode-at-refresh materialisation instead
    (DESIGN.md §12).
    """
    from repro.kernels import ref as kref

    lo, hi, _ = kops.decode(ew.lo, ew.hi, ew.parity, codec=codec)
    if lo.ndim == 3:  # layer-stacked (G, K/8, N): unpack per group
        w_i8 = jnp.stack(
            [kref.unpack_ecc_weights(lo[g], hi[g]) for g in range(lo.shape[0])]
        )[..., : ew.n]
        return w_i8.astype(jnp.float32) * ew.scale[:, None, :]
    w_i8 = kref.unpack_ecc_weights(lo, hi)[:, : ew.n]
    return w_i8.astype(jnp.float32) * ew.scale


def _pack_stacked(leaf) -> kops.EccWeight:
    """Pack a layer-stacked (G, K, N) float weight into stacked ECC planes.

    The scan over layer groups slices the leading G off every plane leaf, so
    the in-scan view is exactly the 2D EccWeight the kernels expect."""
    g = leaf.shape[0]
    packed = [kops.pack_ecc_weights(jnp.asarray(leaf[i], jnp.float32)) for i in range(g)]
    return kops.EccWeight(
        lo=jnp.stack([p.lo for p in packed]),
        hi=jnp.stack([p.hi for p in packed]),
        parity=jnp.stack([p.parity for p in packed]),
        scale=jnp.stack([p.scale for p in packed]),
        k=packed[0].k,
        n=packed[0].n,
        fuse=packed[0].fuse,
    )


# memory domains whose matrices the inline layout reads through ecc_matmul
_MATMUL_DOMAINS = ("attention", "mlp", "ssm")


def protect_params_inline(
    params, cfg: ModelConfig, seed: int = 0, include_embed: bool = False
):
    """Replace weight matrices (K%8==0) with SECDED int8 EccWeight planes.

    Handles both plain (K, N) and layer-stacked (G, K, N) leaves. Returns
    (new_params, plane_sizes) where plane_sizes maps path -> word count
    (for voltage-dependent fault injection). ``include_embed`` extends the
    protected set to the embedding table (multi-rail engines protect it as
    its own voltage domain).
    """
    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    out, fields = [], {}
    for path, leaf in flat:
        key = jax.tree_util.keystr(path)
        domain = shapes.domain_of(key, default="")
        wanted = domain in _MATMUL_DOMAINS or (include_embed and domain == "embedding")
        if not hasattr(leaf, "ndim") or not wanted:
            out.append(leaf)
            continue
        stacked = key.startswith("['blocks']")  # (G, ...) layer stacks
        if leaf.ndim == 2 and not stacked and leaf.shape[0] % 8 == 0 and min(leaf.shape) >= 64:
            ew = kops.pack_ecc_weights(jnp.asarray(leaf, jnp.float32))
        elif leaf.ndim == 3 and leaf.shape[1] % 8 == 0 and min(leaf.shape[1:]) >= 64:
            ew = _pack_stacked(leaf)
        else:
            out.append(leaf)
            continue
        out.append(ew)
        fields[key] = ew.lo.size
    return jax.tree_util.tree_unflatten(treedef, out), fields


class ServingEngine:
    def __init__(
        self,
        cfg: ModelConfig,
        params,
        rel: ReliabilityConfig | None = None,
        max_len: int = 512,
        mesh=None,
        recorder=None,
    ):
        self.cfg = cfg
        self.rel = rel
        self.max_len = max_len
        self.mesh = mesh
        # Optional reliability flight recorder (obs.TraceRecorder): every
        # rail decision, serve-loop event and canary probe lands in one
        # causally-ordered deterministic trace. None (the default) is the
        # bit-identical zero-overhead path (DESIGN.md §17).
        self.recorder = recorder
        # One typed gate replaces the historical scattered inline asserts:
        # every contradictory combination (mesh-sharded reliability included,
        # DESIGN.md §13) raises ReliabilityConfigError before any state is
        # built.
        if rel is not None:
            rel.validate(mesh=mesh)
        elif mesh is not None:
            raise ReliabilityConfigError(
                "mesh engines drive the multi-rail plane arena "
                "(a ReliabilityConfig is required)"
            )
        self.platform = vmod.PLATFORMS[rel.platform] if rel else None
        self.controller = (
            UndervoltController(
                self.platform,
                step_v=rel.rails.step_v,
                paranoid=rel.canary.paranoid,
                start_v=rel.rails.start_v,
                divergence_slo=rel.canary.divergence_slo,
            )
            if rel and not rel.rails.multi_rail
            else None  # multi-rail controller is built once the arena exists
        )
        self._canary_ref = None  # clean-nominal canary rollout, built lazily
        self.rails = None  # {domain: voltage} when multi_rail; [dict] per shard on a mesh
        self.rail_stats = DomainFaultStats()  # cumulative per-domain telemetry
        self.shard_stats = ShardFaultStats()  # cumulative per-shard rows (mesh)
        self.stats = FaultStats()
        self._clean_params = params
        if rel is None:
            self.params = params
            self.domain = None
        elif rel.mode == "domain":
            self.domain = EccMemoryDomain(
                rel.platform, seed=rel.seed, ecc_enabled=rel.ecc,
                voltage=rel.voltage or 1.0,
            )
            self.domain.write_pytree("w", params)
            self.params = params  # refreshed by set_voltage
            self.set_voltage(self.domain.voltage)
        else:  # inline (validate() already rejected the contradictory combos)
            self.domain = None
            protected, _ = protect_params_inline(
                params, cfg, seed=rel.seed, include_embed=rel.embed_protected
            )
            # Plane arena: flatten once, record which flat slots hold
            # EccWeight planes, and key each by its tree path (the per-leaf
            # fault-field seeds depend on it).
            flat, self._inline_treedef = jax.tree_util.tree_flatten_with_path(
                protected,
                is_leaf=lambda x: isinstance(x, kops.EccWeight),
            )
            self._inline_template = [leaf for _, leaf in flat]
            self._ecc_slots = [
                (i, jax.tree_util.keystr(path))
                for i, (path, leaf) in enumerate(flat)
                if isinstance(leaf, kops.EccWeight)
            ]
            multi_rail = rel.rails.multi_rail
            rail_profiles = (
                vmod.derive_domain_profiles(
                    self.platform, shapes.MEMORY_DOMAINS,
                    spread=rel.rails.spread, seed=rel.seed,
                )
                if multi_rail and rel.rails.spread > 0
                else None
            )
            if multi_rail:
                store_codecs = shapes.domain_codecs(rel.protection.codecs)
            else:
                store_codecs = rel.protection.codecs
            self._store = PlaneStore(
                [self._inline_template[i] for i, _ in self._ecc_slots],
                [key for _, key in self._ecc_slots],
                self.platform,
                seed=rel.seed,
                mask_source=rel.fault_model.mask_source,
                domain_key=shapes.domain_of if multi_rail else None,
                profiles=rail_profiles,
                codecs=store_codecs,
                mesh=mesh,
                env=rel.environment_profile,
            )
            self.voltage = rel.voltage or self.platform.v_nom
            if multi_rail:
                rail_kw = dict(
                    step_v=rel.rails.step_v,
                    paranoid=rel.canary.paranoid,
                    start_v=rel.rails.start_v,
                    profiles={
                        d: self._store.domain_profile(d)
                        for d in self._store.domains
                    },
                    escalation=rel.escalation_policy,
                    codecs={
                        d: self._store.codec_of(d) for d in self._store.domains
                    },
                    adaptive=rel.rails.adaptive,
                    divergence_slo=rel.canary.divergence_slo,
                )
                if mesh is not None:
                    self.controller = MeshRailController(
                        self.platform,
                        self._store.domains,
                        self._store.n_shards,
                        policy=rel.rails.policy,
                        **rail_kw,
                    )
                else:
                    self.controller = MultiRailController(
                        self.platform, self._store.domains, **rail_kw
                    )
                self.set_rails({d: self.voltage for d in self._store.domains})
            else:
                self.set_voltage(self.voltage)
        if recorder is not None and self.controller is not None:
            self.controller.bind_recorder(recorder)

        self._prefill = jax.jit(
            lambda p, t, c: lm.prefill(p, t, cfg, c)
        )
        self._decode_loop = jax.jit(
            lambda p, t, c, s0, n: lm.greedy_decode_loop(p, t, cfg, c, s0, n),
            static_argnums=(4,),
        )

    # -- voltage control ------------------------------------------------------
    def set_voltage(self, v: float):
        self.voltage = float(v)
        if self.rel is None:
            return
        if self.rel.rails.multi_rail:
            self.set_rails({d: float(v) for d in self._store.domains})
        elif self.rel.mode == "domain":
            self.domain.set_voltage(v)
            self.params, stats = self.domain.read_pytree("w", self._clean_params)
            self.stats.accumulate(stats)
        else:
            # Whole-model voltage step: one fused inject+scrub kernel launch
            # over the plane arena; only the (8,) counter vector crosses to
            # host.
            leaves, stats = self._store.set_voltage(v, ecc=self.rel.ecc)
            self.params = self._reassemble_params(leaves)
            self.stats.accumulate(stats)
            self._last_scrub = stats

    def set_rails(self, volts: dict):
        """Per-domain voltage step: one fused launch, one counter row per
        domain crossing to host (multi-rail engines only). Rails not named
        in ``volts`` (the late-bound `kv` cache rail, whose storage lives
        outside the weight arena) keep their current voltage — dropping
        them would silently skew the power accounting, which weights every
        domain in ``words_by_domain`` including the registered cache words."""
        assert self.rel is not None and self.rel.rails.multi_rail
        if self.mesh is not None:
            return self._set_rails_mesh(volts)
        new = {d: float(v) for d, v in volts.items()}
        if self.rails:
            new = {**self.rails, **new}
        self.rails = new
        self.voltage = max(self.rails.values())  # most conservative rail
        leaves, dstats = self._store.set_rails(self.rails, ecc=self.rel.ecc)
        self.params = self._reassemble_params(leaves)
        self.rail_stats.accumulate(dstats)
        self.stats.accumulate(dstats.total())
        self._last_scrub = dstats

    def _set_rails_mesh(self, volts):
        """Mesh rail step: one shard_map'd fused launch per codec group,
        every chip at its own schedule (DESIGN.md §13). ``volts`` is any
        form ``PlaneStore._normalize_schedule`` accepts — one dict, a
        per-shard list, or per-shard value arrays."""
        schedule = self._store._normalize_schedule(volts)
        if self.rails:
            schedule = [
                {**old, **{d: float(v) for d, v in new.items()}}
                for old, new in zip(self.rails, schedule)
            ]
        else:
            schedule = [
                {d: float(v) for d, v in s.items()} for s in schedule
            ]
        self.rails = schedule
        self.voltage = max(v for s in schedule for v in s.values())
        leaves, sstats = self._store.set_rails_sharded(
            schedule, ecc=self.rel.ecc
        )
        self.params = self._reassemble_params(leaves)
        self.shard_stats.accumulate(sstats)
        reduced = sstats.reduced()
        self.rail_stats.accumulate(reduced)
        self.stats.accumulate(reduced.total())
        self._last_scrub = sstats

    def _leaf_codec(self, key: str) -> str:
        if self.rel.rails.multi_rail:
            return self._store.codec_of(shapes.domain_of(key))
        slots = self._store.slots
        return self._store.codec_of(slots[0].domain) if slots else "secded72"

    def _reassemble_params(self, leaves):
        """Put faulty arena slices back into the param tree; embedding-like
        tables (read by gather, not matmul) are materialised through the ECC
        decode at refresh time — the fused read path only covers matmuls.
        Leaves protected by a non-SECDED codec take the same decode-at-
        refresh path: the fused decode-matmul kernel reads Hsiao planes
        only (DESIGN.md §12)."""
        flat = list(self._inline_template)
        for (i, key), leaf in zip(self._ecc_slots, leaves):
            codec = self._leaf_codec(key)
            if "embed" in key or codec != "secded72":
                flat[i] = _decode_gather_table(leaf, codec=codec)
            else:
                flat[i] = leaf
        return jax.tree_util.tree_unflatten(self._inline_treedef, flat)

    # -- serving --------------------------------------------------------------
    def generate(self, prompts: np.ndarray, n_tokens: int, *, params=None):
        """Greedy-decode a batch. prompts: (B, S0) int32. Returns (B, n).

        The decode loop is one lax.scan program (one dispatch for the whole
        rollout; compiled once per n_tokens value). ``params`` overrides the
        engine's (possibly fault-injected) weights for this rollout — the
        accuracy canary uses it to decode the clean reference through the
        same jitted programs.
        """
        p = self.params if params is None else params
        b, s0 = prompts.shape
        cache = lm.init_cache(self.cfg, b, self.max_len)
        logits, cache = self._prefill(p, jnp.asarray(prompts), cache)
        tok = jnp.argmax(logits, -1).astype(jnp.int32)[:, None]
        # decode at the serve lanes' padded batch (sched.DECODE_ROWS), so a
        # request's rollout is the one serve() gives it
        pad = sched.decode_rows(b) - b
        tok = jnp.pad(tok, ((0, pad), (0, 0)))
        cache = jax.tree.map(
            lambda c: jnp.pad(c, [(0, 0), (0, pad)] + [(0, 0)] * (c.ndim - 2)),
            cache,
        )
        toks, _ = self._decode_loop(
            p, tok, cache, jnp.int32(s0), n_tokens - 1
        )
        return np.concatenate([np.asarray(tok), np.asarray(toks)], axis=1)[:b]

    # -- accuracy canary (DESIGN.md §15) ---------------------------------------
    def canary_divergence(self) -> float | None:
        """Greedy-decode the canary prompts at the current rails and score
        them against the cached clean-nominal rollout.

        Returns ``1 - mean(matched prefix fraction)`` in [0, 1] (exactly 0.0
        when every canary continuation is bit-identical to the clean run), or
        None when the canary is disabled (``rel.canary.prompts == 0``). The
        reference is decoded once, lazily, from the *clean* plane templates
        through the same quantized ECC read path — so quantization noise
        cancels and only injected faults can score.
        """
        if self.rel is None or not self.rel.canary.prompts:
            return None
        assert self.rel.mode == "inline", (
            "the accuracy canary decodes against the clean inline plane "
            "templates; mode='domain' has no arena to diff"
        )
        from repro.core import campaign

        prompts = campaign.eval_prompts(
            self.cfg.vocab,
            self.rel.canary.prompts,
            campaign.CANARY_PROMPT_LEN,
            seed=self.rel.seed ^ 0xACC,
        )
        if self._canary_ref is None:
            clean = self._reassemble_params(
                [self._inline_template[i] for i, _ in self._ecc_slots]
            )
            self._canary_ref = self.generate(
                prompts, self.rel.canary.tokens, params=clean
            )
        cur = self.generate(prompts, self.rel.canary.tokens)
        div = campaign.token_divergence(self._canary_ref, cur)
        if self.recorder:
            self.recorder.emit("canary_probe", divergence=float(div))
        return div

    # -- continuous batching over the paged SECDED KV cache --------------------
    def serve(
        self,
        requests,
        *,
        n_lanes: int = 4,
        page_tokens: int = PAGE_TOKENS,
        n_pages: int | None = None,
        scrub_interval: int = 1,
        max_block: int = 16,
        kv_voltage: float | None = None,
        walk_kv: bool = False,
        share_prefix: bool = False,
        speculative: int = 0,
        draft_params=None,
        draft_cfg: ModelConfig | None = None,
        scrub_overlap: bool | None = None,
    ) -> sched.ServeReport:
        """Serve a stream of variable-length requests (DESIGN.md §11/§16).

        ``requests``: iterable of (prompt (s0,) int32, max_new_tokens) pairs
        or ``scheduler.Request`` objects. The KV cache lives in
        SECDED pages on the `kv` voltage domain; every read scrubs. At
        nominal voltage the output tokens are bit-identical to `generate` on
        the same batch composition (tested): prefill groups must match, and
        decode always runs at the batch padded to ``scheduler.DECODE_ROWS``
        rows, so with up to that many lanes a request prefilled alone
        serves exactly its own ``generate`` rollout.

        ``share_prefix=True`` enables the copy-on-write prefix-sharing trie:
        requests with identical full-page prompt prefixes share physical
        pages (scrubbed once, chunk-prefilled only on the private suffix)
        with reader-weighted DED telemetry (DESIGN.md §16). Bit-identical
        outputs at nominal voltage, gated by the shared_over_private
        throughput ratio in BENCH_serve.

        ``speculative=K`` (K >= 2, with ``draft_params``/``draft_cfg``)
        drafts K-1 tokens per dispatch with the draft model and verifies all
        K positions in one chunked target forward; the emitted stream is
        exactly the greedy rollout (accepted-prefix property, tested).

        ``walk_kv`` (multi-rail engines): attach a `kv` rail to the
        MultiRailController and let the per-interval scrub DED counters walk
        the cache voltage independently of the weight rails.

        ``scrub_overlap`` (None = auto, DESIGN.md §18): overlap the interval
        scrub with the decode blocks by deferring its counter harvest to the
        next interval boundary — bit-identical outputs/stats/rail walks to
        the serialized path; auto demotes to serialized when codec
        escalation is live. ``False`` forces the serialized path.

        Mesh engines (DESIGN.md §13) serve the stream data-parallel: the
        requests are partitioned round-robin across the reliability shards,
        every replica runs its own continuous-batching loop over its own
        KV arena (its own chip: per-shard fault stream, per-shard `kv` rail
        under the `per_shard` policy) and the merged MeshServeReport carries
        both the per-shard rows and the cross-shard aggregate.
        """
        assert shapes.supports_paged_kv(self.cfg), (
            f"{self.cfg.name}: paged KV unsupported (see shapes.supports_paged_kv)"
        )
        has_state = shapes.has_state_layers(self.cfg)
        if has_state and (share_prefix or int(speculative) >= 2 or self.mesh is not None):
            raise ReliabilityConfigError(
                f"{self.cfg.name}: recurrent state lives in the per-lane SECDED "
                "state store (core/statestore.py), which serves one chip without "
                "prefix sharing or speculative decoding"
            )
        if int(speculative) >= 2:
            assert draft_params is not None and draft_cfg is not None, (
                "speculative decode needs draft_params + draft_cfg"
            )
        else:
            draft_params = draft_cfg = None
        if self.mesh is not None:
            return self._serve_mesh(
                requests,
                n_lanes=n_lanes,
                page_tokens=page_tokens,
                n_pages=n_pages,
                scrub_interval=scrub_interval,
                max_block=max_block,
                kv_voltage=kv_voltage,
                walk_kv=walk_kv,
                share_prefix=share_prefix,
                speculative=speculative,
                draft_params=draft_params,
                draft_cfg=draft_cfg,
                scrub_overlap=scrub_overlap,
            )
        profile = self.platform or vmod.PLATFORMS["vc707"]
        envp = self.rel.environment_profile if self.rel is not None else None
        if self.rel is not None and self.rel.rails.multi_rail:
            profile = self._store.domain_profile("kv")  # env-scaled flux
        elif envp is not None:
            profile = envp.scale_profile(profile)
        geom = KVGeometry.from_config(self.cfg, page_tokens)
        if n_pages is None:
            n_pages = n_lanes * geom.pages_for(self.max_len)
        kv_codec = (
            shapes.domain_codecs(self.rel.protection.codecs)["kv"]
            if self.rel is not None
            else shapes.DEFAULT_CODEC
        )
        if walk_kv and self.controller is not None:
            rail = getattr(self.controller, "rails", {}).get("kv")
            if rail is not None:
                # A previous serve's escalation persists: the rail learned
                # this domain needs the stronger code, so the fresh arena is
                # protected under it — controller state and applied
                # protection must never diverge (DESIGN.md §12).
                kv_codec = rail.codec
        state_words = (
            sched.decode_rows(n_lanes) * statestore.words_per_lane(self.cfg)
            if has_state
            else 0
        )
        with obs_profile.span("serve.arena", n_pages=n_pages, state_words=state_words):
            arena = KVPageArena(
                geom,
                profile,
                n_pages,
                seed=self.rel.seed if self.rel else 0,
                ecc=self.rel.ecc if self.rel else True,
                codec=kv_codec,
                env=envp,
            )
            if kv_voltage is None:
                if self.rails is not None and "kv" in self.rails:
                    kv_voltage = self.rails["kv"]
                elif self.rel is not None:
                    kv_voltage = self.voltage
                else:
                    kv_voltage = profile.v_nom
            arena.set_voltage(float(kv_voltage))

        kv_controller = None
        if walk_kv:
            assert self.rel is not None and self.rel.rails.multi_rail, (
                "walk_kv needs a multi-rail engine"
            )
            kv_controller = self.controller.add_rail("kv", profile, codec=kv_codec)
            # The controller is the source of truth for the walked rail: the
            # arena must inject interval-1 faults at the voltage the canary
            # believes it is judging, or the first-DED decision is made on
            # telemetry from a different operating point. (An explicit
            # kv_voltage only pins the rail when it is not being walked.)
            arena.set_voltage(kv_controller.voltage)
        helpers = self._paged_helpers(geom, kv_codec, draft_cfg=draft_cfg)
        report = sched.serve_stream(
            self.params,
            self.cfg,
            helpers,
            arena,
            requests,
            n_lanes=n_lanes,
            max_len=self.max_len,
            scrub_interval=scrub_interval,
            max_block=max_block,
            kv_controller=kv_controller,
            # escalation rebuilds the spec helpers too: the draft cfg rides
            # along so a mid-serve codec change keeps speculating
            helpers_factory=lambda cname: self._paged_helpers(
                geom, cname, draft_cfg=draft_cfg
            ),
            share_prefix=share_prefix,
            speculative=speculative,
            draft_params=draft_params,
            draft_cfg=draft_cfg,
            recorder=self.recorder,
            scrub_overlap=scrub_overlap,
        )
        # Fold the cache telemetry + storage into the engine's books: the kv
        # domain now has real words (power weighting) and real counters.
        self.stats.accumulate(report.kv_stats)
        self.rail_stats.accumulate(DomainFaultStats({"kv": report.kv_stats}))
        if has_state:
            self.stats.accumulate(report.state_stats)
            self.rail_stats.accumulate(DomainFaultStats({"ssm": report.state_stats}))
        if self.rel is not None and self.rel.mode == "inline":
            self._store.register_domain_words(
                "kv", arena.n_words, codec=arena.codec_name
            )
            if has_state:
                self._store.register_domain_words(
                    "ssm", state_words, codec=statestore.CODEC
                )
        if self.rails is not None:
            self.rails["kv"] = arena.voltage
        self.kv_arena = arena
        return report

    def _serve_mesh(
        self,
        requests,
        *,
        n_lanes: int,
        page_tokens: int,
        n_pages: int | None,
        scrub_interval: int,
        max_block: int,
        kv_voltage: float | None,
        walk_kv: bool,
        share_prefix: bool = False,
        speculative: int = 0,
        draft_params=None,
        draft_cfg: ModelConfig | None = None,
        scrub_overlap: bool | None = None,
    ) -> "sched.MeshServeReport":
        """Data-parallel continuous batching across the reliability shards.

        Each replica is one chip: its KV arena draws the shard's own fault
        stream (KVPageArena(shard=s) — the host-side mirror of the
        shard_map path's axis_index key fold) and, under `per_shard` rails,
        walks its own `kv` voltage. The `uniform` policy threads ONE shared
        kv rail through every replica's stream in turn, so its canary sees
        every chip's DED events — the worst-shard lock.
        """
        import dataclasses as _dc

        geom = KVGeometry.from_config(self.cfg, page_tokens)
        if n_pages is None:
            n_pages = n_lanes * geom.pages_for(self.max_len)
        profile = self._store.domain_profile("kv")
        n_shards = self._store.n_shards
        parts = sched.partition_requests(
            sched.normalize_requests(requests), n_shards
        )
        base_codec = shapes.domain_codecs(self.rel.protection.codecs)["kv"]
        kv_rails = (
            self.controller.add_rail("kv", profile, codec=base_codec)
            if walk_kv
            else [None] * n_shards
        )
        reports = []
        for s, dev in enumerate(meshrel.shard_devices(self.mesh)):
            rail = kv_rails[s]
            # A previous serve's escalation persists per rail (DESIGN.md §12).
            kv_codec = rail.codec if rail is not None else base_codec
            # Replica s runs on shard s's chip: its own copy of the weights,
            # and a KV arena and lane caches created there, so every decode
            # dispatch lands there too.
            params, draft = jax.device_put((self.params, draft_params), dev)
            with jax.default_device(dev):
                arena = KVPageArena(
                    geom,
                    profile,
                    n_pages,
                    seed=self.rel.seed,
                    ecc=self.rel.ecc,
                    codec=kv_codec,
                    shard=s,
                    env=self.rel.environment_profile,
                )
                if kv_voltage is not None:
                    arena.set_voltage(float(kv_voltage))
                else:
                    arena.set_voltage(float(self.rails[s].get("kv", self.voltage)))
                if rail is not None:
                    # The controller is the source of truth for a walked rail
                    # (see serve()); under `uniform` the shared rail resumes
                    # from wherever the previous shard's stream left it — the
                    # worst-shard canary by construction.
                    arena.set_voltage(rail.voltage)
                report = sched.serve_stream(
                    params,
                    self.cfg,
                    self._paged_helpers(geom, kv_codec, draft_cfg=draft_cfg),
                    arena,
                    parts[s],
                    n_lanes=n_lanes,
                    max_len=self.max_len,
                    scrub_interval=scrub_interval,
                    max_block=max_block,
                    kv_controller=rail,
                    helpers_factory=lambda cname: self._paged_helpers(
                        geom, cname, draft_cfg=draft_cfg
                    ),
                    share_prefix=share_prefix,
                    speculative=speculative,
                    draft_params=draft,
                    draft_cfg=draft_cfg,
                    recorder=self.recorder,
                    scrub_overlap=scrub_overlap,
                )
            reports.append(report)
            self._store.register_domain_words(
                "kv", arena.n_words, codec=arena.codec_name, shard=s
            )
            self.rails[s]["kv"] = arena.voltage
        mesh_report = sched.MeshServeReport.merge(reports)
        self.stats.accumulate(mesh_report.kv_stats)
        self.rail_stats.accumulate(
            DomainFaultStats({"kv": mesh_report.kv_stats})
        )
        self.shard_stats.accumulate(
            ShardFaultStats(
                [
                    DomainFaultStats(
                        {"kv": _dc.replace(r.kv_stats, shard=s)}, shard=s
                    )
                    for s, r in enumerate(reports)
                ]
            )
        )
        self.kv_arenas = [r.arena for r in reports]
        self.kv_arena = self.kv_arenas[0]
        return mesh_report

    def _paged_helpers(
        self,
        geom: KVGeometry,
        codec: str = "secded72",
        draft_cfg: ModelConfig | None = None,
    ) -> serve_steps.PagedHelpers:
        cache = getattr(self, "_paged_helper_cache", None)
        if cache is None:
            cache = self._paged_helper_cache = {}
        key = (geom, codec, draft_cfg)
        if key not in cache:
            cache[key] = serve_steps.make_paged_helpers(
                self.cfg, geom, codec, draft_cfg=draft_cfg
            )
        return cache[key]

    # -- runtime undervolting loop ---------------------------------------------
    def autotune_voltage(self, max_rounds: int = 60):
        """Paper §III/IV: lower the rail(s) until the ECC's DED flag trips.

        Single-rail: returns (locked voltage, history). Multi-rail: every
        domain walks its own rail to its own first-DED point independently;
        returns ({domain: voltage}, {domain: history}).
        """
        assert self.rel is not None and self.controller is not None
        if self.mesh is not None:
            return self._autotune_rails_mesh(max_rounds)
        if self.rel.rails.multi_rail:
            return self._autotune_rails(max_rounds)
        for _ in range(max_rounds):
            if self.recorder:
                self.recorder.advance(1)  # one autotune round == one clock step
            round_stats = (
                self._last_scrub if self.rel.mode == "inline" else self._domain_scrub()
            )
            v = self.controller.update(
                round_stats, divergence=self.canary_divergence()
            )
            if self.controller.locked:
                # re-apply the backed-off (safe) voltage before serving
                self.set_voltage(self.controller.voltage)
                break
            self.set_voltage(v)
        return self.controller.voltage, self.controller.history

    def _autotune_rails(self, max_rounds: int):
        # Align the arena with the controller's starting schedule so the
        # first scrub interval reflects the voltages being judged.
        self.set_rails(self.controller.voltages)
        # Only the weight-arena rails are judged here: a late-attached `kv`
        # rail gets its telemetry from the serving stream (serve(walk_kv=True)),
        # not from the weight scrub, and must not stall this loop.
        arena_rails = self._store.domains
        for _ in range(max_rounds):
            if self.recorder:
                self.recorder.advance(1)
            # Scalar canary score broadcast to every rail: the canary rollout
            # exercises the whole model, so a violation retreats all rails
            # (protect-accuracy semantics; see MultiRailController.update).
            volts = self.controller.update(
                self._last_scrub, divergence=self.canary_divergence()
            )
            # A rail that escalated its codec re-protects its domain before
            # the schedule is applied: the next interval's telemetry must be
            # judged under the stronger code (DESIGN.md §12). Only arena
            # rails are polled here — a late-bound rail's changes stay
            # pending for the component that owns its storage (the serving
            # loop applies `kv` escalations via the scheduler).
            for d in arena_rails:
                cname = self.controller.rails[d].pop_codec_change()
                if cname:
                    self._store.set_domain_codec(d, cname)
            # apply the new schedule (the backed-off one on the final round)
            self.set_rails(volts)
            if all(self.controller.rails[d].locked for d in arena_rails):
                break
        return self.controller.voltages, self.controller.history

    def _autotune_rails_mesh(self, max_rounds: int):
        """Mesh rail search: every chip's canary is judged on its own
        counter rows. `per_shard` walks each chip to its own V_min;
        `uniform` locks one schedule at the worst chip's first DED (the
        psum-aggregated counters trip on any shard's event)."""
        self.set_rails(self.controller.voltages)
        arena_rails = self._store.domains
        for _ in range(max_rounds):
            if self.recorder:
                self.recorder.advance(1)
            schedule = self.controller.update(
                self._last_scrub, divergence=self.canary_divergence()
            )
            if self.controller.policy == "uniform":
                # Escalations apply store-wide (one codec per domain across
                # the mesh); per_shard policy forbids ladders at init.
                for d in arena_rails:
                    cname = self.controller.shards[0].rails[d].pop_codec_change()
                    if cname:
                        self._store.set_domain_codec(d, cname)
            self.set_rails(schedule)
            if self.controller.locked_for(arena_rails):
                break
        return self.controller.voltages, self.controller.history

    def _domain_scrub(self) -> FaultStats:
        agg = FaultStats()
        for name in self.domain.names():
            _, st = self.domain.read(name)
            agg.accumulate(st)
        return agg

    def _check_bits(self) -> dict:
        """Per-domain ECC check bits (the redundancy-cost power weighting)."""
        store = getattr(self, "_store", None)
        return store.check_bits_by_domain() if store is not None else {}

    def power_w(self) -> float:
        """Modeled accelerator power at the current rail voltage(s); on a
        mesh, the fleet total (every reliability shard is its own chip)."""
        ecc = bool(self.rel and self.rel.ecc)
        if self.mesh is not None:
            return self._store.n_shards * vmod.P_REST_W + vmod.mesh_bram_power(
                self.rails, self._store.shard_words_by_domain(), ecc=ecc,
                check_bits=self._check_bits(),
            )
        if self.rails is not None:
            return vmod.P_REST_W + vmod.multi_rail_bram_power(
                self.rails, self._store.words_by_domain(), ecc=ecc,
                check_bits=self._check_bits(),
            )
        # Single rail: the whole arena shares one codec; its redundancy
        # scales the BRAM draw (factor 1 for the measured SECDED geometry).
        bits = self._check_bits()
        factor = vmod.redundancy_factor(next(iter(bits.values()), 8))
        return vmod.P_REST_W + vmod.bram_power(self.voltage, ecc=ecc) * factor

    def power_report(self) -> dict:
        """Per-rail power breakdown + fractional BRAM saving vs nominal,
        including each domain's codec and its redundancy cost. Mesh engines
        report per-shard chips plus the fleet aggregate (DESIGN.md §13)."""
        ecc = bool(self.rel and self.rel.ecc)
        if self.mesh is not None:
            words = self._store.shard_words_by_domain()
            bits = self._check_bits()
            per_shard = [
                {
                    "shard": s,
                    "rails": dict(self.rails[s]),
                    "bram_w": vmod.multi_rail_bram_power(
                        self.rails[s], words[s], ecc=ecc, check_bits=bits
                    ),
                    "saving_vs_nominal": vmod.multi_rail_power_saving(
                        self.rails[s], words[s], ecc=ecc, check_bits=bits
                    ),
                }
                for s in range(self._store.n_shards)
            ]
            bram = vmod.mesh_bram_power(
                self.rails, words, ecc=ecc, check_bits=bits
            )
            return {
                "n_shards": self._store.n_shards,
                "policy": self.rel.rails.policy,
                "codecs": self._store.codecs_by_domain(),
                "check_bits": bits,
                "shards": per_shard,
                "bram_w": bram,
                "total_w": self.power_w(),
                "saving_vs_nominal": vmod.mesh_power_saving(
                    self.rails, words, ecc=ecc, check_bits=bits
                ),
            }
        if self.rails is not None:
            words = self._store.words_by_domain()
            total = max(sum(words.values()), 1)
            bits = self._check_bits()
            codecs = self._store.codecs_by_domain()
            return {
                "rails": dict(self.rails),
                "codecs": codecs,
                "check_bits": bits,
                "bram_w": vmod.multi_rail_bram_power(
                    self.rails, words, ecc=ecc, check_bits=bits
                ),
                "bram_w_by_domain": {
                    d: (words[d] / total)
                    * vmod.bram_power(v, ecc=ecc)
                    * vmod.redundancy_factor(bits.get(d, 8))
                    for d, v in self.rails.items()
                },
                "total_w": self.power_w(),
                "saving_vs_nominal": vmod.multi_rail_power_saving(
                    self.rails, words, ecc=ecc, check_bits=bits
                ),
            }
        bits = self._check_bits()
        factor = vmod.redundancy_factor(next(iter(bits.values()), 8))
        return {
            "rails": {"all": self.voltage},
            "codecs": dict(getattr(self, "_store", None).codecs_by_domain())
            if getattr(self, "_store", None) is not None
            else {},
            "bram_w": vmod.bram_power(self.voltage, ecc=ecc) * factor,
            "total_w": self.power_w(),
            "saving_vs_nominal": 1.0
            - vmod.bram_power(self.voltage, ecc=ecc) * factor
            / vmod.bram_power(1.0, ecc=False),
        }
