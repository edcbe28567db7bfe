"""Mesh-sharded reliability layer (DESIGN.md §13).

In-process tests pin the two load-bearing properties on a 1-device mesh —
bit-identity with the unsharded path, and per-shard PRNG stream disjointness
— plus the controller policies and telemetry containers. The 8-fake-device
acceptance path (per-shard rails actually diverging) runs in a subprocess in
tests/test_mesh_serve.py (device count is locked at jax init).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import AbstractMesh

from conftest import tiny_cfg
from repro.core.controller import MeshRailController
from repro.core.kvpages import KVGeometry, KVPageArena
from repro.core.planestore import PlaneStore
from repro.core.telemetry import DomainFaultStats, FaultStats, ShardFaultStats
from repro.core.voltage import PLATFORMS
from repro.distributed import meshrel
from repro.distributed.sharding import reliability_axes, reliability_shards
from repro.kernels import ops as kops
from repro.launch.mesh import make_reliability_mesh


# ---------------------------------------------------------------------------
# axis conventions
# ---------------------------------------------------------------------------
def test_reliability_axes_conventions():
    m = AbstractMesh((2, 4), ("data", "model"))
    assert reliability_axes(m) == ("data",)
    assert reliability_shards(m) == 2
    mp = AbstractMesh((2, 4, 4), ("pod", "data", "model"))
    assert reliability_axes(mp) == ("pod", "data")
    assert reliability_shards(mp) == 8
    bare = AbstractMesh((4,), ("shard",))
    assert reliability_axes(bare) == ("shard",)
    assert reliability_shards(bare) == 4
    assert meshrel.pad_to_shards(10, 4) == 12
    assert meshrel.pad_to_shards(8, 4) == 8


def test_rail_policy_validation():
    from repro.configs import shapes

    assert shapes.rail_policy("uniform") == "uniform"
    assert shapes.rail_policy("per_shard") == "per_shard"
    with pytest.raises(AssertionError):
        shapes.rail_policy("per_chip")


# ---------------------------------------------------------------------------
# telemetry: shard dimension
# ---------------------------------------------------------------------------
def test_shard_fault_stats_container():
    cnt = np.zeros((2, 2, 8), np.int64)
    cnt[0, 0, 2] = 3  # shard 0, domain a: detected
    cnt[1, 1, 1] = 5  # shard 1, domain b: corrected
    words = [{"a": 10, "b": 20}, {"a": 10, "b": 20}]
    st = ShardFaultStats.from_counter_blocks(cnt, ("a", "b"), words)
    assert st.n_shards == 2 and st.domains == ("a", "b")
    assert st[0]["a"].detected == 3 and st[0]["a"].shard == 0
    assert st[1]["b"].corrected == 5 and st[1]["b"].shard == 1
    red = st.reduced()
    assert red["a"].detected == 3 and red["b"].corrected == 5
    assert red.shard == -1 and red["a"].shard == -1  # aggregate, not a shard row
    assert red["a"].words == 20  # summed across both chips' arrays
    assert st.total().detected == 3 and st.total().corrected == 5
    # accumulate keeps per-shard rows separate
    st.accumulate(st)
    assert st[0]["a"].detected == 6 and st[1]["b"].corrected == 10
    assert st[0]["a"].shard == 0  # same-shard accumulate keeps the tag


def test_summed_accepts_containers():
    d0 = DomainFaultStats({"a": FaultStats(words=1, detected=2, shard=0)}, shard=0)
    d1 = DomainFaultStats({"a": FaultStats(words=1, corrected=3, shard=1)}, shard=1)
    tot = FaultStats.summed([d0, d1])
    assert tot.detected == 2 and tot.corrected == 3 and tot.shard == -1
    sh = ShardFaultStats([d0, d1])
    assert FaultStats.summed([sh]).detected == 2
    # cross-shard reduction of domain rows
    red = DomainFaultStats.summed([d0, d1])
    assert red["a"].detected == 2 and red["a"].corrected == 3
    assert red.shard == -1


# ---------------------------------------------------------------------------
# sharded plane arena: 1-device-mesh bit-identity (the correctness anchor)
# ---------------------------------------------------------------------------
def _mk_store(mesh=None, seed=3):
    rng = np.random.default_rng(0)

    def leaf(k, n):
        return kops.pack_ecc_weights(
            jnp.asarray(rng.standard_normal((k, n)).astype(np.float32))
        )

    leaves = [leaf(64, 128), leaf(64, 64), leaf(128, 64)]
    keys = ["w_attn", "w_mlp", "w_embed"]
    return PlaneStore(
        leaves,
        keys,
        PLATFORMS["vc707"],
        seed=seed,
        mask_source="device",
        domain_key=lambda k: k.split("_")[1],
        mesh=mesh,
    )


def test_sharded_1dev_bit_identical_to_unsharded():
    """Property: on a 1-device mesh the shard_map'd scrub equals the
    unsharded device path bit-for-bit — counters AND corrected words — for
    uniform and non-uniform rail schedules, across repeated steps."""
    ref = _mk_store()
    mesh = make_reliability_mesh(1)
    sh = _mk_store(mesh=mesh)
    assert sh.n_shards == 1
    schedules = [
        {"attn": 0.58, "mlp": 0.58, "embed": 0.58},
        {"attn": 0.55, "mlp": 0.60, "embed": 0.57},
        {"attn": 0.545, "mlp": 0.545, "embed": 0.58},
    ]
    for volts in schedules:
        l1, d1 = ref.set_rails(volts)
        l2, s2 = sh.set_rails_sharded(volts)
        assert s2.n_shards == 1
        for a, b in zip(l1, l2):
            assert np.array_equal(np.asarray(a.lo), np.asarray(b.lo))
            assert np.array_equal(np.asarray(a.hi), np.asarray(b.hi))
            assert np.array_equal(np.asarray(a.parity), np.asarray(b.parity))
        for d in d1.domains:
            assert d1[d].counters().tolist() == s2[0][d].counters().tolist(), d
            assert d1[d].words == s2[0][d].words
            assert s2[0][d].shard == 0


def test_sharded_1dev_bit_identical_multi_codec_groups():
    """Per-domain codecs split the arena into several codec groups, each
    with its own stream and its own shard_map'd launch — the 1-device mesh
    must still match the unsharded device path group-for-group."""
    rng = np.random.default_rng(2)

    def leaf(k, n):
        return kops.pack_ecc_weights(
            jnp.asarray(rng.standard_normal((k, n)).astype(np.float32))
        )

    leaves = [leaf(64, 128), leaf(64, 64)]

    def store(mesh=None):
        return PlaneStore(
            leaves,
            ["w_attn", "w_mlp"],
            PLATFORMS["vc707"],
            seed=9,
            mask_source="device",
            domain_key=lambda k: k.split("_")[1],
            codecs={"mlp": "dected79"},
            mesh=mesh,
        )

    ref, sh = store(), store(make_reliability_mesh(1))
    volts = {"attn": 0.55, "mlp": 0.55}
    l1, d1 = ref.set_rails(volts)
    l2, s2 = sh.set_rails_sharded(volts)
    for a, b in zip(l1, l2):
        assert np.array_equal(np.asarray(a.lo), np.asarray(b.lo))
        assert np.array_equal(np.asarray(a.parity), np.asarray(b.parity))
    for d in d1.domains:
        assert d1[d].counters().tolist() == s2[0][d].counters().tolist(), d


def test_sharded_schedule_forms_equivalent():
    mesh = make_reliability_mesh(1)
    store = _mk_store(mesh=mesh)
    volts = {"attn": 0.56, "mlp": 0.58, "embed": 0.57}
    _, a = store.set_rails_sharded(volts)
    _, b = store.set_rails_sharded([volts])
    _, c = store.set_rails_sharded({d: np.array([v]) for d, v in volts.items()})
    for d in a.domains:
        assert (
            a[0][d].counters().tolist()
            == b[0][d].counters().tolist()
            == c[0][d].counters().tolist()
        )


def test_sharded_store_guards():
    mesh = make_reliability_mesh(1)
    with pytest.raises(AssertionError):
        _ = PlaneStore([], [], PLATFORMS["vc707"], mask_source="host", mesh=mesh)
    store = _mk_store(mesh=mesh)
    with pytest.raises(AssertionError):
        store.set_rails({"attn": 0.6, "mlp": 0.6, "embed": 0.6})
    with pytest.raises(AssertionError):
        store.set_voltage(0.6)


# ---------------------------------------------------------------------------
# per-shard PRNG stream disjointness
# ---------------------------------------------------------------------------
def test_weight_shard_streams_disjoint_100_step_walk():
    """No shard reproduces another's fault mask at any step of a 100-step
    voltage walk. Shard keys here are exactly what collectives.shard_key
    computes inside shard_map: base for shard 0, fold_in(base, s) above."""
    from repro.core.faultsim import _device_chunk_masks

    base = jax.random.PRNGKey(3 ^ 0xECC)
    n_shards, n_words = 4, 4096
    prof = PLATFORMS["vc707"]
    # the critical region: shallow steps draw empty fault populations, and
    # an empty mask is trivially shared — disjointness is a property of the
    # *faults*, so every compared step must be non-empty for every shard
    voltages = np.linspace(0.57, prof.v_crash, 100)
    keys = [base] + [jax.random.fold_in(base, s) for s in range(1, n_shards)]
    nonzero_steps = 0
    for vi, v in enumerate(voltages):
        rate = jnp.float32(prof.fault_rate(float(v)))
        sigs, empty = set(), False
        for key in keys:
            chunk_key = jax.random.fold_in(key, 0)  # chunk 0, as the step folds
            mlo, mhi, mpar = _device_chunk_masks(
                chunk_key, n_words, rate, jnp.float32(prof.row_sigma)
            )
            mlo, mhi, mpar = np.asarray(mlo), np.asarray(mhi), np.asarray(mpar)
            if not (mlo.any() or mhi.any() or mpar.any()):
                empty = True
                continue
            sig = (mlo.tobytes(), mhi.tobytes(), mpar.tobytes())
            assert sig not in sigs, (
                f"shard mask collision at step {vi} (v={v:.3f})"
            )
            sigs.add(sig)
        if not empty:
            nonzero_steps += 1
    # the walk genuinely exercised the property on most of its 100 steps
    assert nonzero_steps >= 60, nonzero_steps


def test_kv_shard_streams_disjoint_100_intervals():
    """Replica KV arenas: shard 0 is bit-identical to the historical
    stream; no shard's interval masks ever equal another's."""
    cfg = tiny_cfg()
    geom = KVGeometry.from_config(cfg, page_tokens=4)
    prof = PLATFORMS["vc707"]

    def arena(shard):
        a = KVPageArena(geom, prof, n_pages=2, seed=7, shard=shard)
        a.set_voltage(0.55)
        return a

    legacy = KVPageArena(geom, prof, n_pages=2, seed=7)  # pre-mesh signature
    s0 = arena(0)
    assert np.array_equal(
        np.asarray(jax.random.key_data(s0._key)),
        np.asarray(jax.random.key_data(legacy._key)),
    )
    arenas = [arena(s) for s in range(3)]
    for step in range(100):
        sigs = set()
        for a in arenas:
            before = (np.asarray(a.lo), np.asarray(a.hi), np.asarray(a.parity))
            a.tick()
            mask = tuple(
                (np.asarray(x) ^ b).tobytes()
                for x, b in zip((a.lo, a.hi, a.parity), before)
            )
            assert mask not in sigs, f"kv mask collision at interval {step}"
            sigs.add(mask)


def test_sweep_sharded_shard0_matches_unsharded():
    from repro.core import sweep

    prof = PLATFORMS["vc707"]
    grid = [(prof, v) for v in (0.58, 0.56, 0.545)]
    ref = sweep.sweep_platform_grid(grid, n_words=4096, seed=5)
    per_shard = sweep.sweep_platform_grid_sharded(grid, 4096, n_shards=3, seed=5)
    assert len(per_shard) == 3
    for a, b in zip(ref, per_shard[0]):
        assert a.stats.counters().tolist() == b.stats.counters().tolist()
        assert b.stats.shard == 0
    # other shards draw different fault populations
    diffs = [
        per_shard[s][-1].stats.counters().tolist() != ref[-1].stats.counters().tolist()
        for s in (1, 2)
    ]
    assert any(diffs)
    vmins = sweep.shard_vmin_spread(
        prof, np.round(np.arange(0.60, 0.539, -0.005), 3), 4096, 3, seed=5
    )
    assert len(vmins) == 3
    assert all(v is not None and prof.v_crash <= v <= 0.60 for v in vmins)
    # a grid whose top voltage already DEDs holds no safe point: None, not
    # the faulting top-of-grid voltage
    deep = sweep.shard_vmin_spread(prof, [prof.v_crash], 1 << 16, 2, seed=5)
    assert deep == [None, None]


# ---------------------------------------------------------------------------
# shard_map'd paged scrub-on-read vs the per-replica arena
# ---------------------------------------------------------------------------
def test_kv_scrub_step_matches_arena_scrub():
    cfg = tiny_cfg()
    geom = KVGeometry.from_config(cfg, page_tokens=4)
    prof = PLATFORMS["vc707"]
    arena = KVPageArena(geom, prof, n_pages=3, seed=11)
    payload = np.random.default_rng(1).standard_normal(
        (4, geom.token_f32)
    ).astype(np.float32)
    arena.commit_tokens(payload, np.array([0, 0, 1, 2]), np.array([0, 1, 0, 0]))
    arena.set_voltage(0.545)
    arena.tick()
    table = np.array([0, 1, 2, arena.scratch_page], np.int32)

    mesh = make_reliability_mesh(1)
    step = meshrel.make_kv_scrub_step(
        mesh, geom.words_per_page, arena._total_words, table.size
    )
    lo, hi, par = arena.lo, arena.hi, arena.parity
    slo, shi, spar, _, _, cnt = step(lo, hi, par, jnp.asarray(table[None]))
    _, acnt = arena.scrub_pages(table)
    assert np.array_equal(np.asarray(cnt)[0], acnt)
    assert np.array_equal(np.asarray(slo), np.asarray(arena.lo))
    assert np.array_equal(np.asarray(shi), np.asarray(arena.hi))
    assert np.array_equal(np.asarray(spar), np.asarray(arena.parity))


# ---------------------------------------------------------------------------
# mesh rail controller policies
# ---------------------------------------------------------------------------
def _shard_stats(per_shard_detected, domain="mlp", words=1000):
    return ShardFaultStats(
        [
            DomainFaultStats(
                {domain: FaultStats(words=words, detected=d, shard=s)}, shard=s
            )
            for s, d in enumerate(per_shard_detected)
        ]
    )


def test_mesh_controller_uniform_worst_shard_lock():
    prof = PLATFORMS["vc707"]
    ctrl = MeshRailController(prof, ("mlp",), n_shards=4, policy="uniform")
    ctrl.update(_shard_stats([0, 0, 0, 0]))
    assert not ctrl.locked
    v_before = ctrl.voltages[0]["mlp"]
    # one shard trips -> the aggregate canary trips -> ALL shards back off
    ctrl.update(_shard_stats([0, 0, 7, 0]))
    assert ctrl.locked
    volts = ctrl.voltages
    assert len(volts) == 4
    assert all(v["mlp"] == volts[0]["mlp"] for v in volts)
    assert volts[0]["mlp"] > v_before - 0.01  # backed off, not descended
    # a reduced DomainFaultStats is accepted too (the psum view)
    ctrl2 = MeshRailController(prof, ("mlp",), n_shards=4, policy="uniform")
    ctrl2.update(_shard_stats([0, 0, 7, 0]).reduced())
    assert ctrl2.locked


def test_mesh_controller_per_shard_independent_walks():
    prof = PLATFORMS["vc707"]
    ctrl = MeshRailController(prof, ("mlp",), n_shards=3, policy="per_shard")
    ctrl.update(_shard_stats([0, 5, 0]))  # only shard 1 trips
    assert ctrl.shard(1).rails["mlp"].locked
    assert not ctrl.shard(0).rails["mlp"].locked
    assert not ctrl.locked
    ctrl.update(_shard_stats([0, 0, 0]))
    volts = ctrl.voltages
    assert volts[0]["mlp"] < volts[1]["mlp"]  # 0 kept walking, 1 held
    # history records carry the shard dimension
    recs = ctrl.history[(1, "mlp")]
    assert recs and all(r.shard == 1 for r in recs)
    with pytest.raises(AssertionError):
        ctrl.update(_shard_stats([0, 0]))  # wrong shard count
    with pytest.raises(AssertionError):
        ctrl.update(_shard_stats([0, 0, 0]).reduced())  # collapsed rows
    with pytest.raises(AssertionError):
        ctrl.pop_codec_changes()  # per-shard ladders unsupported

    one = MeshRailController(prof, ("mlp",), n_shards=1, policy="per_shard")
    from repro.core.controller import MultiRailController

    solo = MultiRailController(prof, ("mlp",))
    for det in (0, 0, 3, 0):
        one.update(_shard_stats([det]))
        solo.update({"mlp": FaultStats(words=1000, detected=det)})
    assert one.voltages[0]["mlp"] == solo.voltages["mlp"]
    assert one.locked == solo.locked


# ---------------------------------------------------------------------------
# request partitioning / merged reports
# ---------------------------------------------------------------------------
def test_partition_requests_round_robin():
    from repro.serving import scheduler as sched

    reqs = sched.normalize_requests(
        [(np.arange(1, 4, dtype=np.int32), 2) for _ in range(7)]
    )
    assert [r.rid for r in reqs] == list(range(7))
    parts = sched.partition_requests(reqs, 3)
    assert [[r.rid for r in p] for p in parts] == [[0, 3, 6], [1, 4], [2, 5]]
    # 1-shard: the whole stream, in order (serve bit-identity anchor)
    assert [r.rid for r in sched.partition_requests(reqs, 1)[0]] == list(range(7))


def test_mesh_serve_report_merge_rejects_duplicates():
    from repro.serving import scheduler as sched

    def rep(rids, detected):
        return sched.ServeReport(
            outputs={r: np.zeros(2, np.int32) for r in rids},
            request_stats={r: FaultStats() for r in rids},
            kv_stats=FaultStats(words=10, detected=detected),
            steps=3,
            preemptions=1,
            kv_voltages=[1.0],
            arena=None,
            pages_free_at_end=0,
        )

    merged = sched.MeshServeReport.merge([rep([0, 2], 1), rep([1], 5)])
    assert set(merged.outputs) == {0, 1, 2}
    assert merged.shard_of == {0: 0, 2: 0, 1: 1}
    assert merged.kv_stats.detected == 6 and merged.steps == 6
    assert [s.detected for s in merged.kv_stats_by_shard] == [1, 5]
    assert [s.shard for s in merged.kv_stats_by_shard] == [0, 1]
    with pytest.raises(AssertionError):
        sched.MeshServeReport.merge([rep([0], 0), rep([0], 0)])
