"""Mesh-sharded serving (DESIGN.md §13): engine-level bit-identity on a
1-device mesh, and the 8-fake-device acceptance path in a subprocess
(forced host-device count is locked at jax init, so multi-device mesh
behaviour cannot run inside the pytest process)."""

import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

import jax

from conftest import tiny_cfg
from repro.launch.mesh import make_reliability_mesh
from repro.models import lm
from repro.serving.engine import (
    FaultModelConfig,
    ProtectionConfig,
    RailsConfig,
    ReliabilityConfig,
    ServingEngine,
)


@pytest.fixture(scope="module")
def setup():
    cfg = tiny_cfg(d_model=64, n_layers=2, d_ff=128, vocab=128)
    params = lm.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    reqs = [
        (rng.integers(1, 100, size=s).astype(np.int32), n)
        for s, n in [(5, 6), (3, 4), (7, 5), (4, 8)]
    ]
    return cfg, params, reqs


def _rel(mask_source="device", protection=ProtectionConfig(), **rails):
    return ReliabilityConfig(
        mode="inline", voltage=0.60, seed=1,
        fault_model=FaultModelConfig(mask_source=mask_source),
        rails=RailsConfig(**{"multi_rail": True, **rails}),
        protection=protection,
    )


def test_engine_mesh_1dev_bit_identical(setup):
    """The serve acceptance anchor: a 1-shard mesh engine reproduces the
    unsharded engine exactly — decoded tokens, kv counters, weight-rail
    counters, autotuned schedules, and the power report."""
    cfg, params, reqs = setup
    e1 = ServingEngine(cfg, params, _rel(), max_len=64)
    r1 = e1.serve(reqs, n_lanes=2, scrub_interval=2, kv_voltage=0.57, walk_kv=True)
    e2 = ServingEngine(
        cfg, params, _rel(policy="per_shard"), max_len=64,
        mesh=make_reliability_mesh(1),
    )
    r2 = e2.serve(reqs, n_lanes=2, scrub_interval=2, kv_voltage=0.57, walk_kv=True)

    assert set(r1.outputs) == set(r2.outputs)
    for rid in r1.outputs:
        assert np.array_equal(r1.outputs[rid], r2.outputs[rid]), rid
    assert r1.kv_stats.counters().tolist() == r2.kv_stats.counters().tolist()
    assert r2.shard_of == {rid: 0 for rid in r2.outputs}
    for d in e1.rail_stats.domains:
        assert (
            e1.rail_stats[d].counters().tolist()
            == e2.rail_stats[d].counters().tolist()
        ), d
    # per-shard telemetry rows exist and carry the shard dimension
    assert e2.shard_stats.n_shards == 1
    assert e2.shard_stats[0].shard == 0

    v1, _ = e1.autotune_voltage(max_rounds=8)
    v2, _ = e2.autotune_voltage(max_rounds=8)
    assert v2[0] == v1
    p1, p2 = e1.power_report(), e2.power_report()
    assert p2["n_shards"] == 1 and p2["policy"] == "per_shard"
    assert abs(p1["total_w"] - p2["total_w"]) < 1e-9
    assert abs(p1["saving_vs_nominal"] - p2["saving_vs_nominal"]) < 1e-9


def test_engine_mesh_guards(setup):
    cfg, params, _ = setup
    mesh = make_reliability_mesh(1)
    with pytest.raises(AssertionError):
        ServingEngine(cfg, params, _rel(mask_source="host"), mesh=mesh)
    with pytest.raises(AssertionError):
        ServingEngine(cfg, params, _rel(multi_rail=False), mesh=mesh)
    with pytest.raises(AssertionError):
        ServingEngine(
            cfg, params,
            _rel(
                policy="per_shard",
                protection=ProtectionConfig(escalation=("secded72", "dected79")),
            ),
            mesh=mesh,
        )
    with pytest.raises(AssertionError):
        ServingEngine(cfg, params, _rel(policy="per_chip"), mesh=mesh)


def test_mesh8_serve_acceptance(tmp_path):
    """ISSUE 5 acceptance: on a forced 8-host-device mesh, serve(walk_kv)
    under per_shard rails completes a mixed-length stream with per-shard DED
    counters differing across shards, and the aggregated power_report lands
    within noise of 8x the 1-device report at equal voltage."""
    script = textwrap.dedent(
        """
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import json
        import numpy as np
        import jax
        from conftest import tiny_cfg
        from repro.launch.mesh import make_reliability_mesh
        from repro.models import lm
        from repro.serving.engine import (
            FaultModelConfig, RailsConfig, ReliabilityConfig, ServingEngine,
        )

        cfg = tiny_cfg(d_model=64, n_layers=2, d_ff=128, vocab=128)
        params = lm.init_params(cfg, jax.random.PRNGKey(0))
        rng = np.random.default_rng(0)
        reqs = [
            (rng.integers(1, 100, size=int(s), dtype=np.int32), int(n))
            for s, n in zip(
                rng.integers(3, 10, size=16), rng.integers(8, 17, size=16)
            )
        ]
        mesh = make_reliability_mesh(8)
        rel = ReliabilityConfig(
            mode="inline", voltage=0.60, seed=1,
            fault_model=FaultModelConfig(mask_source="device"),
            rails=RailsConfig(multi_rail=True, policy="per_shard", start_v=0.60),
        )
        e = ServingEngine(cfg, params, rel, max_len=64, mesh=mesh)
        r = e.serve(reqs, n_lanes=2, scrub_interval=1, walk_kv=True)
        rows = [st.counters().tolist() for st in r.kv_stats_by_shard]

        # equal-voltage power comparison vs the unsharded 1-device engine
        e.set_rails({d: 0.56 for d in e._store.domains})
        e1 = ServingEngine(cfg, params, ReliabilityConfig(
            mode="inline", voltage=0.60, seed=1,
            fault_model=FaultModelConfig(mask_source="device"),
            rails=RailsConfig(multi_rail=True),
        ), max_len=64)
        r1 = e1.serve(reqs, n_lanes=2, scrub_interval=1, kv_voltage=0.56)
        e1.set_rails({d: 0.56 for d in e1._store.domains})
        e1.rails["kv"] = 0.56
        for s in range(8):
            e.rails[s]["kv"] = 0.56
        print(json.dumps({
            "served": sorted(r.outputs),
            "n_requests": len(reqs),
            "detected": [st.detected for st in r.kv_stats_by_shard],
            "shards_tagged": [st.shard for st in r.kv_stats_by_shard],
            "distinct_rows": len({tuple(x) for x in rows}),
            "kv_locks": [s["kv"] for s in e.rails],
            "p8": e.power_report()["total_w"],
            "p1": e1.power_report()["total_w"],
            "replica_devices": [
                sorted(d.id for d in a.lo.devices()) for a in e.kv_arenas
            ],
        }))
        """
    )
    env = dict(os.environ)
    here = os.path.dirname(__file__)
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(here, "..", "src"), here]
    )
    out = subprocess.run(
        [sys.executable, "-c", script], capture_output=True, text=True,
        env=env, timeout=600,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["served"] == list(range(res["n_requests"]))  # stream completed
    assert res["shards_tagged"] == list(range(8))
    # per-shard DED canaries saw different chips: counters differ
    assert len(set(res["detected"])) > 1, res["detected"]
    assert sum(res["detected"]) > 0
    assert res["distinct_rows"] >= 2
    # fleet power at equal voltage == 8x one chip, within noise (per-shard
    # arena padding shifts domain fractions by well under a percent)
    assert res["p8"] == pytest.approx(8 * res["p1"], rel=0.02)
    # every replica's KV arena (an output of its decode dispatches) sits on
    # its own shard's device
    assert res["replica_devices"] == [[s] for s in range(8)]


def test_mesh_uniform_policy_shared_walk(setup):
    """Uniform policy on a 1-shard mesh: one schedule, same walk as the
    unsharded controller; rails list still has one entry per shard."""
    cfg, params, reqs = setup
    e = ServingEngine(
        cfg, params, _rel(policy="uniform", start_v=0.62),
        max_len=64, mesh=make_reliability_mesh(1),
    )
    schedules, history = e.autotune_voltage(max_rounds=40)
    assert len(schedules) == 1
    ref = ServingEngine(
        cfg, params, _rel(start_v=0.62), max_len=64
    )
    v_ref, _ = ref.autotune_voltage(max_rounds=40)
    assert schedules[0] == v_ref
    assert all(shard in (-1,) for shard, _ in history)  # shared walk, no shard tag
