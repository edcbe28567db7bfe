"""Sharding rules + an 8-fake-device end-to-end lowering (subprocess)."""

import json
import os
import subprocess
import sys
import textwrap

import pytest

import jax
from jax.sharding import AbstractMesh, AxisType
from jax.sharding import PartitionSpec as P

from repro.distributed import sharding as shd


@pytest.fixture(scope="module")
def mesh():
    # single-device mesh: rule logic only depends on axis names/sizes
    return jax.make_mesh((1, 1), ("data", "model"), axis_types=(AxisType.Auto,) * 2)


def test_spec_rules_basic(mesh):
    # TP axes map to model; embed replicated without fsdp
    assert shd.spec_for(("embed", "heads"), (64, 64), mesh, False) == P(None, "model")
    assert shd.spec_for(("vocab", "embed"), (128, 64), mesh, True) == P("model", "data")
    # one mesh axis never used twice
    assert shd.spec_for(("experts", "embed", "ffn"), (4, 8, 16), mesh, False) == P(
        "model", None, None
    )


def test_spec_divisibility_fallback():
    # AbstractMesh: rule logic only needs axis names/sizes, no devices
    m = AbstractMesh((1, 2), ("data", "model"))
    # 3 not divisible by model=2 -> replicate, next axis picks model up
    assert shd.spec_for(("experts", "ffn"), (3, 8), m, False) == P(None, "model")


def test_dryrun_8dev_subprocess(tmp_path):
    """End-to-end: lower+compile a smoke config on 8 fake devices."""
    script = textwrap.dedent(
        """
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp, dataclasses, json, sys
        from jax.sharding import AxisType, NamedSharding, PartitionSpec as P
        from repro.configs import get_smoke_config
        from repro.distributed import sharding as shd
        from repro.models import lm
        from repro.optim import adamw
        from repro.train import train_step as ts

        cfg = get_smoke_config("qwen3-0.6b")
        mesh = jax.make_mesh((2, 4), ("data", "model"), axis_types=(AxisType.Auto,) * 2)
        tcfg = ts.TrainConfig(optimizer=adamw.AdamWConfig(), remat="full")
        fn = ts.make_train_step(cfg, tcfg)
        pstruct = lm.param_struct(cfg)
        pshard = shd.param_shardings(cfg, mesh, fsdp=False)
        opt_struct = jax.eval_shape(lambda p: adamw.init(p, tcfg.optimizer), pstruct)
        opt_shard = {"m": pshard, "v": pshard, "step": shd.replicated(mesh)}
        batch = {
            "tokens": jax.ShapeDtypeStruct((8, 32), jnp.int32),
            "labels": jax.ShapeDtypeStruct((8, 32), jnp.int32),
        }
        bshard = shd.batch_shardings(mesh, batch)
        with mesh:
            compiled = jax.jit(
                fn, in_shardings=(pshard, opt_shard, bshard)
            ).lower(pstruct, opt_struct, batch).compile()
        ca = compiled.cost_analysis()
        if isinstance(ca, (list, tuple)):  # older jax: one entry per program
            ca = ca[0]
        print(json.dumps({"flops": float(ca.get("flops", 0)), "ok": True}))
        """
    )
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    out = subprocess.run(
        [sys.executable, "-c", script], capture_output=True, text=True, env=env,
        timeout=600,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["ok"] and res["flops"] > 0


def test_cache_shardings_flash_decoding(mesh):
    from repro.configs import get_config
    from repro.configs.shapes import cache_struct

    cfg = get_config("qwen3-0.6b")
    cs = cache_struct(cfg, 128, 1024)
    shards = shd.cache_shardings(cfg, mesh, cs)
    kv = shards["p0"]["k"].spec
    assert kv == P(None, "data", "model", None, None)  # B on data, S on model
