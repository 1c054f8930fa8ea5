"""Distribution integration tests.

The production 256/512-chip meshes are exercised by
``launch/dryrun.py`` (its own process, 512 forced host devices).  Here
we run a REDUCED mesh (8 forced devices, 2x4) in a subprocess so the
pytest process keeps its single CPU device, proving the same
pjit/shard_map plumbing end to end — including a real
numerically-checked sharded run, not just lowering.
"""
import json
import os
import subprocess
import sys
import textwrap

import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _run(code: str) -> dict:
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


_PRELUDE = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import jax, jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.configs import get_smoke_config
from repro.launch import sharding as shd
from repro.models import transformer as tfm
from repro.training import AdamW, make_train_step
mesh = jax.make_mesh((2, 4), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
"""


def test_sharded_train_step_matches_single_device():
    """jit(train_step) on a 2x4 mesh == single-device reference."""
    code = _PRELUDE + textwrap.dedent("""
        cfg = get_smoke_config("internlm2-20b").replace(
            dtype="float32", remat=False)
        params = tfm.init_lm(cfg, jax.random.PRNGKey(0))
        opt = AdamW(lr=1e-3)
        state = opt.init(params)
        step = make_train_step(cfg, opt)
        batch = {"tokens": jax.random.randint(
            jax.random.PRNGKey(1), (4, 17), 0, cfg.vocab)}

        # single-device reference
        p_ref, s_ref, m_ref = jax.jit(step)(params, state, batch)

        p_spec = shd.param_specs(params, mesh)
        p_sh = shd.to_named(p_spec, mesh)
        b_sh = {"tokens": NamedSharding(mesh, P("data", None))}
        o_sh = shd.to_named(shd.param_specs(state, mesh), mesh)
        stepd = jax.jit(step, in_shardings=(p_sh, o_sh, b_sh))
        p_d, s_d, m_d = stepd(
            jax.device_put(params, p_sh), jax.device_put(state, o_sh),
            jax.device_put(batch, b_sh))
        err = abs(float(m_ref["loss"]) - float(m_d["loss"]))
        werr = max(float(jnp.max(jnp.abs(a - b)))
                   for a, b in zip(jax.tree_util.tree_leaves(p_ref),
                                   jax.tree_util.tree_leaves(p_d)))
        print(json.dumps({"loss_err": err, "w_err": werr}))
    """)
    res = _run(code)
    assert res["loss_err"] < 1e-4
    assert res["w_err"] < 1e-3


def test_sharded_decode_matches_single_device():
    code = _PRELUDE + textwrap.dedent("""
        cfg = get_smoke_config("granite-moe-3b-a800m").replace(
            dtype="float32", remat=False, capacity_factor=4.0)
        params = tfm.init_lm(cfg, jax.random.PRNGKey(0))
        toks = jax.random.randint(jax.random.PRNGKey(1), (4, 9),
                                  0, cfg.vocab)
        cache = tfm.init_cache(cfg, 4, 32, dtype=jnp.float32)
        _, cache = tfm.prefill(cfg, params, toks[:, :8], cache)
        ref, _ = tfm.decode_step(cfg, params, toks[:, 8:9], cache, 8)

        p_sh = shd.to_named(shd.param_specs(params, mesh), mesh)
        c_sh = shd.to_named(shd.cache_specs(cfg, cache, mesh, 4), mesh)
        t_sh = NamedSharding(mesh, P("data", None))
        r_sh = NamedSharding(mesh, P())
        fn = jax.jit(lambda p, t, c, pos: tfm.decode_step(cfg, p, t, c,
                                                          pos),
                     in_shardings=(p_sh, t_sh, c_sh, r_sh))
        out, _ = fn(jax.device_put(params, p_sh),
                    jax.device_put(toks[:, 8:9], t_sh),
                    jax.device_put(cache, c_sh),
                    jax.device_put(jnp.asarray(8), r_sh))
        err = float(jnp.max(jnp.abs(out - ref)))
        print(json.dumps({"err": err}))
    """)
    assert _run(code)["err"] < 1e-3


def test_seq_sharded_decode_batch1():
    """long-context pattern: batch=1, KV sequence sharded over data."""
    code = _PRELUDE + textwrap.dedent("""
        cfg = get_smoke_config("internlm2-20b").replace(
            dtype="float32", remat=False)
        params = tfm.init_lm(cfg, jax.random.PRNGKey(0))
        toks = jax.random.randint(jax.random.PRNGKey(1), (1, 17),
                                  0, cfg.vocab)
        cache = tfm.init_cache(cfg, 1, 32, dtype=jnp.float32)
        _, cache = tfm.prefill(cfg, params, toks[:, :16], cache)
        ref, _ = tfm.decode_step(cfg, params, toks[:, 16:17], cache, 16)

        p_sh = shd.to_named(shd.param_specs(params, mesh), mesh)
        c_spec = shd.cache_specs(cfg, cache, mesh, 1)
        assert c_spec.layers.kv.k[2] == "data", c_spec.layers.kv.k
        c_sh = shd.to_named(c_spec, mesh)
        t_sh = NamedSharding(mesh, P(None, None))
        r_sh = NamedSharding(mesh, P())
        fn = jax.jit(lambda p, t, c, pos: tfm.decode_step(cfg, p, t, c,
                                                          pos),
                     in_shardings=(p_sh, t_sh, c_sh, r_sh))
        out, _ = fn(jax.device_put(params, p_sh),
                    jax.device_put(toks[:, 16:17], t_sh),
                    jax.device_put(cache, c_sh),
                    jax.device_put(jnp.asarray(16), r_sh))
        err = float(jnp.max(jnp.abs(out - ref)))
        print(json.dumps({"err": err}))
    """)
    assert _run(code)["err"] < 1e-3


@pytest.mark.slow
def test_production_mesh_lowering_sample():
    """One full production-mesh (256-chip) lowering as a test — the
    complete matrix lives in results/dryrun (launch/dryrun.py)."""
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun", "--arch",
         "stablelm-3b", "--shape", "decode_32k", "--mesh", "single",
         "--out", "/tmp/dryrun_test"],
        env=env, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "1 ok" in out.stdout
