"""Distribution: shard_map KrK-Picard == single-device, sharding policy,
elastic re-mesh, int8 gradient compression. Multi-device cases run in a
subprocess with 8 forced host devices (the main test process must keep
seeing exactly 1 CPU device)."""

import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _run_subprocess(code: str) -> str:
    # the child simulates devices on the CPU by design; pinned there, it
    # never needs the chip that this process may hold
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               PYTHONPATH=SRC)
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


def test_single_device_default():
    assert len(jax.devices()) == 1   # guards against flag leakage


def test_distributed_krk_matches_local():
    out = _run_subprocess("""
        import jax, numpy as np, jax.numpy as jnp
        from repro.core import SubsetBatch, random_krondpp, sample_krondpp
        from repro.core.krk_picard import krk_picard_step
        from repro.core.distributed import make_distributed_krk_step, shard_subsets
        mesh = jax.make_mesh((8,), ("data",))
        rng = np.random.default_rng(0)
        true = random_krondpp(jax.random.PRNGKey(7), (4, 5))
        subs = [s for s in (sample_krondpp(rng, true) for _ in range(40)) if s][:32]
        kmax = max(len(s) for s in subs)
        batch = SubsetBatch.from_lists(subs, k_max=kmax)
        init = random_krondpp(jax.random.PRNGKey(3), (4, 5))
        L1, L2 = init.factors
        l1, l2 = krk_picard_step(L1, L2, batch, 1.0)
        step = make_distributed_krk_step(mesh, ("data",))
        sb = shard_subsets(mesh, batch, ("data",))
        with mesh:
            d1, d2 = step(L1, L2, sb, 1.0)
        np.testing.assert_allclose(np.asarray(d1), np.asarray(l1), rtol=2e-3, atol=2e-3)
        np.testing.assert_allclose(np.asarray(d2), np.asarray(l2), rtol=2e-3, atol=2e-3)
        print("DIST_OK")
    """)
    assert "DIST_OK" in out


def test_sharded_train_step_runs_and_matches():
    """Real multi-device train step == single-device step (same loss)."""
    out = _run_subprocess("""
        import jax, numpy as np, jax.numpy as jnp
        from repro.configs import smoke_config
        from repro.models import LM
        from repro.optim import AdamW, OptState
        from repro.train.steps import make_train_step
        from repro.distributed.sharding import ShardingPolicy
        mesh = jax.make_mesh((4, 2), ("data", "model"),
                             axis_types=(jax.sharding.AxisType.Auto,) * 2)
        cfg = smoke_config("qwen2-0.5b")
        lm = LM(cfg)
        params = lm.init_params(jax.random.PRNGKey(0))
        opt = AdamW(lr=1e-3)
        ost = opt.init(params)
        tokens = jax.random.randint(jax.random.PRNGKey(1), (8, 33), 0, cfg.vocab)
        batch = {"tokens": tokens}
        step = make_train_step(lm, opt)
        _, _, m_local = jax.jit(step)(params, ost, batch)

        policy = ShardingPolicy(mesh, cfg)
        ps = policy.params_shardings(jax.eval_shape(lambda: params))
        os_ = OptState(step=policy.replicated(),
                       m=policy.params_shardings(jax.eval_shape(lambda: ost.m)),
                       v=policy.params_shardings(jax.eval_shape(lambda: ost.v)))
        bs = policy.batch_shardings(jax.eval_shape(lambda: batch))
        with jax.set_mesh(mesh):
            jstep = jax.jit(step, in_shardings=(ps, os_, bs))
            _, _, m_dist = jstep(jax.device_put(params, ps),
                                 jax.device_put(ost, os_),
                                 jax.device_put(batch, bs))
        np.testing.assert_allclose(float(m_dist["loss"]), float(m_local["loss"]),
                                   rtol=2e-3)
        print("TRAIN_OK", float(m_dist["loss"]))
    """)
    assert "TRAIN_OK" in out


def test_elastic_remesh_plan():
    from repro.distributed.elastic import elastic_remesh
    devs = jax.devices() * 8              # simulated 8 survivors (1 real dev)
    plan = elastic_remesh(devs[:6], model_parallel=2, old_data_parallel=4)
    assert plan is not None
    assert plan.data_parallel == 3
    assert plan.microbatch_multiplier == 2
    assert elastic_remesh(devs[:1], model_parallel=2, old_data_parallel=4) is None


def test_int8_compression_error_feedback():
    """Quantize + error feedback: residual-corrected stream converges to the
    true mean over steps (bias cancellation)."""
    from repro.optim.compression import _quantize
    rng = np.random.default_rng(0)
    g = rng.standard_normal(512).astype(np.float32)
    resid = np.zeros_like(g)
    errs = []
    acc = np.zeros_like(g)
    for t in range(20):
        q, s = _quantize(jnp.asarray(g + resid))
        deq = np.asarray(q, np.float32) * float(s)
        resid = (g + resid) - deq
        acc += deq
        errs.append(np.abs(acc / (t + 1) - g).mean())
    assert errs[-1] < errs[0] * 0.25          # error feedback shrinks bias


def test_sharding_policy_specs():
    """Spec table sanity on a fake 4x2 mesh (no devices needed)."""
    out = _run_subprocess("""
        import jax, jax.numpy as jnp
        from repro.configs import get_config
        from repro.models import LM
        from repro.distributed.sharding import ShardingPolicy
        mesh = jax.make_mesh((4, 2), ("data", "model"))
        cfg = get_config("mixtral-8x7b")
        lm = LM(cfg)
        shapes = jax.eval_shape(lm.init_params, jax.random.PRNGKey(0))
        policy = ShardingPolicy(mesh, cfg)
        sh = policy.params_shardings(shapes)
        # expert weights: E over model, last dim over data
        spec = sh["blocks"]["head"]["layer0"]["moe"]["w_gate"].spec
        assert spec[1] == "model" and spec[3] in ("data", ("data",)), spec
        # wq: TP on out dim
        spec = sh["blocks"]["head"]["layer0"]["attn"]["wq"].spec
        assert spec[2] == "model", spec
        print("SPEC_OK")
    """)
    assert "SPEC_OK" in out
