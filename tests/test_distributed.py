"""Distributed-correctness tests on a fake multi-device mesh.

These run in a subprocess so the 8 fake CPU devices never leak into the
other tests (jax pins the device count at first init).
"""
import json
import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_sub(code: str) -> dict:
    prog = ("import os\n"
            "os.environ['XLA_FLAGS'] = "
            "'--xla_force_host_platform_device_count=8'\n"
            + textwrap.dedent(code))
    env = dict(os.environ,
               PYTHONPATH=os.path.join(REPO, "src"))
    out = subprocess.run([sys.executable, "-c", prog], capture_output=True,
                         text=True, env=env, timeout=900)
    assert out.returncode == 0, f"stderr:\n{out.stderr[-4000:]}"
    return json.loads(out.stdout.strip().splitlines()[-1])


@pytest.mark.slow
def test_sharded_train_step_matches_single_device():
    """One train step on a (2, 4) mesh must equal the unsharded step."""
    res = run_sub("""
        import json
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import AxisType, NamedSharding, PartitionSpec as P
        from repro.configs import get_config
        from repro.models import init_lm
        from repro.optim import AdamW
        from repro.runtime.steps import TrainState, make_train_step
        from repro.launch.specs import build_cell, _with_rules
        from repro.sharding.rules import param_sharding, batch_spec
        from repro.models.config import ShapeCell

        cfg = get_config("qwen3_32b", reduced=True)
        mesh = jax.make_mesh((2, 4), ("data", "model"),
                             axis_types=(AxisType.Auto,) * 2)
        opt = AdamW(lr=1e-3)
        params = init_lm(jax.random.PRNGKey(0), cfg)
        state = TrainState(params, opt.init(params))
        rng = np.random.RandomState(0)
        batch = {"tokens": rng.randint(0, cfg.vocab, (2, 4, 32)).astype(np.int32),
                 "labels": rng.randint(0, cfg.vocab, (2, 4, 32)).astype(np.int32)}

        # single-device reference
        step_ref = jax.jit(make_train_step(cfg, opt))
        st_ref, m_ref = step_ref(state, batch)

        # sharded
        ps = param_sharding(params, mesh)
        bs = batch_spec(mesh)
        b_sh = {k: NamedSharding(mesh, P(*((None,) + tuple(bs[k]))))
                for k in batch}
        state2 = TrainState(jax.device_put(params, ps), opt.init(params))
        with mesh:
            step_sh = jax.jit(_with_rules(make_train_step(cfg, opt), mesh),
                              in_shardings=(None, b_sh))
            st_got, m_got = step_sh(state2, batch)

        d = float(max(abs(float(m_got["loss"]) - float(m_ref["loss"])), 0))
        # parameter agreement after one update
        diffs = jax.tree_util.tree_map(
            lambda a, b: float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                               - b.astype(jnp.float32)))),
            st_got.params, st_ref.params)
        mx = max(jax.tree_util.tree_leaves(diffs))
        print(json.dumps({"loss_diff": d, "param_diff": mx}))
    """)
    assert res["loss_diff"] < 1e-3, res
    assert res["param_diff"] < 1e-3, res


def test_compressed_psum_error_feedback():
    """Int8 error-feedback gradient compression: mean over replicas is
    recovered to within quantization error, and the error feedback keeps the
    long-run average unbiased."""
    res = run_sub("""
        import json
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.optim import compressed_psum, init_error_feedback

        mesh = jax.make_mesh((8,), ("data",))
        x = np.random.RandomState(0).randn(8, 64, 256).astype(np.float32)

        def body(xs, errs):
            g, e = compressed_psum({"g": xs}, {"g": errs}, "data")
            return g["g"], e["g"]

        f = jax.jit(jax.shard_map(body, mesh=mesh,
                              in_specs=(P("data"), P("data")),
                              out_specs=(P("data"), P("data"))))
        errs = jnp.zeros_like(x)
        red, errs = f(x, errs)
        true_mean = np.mean(x, axis=0, keepdims=True)
        err1 = float(np.max(np.abs(np.asarray(red)[0] - true_mean[0])))

        # steady-state: same gradients repeatedly, EF should correct bias
        acc = np.zeros_like(true_mean[0])
        e = jnp.zeros_like(x)
        for _ in range(20):
            r, e = f(x, e)
            acc += np.asarray(r)[0]
        err_avg = float(np.max(np.abs(acc / 20 - true_mean[0])))
        print(json.dumps({"err1": err1, "err_avg": err_avg}))
    """)
    assert res["err1"] < 0.05, res          # single-shot quantization error
    assert res["err_avg"] < 0.02, res       # EF drives the average error down


@pytest.mark.slow
def test_elastic_remesh_preserves_state():
    """Re-sharding a train state onto a smaller mesh (device loss) keeps
    values identical — the elastic-scaling path."""
    res = run_sub("""
        import json
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs import get_config
        from repro.models import init_lm
        from repro.sharding.rules import param_sharding
        from repro.launch.mesh import make_mesh_for

        cfg = get_config("hymba_1_5b", reduced=True)
        params = init_lm(jax.random.PRNGKey(0), cfg)
        mesh8 = make_mesh_for(8, model_parallel=4)
        p8 = jax.device_put(params, param_sharding(params, mesh8))
        # "lose" half the devices -> remesh to 4
        mesh4 = make_mesh_for(4, model_parallel=2)
        p4 = jax.device_put(jax.tree_util.tree_map(np.asarray, p8),
                            param_sharding(params, mesh4))
        diff = max(jax.tree_util.tree_leaves(jax.tree_util.tree_map(
            lambda a, b: float(jnp.max(jnp.abs(a - b))), params, p4)))
        print(json.dumps({"diff": diff,
                          "mesh4": dict(mesh4.shape)}))
    """)
    assert res["diff"] == 0.0
    assert res["mesh4"] == {"data": 2, "model": 2}


@pytest.mark.slow
def test_dryrun_cell_compiles_on_toy_mesh():
    """End-to-end build_cell -> lower -> compile on an 8-device mesh with a
    reduced config (fast proxy for the 512-device dry-run)."""
    res = run_sub("""
        import json
        import jax
        from jax.sharding import AxisType
        from repro.configs import get_config
        from repro.launch.specs import build_cell
        from repro.launch.hlostats import analyze_hlo
        from repro.models.config import ShapeCell

        mesh = jax.make_mesh((2, 4), ("data", "model"),
                             axis_types=(AxisType.Auto,) * 2)
        cfg = get_config("gemma3_12b", reduced=True)
        cell = ShapeCell("t", 64, 8, "train")
        low = build_cell(cfg, cell, mesh)
        with mesh:
            comp = jax.jit(low.fn, in_shardings=low.in_shardings,
                           out_shardings=low.out_shardings,
                           donate_argnums=low.donate_argnums
                           ).lower(*low.arg_specs).compile()
        st = analyze_hlo(comp.as_text())
        mem = comp.memory_analysis()
        print(json.dumps({
            "flops": st.flops,
            "wire": st.wire_bytes,
            "temp": mem.temp_size_in_bytes}))
    """)
    assert res["flops"] > 0
    assert res["temp"] > 0
