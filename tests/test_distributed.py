"""Distribution tests: sharding rules, activation constraints, gradient
compression, and a reduced multi-device dry-run.

Multi-device cases run in a subprocess with
``XLA_FLAGS=--xla_force_host_platform_device_count`` (the flag must be set
before the first jax init, and the main test process already initialised
jax single-device)."""

import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import numpy as np
import pytest

# every test here spawns a fresh python + jax subprocess (the
# XLA_FLAGS device-count flag must precede jax init): minutes, not
# seconds -- deselect locally with -m "not slow"
pytestmark = pytest.mark.slow

SRC = str(Path(__file__).resolve().parents[1] / "src")


def _run_subprocess(code: str, devices: int = 8) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = SRC
    out = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        capture_output=True, text=True, env=env, timeout=540,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


class TestShardingRules:
    def test_param_specs_resolve(self):
        code = """
        import jax
        from jax.sharding import AxisType
        from repro.configs import get_config
        from repro.models import Model
        from repro.sharding import param_pspecs
        mesh = jax.make_mesh((2, 4), ("data", "model"), axis_types=(AxisType.Auto,) * 2)
        for arch in ("granite-3-8b", "jamba-1.5-large-398b", "arctic-480b", "rwkv6-3b"):
            cfg = get_config(arch).reduced()
            aparams = jax.eval_shape(lambda k: Model(cfg).init(k), jax.random.PRNGKey(0))
            specs = param_pspecs(aparams, mesh)
            names = set()
            for leaf, spec in zip(jax.tree.leaves(aparams), jax.tree.leaves(specs)):
                for dim, axis in enumerate(spec):
                    if axis is None: continue
                    size = 1
                    for a in (axis if isinstance(axis, tuple) else (axis,)):
                        size *= mesh.shape[a]
                    assert leaf.shape[dim] % size == 0, (arch, leaf.shape, spec)
                    names.add(axis if isinstance(axis, str) else axis[0])
            assert "model" in names, arch  # TP actually engaged
        print("OK")
        """
        assert "OK" in _run_subprocess(code)

    def test_sharded_train_step_runs(self):
        """A real sharded train step executes on 8 virtual devices and the
        loss matches the single-device step."""
        code = """
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import AxisType
        from repro.configs import get_config
        from repro.models import Model
        from repro.sharding import shard_params
        from repro.train import optimizer as opt
        from repro.train.train_step import make_train_step
        cfg = get_config("granite-3-8b").reduced()
        model = Model(cfg)
        params = model.init(jax.random.PRNGKey(0))
        tokens = jax.random.randint(jax.random.PRNGKey(1), (8, 32), 0, cfg.vocab_size)
        ref_loss = float(model.loss(params, tokens, tokens, remat=False))

        mesh = jax.make_mesh((2, 4), ("data", "model"), axis_types=(AxisType.Auto,) * 2)
        ocfg = opt.OptimizerConfig()
        step, (psh, osh, bsh), _ = make_train_step(model, ocfg, mesh, batch=8, donate=False)
        params_s = jax.device_put(params, psh)
        opt_s = jax.device_put(opt.init(ocfg, params), osh)
        batch = jax.device_put({"tokens": tokens, "labels": tokens}, bsh)
        new_p, new_o, metrics = step(params_s, opt_s, batch)
        got = float(metrics["loss"])
        assert abs(got - ref_loss) / ref_loss < 0.05, (got, ref_loss)
        assert int(new_o.step) == 1
        print("OK", got, ref_loss)
        """
        assert "OK" in _run_subprocess(code)

    def test_compressed_psum_matches_mean(self):
        code = """
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import AxisType, PartitionSpec as P
        from repro.train.train_step import compressed_psum
        mesh = jax.make_mesh((8,), ("pod",), axis_types=(AxisType.Auto,))
        x = jax.random.normal(jax.random.PRNGKey(0), (8, 64))
        def f(xs):
            return compressed_psum({"g": xs}, "pod")["g"]
        out = jax.jit(jax.shard_map(f, mesh=mesh, in_specs=P("pod", None),
                                    out_specs=P("pod", None), check_vma=False))(x)
        expected = np.sum(np.asarray(x), axis=0)
        got = np.asarray(out)[0]
        err = np.abs(got - expected).max() / (np.abs(expected).max() + 1e-9)
        assert err < 0.02, err  # int8 quantization error bound
        print("OK", err)
        """
        assert "OK" in _run_subprocess(code)

    def test_dp_compressed_train_step(self):
        code = """
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import AxisType
        from repro.configs import get_config
        from repro.models import Model
        from repro.train import optimizer as opt
        from repro.train.train_step import make_dp_compressed_step
        cfg = get_config("phi4-mini-3.8b").reduced()
        model = Model(cfg)
        params = model.init(jax.random.PRNGKey(0))
        ocfg = opt.OptimizerConfig()
        mesh = jax.make_mesh((4,), ("pod",), axis_types=(AxisType.Auto,))
        step = make_dp_compressed_step(model, ocfg, mesh)
        opt_state = opt.init(ocfg, params)
        err = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
        tokens = jax.random.randint(jax.random.PRNGKey(1), (8, 16), 0, cfg.vocab_size)
        p, s, err, metrics = step(params, opt_state, err, tokens, tokens)
        assert np.isfinite(float(metrics["loss"]))
        # error-feedback buffers are populated after a compressed step
        total_err = sum(float(jnp.abs(e).sum()) for e in jax.tree.leaves(err))
        assert total_err > 0
        print("OK", float(metrics["loss"]))
        """
        assert "OK" in _run_subprocess(code)


class TestDryRunReduced:
    """The dry-run machinery itself, on a small virtual mesh (the full
    512-device sweep runs via `python -m repro.launch.dryrun --all`)."""

    def test_lower_compile_reduced_mesh(self):
        code = """
        import jax, jax.numpy as jnp
        import numpy as np
        from jax.sharding import AxisType
        from repro.configs import get_config
        from repro.models import Model
        from repro.train import optimizer as opt
        from repro.train.train_step import make_train_step, make_decode_step
        mesh = jax.make_mesh((2, 4), ("data", "model"), axis_types=(AxisType.Auto,) * 2)
        cfg = get_config("granite-3-8b").reduced()
        model = Model(cfg)
        ocfg = opt.OptimizerConfig()
        step, _, _ = make_train_step(model, ocfg, mesh, batch=8)
        aparams = jax.eval_shape(lambda k: model.init(k), jax.random.PRNGKey(0))
        aopt = jax.eval_shape(lambda p: opt.init(ocfg, p), aparams)
        batch = {
            "tokens": jax.ShapeDtypeStruct((8, 64), jnp.int32),
            "labels": jax.ShapeDtypeStruct((8, 64), jnp.int32),
        }
        compiled = step.lower(aparams, aopt, batch).compile()
        assert compiled.memory_analysis() is not None
        dstep, _, _ = make_decode_step(model, mesh, batch=8, max_len=64)
        acache = jax.eval_shape(lambda: model.init_cache(8, 64))
        compiled2 = dstep.lower(
            aparams, jax.ShapeDtypeStruct((8, 1), jnp.int32), acache,
            jax.ShapeDtypeStruct((), jnp.int32)).compile()
        txt = compiled.as_text()
        assert any(op in txt for op in ("all-reduce", "all-gather", "reduce-scatter"))
        print("OK")
        """
        assert "OK" in _run_subprocess(code)

    def test_hlo_analysis_trip_counts(self):
        code = """
        import jax, jax.numpy as jnp
        from repro.launch.hlo_analysis import analyze_hlo
        w = jnp.ones((128, 128), jnp.float32)
        x = jnp.ones((64, 128), jnp.float32)
        def scanned(x, w):
            def body(c, _):
                return c @ w, None
            out, _ = jax.lax.scan(body, x, None, length=12)
            return out
        comp = jax.jit(scanned).lower(x, w).compile()
        ha = analyze_hlo(comp.as_text())
        expected = 2 * 64 * 128 * 128 * 12
        assert abs(ha.dot_flops - expected) / expected < 0.01, (ha.dot_flops, expected)
        assert 12 in ha.while_trip_counts
        print("OK")
        """
        assert "OK" in _run_subprocess(code, devices=1)
