"""Ahead-of-time compiles of the main path for a TPU v5e, without the chip.

The TPU compiler ships with the installed JAX and compiles for a chip
that is described rather than attached.  Interpret-mode tests cannot see
what Mosaic refuses (unsupported casts, scoped-VMEM overflows), so each
Pallas kernel of the gateway path, and the jitted launch and explain
programs that call them, are compiled here at the shape of one B=4096 x
64-node gateway batch: N = 262144 nodes, a window of W = 8 assertion rows
per node, and M = 256 property-table rows.  A compile that passes is not a
chip run; it only says the chip's compiler accepts the program.

This is the only file that describes the chip.  The topology is built
inside a module-scoped fixture, never at import: only one process at a
time may load the TPU library, and the test runner's workers all import
every test file.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

N_NODES = 4096 * 64
WINDOW = 8
N_ROWS = 256


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", os.environ.get("TPU_LOG_DIR", "disabled"))
        try:
            return topologies.get_topology_desc(
                platform="tpu", topology_name="v5e:2x2"
            )
        except Exception as e:  # noqa: BLE001 -- any failure means "cannot describe"
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep the cache off meanwhile."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture
def on_chip(one_chip, no_persistent_cache):
    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    return sds


def _node_cols(sds):
    n = N_NODES
    return {
        "type": sds((n,), jnp.int32),
        "is_int": sds((n,), jnp.int32),
        "num": sds((n,), jnp.float32),
        "size": sds((n,), jnp.int32),
        "acquired": sds((n,), jnp.int32),
        "str_hash": sds((n, 8), jnp.uint32),
        "str_prefix": sds((n, 2), jnp.uint32),
    }


def _row_cols(sds, shape):
    return {
        "op": sds(shape, jnp.int32),
        "f0": sds(shape, jnp.float32),
        "i0": sds(shape, jnp.int32),
        "i1": sds(shape, jnp.int32),
        "u0": sds(shape, jnp.uint32),
        "u1": sds(shape, jnp.uint32),
        "hash": sds(shape + (8,), jnp.uint32),
    }


def _hash_match(sds):
    from repro.kernels.hash_match import hash_match_pallas

    args = (
        sds((N_NODES, 8), jnp.uint32),
        sds((N_NODES,), jnp.int32),
        sds((N_ROWS, 8), jnp.uint32),
        sds((N_ROWS,), jnp.int32),
    )
    return hash_match_pallas, args


def _assertion_eval_window(sds):
    from repro.kernels.assertion_eval import assertion_eval_window_pallas

    return assertion_eval_window_pallas, (
        _node_cols(sds),
        _row_cols(sds, (N_NODES, WINDOW)),
    )


@pytest.mark.parametrize(
    "kernel", [_hash_match, _assertion_eval_window], ids=lambda k: k.__name__[1:]
)
def test_kernel_compiles_for_v5e(kernel, on_chip):
    fn, args = kernel(on_chip)
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


@pytest.fixture
def mosaic_kernels(monkeypatch):
    """Make the kernel wrappers lower Mosaic kernels although this
    process's default backend is the CPU; traces made meanwhile are
    dropped afterwards so no later test reuses them."""
    from repro.kernels import ops

    monkeypatch.setattr(ops, "_interpret_default", lambda: False)
    jax.clear_caches()
    yield
    jax.clear_caches()


def _gateway_program(sds, program):
    """One of the gateway validator's jitted programs, lowered at one
    B=4096 x 64-node batch."""
    from repro.data.doc_table import encode_batch
    from repro.registry import SchemaRegistry
    from repro.registry.presets import GATEWAY_SCHEMAS

    reg = SchemaRegistry(use_pallas=True)
    for name, schema in GATEWAY_SCHEMAS.items():
        reg.register(name, schema)
    validator = reg.batch_validator()
    assert validator.tape.n_members == len(GATEWAY_SCHEMAS)
    batch = N_NODES // 64
    columns = encode_batch([None], max_nodes=64).columns()
    cols = {
        k: sds((batch,) + v.shape[1:], jax.dtypes.canonicalize_dtype(v.dtype))
        for k, v in columns.items()
    }
    return getattr(validator, program).lower(cols, sds((batch,), jnp.int32))


def test_gateway_launch_compiles_for_v5e(on_chip, mosaic_kernels):
    compiled = _gateway_program(on_chip, "_fn").compile()
    # hash_match and the windowed assertion kernel
    assert compiled.as_text().count("tpu_custom_call") >= 2


def test_explain_launch_compiles_for_v5e(on_chip, mosaic_kernels):
    compiled = _gateway_program(on_chip, "_explain_fn").compile()
    # hash_match and the windowed assertion kernel
    assert compiled.as_text().count("tpu_custom_call") >= 2
