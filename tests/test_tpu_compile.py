"""Compile rehearsal: the scan's main-path programs at real widths, compiled
for a described (not attached) TPU v5e chip.

Nothing runs: the TPU compiler only has to accept each program, which
catches what interpret mode cannot (illegal block shapes, VMEM overuse,
programs that do not fit device memory).  Widths are the paper's §3.1 cell
(``configs/gwas_ukb.py``): N=23,000 samples, 12 covariates, 8,192-marker
batches, blocks (256, 512, 256), P=2,048 traits.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and every test worker imports this file.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.association import AssocOptions
from repro.core.engines import build_dense_step, build_fused_step, build_lmm_step
from repro.kernels import tstat
from repro.kernels.gwas_dot import ops as kops
from repro.kernels.gwas_dot.gwas_dot import build_gwas_dot

N, Q, M, P = 23_000, 12, 8_192, 2_048
N_PAD = N + (-N) % 512          # samples padded to the fused kernel's block_n
DOF = N - 2
V5E_HBM = 16 * 2**30


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means no description
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip is written to the persistent cache but
    # cannot be read back without one; keep the cache out of these compiles.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture
def mosaic(monkeypatch):
    """Kernels that pick interpret mode from the (CPU) backend compile for
    the TPU instead."""
    monkeypatch.setattr(tstat, "pallas_interpret", lambda: False)


def _shape(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args, kernel: bool):
    compiled = fn.lower(*args).compile()
    assert ("tpu_custom_call" in compiled.as_text()) == kernel
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert used < V5E_HBM, f"{used / 2**30:.2f} GiB does not fit one v5e chip"
    return compiled


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["fp32", "bf16"])
def test_gwas_dot_compiles(one_chip, dtype):
    call = build_gwas_dot(M, N_PAD, P, block_m=256, block_n=512, block_p=256,
                          n_samples=N, dof=DOF, input_dtype=dtype)
    _compile(
        jax.jit(call),
        _shape(one_chip, (M, N_PAD // 4), jnp.uint8),
        _shape(one_chip, (M, 1)), _shape(one_chip, (M, 1)),
        _shape(one_chip, (N_PAD, P)),
        kernel=True,
    )


def test_tstat_compiles(one_chip):
    _compile(
        jax.jit(lambda r: tstat.tstat(r, DOF, interpret=False)),
        _shape(one_chip, (M, P)),
        kernel=True,
    )


def test_screen_compact_compiles(one_chip):
    _compile(
        jax.jit(lambda r: tstat.screen_compact(r, DOF, 30.0, 4096, interpret=False)),
        _shape(one_chip, (M, P)),
        kernel=True,
    )


@pytest.mark.parametrize("sparse", [True, False], ids=["sparse", "dense_epilogue"])
def test_fused_step_compiles(one_chip, sparse):
    step = build_fused_step(n_samples=N, n_covariates=Q, options=AssocOptions(),
                            interpret=False, sparse_epilogue=sparse)
    _compile(
        step,
        _shape(one_chip, (M, N_PAD // 4), jnp.uint8),
        _shape(one_chip, (M, 1)), _shape(one_chip, (M, 1)),
        _shape(one_chip, (M,), jnp.bool_), _shape(one_chip, (N, P)),
        kernel=True,
    )
    # packed staging's device front end: PLINK bytes -> the kernel layout
    _compile(
        jax.jit(lambda b: kops.repack_plink_tiled_device(
            b, n_samples=N, block_n=512, block_m=256)),
        _shape(one_chip, (M, -(-N // 4)), jnp.uint8),
        kernel=False,
    )


@pytest.mark.parametrize("sparse", [True, False], ids=["sparse", "dense_epilogue"])
def test_dense_steps_compile(one_chip, sparse):
    step = build_dense_step(n_samples=N, n_covariates=Q, options=AssocOptions(),
                            trait_tile=256, sparse_epilogue=sparse)
    _compile(
        jax.jit(lambda b: kops.decode_packed_device(b, n_samples=N)),
        _shape(one_chip, (M, -(-N // 4)), jnp.uint8),
        kernel=False,
    )
    _compile(step.prolog, _shape(one_chip, (M, N)), kernel=False)
    _compile(
        step.cell,
        _shape(one_chip, (M, N)), _shape(one_chip, (M,)),
        _shape(one_chip, (M,), jnp.bool_), _shape(one_chip, (N, P)),
        kernel=False,
    )


def test_lmm_prolog_compiles(one_chip):
    step = build_lmm_step(n_samples=N, n_covariates=Q, options=AssocOptions())
    _compile(
        step.prolog,
        _shape(one_chip, (M, N)), _shape(one_chip, (N, N)),
        _shape(one_chip, (N, Q + 1)),  # [1 | C] whitened
        kernel=False,
    )


@pytest.mark.parametrize("epilogue", ["dense", "fused"])
def test_lmm_cell_compiles(one_chip, mosaic, epilogue):
    step = build_lmm_step(n_samples=N, n_covariates=Q, options=AssocOptions(),
                          epilogue=epilogue, sparse_epilogue=True)
    _compile(
        step.cell,
        _shape(one_chip, (M, N)), _shape(one_chip, (M,)),
        _shape(one_chip, (M,), jnp.bool_), _shape(one_chip, (N, P)),
        kernel=epilogue == "fused",
    )
