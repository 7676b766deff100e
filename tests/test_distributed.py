"""Multi-device semantics, validated on 8 fake host devices in a subprocess
(unit tests must keep seeing 1 device, so the flag is set only in the child
process).  Covers: sharded GWAS step vs single-device reference, logical-axis
rules, compressed psum accuracy, collective parsing calibration."""
import json
import subprocess
import sys
import textwrap

import numpy as np
import pytest

_CHILD = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json
    import traceback
    import jax, jax.numpy as jnp
    import numpy as np
    from jax.sharding import PartitionSpec as P
    from repro.core.association import AssocOptions
    from repro.launch.mesh import make_mesh

    mesh = make_mesh((4, 2), ("data", "model"))
    rng = np.random.default_rng(0)
    M, N, Pn = 16, 64, 8
    g = rng.integers(0, 3, size=(M, N)).astype(np.float32)
    y = rng.normal(size=(N, Pn)).astype(np.float32)
    y = (y - y.mean(0)) / y.std(0)
    codes = rng.choice([0,1,2,3], p=[.3,.02,.38,.3], size=(M*4, N)).astype(np.uint8)
    a_rot = np.linalg.qr(rng.normal(size=(N, N)))[0].astype(np.float32)
    qhat = np.linalg.qr(rng.normal(size=(N, 3)))[0].astype(np.float32)
    vals = rng.normal(size=(8, 256)).astype(np.float32)

    def dense():
        # sharded dense GWAS step equals the single-device reference
        from repro.core.screening import build_dense_step
        ref_step = build_dense_step(n_samples=N, n_covariates=0, options=AssocOptions())
        ref = ref_step(jnp.asarray(g), jnp.asarray(y))
        out = {}
        for mode in ("mp", "sample"):
            step = build_dense_step(n_samples=N, n_covariates=0, options=AssocOptions(),
                                    mesh=mesh, mode=mode)
            got = step(jnp.asarray(g), jnp.asarray(y))
            out[f"{mode}_err"] = float(jnp.abs(got["t"] - ref["t"]).max())
        return out

    def fused():
        # fused engine under shard_map
        from repro.core.screening import build_fused_step
        from repro.kernels.gwas_dot import ops as kops
        mean, inv, valid = kops.marker_stats_from_codes(codes)
        packed = kops.pack_tiled(codes, 32)
        args = (jnp.asarray(packed), jnp.asarray(mean.reshape(-1,1)),
                jnp.asarray(inv.reshape(-1,1)), jnp.asarray(valid), jnp.asarray(y))
        kw = dict(n_samples=N, n_covariates=0, options=AssocOptions(),
                  block_m=16, block_n=32, block_p=4)
        a = build_fused_step(mesh=mesh, **kw)(*args)
        b = build_fused_step(**kw)(*args)
        return {"err": float(jnp.abs(a["t"] - b["t"]).max())}

    def lmm():
        # lmm step (rotation + whitened projection + epilogue) under pjit
        from repro.core.screening import build_lmm_step
        lref = build_lmm_step(n_samples=N, n_covariates=2, options=AssocOptions())
        lsh = build_lmm_step(n_samples=N, n_covariates=2, options=AssocOptions(), mesh=mesh)
        args = (jnp.asarray(g), jnp.asarray(a_rot), jnp.asarray(qhat), jnp.asarray(y))
        return {"err": float(jnp.abs(lref(*args)["t"] - lsh(*args)["t"]).max())}

    def psum():
        from repro.runtime.compression import compressed_psum
        f = jax.shard_map(lambda x: compressed_psum(x, "data", bits=8), mesh=mesh,
                          in_specs=P("data", None), out_specs=P("data", None),
                          check_vma=False)
        got = np.asarray(f(jnp.asarray(vals)))
        # data axis has 4 shards of 2 rows; each shard's psum = sum over shards.
        expect = np.tile(vals.reshape(4, 2, 256).sum(0), (4, 1)).reshape(8, 256)
        rms = float(np.sqrt(np.mean((got - expect) ** 2)) / np.sqrt(np.mean(expect ** 2)))
        return {"rms": rms}

    def rules():
        # logical rules + divisibility degrade
        from repro.runtime.sharding import DEFAULT_RULES
        from repro.train.partition import divisible_sharding
        spec = DEFAULT_RULES.physical(("batch", None, "heads"), mesh)
        s = divisible_sharding(mesh, P("data", "model"), (3, 64))
        return {"spec": str(spec), "degraded": str(s.spec)}

    def moe():
        # manual all-to-all MoE == GSPMD MoE under the same scope
        import dataclasses
        from repro.configs import get_config
        from repro.models import transformer as TR
        from repro.models.sharding_ctx import activation_sharding_scope
        cfg0 = get_config("granite-moe-1b-a400m").reduced()
        cfg0 = dataclasses.replace(
            cfg0, moe=dataclasses.replace(cfg0.moe, capacity_factor=float(cfg0.moe.n_experts))
        )
        tr_params = TR.init_params(cfg0, jax.random.PRNGKey(0))
        toks = jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0, cfg0.vocab)
        tr_pos = jnp.broadcast_to(jnp.arange(16), (4, 16))
        impl_outs = {}
        for impl in ("gspmd", "manual"):
            cfg_i = dataclasses.replace(cfg0, moe_impl=impl)
            def fwd(p_, t_, po_, cfg_i=cfg_i):
                with activation_sharding_scope(mesh, None):
                    return TR.forward_train(cfg_i, p_, t_, po_)
            o, _ = jax.jit(fwd)(tr_params, toks, tr_pos)
            impl_outs[impl] = o
        return {"err": float(jnp.abs(impl_outs["gspmd"] - impl_outs["manual"]).max())}

    def train():
        # train step on mesh: loss finite, params sharded
        from repro.configs import get_config
        from repro.configs.base import ShapeConfig
        from repro.train.data import make_batch
        from repro.train.train_step import TrainStepConfig, build_train_step, init_train_state
        cfg = get_config("granite-moe-1b-a400m").reduced()
        tcfg = TrainStepConfig(n_microbatches=2)
        params, opt = init_train_state(cfg, tcfg, jax.random.PRNGKey(0), max_positions=64)
        step = build_train_step(cfg, tcfg=tcfg, mesh=mesh, donate=False)
        shape = ShapeConfig("t", 32, 8, "train")
        batch = {k: jnp.asarray(v) for k, v in make_batch(cfg, shape, 0).items()}
        _, _, m = step(params, opt, batch)
        return {"loss": float(m["loss"])}

    # Each phase runs on its own: one phase's crash is reported against that
    # phase's tests only.
    out = {}
    for phase in (dense, fused, lmm, psum, rules, moe, train):
        try:
            out[phase.__name__] = phase()
        except Exception:
            out[phase.__name__] = {"error": traceback.format_exc()[-3000:]}
    print(json.dumps(out))
    """
)


@pytest.fixture(scope="module")
def child_results():
    proc = subprocess.run(
        [sys.executable, "-c", _CHILD], capture_output=True, text=True, timeout=900
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def _phase(child_results, name):
    got = child_results[name]
    assert "error" not in got, f"child phase {name!r} failed:\n{got.get('error')}"
    return got


def test_sharded_dense_modes_match_reference(child_results):
    got = _phase(child_results, "dense")
    assert got["mp_err"] < 1e-3
    assert got["sample_err"] < 1e-3


def test_sharded_fused_matches_reference(child_results):
    assert _phase(child_results, "fused")["err"] < 1e-3


def test_sharded_lmm_matches_reference(child_results):
    assert _phase(child_results, "lmm")["err"] < 1e-3


def test_compressed_psum_error_budget(child_results):
    # int8 against the global absmax: ~0.9% RMS on these 4-way sums
    assert _phase(child_results, "psum")["rms"] < 0.01


def test_logical_rules_first_fit(child_results):
    spec = _phase(child_results, "rules")["spec"]
    assert "data" in spec and "model" in spec


def test_divisibility_degrade(child_results):
    # dim of size 3 cannot shard 4 ways -> replicated; 64 shards 2-way fine
    assert _phase(child_results, "rules")["degraded"] == "PartitionSpec(None, 'model')"


def test_train_step_on_mesh(child_results):
    assert np.isfinite(_phase(child_results, "train")["loss"])


def test_manual_moe_matches_gspmd(child_results):
    assert _phase(child_results, "moe")["err"] < 1e-3


def test_collective_parser_formulas():
    from repro.launch.roofline import parse_collectives

    hlo = """
      %ar = f32[1024,256] all-reduce(f32[1024,256] %x), replica_groups={{0,1,2,3}}
      %ag = bf16[512] all-gather(bf16[128] %y), replica_groups=[2,4]<=[8]
      %cp = f32[64,64] collective-permute(f32[64,64] %z)
    """
    colls = parse_collectives(hlo)
    kinds = {c.kind: c for c in colls}
    ar = kinds["all-reduce"]
    assert ar.group_size == 4 and ar.out_bytes == 1024 * 256 * 4
    assert abs(ar.wire_bytes - 2 * ar.out_bytes * 3 / 4) < 1
    ag = kinds["all-gather"]
    assert ag.group_size == 4
    cp = kinds["collective-permute"]
    assert cp.wire_bytes == 64 * 64 * 4
