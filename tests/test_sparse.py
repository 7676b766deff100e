"""Sparse threshold-compacted p-value epilogue (DESIGN.md §13).

The contract under test: with ``sparse_epilogue=True`` the scan screens
every lane on t^2 against the host-inverted per-dof threshold, compacts
survivors into a fixed-capacity device buffer, and runs the exact 128-trip
CF only there — and the hit set, hit stats, best-trait tables, lambda-GC,
and checkpoint shards are all *bitwise-identical* to the dense full-tile
CF path, across dense/fused/lmm engines, blocked grids, overflowing
buffers, and the multi-device executor.
"""
import json
import os
import subprocess
import sys
import textwrap

import jax.numpy as jnp
import numpy as np
import pytest

from repro.api.specs import ScanConfig
from repro.core import association as A
from repro.core import stats as S
from repro.core.screening import GenomeScan
from repro.io import plink


@pytest.fixture(scope="module")
def source(cohort_files):
    return plink.PlinkBed(cohort_files["bed"])


def _run(source, cohort, **kw):
    base = dict(
        batch_markers=128, block_m=64, block_n=128, block_p=64,
        hit_threshold_nlp=3.0,
    )
    base.update(kw)
    return GenomeScan(
        source, cohort.phenotypes, cohort.covariates, config=ScanConfig(**base)
    ).run()


def _sorted(hits, stats):
    order = np.lexsort((hits[:, 1], hits[:, 0]))
    return hits[order], stats[order]


def _assert_identical(dense, sparse, label=""):
    np.testing.assert_array_equal(dense.best_nlp, sparse.best_nlp, err_msg=label)
    np.testing.assert_array_equal(
        dense.best_marker, sparse.best_marker, err_msg=label
    )
    dh, ds = _sorted(dense.hits, dense.hit_stats)
    sh, ss = _sorted(sparse.hits, sparse.hit_stats)
    np.testing.assert_array_equal(dh, sh, err_msg=label)
    np.testing.assert_array_equal(ds, ss, err_msg=label)
    assert dense.lambda_gc == sparse.lambda_gc, label
    np.testing.assert_array_equal(dense.maf, sparse.maf, err_msg=label)
    np.testing.assert_array_equal(dense.valid, sparse.valid, err_msg=label)


# ------------------------------------------------------------ plan building


def test_plan_refuses_degenerate_thresholds():
    assert A.plan_sparse_epilogue(0.0, 100.0) is None
    assert A.plan_sparse_epilogue(-2.0, 100.0) is None
    plan = A.plan_sparse_epilogue(7.301, 998.0)
    assert plan.t2_screen > 0 and plan.capacity >= 1


def test_plan_capacity_clamped_to_cell_area():
    plan = A.plan_sparse_epilogue(7.301, 998.0, capacity=4096, cell_area=128)
    assert plan.capacity == 128


def test_plan_capacity_rounds_to_simd_multiple():
    """Capacities round up to a multiple of 64 so the (capacity,) refine
    executable has no scalar remainder lanes (lane position must not be
    able to change a bit)."""
    assert A.plan_sparse_epilogue(7.301, 998.0, capacity=2).capacity == 64
    assert A.plan_sparse_epilogue(7.301, 998.0, capacity=65).capacity == 128
    assert A.plan_sparse_epilogue(7.301, 998.0, capacity=4096).capacity == 4096


# ------------------------------------------------------------- compaction


def _screen(shape, lanes=(), density=0.0, seed=0):
    keep = np.random.default_rng(seed).random(shape) < density
    keep.ravel()[list(lanes)] = True
    return keep


@pytest.mark.parametrize(
    "keep, capacity",
    [
        (_screen((7, 45), density=0.2), 128),              # 315 lanes, ragged tail
        (_screen((3, 5), density=0.5), 64),                # one partial chunk
        (_screen((16, 256)), 64),                          # no survivors
        (_screen((9, 131), lanes=[9 * 131 - 1]), 64),      # the very last lane
        (_screen((4, 300), lanes=range(100, 400)), 512),   # full chunks, a long run
        (np.ones((6, 100), bool), 640),                    # every lane survives
        (_screen((64, 96), density=0.5, seed=1), 256),     # overflow past capacity
        (_screen((8, 16), density=0.7, seed=2),
         A.plan_sparse_epilogue(1.0, 998.0, cell_area=128).capacity),  # clamped
    ],
    ids=["ragged", "sub_chunk", "empty", "last_lane", "long_run", "all",
         "overflow", "clamped"],
)
def test_compact_survivors_matches_nonzero(keep, capacity):
    """The scatter-free compaction is ``np.nonzero`` order, first-K, -1
    padded, bit for bit; the count stays exact past capacity."""
    idx, count = A.compact_survivors(jnp.asarray(keep), capacity)
    want = np.full(capacity, -1, np.int32)
    nz = np.nonzero(keep.ravel())[0][:capacity]
    want[: nz.size] = nz
    assert idx.dtype == jnp.int32 and count.dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(idx), want)
    assert int(count) == int(keep.sum())


def test_sparse_epilogue_has_no_scatter():
    """The epilogue's compaction lowers without a scatter: ``jnp.nonzero``
    would add a scatter-add of every lane of the tile into ``capacity``
    bins, which the TPU serialises."""
    import jax

    plan = A.plan_sparse_epilogue(7.301, 998.0)
    tile = jax.ShapeDtypeStruct((1024, 2048), jnp.float32)
    hlo = jax.jit(
        lambda r, t: A.sparse_epilogue_outputs(r, t, 998.0, plan)
    ).lower(tile, tile).as_text()
    assert "gather" in hlo and "scatter" not in hlo


def test_tie_breaks_match_dense_argmax_rule():
    """Exact t^2 ties (plus nlp plateaus) resolve to the first index in
    both paths — the redefined winner rule both share.  The step emits the
    winner *t*, not its nlp: every emitted p-value is refined host-side
    through the canonical executable."""
    dof = 998.0
    t = np.zeros((6, 3), np.float32)
    t[1, 0], t[4, 0] = 5.0, -5.0        # equal t^2, opposite sign
    t[2, 1], t[3, 1] = 3.0, 3.0         # exact duplicate
    r = (t / 40.0).astype(np.float32)
    plan = A.plan_sparse_epilogue(1.0, dof, capacity=t.size)
    out = {
        k: np.asarray(v)
        for k, v in A.sparse_epilogue_outputs(
            jnp.asarray(r), jnp.asarray(t), dof, plan
        ).items()
    }
    assert "batch_best_nlp" not in out and "hit_nlp" not in out  # no in-step CF
    np.testing.assert_array_equal(out["batch_best_row"], [1, 2, 0])
    np.testing.assert_array_equal(
        out["batch_best_t"], t[[1, 2, 0], np.arange(3)]
    )
    nlp = S.refine_neglog10p(out["batch_best_t"], dof)
    np.testing.assert_array_equal(nlp, S.refine_neglog10p(t[[1, 2, 0], np.arange(3)], dof))


# ----------------------------------------------------- scan-level identity


@pytest.mark.parametrize(
    "kw",
    [
        {"engine": "dense"},
        {"engine": "dense", "options": A.AssocOptions(dof_mode="exact")},
        {"engine": "fused"},
        {"engine": "lmm", "lmm_delta": 1.0},
        {"engine": "lmm", "lmm_delta": 1.0, "lmm_epilogue": "fused"},
    ],
    ids=["dense", "dense_exact", "fused", "lmm", "lmm_fused"],
)
def test_sparse_scan_bitwise_identical(source, cohort, kw):
    dense = _run(source, cohort, sparse_epilogue=False, **kw)
    sparse = _run(source, cohort, sparse_epilogue=True, **kw)
    _assert_identical(dense, sparse, str(kw))
    assert len(sparse.hits) > 0  # the comparison must not be vacuous


def test_sparse_blocked_grid_identical(source, cohort):
    dense = _run(source, cohort, sparse_epilogue=False, trait_block=64)
    sparse = _run(source, cohort, sparse_epilogue=True, trait_block=64)
    _assert_identical(dense, sparse, "blocked")


def test_sparse_overflow_falls_back_bitwise(source, cohort):
    """A permissive threshold with the minimum (64-lane) buffer overflows;
    the host fallback screens the pulled t tile and refines survivors
    through the same (capacity,) executable — identical results."""
    dense = _run(source, cohort, sparse_epilogue=False, hit_threshold_nlp=1.0)
    tiny = _run(source, cohort, sparse_epilogue=True, hit_capacity=2,
                hit_threshold_nlp=1.0)
    _assert_identical(dense, tiny, "overflow")
    assert len(dense.hits) > 64  # far beyond the rounded-up capacity


def test_sparse_checkpoint_shards_identical(source, cohort, tmp_path):
    """Committed shard *contents* match array-for-array: a scan
    checkpointed sparse resumes dense and vice versa (the flag is not
    fingerprinted)."""
    from repro.runtime.checkpoint import ScanCheckpoint

    dirs = {}
    for tag, flag in (("dense", False), ("sparse", True)):
        ck = str(tmp_path / tag)
        _run(source, cohort, sparse_epilogue=flag, trait_block=64,
             checkpoint_dir=ck)
        dirs[tag] = ScanCheckpoint.open_existing(ck)
    a, b = dirs["dense"], dirs["sparse"]
    cells = sorted(a.completed_cells())
    assert cells == sorted(b.completed_cells()) and cells
    for bi, ki in cells:
        sa, sb = a.load_cell(bi, ki), b.load_cell(bi, ki)
        assert sorted(sa) == sorted(sb), (bi, ki)
        for k in sa:
            np.testing.assert_array_equal(sa[k], sb[k], err_msg=f"{bi}.{ki}.{k}")


# -------------------------------------------------------- view-level sparse


def test_batchview_sparse_accessors(source, cohort):
    """A live sparse session serves hits from the compacted buffers (refined
    host-side through the canonical executable) and can still reconstruct
    the dense nlp tile for report/QC paths."""
    from repro.api import GridSpec, Study

    study = Study.from_arrays(source, cohort.phenotypes, cohort.covariates)
    plan = study.plan(
        grid=GridSpec(batch_markers=128, block_m=64, block_n=128, block_p=64),
        hit_threshold_nlp=3.0,
        sparse_epilogue=True,
    )
    session = plan.run()
    seen_hits = False
    for cell in session.events():
        v = cell.view
        assert v.is_sparse and not v.overflowed
        assert v.hit_capacity % 64 == 0
        if v.screen_count:
            keep = (v.hit_idx >= 0) & (v.hit_nlp >= 3.0)
            if keep.any():
                seen_hits = True
                flat = v.hit_idx[keep].astype(np.int64)
                # the cell's extracted rows come straight from the buffers
                np.testing.assert_array_equal(
                    cell.hits[:, 0] - cell.lo, flat // v.n_traits
                )
                np.testing.assert_array_equal(cell.hit_stats[:, 2], v.hit_nlp[keep])
                # the reconstructed tile agrees to CF accuracy (lane
                # positions differ, so bit-equality is not promised there)
                np.testing.assert_allclose(
                    v.nlp[flat // v.n_traits, flat % v.n_traits],
                    v.hit_nlp[keep], rtol=1e-5, atol=1e-5,
                )
    assert seen_hits


def test_batchview_overflow_flag(source, cohort):
    """screen_count past capacity raises the overflow flag; extraction
    still lands on the same rows via the host fallback."""
    from repro.api import GridSpec, Study

    study = Study.from_arrays(source, cohort.phenotypes, cohort.covariates)
    session = study.plan(
        grid=GridSpec(batch_markers=128, block_m=64, block_n=128, block_p=64),
        hit_threshold_nlp=1.0,
        sparse_epilogue=True,
        hit_capacity=2,
    ).run()
    flags = [cell.view.overflowed for cell in session.events()]
    assert any(flags)


# ------------------------------------------------------ multi-device (§12)


_CHILD = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json, tempfile
    import os.path as osp
    from repro.api import ExecSpec, GridSpec, LmmSpec, Study, TsvWriter
    from repro.io import open_genotypes, synth

    co = synth.make_cohort(n_samples=200, n_markers=400, n_traits=12,
                           n_causal=4, seed=5)
    d = tempfile.mkdtemp()
    beds = synth.write_split_plink(co, osp.join(d, "toy"), n_shards=3)
    src = open_genotypes(",".join(beds))
    study = Study.from_arrays(src, co.phenotypes, co.covariates)
    grid = GridSpec(batch_markers=128, block_m=64, block_n=128, block_p=4,
                    trait_block=4)
    FILES = ("hits.tsv", "per_trait_best.tsv", "qc.tsv")

    def scan(tag, sparse, devices, **plan_kw):
        session = study.plan(
            grid=grid, hit_threshold_nlp=2.0, sparse_epilogue=sparse,
            executor=ExecSpec(devices=devices), **plan_kw,
        ).run()
        out = osp.join(d, tag)
        session.stream_to(TsvWriter(out))
        return {f: open(osp.join(out, f)).read() for f in FILES}

    out = {}
    for name, kw in {
        "dense": {},
        "lmm_loco": {"engine": "lmm",
                     "lmm": LmmSpec(loco=True, delta=1.0, epilogue="fused")},
    }.items():
        ref = scan(f"{name}_ref", False, 1, **kw)
        md = scan(f"{name}_md", True, 4, **kw)
        out[f"{name}_identical"] = md == ref
        out[f"{name}_hits"] = ref["hits.tsv"].count("\\n")
    print(json.dumps(out))
    """
)


@pytest.fixture(scope="module")
def sparse_md_results():
    env = dict(os.environ, PYTHONPATH="src")
    proc = subprocess.run(
        [sys.executable, "-c", _CHILD], capture_output=True, text=True,
        timeout=900, env=env, cwd=os.path.dirname(os.path.dirname(__file__)),
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("engine", ["dense", "lmm_loco"])
def test_sparse_multi_device_matches_dense_serial(sparse_md_results, engine):
    """sparse epilogue on 4 fake devices == dense epilogue on the serial
    walk — the §13 contract composed with the §12 executor contract."""
    assert sparse_md_results[f"{engine}_identical"] is True
    assert sparse_md_results[f"{engine}_hits"] > 1
