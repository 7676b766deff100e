"""The virtual genome: i mod pool, fresh copies, no cache key."""
import numpy as np
import pytest

import bench_tiny  # noqa: F401
from genome import VirtualGenome, decode


@pytest.fixture
def genome():
    rng = np.random.default_rng(0)
    pool = rng.integers(0, 256, size=(16, 3), dtype=np.uint8)   # 16 markers, N=10
    return VirtualGenome(pool, n_samples=10, n_markers=100)


def test_marker_i_is_pool_row_i_mod_pool(genome):
    got = genome.read_packed(10, 40)
    assert np.array_equal(got, genome.pool[np.arange(10, 40) % 16])
    assert np.array_equal(genome.read_packed(32, 48), genome.pool)


def test_every_read_is_a_fresh_copy(genome):
    a, b = genome.read_packed(0, 8), genome.read_packed(0, 8)
    assert not np.shares_memory(a, genome.pool) and not np.shares_memory(a, b)
    a[:] = 0
    assert np.array_equal(genome.read_packed(0, 8), b)


def test_no_cache_key_so_the_slab_cache_bypasses(genome):
    from repro.io.packed_cache import PackedSlabCache

    assert not hasattr(genome, "packed_cache_key")
    cache = PackedSlabCache()
    cache.read(genome, 0, 8)
    cache.read(genome, 0, 8)
    assert (cache.hits, cache.misses, cache.bypasses) == (0, 0, 2)


def test_decode_matches_the_plink_reader(genome):
    from repro.io.plink import decode_packed

    assert np.array_equal(decode(genome.pool, 10), decode_packed(genome.pool, 10))
    assert np.array_equal(genome.read_dosages(20, 30), decode_packed(genome.read_packed(20, 30), 10))


def test_ids_are_made_on_demand_and_range_checked(genome):
    ids = genome.marker_ids
    assert len(ids) == 100 and ids[7] == "vm0000007" and ids[-1] == "vm0000099"
    assert ids[2:4] == ["vm0000002", "vm0000003"]
    with pytest.raises(IndexError):
        ids[100]
    with pytest.raises(IndexError):
        genome.read_packed(90, 101)


def test_packed_staging_is_chosen_for_it(genome):
    from repro.core.engines import resolve_genotype_staging

    assert resolve_genotype_staging("auto", genome) == "packed"
