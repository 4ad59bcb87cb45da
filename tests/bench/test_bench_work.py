"""Kernel work is counted from the mapspace rows, never from the kernel's
blocks or padding."""
import pytest

from bench_tiny import REPO  # noqa: F401  (puts the repository on sys.path)
from bench import work


@pytest.fixture(scope="module")
def packed():
    from repro.core import MapperConfig
    from repro.core.designer import make_spatial_arch
    from repro.core.mapspace_array import build_packed_mapspace
    from repro.core.workload import conv2d_workload
    wl = conv2d_workload(batch=2, in_ch=4, out_ch=8, out_h=4, out_w=4,
                         kr=3, ks=3, name="tiny")
    hw = make_spatial_arch(name="t", num_pes=16, rf_words=64,
                           gbuf_words=4096)
    return build_packed_mapspace(wl, hw, MapperConfig(enable_bypass=False,
                                                      max_mappings=300))


def test_hand_count(packed):
    n = len(packed)
    assert n > 0 and packed.factors.shape[1:] == (4, 7)
    assert packed.store.shape[1:] == (3, 3)
    # per row: factors + rank [4, 7] int32, store [3, 3] bool, 2 f32 scores
    per_row_bytes = 4 * 7 * 4 + 4 * 7 * 4 + 3 * 3 * 1 + 2 * 4
    # tiles 7*4, pairs 3*3*(32 + 7*3), levels 6*3 + 6 + 4,
    # max/sum 2*5, static 2*3 + 2, score 1
    per_row_ops = 28 + 477 + 28 + 10 + 8 + 1
    assert work.row_bytes(4, 3) == per_row_bytes == 241
    assert work.row_ops(4, 3) == per_row_ops == 552
    assert work.mapspace_work(packed.factors, packed.rank, packed.store) \
        == (n * 552, n * 241)
    assert work.rows_work(n, 4, 3) == (n * 552, n * 241)


@pytest.mark.parametrize("block", [128, 256, 1024])
def test_unchanged_by_kernel_block_and_layout(packed, block):
    from repro.kernels.mapspace_eval.ops import pack_for_kernel_arrays
    arrays, _, n, blk = pack_for_kernel_arrays(
        packed.static, packed.factors, packed.rank, block=block)
    padded = arrays[0].shape[-1]
    assert n == len(packed) and padded % blk == 0 and padded >= n
    # the count reads the mapspace's n rows, whatever the lanes hold
    assert work.mapspace_work(packed.factors, packed.rank, packed.store) \
        == work.rows_work(n, 4, 3)


def test_roofline_bound_and_share():
    # the bytes' least time at the HBM peak over the measured time
    peaks = {"hbm_bytes_per_s": 1e9}
    assert work.roofline(1e6, 2e-3, peaks) == pytest.approx(50.0)
    assert work.roofline(1e6, 1e-3, peaks) == pytest.approx(100.0)
