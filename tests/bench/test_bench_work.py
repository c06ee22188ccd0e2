"""BSI work counted from shapes, the table of peaks, the roofline share."""
import types

import pytest

from chipbench import peaks, work

PORCINE1 = (303, 167, 212)


def test_bsi_bytes_and_flops_from_shapes():
    w = work.bsi_pass(PORCINE1, (5, 5, 5))
    voxels = 303 * 167 * 212
    grid = 64 * 37 * 46
    assert work.grid_shape(PORCINE1, (5, 5, 5)) == (64, 37, 46)
    assert w.bytes == 12 * (voxels + grid)
    assert w.flops == 2 * 4 * 3 * (64 * 37 * 212 + 64 * 167 * 212 + voxels)
    # a step (forward + adjoint) at porcine1: ~24 B/voxel, ~0.32 ms at 819 GB/s
    step = work.roofline_seconds(w * 2, peaks.peaks_for("TPU v5 lite"))
    assert step == pytest.approx(0.3175e-3, rel=1e-3)


def test_registration_counts_every_evaluation_and_the_final_warp():
    shapes = work.level_shapes(PORCINE1, 3)
    assert shapes == [(75, 41, 53), (151, 83, 106), PORCINE1]
    forward, adjoint = work.registration_bsi(PORCINE1, (5, 5, 5), 3, 10)
    per_level = sum(work.bsi_pass(s, (5, 5, 5)).bytes * 11 for s in shapes)
    assert adjoint.bytes == per_level
    assert forward.bytes == per_level + work.bsi_pass(PORCINE1, (5, 5, 5)).bytes


def test_bsi_is_bytes_bound_on_a_v5e():
    p = peaks.peaks_for("TPU v5e")
    for w in work.registration_bsi(PORCINE1, (5, 5, 5), 3, 10):
        assert w.bytes / p["hbm_bytes_per_s"] > w.flops / p["flops_per_s"]


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peaks_for("TPU v9 imaginary")
    assert "TPU v5e" in peaks.peaks_for("TPU v5 lite")["source"]


def _roofline_ctx(kernel_s, pairs):
    cfg = {"volume": list(PORCINE1), "tile": [5, 5, 5], "levels": 3,
           "iters": 10}
    return types.SimpleNamespace(
        cell=types.SimpleNamespace(config=cfg), pairs=pairs,
        peaks=peaks.peaks_for("TPU v5 lite"),
        trace=types.SimpleNamespace(kernel_s=kernel_s))


@pytest.mark.parametrize("slower", [1.0, 1.5, 40.0])
@pytest.mark.parametrize("with_adjoint", [True, False])
def test_roofline_share_cannot_pass_100_percent(slower, with_adjoint):
    from chipbench import bench

    read = bench.load_reader(bench.os.path.join(
        bench.os.path.dirname(work.__file__), "metrics"), "bsi_roofline.single")
    p = peaks.peaks_for("TPU v5 lite")
    forward, adjoint = (work.roofline_seconds(w, p) for w in
                        work.registration_bsi(PORCINE1, (5, 5, 5), 3, 10))
    pairs = 3
    kernels = {"bsi_separable_pallas": forward * pairs * slower}
    if with_adjoint:
        kernels["bsi_adjoint_pallas_planes"] = adjoint * pairs * slower
    share = read(_roofline_ctx(kernels, pairs))
    assert share == pytest.approx(100.0 / slower)
    assert share <= 100.0 + 1e-9
