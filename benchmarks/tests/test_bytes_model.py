"""The byte model at dim 1 and dim 128, and the two gates: a roofline share
above 100 and a compile inside the window fail the run."""

import pytest

from benchmarks.harness import bytes_model
from benchmarks.harness.cell import load_module
from benchmarks.harness.correctness import window_checks
from benchmarks.harness.peaks import peaks_for


def test_dim_1_one_plane_is_five_row_touches_of_4_bytes():
    # the pull's read, the apply's two reads and two writes: 5 x 4 B a row
    assert bytes_model.step_hbm_bytes(1, dim=1, planes=1) == 20
    assert bytes_model.step_hbm_bytes(40_000, dim=1, planes=1) == 800_000
    assert bytes_model.pull_bytes(40_000, 1) == 160_000
    assert bytes_model.apply_bytes(40_000, 1, planes=1) == 640_000


def test_dim_128_rows_are_512_bytes_and_planes_count():
    assert bytes_model.row_bytes(128) == 512
    assert bytes_model.step_hbm_bytes(30_500, dim=128, planes=1) == 30_500 * 512 * 5
    # Adam: two planes -> 1 + 3 reads + 3 writes
    assert bytes_model.step_hbm_bytes(10, dim=128, planes=2) == 10 * 512 * 7
    assert bytes_model.adagrad_flops(10, 128) == 7 * 10 * 128


def test_roofline_share_is_never_clamped():
    bw = peaks_for("TPU v5 lite")["hbm_bytes_per_s"]
    assert bw == 819e9
    # 819 GB needed in 1 busy second is the roofline; in half a second, 200 %
    assert bytes_model.hbm_roofline_pct(819e9, 1.0, bw) == pytest.approx(100.0)
    assert bytes_model.hbm_roofline_pct(819e9, 0.5, bw) == pytest.approx(200.0)
    assert bytes_model.hbm_roofline_pct(1.0, 0.0, bw) is None
    with pytest.raises(KeyError):
        peaks_for("TPU v9 imaginary")


def test_roofline_above_100_and_a_compile_in_the_window_fail_the_run():
    roof = load_module("layer_metrics", "hbm_roofline")
    assert roof.check(99.9) == [] and roof.check(100.0) == []
    assert roof.check(100.1) and "above 100" in roof.check(296.0)[0]
    losses = [1.0 - 0.01 * i for i in range(40)]
    assert window_checks({}, losses, set(), compiles=0) == []
    assert window_checks({}, losses, set(), compiles=1) == [
        "compiles_in_window = 1"
    ]


def test_loss_fall_compares_whole_passes_over_the_cycle():
    # a cycle of 8 batches whose losses differ by far more than a pass
    # learns: tenths would compare other batches, whole passes the same ones
    per_batch = [0.40, 0.41, 0.42, 0.43, 0.44, 0.45, 0.46, 0.47]
    losses = [per_batch[i % 8] - 0.0002 * (i // 8) for i in range(40)]
    assert sum(losses[-4:]) > sum(losses[:4])  # by tenths: a rise
    assert window_checks({}, losses, set(), 0) != []
    assert window_checks({}, losses, set(), 0, cycle_steps=8) == []
    rising = [per_batch[i % 8] + 0.0002 * (i // 8) for i in range(40)]
    assert window_checks({}, rising, set(), 0, cycle_steps=8)
    # never over half of a short window: two passes of 32 compare 16 and 16
    assert window_checks({}, losses[:32], set(), 0, cycle_steps=64) == []


def test_gradient_check_holds_the_median_and_the_worst_example():
    import numpy as np

    from benchmarks.harness.correctness import compare_grads

    rng = np.random.default_rng(0)
    want = rng.standard_normal((256 * 4, 8)).astype(np.float32)
    limits = dict(median=1e-3, worst=1e-1)
    fails, info = compare_grads(want, want, "g", 256, **limits)
    assert fails == [] and info["worst"] == 0.0
    kink = want.copy()
    kink[5] += 0.2  # one example of 256 off, inside `worst`
    fails, info = compare_grads(kink, want, "g", 256, **limits)
    assert fails == [] and 0.01 < info["worst"] < 0.1 and info["median"] == 0
    wrong = want.copy()
    wrong[5] += 2.0  # one example off by a whole gradient
    assert compare_grads(wrong, want, "g", 256, **limits)[0]
    low = want * np.float32(1.01)  # every example off: a lower precision
    assert compare_grads(low, want, "g", 256, **limits)[0]
    # no example is free where both limits are the same
    assert compare_grads(kink, want, "g", 256, median=1e-5, worst=1e-5)[0]
    assert compare_grads(want[:8], want, "g", 256, **limits)[0]  # shape
