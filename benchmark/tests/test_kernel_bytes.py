"""The least-bytes function of the commit phase against hand arithmetic
for TransferAir at BASELINE-1: 278 columns x 2^14 rows, blowup 8."""

import kernel_bytes


def test_commit_phase_bytes_transfer_air_by_hand():
    n, big = 16384, 131072                     # 2^14 rows, x8
    trace_read = 278 * n * 4                   # 18,219,008
    lde_write = 278 * big * 4                  # 145,752,064
    lde_read_for_leaves = 278 * big * 4        # 145,752,064
    digests = (big + big - 1) * 8 * 4          # 8,388,576
    assert trace_read == 18_219_008
    assert lde_write == 145_752_064
    assert digests == 8_388_576
    by_hand = trace_read + lde_write + lde_read_for_leaves + digests
    assert by_hand == 318_111_712
    assert kernel_bytes.commit_phase_bytes(278, 14, 3) == by_hand


def test_least_time_on_a_v5e_is_under_half_a_millisecond():
    import json
    import os

    peaks = json.load(open(os.path.join(
        os.path.dirname(kernel_bytes.__file__), "peaks.json")))
    bw = peaks["devices"]["TPU v5 lite"]["hbm_bytes_per_s"]
    least = kernel_bytes.commit_phase_bytes(278, 14, 3) / bw
    assert abs(least - 318_111_712 / 819e9) < 1e-12
    assert 0.00038 < least < 0.00039
