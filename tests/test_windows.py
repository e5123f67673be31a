"""Window/slice math vs hand-computed values and reference semantics."""
# fast-registry: default tier — pre-dates the fast registry; re-tier on the next sweep

import numpy as np
import pytest

from video_features_tpu.utils.windows import (
    flow_stack_plan,
    form_slices,
    frame_batch_plan,
    pair_batch_plan,
    slice_starts,
)


def test_form_slices_exact_fit():
    # 100 frames, stack 15, step 15 → 6 full stacks ending at 90 (reference docstring example)
    assert form_slices(100, 15, 15) == [
        (0, 15), (15, 30), (30, 45), (45, 60), (60, 75), (75, 90)
    ]


def test_form_slices_overlap():
    assert form_slices(10, 4, 2) == [(0, 4), (2, 6), (4, 8), (6, 10)]


def test_form_slices_short_video():
    assert form_slices(3, 16, 16) == []


def test_form_slices_single():
    assert form_slices(16, 16, 16) == [(0, 16)]


def test_slice_starts_dtype():
    s = slice_starts(100, 15, 15)
    assert s.dtype == np.int32
    assert s.tolist() == [0, 15, 30, 45, 60, 75]


def test_flow_stack_plan_needs_extra_frame():
    # 65 frames exactly fills one 64-stack (64 pairs need 65 frames)
    assert flow_stack_plan(65, 64, 64).tolist() == [0]
    # 64 frames: not enough
    assert flow_stack_plan(64, 64, 64).tolist() == []
    # 130 frames: stacks at 0 and 64 (needs frame 128 inclusive)
    assert flow_stack_plan(130, 64, 64).tolist() == [0, 64]


def test_flow_stack_plan_overlapping_steps():
    # step < stack keeps overlap, mirroring stack = stack[step:] in the reference loop
    assert flow_stack_plan(11, 4, 2).tolist() == [0, 2, 4, 6]


def test_pair_batch_plan_reference_carry():
    # 10 frames, batch 4: reference runs on 5 frames (4 pairs), carries the last
    # → ranges (0,4), (4,8), final partial (8,9)
    assert pair_batch_plan(10, 4) == [(0, 4), (4, 8), (8, 9)]
    # exact fit: 9 frames, batch 4 → (0,4), (4,8) and no partial
    assert pair_batch_plan(9, 4) == [(0, 4), (4, 8)]
    # single frame: no pairs
    assert pair_batch_plan(1, 4) == []
    # two frames: one pair
    assert pair_batch_plan(2, 4) == [(0, 1)]


def test_pair_batch_plan_covers_all_pairs():
    for n in range(2, 40):
        for b in (1, 3, 7):
            ranges = pair_batch_plan(n, b)
            total = sum(e - s for s, e in ranges)
            assert total == n - 1
            # contiguity with carry
            for (s1, e1), (s2, e2) in zip(ranges, ranges[1:]):
                assert e1 == s2


def test_frame_batch_plan():
    assert frame_batch_plan(10, 4) == [(0, 4), (4, 8), (8, 10)]
    assert frame_batch_plan(4, 4) == [(0, 4)]
    assert frame_batch_plan(0, 4) == []


def test_invalid_args():
    with pytest.raises(ValueError):
        form_slices(10, 0, 1)
    with pytest.raises(ValueError):
        pair_batch_plan(10, 0)


# ---- plan invariants the corpus packer relies on (--pack_corpus): every
# clip yielded exactly once, tails covered or deliberately dropped ----------


def test_form_slices_tail_coverage_invariants():
    for n in range(0, 60):
        for stack, step in ((4, 4), (4, 2), (5, 3), (16, 16)):
            slices = form_slices(n, stack, step)
            # every slice is a full, in-range stack (no short or overrun clip)
            assert all(e - s == stack and 0 <= s and e <= n for s, e in slices)
            # starts advance by exactly `step`: no window skipped or duplicated
            assert [s for s, _ in slices] == [i * step for i in range(len(slices))]
            # maximality: the NEXT window would overrun the frame count
            if slices:
                assert slices[-1][0] + step + stack > n
            else:
                assert n < stack


def test_frame_batch_plan_partitions_every_frame():
    for n in range(0, 40):
        for b in (1, 2, 5):
            plan = frame_batch_plan(n, b)
            # exact partition: no frame dropped, none duplicated, order kept
            assert [i for s, e in plan for i in range(s, e)] == list(range(n))
            # no range exceeds the batch (the packer's slot budget per dispatch)
            assert all(0 < e - s <= b for s, e in plan)


def test_pair_batch_plan_tail_never_exceeds_batch():
    for n in range(2, 40):
        for b in (1, 3, 7):
            assert all(1 <= e - s <= b for s, e in pair_batch_plan(n, b))


# ---- pad_batch edge cases (the packer's corpus-flush padding) --------------


def test_pad_batch_full_batch_is_identity():
    from video_features_tpu.parallel.pipeline import pad_batch

    arr = np.arange(8, dtype=np.uint8).reshape(4, 2)
    assert pad_batch(arr, 4) is arr  # no copy on the hot full-batch path


def test_pad_batch_empty_input_pads_to_all_zeros():
    from video_features_tpu.parallel.pipeline import pad_batch

    out = pad_batch(np.zeros((0, 3), np.float32), 4)
    assert out.shape == (4, 3) and out.dtype == np.float32
    assert not out.any()


def test_pad_batch_preserves_rows_and_dtype():
    from video_features_tpu.parallel.pipeline import pad_batch

    arr = np.arange(6, dtype=np.uint8).reshape(3, 2)
    padded = pad_batch(arr[:1], 4)
    assert padded.shape == (4, 2) and padded.dtype == np.uint8
    np.testing.assert_array_equal(padded[0], arr[0])
    assert not padded[1:].any()


def test_pad_batch_overfull_raises():
    from video_features_tpu.parallel.pipeline import pad_batch

    with pytest.raises(ValueError, match="exceeds batch_size"):
        pad_batch(np.zeros((5, 2)), 4)
