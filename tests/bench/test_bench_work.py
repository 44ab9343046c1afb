"""The roofline counts against cases worked by hand."""
import pytest

from benchmarks.chip import work
from benchmarks.chip.peaks import peaks_for


def test_train_pass_counts_a_hand_worked_case():
    # N = 1000 rows, B = 21 models, D = 54 features, f32 stream:
    # ops = 2*1000*21*54 (products) + 2*1000*21*54 (updates) = 4,536,000
    # bytes = 1000*54*4 (stream) + 1000*4 (labels) + 2*21*54*4 (bank) = 229,072
    assert work.train_pass(1000, 21, 54) == (4_536_000.0, 229_072.0)
    # a bf16 stream halves the stream term only: 108,000 + 4,000 + 9,072
    assert work.train_pass(1000, 21, 54, stream_bytes=2)[1] == 121_072.0


def test_serve_counts_a_hand_worked_case():
    # 10 rows answered in 2 steps, B = 6, D = 8, top-2 answers (16 bytes):
    # ops = 2*10*6*8 = 960; bytes = 10*8*4 + 2*6*8*4 + 10*16 = 320+384+160
    assert work.serve(10, 2, 6, 8, 16) == (960.0, 864.0)


def test_roofline_names_the_bound():
    peaks = peaks_for("TPU v5 lite")
    t, bound = work.roofline_s(197e12, 1.0, peaks)
    assert bound == "ops" and t == pytest.approx(1.0)
    t, bound = work.roofline_s(1.0, 819e9, peaks)
    assert bound == "bytes" and t == pytest.approx(1.0)


def test_unknown_chip_has_no_peaks():
    with pytest.raises(ValueError, match="no published peaks"):
        peaks_for("cpu")
