"""The control: the program's own lower-precision path (bf16 streams and
queries, ``stream_dtype="bf16"``) in place of the f32 path the
configurations state must come out as not correct, in every cell kind."""
import pytest

from conftest import run_tiny


@pytest.mark.parametrize("cell", ["covtype-ovr.train", "imagenet-fc7-ovr.serve"])
def test_the_bf16_control_fails_the_check(tiny_catalog, cell):
    result, _, lines = run_tiny(tiny_catalog, cell, control=True)
    assert result["correct"] is False, lines
    sound, _, _ = run_tiny(tiny_catalog, cell)
    assert sound["correct"] is True
