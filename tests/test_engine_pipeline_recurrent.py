"""Two decode chunks in flight against one (tests/test_engine_pipeline.py), for the engines of the families
that carry a recurrent state and a convolution tail a slot beside their pages (a delta rule, a state-space
scan, linear attention): their interpreted kernels are most of this subject's compile time, hence a file of
their own for `--dist loadfile`. The attention-only families are tests/test_engine_pipeline_families.py."""

import pytest

from test_engine_pipeline import RECURRENT, two_engines_hand_out_the_same


@pytest.mark.parametrize("case", [f"family-{name}" for name in sorted(RECURRENT)])
def test_two_chunks_in_flight_hand_out_what_the_serial_engine_did(case, monkeypatch):
    two_engines_hand_out_the_same(case, monkeypatch)
