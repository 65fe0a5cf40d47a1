"""Two decode chunks in flight against one (tests/test_engine_pipeline.py), for the engines of the families
whose layers are attention alone, over pages, latent pages or rings: each family's tiny configuration under
the one script of submissions, both engines over the same weights. The families that carry a recurrent state
a slot are tests/test_engine_pipeline_recurrent.py."""

import pytest

from test_engine_pipeline import CASES, RECURRENT, two_engines_hand_out_the_same


@pytest.mark.parametrize("case", sorted(c for c in CASES if c.startswith("family-") and c[len("family-"):] not in RECURRENT))
def test_two_chunks_in_flight_hand_out_what_the_serial_engine_did(case, monkeypatch):
    two_engines_hand_out_the_same(case, monkeypatch)
