"""``test_head_where_read.py``'s (a) and (b) with the Pallas kernels on
(interpret mode), every served family: a file of its own, so that the
suite's workers share the two halves."""
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from test_head_where_read import (                              # noqa: E402
    FAMILIES, served_what_the_head_in_every_program_served)


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_a_prompt_is_served_what_the_head_in_every_program_served(
        family, monkeypatch):
    served_what_the_head_in_every_program_served(family, True, monkeypatch)
