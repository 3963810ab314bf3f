"""``test_program_text.py``'s golden comparison with the Pallas kernels on
(interpret mode), every served family: a file of its own, so that the
suite's workers share the two halves.  One engine a fixture here too."""
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from test_program_text import KINDS, harness, held_to_the_golden  # noqa: E402


@pytest.mark.parametrize("kind", KINDS)
@pytest.mark.parametrize("family", sorted(harness.FAMILIES))
def test_the_programs_are_what_they_were(family, kind):
    held_to_the_golden(family, kind, "on")
