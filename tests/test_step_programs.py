"""Every family's serving step programs lower to the text recorded for
them (tests/step_program_texts.py says what was recorded when): a change
made for one family is seen to leave the others' device programs as they
were, and a refactor of a family's adapter to leave its own."""

import json
import os

import pytest

from tests.step_program_texts import CASES, hashes

with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "step_program_texts_parent.json")) as f:
    PARENT = json.load(f)


def test_every_case_is_recorded_and_nothing_else():
    assert sorted(PARENT) == sorted(CASES)


@pytest.mark.parametrize("case", CASES)
def test_step_programs_lower_to_the_parents_text(case):
    """The chunk, finish-and-install and decode programs of a preset — of a
    state family also with its kernels interpreted, "<preset>@interpret" —
    lower to the recorded text (its sha256). The decode programs of
    `gpt2-test`, `olmoe-test` and `keye-test` are what they were before PR
    38 moved the head out of the chunk program; the four state families'
    programs what their own adapter classes lowered to on PR 60's parent,
    Brumby's `_decode` through the private layer loop it had there."""
    assert hashes(case) == PARENT[case]
