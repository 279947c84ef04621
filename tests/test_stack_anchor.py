"""``core/stack_anchor.py``: the call goes through unchanged, from a
frame that cannot share a 16 KiB chunk of the interpreter's frame
stack with what lies below it."""
import sys

import pytest

from paddle_tpu.core import stack_anchor
from paddle_tpu.core.stack_anchor import above_stack_anchor


def test_returns_what_the_function_returns_and_passes_its_arguments():
    assert above_stack_anchor(lambda a, b=0: (a, b), 1, b=2) == (1, 2)


def test_an_exception_passes_through():
    with pytest.raises(KeyError, match="gone"):
        above_stack_anchor({}.__getitem__, "gone")


def test_the_anchor_frame_is_larger_than_a_chunk():
    seen = []
    above_stack_anchor(lambda: seen.append(sys._getframe(1).f_code))
    code, = seen
    assert code.co_name == "anchor"
    assert 8 * (code.co_nlocals + code.co_stacksize) > 16 * 1024
    assert above_stack_anchor.__module__ == stack_anchor.__name__
