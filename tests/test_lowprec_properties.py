"""Hypothesis twin of test_lowprec: random u8 frames and random geometry.

Same contract — integer lane bit-identical to the f32 lane — but over
drawn operators, paddings and ragged shapes instead of the fixed matrix.
"""
import numpy as np
import pytest

pytest.importorskip("hypothesis")  # optional [test] extra; module skips without it
from hypothesis import given, settings, strategies as st

from repro.api import EdgeConfig, edge_detect
from repro.core.filters import list_operators

FIELDS = ("magnitude", "components", "orientation", "peak", "thin", "edges")


def _assert_bit_identical(out, ref, what):
    for f in FIELDS:
        a, b = getattr(out, f), getattr(ref, f)
        assert (a is None) == (b is None), (what, f)
        if a is not None:
            np.testing.assert_array_equal(
                np.asarray(a), np.asarray(b), err_msg=str((what, f))
            )


@settings(max_examples=8, deadline=None)
@given(
    seed=st.integers(0, 2**32 - 1),
    h=st.integers(9, 48),
    w=st.integers(9, 48),
    operator=st.sampled_from(list_operators()),
    padding=st.sampled_from(["reflect", "edge", "zero"]),
    nms=st.booleans(),
)
def test_int_lane_bit_exact_random(seed, h, w, operator, padding, nms):
    img = np.random.default_rng(seed).integers(0, 256, (h, w)).astype(np.uint8)
    base = EdgeConfig(operator=operator, backend="pallas-interpret",
                      padding=padding, nms=nms, with_max=True,
                      with_components=True, with_orientation=True)
    ref = edge_detect(img, base.replace(precision="f32"))
    out = edge_detect(img, base.replace(precision="int"))
    _assert_bit_identical(out, ref, (operator, padding, (h, w), nms))

