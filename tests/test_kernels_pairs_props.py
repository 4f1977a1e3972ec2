"""Property-based test (hypothesis): the fused ``compat_join_pairs``
kernel equals ``compat_mask`` + ``extract_pairs`` — same pair set when
nothing overflows, exact ``n_dropped`` always, and the keep-subset of
a sweep over the whole padded grid in tile order, on validity layouts
drawn as tables and deltas hold them (none live, a live prefix, holes,
one live row at the end).

Lives in its own module because the module-level importorskip skips the
whole file when the optional dev dep is absent (same pattern as
tests/test_engine_props.py)."""

import numpy as np
import pytest

hypothesis = pytest.importorskip(
    "hypothesis", reason="optional dev dependency (see requirements-dev.txt)")
from hypothesis import given, settings, strategies as st  # noqa: E402

from test_kernels_compat_join import (  # noqa: E402
    _check_pairs_vs_oracle, live_rows, rand_case)


@settings(max_examples=25, deadline=None)
@given(
    seed=st.integers(0, 2**31 - 1),
    ca=st.integers(1, 90),
    cb=st.integers(1, 90),
    nva=st.integers(1, 4),
    nvb=st.integers(1, 3),
    nea=st.integers(1, 3),
    neb=st.integers(1, 2),
    window=st.one_of(st.none(), st.integers(1, 40)),
    max_new=st.sampled_from([1, 8, 33, 512]),
    layout_a=st.sampled_from(["dense", "none", "prefix", "holes", "last"]),
    layout_b=st.sampled_from(["dense", "none", "prefix", "holes", "last"]),
)
def test_fused_pairs_property(seed, ca, cb, nva, nvb, nea, neb, window,
                              max_new, layout_a, layout_b):
    rng = np.random.default_rng(seed)
    args = list(rand_case(rng, ca, cb, nva, nvb, nea, neb, window))
    if layout_a != "dense":
        args[2] = live_rows(rng, ca, layout_a)
    if layout_b != "dense":
        args[5] = live_rows(rng, cb, layout_b)
    _check_pairs_vs_oracle(tuple(args), max_new)
