"""The plain reference against float64 NumPy, at row counts that span
several of its row blocks and a ragged tail.

    python -m pytest chipbench/tests/test_reference.py
"""

import numpy as np
import pytest

from chipbench import reference


@pytest.mark.parametrize("n", [reference.BLOCK - 5, 2 * reference.BLOCK + 123])
def test_error_and_total_sum_of_squares_match_float64(n):
    rng = np.random.default_rng(n)
    x = (rng.standard_normal((n, 5)) * 3.0 + 1.5).astype(np.float32)
    c = x[rng.choice(n, 7, replace=False)]
    x64 = x.astype(np.float64)
    d = ((x64[:, None, :] - c.astype(np.float64)[None]) ** 2).sum(-1)
    want_error = d.min(1).sum()
    want_tss = ((x64 - x64.mean(0)) ** 2).sum()
    assert reference.error(x, c) == pytest.approx(want_error, rel=1e-5)
    assert reference.total_sum_of_squares(x) == pytest.approx(want_tss, rel=1e-5)
