"""The reference's weights laid out as the program's pytree and read back
give the same weights, for the published period of 1 and the CPU-sized
model's period of 2."""

import jax
import numpy as np
import pytest

from chipbench.reference import llama
from chipbench.tests import tiny


@pytest.mark.parametrize("period", [1, 2])
@pytest.mark.parametrize("tied", [True, False])
def test_from_program_inverts_to_program(period, tied):
    c = dict(tiny.CONFIG, tie_word_embeddings=tied)
    p = llama.init(jax.random.PRNGKey(7), c)
    back = llama.from_program(llama.to_program(p, c, period), c, period)
    assert sorted(back) == sorted(p)
    for k in p:
        np.testing.assert_array_equal(back[k], p[k], err_msg=k)
