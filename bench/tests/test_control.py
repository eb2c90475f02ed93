"""The control: the reference quantizer in the program's place. In float32
it meets the guarantee; in bfloat16, the step below the configuration's
float32, `correct` comes out false."""

import jax.numpy as jnp
import pytest
from conftest import TINY, run_cell

from bench import reference


@pytest.mark.parametrize("dtype,correct", [(jnp.float32, True), (jnp.bfloat16, False)])
def test_quantize_reference_in_the_programs_place(tiny_root, capsys, dtype, correct):
    rc, res = run_cell(tiny_root, capsys, "--workload", TINY, "--seed", "3",
                       "--seconds", "0.3", "--trace", "0",
                       make_system=lambda spec: reference.QuantizeReference(spec, dtype))
    assert rc == 0
    assert res["correct"] is correct, res["checks"]
    if not correct:
        # both numbers fail, each by far more than its limit
        for c in res["checks"].values():
            assert c["value"] > 3 * c["limit"]
