"""The state-space recurrence's kernels (``pallas/ssd.py``), compiled and
run on the chip at ``nemotron3_nano``'s widths."""
import jax.numpy as jnp

from test_nemotron_h import check_ssd_at_the_tile


def test_ssd_kernels_match_the_token_recurrence_on_the_chip():
    """64 heads of 64 in 8 groups, state 128, chunks of 128, bfloat16, one
    sequence of 1,024 tokens: forward and every input's gradient against
    the float32 scan over tokens."""
    check_ssd_at_the_tile(1024, jnp.bfloat16, dims=(64, 64, 128, 8))
