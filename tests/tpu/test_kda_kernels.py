"""The delta rule's within-chunk kernels (``pallas/kda.py``), compiled and
run on the chip at ``kimi_linear``'s widths."""
import jax.numpy as jnp

from test_kimi_linear import check_kda_at_the_tile


def test_kda_kernels_match_the_token_scan_on_the_chip():
    """32 heads of 128 for keys and values, chunks of 64 in sub-blocks of
    16, bfloat16, one sequence of 1,024 tokens: forward and every input's
    gradient against the float32 scan over tokens."""
    check_kda_at_the_tile(1024, jnp.bfloat16, dims=(32, 128, 128))
