"""The delta rule's kernels (``pallas/kda.py``), compiled and run on the
chip at ``kimi_linear``'s widths."""
import jax.numpy as jnp

from test_kimi_linear import check_kda_at_the_tile, check_kda_state_at_the_tile


def test_kda_kernels_match_the_token_scan_on_the_chip():
    """32 heads of 128 for keys and values, chunks of 64 in sub-blocks of
    16, bfloat16, one sequence of 1,024 tokens: forward and every input's
    gradient against the float32 scan over tokens."""
    check_kda_at_the_tile(1024, jnp.bfloat16, dims=(32, 128, 128))


def test_kda_state_kernels_match_the_chunk_scan_on_the_chip():
    """``kda_state_fwd`` / ``kda_state_bwd`` at the cell's shapes (one
    layer: one sequence of 8,192 tokens, 32 heads of 128, chunks of 64),
    not interpreted, against the ``jax.numpy`` scan over chunks they
    replaced: the output and the six inputs' gradients."""
    print("kda_state gaps", check_kda_state_at_the_tile(8192, (32, 128, 128)))
