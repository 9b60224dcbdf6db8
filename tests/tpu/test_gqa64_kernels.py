"""The flash kernels at the LFM2 cell's attention shape, compiled and run
on the chip: head width 64, 32 query heads on 8 K/V heads."""
import jax.numpy as jnp

from test_lfm2_moe import check_gqa_at_64


def test_flash_kernels_at_head_width_64_on_the_chip():
    """(1, 32/8, 8192, 64) bfloat16, causal: the output and the three
    gradients against plain softmax attention in float32, to bfloat16's
    places (the products' operands rounded once each)."""
    print("gqa 64 gaps", check_gqa_at_64(8192, jnp.bfloat16, 3e-2))
