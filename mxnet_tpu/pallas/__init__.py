"""Hand-written Pallas TPU kernels for the hot ops.

The compute path of this framework is XLA; these kernels cover the spots
where XLA's automatic fusion is not enough (blockwise attention with an
online-softmax accumulator, the chunked state-space recurrence's and
the delta rule's within-chunk work, quantised communication payloads). Every
kernel has an ``interpret`` fallback so the suite runs on the virtual CPU
mesh (tests/conftest.py) and compiles natively on TPU.
"""
from .flash_attention import flash_attention, flash_attention_carry
from .kda import kda_intra
from .ssd import ssd_chunk, ssd_states

__all__ = ["flash_attention", "flash_attention_carry", "kda_intra",
           "ssd_chunk", "ssd_states"]
