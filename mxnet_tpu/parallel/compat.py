"""The one ``shard_map`` every per-device collective program in this
package (ring/ulysses attention, MoE dispatch, the GPipe schedule)
imports: ``jax.shard_map`` of the installed JAX."""
from __future__ import annotations

import jax

__all__ = ["shard_map"]

shard_map = jax.shard_map
