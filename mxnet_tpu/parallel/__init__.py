"""Distributed execution over TPU meshes.

This package is the TPU-native replacement for the reference's entire
distributed stack (SURVEY.md §5.8): KVStore comm trees, NCCL, and the
ps-lite parameter server all become sharding annotations on ONE compiled
program — XLA GSPMD inserts the ICI/DCN collectives (psum/all_gather/
reduce_scatter) where the shardings require them.

Components:
- mesh:        device-mesh construction helpers
- collectives: named wrappers over XLA collectives (the "comm backend")
- spmd:        sharded train-step compiler (dp/tp batch+param sharding)
- ring_attention: sequence-parallel blockwise attention over ppermute
"""
from .compat import shard_map
from .mesh import make_mesh, default_mesh, mesh_from_contexts, barrier
from .collectives import (all_reduce, all_gather, reduce_scatter, ppermute,
                          all_to_all)
from .spmd import (SPMDTrainer, shard_params_rule, DataParallelSpec,
                   dp_spec, rule_spec, check_batch_divisible, shard_put,
                   DP_AXIS, MP_AXIS)
from .partition import (PartitionRules, UNMATCHED_REPLICATE,
                        UNMATCHED_ERROR, partition_summary)
from .ring_attention import ring_attention, attention
from .ulysses import ulysses_attention
from .moe import moe_layer
from .pipeline import pipeline_apply
