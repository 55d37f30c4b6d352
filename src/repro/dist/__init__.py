"""Distributed substrate: sharding contexts, spec builders, pipeline
parallelism.

Modules
-------
sharding     : ShardCtx + PartitionSpec rules for params/activations/state.
pipeline_par : GPipe-style pipeline parallelism over a mesh axis.
"""
from repro.dist import sharding  # noqa: F401
