"""Distribution layer: logical-axis sharding, sharded featurization
sweeps, compressed collectives, elastic fault handling.

``repro.dist.sweep`` is the multi-device sweep layer: activate a mesh via
``sharding.use_mesh`` and every ``features_sweep`` shards its slice axis
across the mesh's "data" axis (padding non-divisible slice counts,
gathering -- or optionally keeping sharded -- the (k, e, 2) result).
"""
from repro.dist import sharding  # noqa: F401
