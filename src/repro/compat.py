"""The sharding entry points every module uses, spelled in one place.

The tree targets the installed JAX, whose `jax.shard_map` (with
``check_vma``) and `jax.make_mesh` are used as they are.
"""

from jax import make_mesh, shard_map

__all__ = ["make_mesh", "shard_map"]
