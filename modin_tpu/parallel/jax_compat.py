"""The one import site for jax APIs the kernels share.

Written for the installed jax (0.9): ``jax.shard_map`` is the public name,
with ``check_vma`` as its replication-check flag.  Kernel code imports it
from here so graftlint's seam rules keep one module to name.
"""

from __future__ import annotations

from jax import shard_map

__all__ = ["shard_map"]
