"""The one import point for jax's explicit-SPMD surface.

Every internal caller takes ``shard_map`` and ``pcast`` (the
varying-manual-axes cast that shard_map's vma typing requires at every
branch-merge point) from here, so the next time jax moves either name the
change lands in one file.
"""
from __future__ import annotations

import jax
from jax import lax as _lax

shard_map = jax.shard_map
pcast = _lax.pcast
