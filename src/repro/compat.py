"""Host-side payload packing for the cross-process wire, and a flat
view of a compiled program's cost analysis.

The repo is pinned to one jax release (see ``pyproject.toml``) and calls
``jax.shard_map``, ``jax.sharding.get_abstract_mesh`` and
``AbstractMesh(axis_sizes, axis_names)`` directly; nothing here papers
over another jax version.
"""

from __future__ import annotations

import pickle
from typing import Any

import jax
import numpy as np


def _leaf_to_host(leaf: Any) -> Any:
    """Pytree-leaf normalization for the cross-process wire: committed
    jax Arrays become host numpy (device/sharding state does not survive a
    pickle across ``jax.distributed`` processes on every jax line this repo
    rides — and the receiver wants host data anyway); every other leaf
    (ints, dicts-as-leaves, dataclasses) passes through to pickle."""
    if isinstance(leaf, jax.Array):
        return np.asarray(leaf)
    return leaf


def pack_payload(obj: Any) -> bytes:
    """Serialize an arbitrary SiteJob result for ``process_allgather``
    shipping: jax array leaves are pulled to host numpy via ``tree_map``
    (NamedTuples like SuffStats/MergeResult/TimedResult and ordinary
    list/tuple/dict containers are traversed; unregistered objects such as
    itemset-count dicts inside LocalMineResult are pickled whole), then the
    whole tree is pickled.  The inverse is :func:`unpack_payload`.

    Note dict keys are re-ordered by jax's tree flattening (sorted) — all
    consumers in this repo are key-lookup/sort-before-iterate, so the
    round-trip is value-identical.
    """
    host = jax.tree_util.tree_map(_leaf_to_host, obj)
    return pickle.dumps(host, protocol=pickle.HIGHEST_PROTOCOL)


def unpack_payload(data: bytes) -> Any:
    """Deserialize a :func:`pack_payload` wire payload.  Array leaves come
    back as host numpy — bit-identical values; downstream jnp ops accept
    them transparently."""
    return pickle.loads(data)


def cost_analysis_dict(compiled) -> dict[str, Any]:
    """``Compiled.cost_analysis()`` as a dict (``{}`` when XLA gives none)."""
    return dict(compiled.cost_analysis() or {})
