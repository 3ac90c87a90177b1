"""The bytes of device memory the algorithm needs to move for one
committed operation, from the deployment's shapes alone (not XLA's
cost estimate, and not what the step as written moves).

Per replica touched, an operation reads the slot's object planes
(epoch, seq, value handle: 3 x int32 = 12 B) and its 16-byte Merkle
leaf, and verifies the path to the root: at each tree level the 16
children of one node, 16 B each.  A write also writes the object
planes, the leaf and one 16-byte node per level back.  A write touches
all M replicas; a device read touches the read quorum (M // 2 + 1).
"""

from __future__ import annotations

OBJECT_BYTES = 12
HASH_BYTES = 16
TREE_WIDTH = 16


def tree_levels(n_slots: int) -> int:
    """Levels of interior nodes over ``n_slots`` leaves at width 16."""
    levels, span = 0, 1
    while span < n_slots:
        span *= TREE_WIDTH
        levels += 1
    return levels


def read_bytes_per_replica(n_slots: int) -> int:
    return (OBJECT_BYTES + HASH_BYTES
            + tree_levels(n_slots) * TREE_WIDTH * HASH_BYTES)


def write_bytes(n_peers: int, n_slots: int) -> int:
    per_replica = (read_bytes_per_replica(n_slots) + OBJECT_BYTES
                   + HASH_BYTES + tree_levels(n_slots) * HASH_BYTES)
    return n_peers * per_replica


def read_bytes(n_peers: int, n_slots: int) -> int:
    return (n_peers // 2 + 1) * read_bytes_per_replica(n_slots)
