"""``needs.py`` against hand arithmetic for both rings."""

import needs


def test_tree_levels():
    assert needs.tree_levels(128) == 2        # 128 leaves -> 8 -> 1
    assert needs.tree_levels(65_536) == 4     # 16**4
    assert needs.tree_levels(4_096) == 3
    assert needs.tree_levels(16) == 1


def test_ring10k_n5():
    # per replica read: 12 + 16 + 2 * 16 * 16 = 540; written back:
    # 12 + 16 + 2 * 16 = 60
    assert needs.read_bytes_per_replica(128) == 540
    assert needs.write_bytes(5, 128) == 5 * 600 == 3000
    assert needs.read_bytes(5, 128) == 3 * 540 == 1620


def test_ring64_n3_deep():
    # per replica read: 12 + 16 + 4 * 256 = 1052; written back: 92
    assert needs.read_bytes_per_replica(65_536) == 1052
    assert needs.write_bytes(3, 65_536) == 3 * 1144 == 3432
    assert needs.read_bytes(3, 65_536) == 2 * 1052 == 2104
