"""Needed bytes against hand counts."""

import numpy as np

import needed_bytes


def test_distinct_keys_counts_per_column_after_modulus():
    sparse = np.array([[1, 7], [11, 7], [1, 8], [21, 17]], np.uint32).view(np.int32)
    # column 0 mod 10: {1}; column 1 mod 10: {7, 8}
    assert needed_bytes.distinct_keys(sparse, 10) == 3
    # mod 100: column 0 {1, 11, 21}, column 1 {7, 8, 17}
    assert needed_bytes.distinct_keys(sparse, 100) == 6


def test_distinct_keys_reads_hashes_unsigned():
    sparse = np.array([[0xFFFFFFFF], [0xFFFFFFFF - 10]], np.uint32).view(np.int32)
    # 4294967295 % 10 = 5 and 4294967285 % 10 = 5
    assert needed_bytes.distinct_keys(sparse, 10) == 1


def test_loop_bytes_by_hand():
    # 1000 framed bytes, 30 distinct keys: 1000 + 30 * (4 read + 4 write)
    assert needed_bytes.loop1_bytes(1000, 30) == 1240
    # 1000 framed bytes, 10 rows x 26 ordinals x 4 B, 10 rows x 160 B out
    assert needed_bytes.loop2_bytes(1000, 10, 26) == 1000 + 1040 + 1600


def test_job_bytes_sums_chunks():
    sparse = np.array([[1], [1], [2], [3], [3]], np.uint32).view(np.int32)
    table = {"sparse": sparse}
    got = needed_bytes.job_bytes(table, [2, 3], [50, 70], vocab_range=10)
    # chunk 0: rows {1, 1} -> 1 key; chunk 1: rows {2, 3, 3} -> 2 keys
    assert got["loop1"] == (50 + 8 * 1) + (70 + 8 * 2)
    assert got["loop2"] == (50 + 2 * 4 + 2 * 160) + (70 + 3 * 4 + 3 * 160)
