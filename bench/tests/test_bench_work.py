"""The bytes a batch's work needs, against a batch counted by hand."""
import numpy as np

import bench_tiny  # noqa: F401
from starbench import work
from starbench.reference import (ADD, DELETE_IDX, INSERT_IDX, READ,
                                 SCAN_CONSUME, SCAN_READ, SET)

C = 10
RECORD = C * 4 + 4                      # value words + TID
ENTRY = 12                              # key, row, TID


def batch(rows, kinds, valid, abort, cross_rows=None, cross_kinds=None):
    rows, kinds = np.array(rows), np.array(kinds)
    P, T, M = rows.shape
    cr = np.array(cross_rows if cross_rows is not None else np.zeros((1, M)))
    ck = np.array(cross_kinds if cross_kinds is not None
                  else np.zeros((1, M)))
    return {"ptxn": {"row": rows, "kind": kinds,
                     "valid": np.array(valid), "user_abort": np.array(abort)},
            "cross": {"row": cr, "kind": ck,
                      "valid": np.ones(cr.shape[0], bool),
                      "user_abort": np.zeros(cr.shape[0], bool)}}


def test_partitioned_bytes_by_hand():
    # partition 0, slot 0: read row 5, add row 7, pad (read row 0) twice:
    #   3 distinct rows read (0, 5, 7), 1 written
    # partition 0, slot 1: invalid -> nothing
    # partition 1, slot 0: set row 3, scan, insert, read row 3 again:
    #   1 distinct row read (3; the index ops name no record), 1 written,
    #   one scan window, one insert
    # partition 1, slot 1: aborts by itself -> nothing
    rows = [[[5, 7, 0, 0], [1, 1, 1, 1]],
            [[3, 0, 0, 3], [2, 2, 2, 2]]]
    kinds = [[[READ, ADD, READ, READ], [SET] * 4],
             [[SET, SCAN_READ, INSERT_IDX, READ], [SET] * 4]]
    valid = [[True, False], [True, True]]
    abort = [[False, False], [False, True]]
    b = batch(rows, kinds, valid, abort)
    want = (3 + 1) * RECORD + (1 + 1) * RECORD + 9 * ENTRY + 2 * ENTRY
    assert work.partitioned_bytes(b, C) == want


def test_consume_reads_its_row_window_and_deletes_one_entry():
    rows = [[[9, 4, 0, 0]]]
    kinds = [[[SCAN_CONSUME, DELETE_IDX, READ, READ]]]
    b = batch(rows, kinds, [[True]], [[False]])
    # records: the consume's row 9 and the pad row 0 read, row 9 zeroed
    want = (2 + 1) * RECORD + 9 * ENTRY + ENTRY + 2 * ENTRY
    assert work.partitioned_bytes(b, C) == want


def test_single_master_bytes_count_each_live_lane_once():
    b = batch([[[0, 0]]], [[[READ, READ]]], [[False]], [[False]],
              cross_rows=[[10, 20], [10, 10]],
              cross_kinds=[[READ, SET], [ADD, READ]])
    # lane 0: rows 10, 20 read, 20 written; lane 1: row 10 read, written
    assert work.single_master_bytes(b, C) == (2 + 1 + 1 + 1) * RECORD
