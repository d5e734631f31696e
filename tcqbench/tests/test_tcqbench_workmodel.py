"""The step work model on a hand-counted window."""

import numpy as np

from tcqbench import workmodel


def test_hand_counted_window():
    # edges inside [10, 20]: (0,1)@10, (1,0)@12, (1,2)@20; (2,3)@21 is out,
    # the self-loop (4,4)@15 never counts
    u = np.array([0, 1, 1, 2, 4], np.int32)
    v = np.array([1, 0, 2, 3, 4], np.int32)
    t = np.array([10, 12, 20, 21, 15], np.int32)
    assert workmodel.window_counts(u, v, t, 10, 20) == (3, 2, 3)
    # 3 edges x 16 + 2 pairs x 8 + 3 vertices x 4 = 76; 8 lanes over a
    # 100-vertex graph: masks of 4 words = 16 B, in and out, plus 12 B each
    assert workmodel.step_bytes(3, 2, 3, 8, 100) == 76 + 8 * (32 + 12)
