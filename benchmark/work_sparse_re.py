"""What the subspace passes of a sparse random effect have to do, computed
from the data: beside ``work.py``, whose definitions it keeps.

One *objective pass* of one entity is one evaluation of its value and
gradient over ITS rows in ITS subspace: ``work.sparse_pass`` with the
entity's row count, its nonzeros and, for the column count, its support
(the distinct columns its rows touch). L-BFGS needs at least one pass an
iteration, so an entity that ran ``it`` iterations needed ``it`` passes:
extra line-search passes, lanes in lock step with a slower one and rows or
columns of padding are an implementation's, not the algorithm's.
"""

from __future__ import annotations

import numpy as np

from benchmark import work


def entity_passes(rows, nonzeros, support) -> tuple[np.ndarray, np.ndarray]:
    """Per entity, the (operations, bytes) of one objective pass:
    ``work.sparse_pass`` applied elementwise to arrays over the entities."""
    return work.sparse_pass(
        np.asarray(rows, np.float64), np.asarray(support, np.float64),
        np.asarray(nonzeros, np.float64),
    )

