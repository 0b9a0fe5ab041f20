"""The plain rule: of the ``k`` times an id stands in one request, the first
``grants_per_request`` are granted.  Over many requests, per id."""

import numpy as np


def granted(requests: np.ndarray, n_ids: int, grants_per_request: int) -> np.ndarray:
    """``requests`` is (requests, ids per request); returns grants per id."""
    out = np.zeros(n_ids, np.int64)
    for row in requests:
        out += np.minimum(np.bincount(row, minlength=n_ids), grants_per_request)
    return out
