"""What a kernel's call has to do, from its shapes alone: operations and
bytes, for its share of the roofline (``perfbench/peaks.json`` holds the
chip's peaks; the call's time comes from a trace, ``timeline.py trace``'s
``device_stages.kernels``).  One function a kernel, named as the kernel is
in the trace.  Kept with the benchmark so that no PR that changes a kernel
also changes how its share is counted.
"""

from __future__ import annotations

from typing import Tuple

#: lanes of the one-hot minor axis, and sublane rows of one stretch (ops/fused.py)
N_LO = 128


def scatter_sorted(items: int, table_rows: int, digit_planes: int) -> Tuple[float, float]:
    """``(operations, bytes)`` of one ``scatter_sorted`` call (ops/fused.py):
    ``items`` sorted (row, packed value) pairs landed into an f32 table of
    ``table_rows`` rows by ``digit_planes`` planes.

    Operations: every item is contracted once against the one stretch of
    N_LO x N_LO rows its row falls in, a [N_LO, items] one-hot by an
    [items, digit_planes * N_LO] digit matrix: 2 * N_LO * digit_planes * N_LO
    a item.  A tile that straddles two stretches is contracted against both
    and a sparse tick's tile against every stretch between its first and its
    last row; that is the kernel's own overhead, not what the call needs.
    Bytes: the two int32 item columns read, the table written once (it is
    zeroed and accumulated in fast memory)."""
    operations = 2.0 * N_LO * N_LO * digit_planes * items
    moved = 8.0 * items + 4.0 * table_rows * digit_planes
    return operations, moved


def roofline_share(operations: float, moved: float, seconds: float, peak: dict) -> Tuple[float, str]:
    """The least time the chip could take over the time it took, in per
    cent, and which of the two peaks bounds it (``peak``: one entry of
    ``peaks.json``)."""
    by_ops = operations / peak["bf16_flops_per_s"]
    by_bytes = moved / peak["hbm_bytes_per_s"]
    bound = "operations" if by_ops >= by_bytes else "bytes"
    return 100.0 * max(by_ops, by_bytes) / seconds, bound
