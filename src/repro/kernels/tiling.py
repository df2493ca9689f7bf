"""What the Pallas kernels share: where a kernel runs when its caller
does not say, and the tile pickers that obey the TPU's block rule.

Mosaic accepts a block whose last two dimensions are multiples of
(8, 128) or equal to the array's own dimensions. The pickers below only
return such tiles; interpret mode accepts any tile, so a tile the chip
would refuse never shows up in a CPU test — ``tests/test_tpu_compile.py``
compiles the kernels for a described v5e to catch it.
"""
from __future__ import annotations

from typing import Optional

import jax

LANE = 128
SUBLANE = 8


def default_interpret() -> bool:
    """Mosaic on a TPU; the Pallas interpreter on any other backend
    (the CPU test path). Kernels resolve ``interpret=None`` through
    this, so no path runs interpreted on a chip by default."""
    return jax.default_backend() != "tpu"


def resolve_interpret(interpret: Optional[bool]) -> bool:
    return default_interpret() if interpret is None else bool(interpret)


def pick_blk_m(M: int, tuned: Optional[int] = None) -> int:
    """Sublane tile of the block axis: ``SUBLANE`` when it divides M,
    else M itself (the M grid is never padded — block j is row j
    everywhere, the block-id contract — so M=1 PS commits and odd model
    shards take the whole axis). A cached autotuner winner ``tuned`` is
    used when the chip accepts it: a multiple of 8 dividing M, or M."""
    if tuned is not None and tuned > 0 and M % tuned == 0 \
            and (tuned % SUBLANE == 0 or tuned == M):
        return tuned
    return SUBLANE if M % SUBLANE == 0 else M


def pick_lane_tile(d: int, cap: int, tuned: Optional[int] = None,
                   rows: int = SUBLANE) -> int:
    """Lane tile: the largest lane multiple <= ``cap * SUBLANE / rows``
    dividing d, so a tile of ``rows`` sublanes takes no more VMEM than a
    (SUBLANE, cap) one (a tile under 8 rows still fills 8 sublanes). A
    cached autotuner winner ``tuned`` is used verbatim when it is a lane
    multiple dividing d.

    Precondition: ``d % 128 == 0``. Lane-aligned layouts
    (core.blocks.make_flat_blocks / make_block_layout) guarantee it;
    raw ragged widths raise an actionable error."""
    if d % LANE != 0:
        raise ValueError(
            f"lane tile requires d % {LANE} == 0, got d={d}; build the "
            f"block table through a lane-aligned layout "
            f"(core.blocks.make_flat_blocks / make_block_layout round "
            f"block_dim up to {LANE}) instead of passing ragged rows.")
    if tuned is not None and tuned % LANE == 0 and 0 < tuned <= d \
            and d % tuned == 0:
        return tuned
    blk_d = min(d, max(LANE, cap * SUBLANE // max(rows, SUBLANE)
                       // LANE * LANE))
    while d % blk_d:
        blk_d -= LANE
    return blk_d
