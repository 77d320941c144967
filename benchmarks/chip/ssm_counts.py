"""Operations and bytes one ``ssm_decode_update`` call requires, from
shapes alone (beside ``counts.py``, whose formulas it shares the use of).

The call updates, for each row of the decode step, that row's Mamba-2
state of one layer: the decay, the rank-1 update and the readout.
Padding rows update a scratch slot and cost the same as live ones, so a
call counts every row the step runs.
"""

from __future__ import annotations

from typing import Tuple


def ssm_decode_call(rows: int, heads: int, head_dim: int, state: int,
                    state_bytes: int = 4, io_bytes: int = 4,
                    ) -> Tuple[float, float]:
    """(FLOPs, bytes) one ``ssm_decode_update`` call over ``rows`` rows
    requires.  Per row and head, with S [head_dim, state]: the decay
    ``exp(dt A) S`` (one multiply per element, and dt A and its exp);
    the rank-1 update ``+ (dt x) B^T`` (dt x, then a multiply and an add
    per element); the readout ``y = S C + D x`` (a multiply and an add
    per element, D x and its add).  Bytes: each row's state read and
    written, x, B, C and dt in and y out; A and D once a call."""
    H, P, N = heads, head_dim, state
    per_row = 5 * H * P * N + 3 * H * P + 2 * H
    io = (2 * H * P + 2 * N + H) * io_bytes
    nbytes = rows * (2 * H * P * N * state_bytes + io) + 2 * H * io_bytes
    return float(rows * per_row), float(nbytes)
