"""Operations and bytes, from shapes alone, of what a configuration with a
state-space (Mamba-2) mixer adds (beside ``costs.py``): the decode rows'
one-step state update, counted in ROWS (the program's counter
``ssm_state_rows``: live rows summed over layers and inner steps)."""

from __future__ import annotations

from benchmark.costs import _itemsize

STATE_ITEMSIZE = 4          # the recurrent state is float32 (``assumed``)


def _mixer(cfg: dict) -> tuple[int, int, int]:
    """(state elements, conv-state elements, xBC channels) of a row and layer."""
    H, P, N = cfg["mamba_n_heads"], cfg["mamba_d_head"], cfg["mamba_d_state"]
    d_xbc = H * P + 2 * cfg["mamba_n_groups"] * N
    return H * P * N, (cfg["mamba_d_conv"] - 1) * d_xbc, d_xbc


def update_row_bytes(cfg: dict) -> int:
    """Least bytes one live row's update moves in one layer: its state read
    and written back (2 x 4,194,304 B at 32 heads x 128 x 256 in float32), its
    conv rows read and written, its inputs (the row of ``xBC`` and ``dt``) and
    its output ``y`` in float32."""
    state, conv, d_xbc = _mixer(cfg)
    H = cfg["mamba_n_heads"]
    return (2 * state * STATE_ITEMSIZE + 2 * conv * _itemsize(cfg)
            + d_xbc * _itemsize(cfg) + H * 4
            + H * cfg["mamba_d_head"] * 4)


def update_row_flops(cfg: dict) -> int:
    """Operations of the same update: per state element a decay, an outer
    product and its add, and the contraction with C (a multiply and an add)."""
    return 5 * _mixer(cfg)[0]


def update_least_s(cfg: dict, rows: int, peaks: dict) -> float:
    """Least time of ``rows`` updates on a chip with ``peaks``: 0.62 FLOP a
    byte, far under the v5e's ridge of 240: memory bounds it."""
    return max(rows * update_row_bytes(cfg) / peaks["hbm_bytes_per_s"],
               rows * update_row_flops(cfg) / peaks["bf16_flops_per_s"])
