"""The reader of the forecast's tile-loop counter, ``forecast_tile_fill_pct``:
the ready rows of the window's ticks over the rows of the tiles the
program ran, worked out by hand, and nothing read from a program that
does not count tile rows or from a run that traced no device."""
import types

import pytest

from harness import cell

DEV = "/device:TPU:0"
# one device op inside a 0..10,000 ns traced window
REC = {"devices": {DEV: [("%fusion.1 = f32[8] fusion(%p), kind=kLoop", 100, 500)]},
       "host": [("bench.traced", 0, 10_000)]}
# two members, two ticks; the cohort's tiles held 16 + 32 rows, counted
# on the member with the most passes
TICKS = {"forecast_rows": [[4, 2], [6, 20]], "forecast_tile_rows": [[16, 32], [16, 0]]}


def _ctx(rec, ticks):
    return cell.Context(rec, types.SimpleNamespace(chunks=[]), ticks, {}, None, 32)


def test_forecast_tile_fill_by_hand():
    read = cell.reader("forecast_tile_fill_pct")
    assert read(_ctx(REC, TICKS)) == pytest.approx(100 * 32 / 48)
    # a program that does not count tile rows, or a run that traced no
    # device, reads nothing
    old = {k: v for k, v in TICKS.items() if k != "forecast_tile_rows"}
    assert read(_ctx(REC, old)) is None
    assert read(_ctx({"devices": {}, "host": []}, TICKS)) is None
