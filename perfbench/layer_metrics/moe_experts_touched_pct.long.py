"""``moe_experts_touched_pct`` for the cells whose end-to-end gap is
``itl_p50_ms``: the same reader under a second name, because a per-layer
metric moves one end-to-end metric and every cell it lists reports it."""

from perfbench.byname import load_beside

_base = load_beside(__file__, "moe_experts_touched_pct")
LAYER, UNIT, SOURCE = _base.LAYER, _base.UNIT, _base.SOURCE
MOVES = "itl_p50_ms"
read = _base.read
