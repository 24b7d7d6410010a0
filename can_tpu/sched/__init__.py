"""can_tpu.sched — the cost-priced scheduling core all four batch-
formation engines consume (offline ShardedBatcher, serve MicroBatcher,
eval prefetch, fleet work queue).  See sched/core.py."""

from .core import (
    COST_UNIT,
    DEFAULT_LAUNCH_COST_SLOTS,
    DEFAULT_MENU_BUDGET,
    ServeSched,
    cover_cost,
    default_serve_menu,
    normalize_sizes,
    offline_planner,
    pick_work,
    prefetch_depth,
    prefetch_depth_for,
    select_menu,
)

__all__ = [
    "COST_UNIT",
    "DEFAULT_LAUNCH_COST_SLOTS",
    "DEFAULT_MENU_BUDGET",
    "ServeSched",
    "cover_cost",
    "default_serve_menu",
    "normalize_sizes",
    "offline_planner",
    "pick_work",
    "prefetch_depth",
    "prefetch_depth_for",
    "select_menu",
]
