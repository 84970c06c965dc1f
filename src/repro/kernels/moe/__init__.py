from .grouped import (DEFAULT_CONFIG, DOWN_NAME, GATE_UP_NAME,
                      analytical_time, combine_pair_counts, combine_vmem,
                      grid_tiles, grouped_tile_counts, make_moe_experts,
                      round_rows, validate_config, vmem_footprint)
from .ops import (MOE_EXPERTS, heuristic_config, lookup_config, moe_experts,
                  shape_key, tuning_space)
from .ref import moe_experts_reference

__all__ = [
    "DEFAULT_CONFIG", "DOWN_NAME", "GATE_UP_NAME", "MOE_EXPERTS",
    "analytical_time", "combine_pair_counts", "combine_vmem", "grid_tiles",
    "grouped_tile_counts",
    "heuristic_config", "lookup_config", "make_moe_experts", "moe_experts",
    "moe_experts_reference", "round_rows", "shape_key", "tuning_space",
    "validate_config", "vmem_footprint",
]
