from .flash import (DEFAULT_CONFIG, analytical_time, causal_block_counts,
                    last_visible_block, make_flash_attention, validate_config,
                    vmem_footprint)
from .ops import (FLASH_ATTENTION, flash_attention, heuristic_config,
                  lookup_config, make_tuner, shape_key,
                  tune_flash_attention, tuning_space)
from .ref import attention_flops, attention_reference

__all__ = [
    "DEFAULT_CONFIG", "FLASH_ATTENTION", "analytical_time",
    "causal_block_counts", "last_visible_block",
    "make_flash_attention", "validate_config", "vmem_footprint",
    "flash_attention", "heuristic_config", "lookup_config", "make_tuner",
    "shape_key", "tune_flash_attention", "tuning_space", "attention_flops",
    "attention_reference",
]
