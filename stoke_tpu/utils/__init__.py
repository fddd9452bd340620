"""Utility helpers (reference: stoke/utils.py:1-151, TPU-native re-design)."""

from stoke_tpu.utils.init import init_module
from stoke_tpu.utils.yaml_config import stoke_from_config, stoke_kwargs_from_config
from stoke_tpu.utils.printing import unrolled_print, make_folder
from stoke_tpu.utils.trees import (
    tree_count_params,
    tree_cast,
    tree_zeros_like,
    tree_add,
    tree_scale,
    tree_finite,
    place_data_on_device,
    to_numpy_tree,
)

__all__ = [
    "init_module",
    "stoke_from_config",
    "stoke_kwargs_from_config",
    "unrolled_print",
    "make_folder",
    "tree_count_params",
    "tree_cast",
    "tree_zeros_like",
    "tree_add",
    "tree_scale",
    "tree_finite",
    "place_data_on_device",
    "to_numpy_tree",
]
