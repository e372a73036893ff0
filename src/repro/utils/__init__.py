from repro.utils.tree import (
    tree_bytes,
    tree_count,
    tree_map_with_path_names,
    tree_norm,
)
