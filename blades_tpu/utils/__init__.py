from blades_tpu.utils.tree import (  # noqa: F401
    ravel_fn,
    tree_size,
    tree_zeros_like_flat,
)
