"""Core: space-filling-curve orderings, cache model, layouts (the paper's contribution)."""

from .orderings import (  # noqa: F401
    OrderingSpec, ROW_MAJOR, COLUMN_MAJOR, MORTON, HILBERT,
    rmo_to_path, path_to_rmo, path_positions, path_index_2d, ordering_from_name,
)
from .morton import (  # noqa: F401
    morton_encode3, morton_decode3, morton_encode2, morton_decode2,
    morton_encode3_level, morton_decode3_level,
)
from .hilbert import hilbert_encode3, hilbert_decode3, hilbert_encode, hilbert_decode  # noqa: F401
from .cache_model import (  # noqa: F401
    offset_histogram, offset_summary, cache_misses, surface_cache_misses,
    simulate_lru, stencil_offsets,
)
from .surfaces import (  # noqa: F401
    FACES, PAPER_SURFACE_NAMES, surface_path_indices, run_stats, surface_runs,
)
from .layout import (  # noqa: F401
    apply_ordering, undo_ordering, blockize, unblockize, blockize_fields,
    unblockize_fields, block_order,
)
from .neighbors import (  # noqa: F401
    OFFSETS_FULL, OFFSETS_FACE, FACE_COLS, SELF_COL,
    block_kind_of, boundary_face_table, neighbor_table, ring_perms,
)
from .boundary import (  # noqa: F401
    BoundarySpec, MixedBoundary, PERIODIC, NEUMANN0, dirichlet, mixed,
    as_boundary, axes_periodic, pad_cube,
)
