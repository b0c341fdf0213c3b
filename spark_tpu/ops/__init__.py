"""Hand-written TPU kernels (Pallas) for hot ops the XLA autofusion
path leaves on the table. Selection is measured, not assumed: callers
go through ``maybe_*`` entry points that fall back to the pure-XLA
kernels in physical/kernels.py whenever shapes/dtypes/platform don't
qualify."""

from spark_tpu.ops.pallas_agg import (  # noqa: F401
    maybe_pallas_seg_count,
    maybe_pallas_seg_max,
    maybe_pallas_seg_min,
    pallas_available,
    pallas_seg_minmax,
    pallas_seg_sum,
)
