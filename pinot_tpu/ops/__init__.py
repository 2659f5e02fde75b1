"""Device kernels / numeric primitives (the XLA/Pallas op layer).

The slot where the reference's JNI-native dependencies live (SURVEY.md 2.4):
here they are TPU kernels — segmented reductions, bitmap algebra, sketch
updates — shared by the SSE planner, the distributed engine and the MSE.
"""
from pinot_tpu.ops.segmented import (  # noqa: F401
    MaskFacts,
    accum_policy,
    fused_group_tables,
    int_sum_entry,
    limb_prefix_table,
    prefix_group_sums,
    sum_limb_plan,
    sum_limb_plan64,
    group_count,
    group_max,
    group_min,
    group_sum,
    group_sum_sq,
    masked_count,
    masked_max,
    masked_min,
    masked_sum,
    masked_sum_sq,
    mask_facts,
    sketch_count_table,
    sketch_max_table,
    unpack_bitmap_words,
)
from pinot_tpu.ops.pallas_scan import (  # noqa: F401
    fused_group_tables_pallas,
    merge_sparse_tables,
    pallas_supported,
    scan_backend,
)
