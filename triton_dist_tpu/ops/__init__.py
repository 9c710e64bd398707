"""Kernel library — overlapping distributed ops (the analog of reference
python/triton_dist/kernels/nvidia/*, re-exported the same way its
kernels/nvidia/__init__.py:25-89 does).

This surface is load-bearing: ``triton_dist_tpu.analysis.registry`` (the
sigcheck static verifier) enumerates every name exported here and requires
each to be either protocol-checked or carry a documented skip, and
tests/test_sigcheck.py asserts the two stay in lockstep — add an export and
the registry must learn about it in the same change."""

from triton_dist_tpu.ops.common import collective_id_for, barrier_all_op  # noqa: F401
from triton_dist_tpu.ops.gemm import GemmConfig, best_gemm_config  # noqa: F401
from triton_dist_tpu.ops.allgather import (all_gather, all_gather_ll,  # noqa: F401
                                           AgLLContext,
                                           create_ag_ll_workspace, broadcast)
from triton_dist_tpu.ops.reduce_scatter import reduce_scatter  # noqa: F401
from triton_dist_tpu.ops.allgather_gemm import (  # noqa: F401
    AgGemmContext, ag_gemm, ag_gemm_ws, create_ag_gemm_context,
    create_ag_gemm_workspace, tp_column_linear)
from triton_dist_tpu.ops.gemm_reduce_scatter import (  # noqa: F401
    GemmRsContext, gemm_rs, gemm_rs_ws, create_gemm_rs_context,
    create_gemm_rs_workspace)
from triton_dist_tpu.ops.autodiff import ag_gemm_diff, gemm_rs_diff  # noqa: F401
from triton_dist_tpu.ops.ring_attention import (  # noqa: F401
    ring_attention, ring_attention_fwd, ring_attention_bwd, zigzag_indices)
from triton_dist_tpu.ops.page_migrate import (migrate_pages,  # noqa: F401
                                              paged_transport)
from triton_dist_tpu.ops.lend_pages import lend_pages  # noqa: F401
from triton_dist_tpu.ops.all_to_all import (  # noqa: F401
    EpAllToAllContext, Ep2dAllToAllContext, all_to_all_push,
    all_to_all_push_seg, a2a_wire_bytes,
    pick_wire_dtype, create_all_to_all_context, create_all_to_all_context_2d,
    route_tokens, route_tokens_2d, dispatch, dispatch_2d, combine, combine_2d,
    expected_capacity)
from triton_dist_tpu.ops.flash_decode import (  # noqa: F401
    gqa_decode_partial, gqa_decode_paged, gqa_prefill_paged, paged_kv_write,
    paged_rows_write,
    decode_combine, ll_ag_merge, sp_gqa_flash_decode, sp_paged_attend_write,
    pool_ag_start_local, flash_decode_dist)
from triton_dist_tpu.ops.mla_decode import mla_decode_paged  # noqa: F401
from triton_dist_tpu.ops.ssm import ssm_decode_update, ssd_chunk_scan  # noqa: F401
from triton_dist_tpu.ops.gdn import gdn_decode_update, gdn_chunk_scan  # noqa: F401
from triton_dist_tpu.ops.group_gemm import (  # noqa: F401
    PackedGatedWeights, align_tokens_by_expert, used_block_count,
    emit_grouped_gemm, grouped_gemm, pack_gated_weights, grouped_gemm_gated,
    apply_grouped, moe_ffn_local)
from triton_dist_tpu.ops.moe import ag_moe_group_gemm, moe_reduce_rs  # noqa: F401
from triton_dist_tpu.ops.autotuned import (  # noqa: F401
    ag_gemm_autotuned, gemm_rs_autotuned, ag_moe_group_gemm_autotuned,
    grouped_gemm_autotuned, moe_ffn_gated_autotuned, moe_reduce_rs_autotuned,
    ring_attention_autotuned)
