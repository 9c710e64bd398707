"""The system under test for configurations whose sliding-window layers (with
a learned attention sink) and full-attention layers differ in SHAPE, behind a
leading dense layer, with bias-selected routed experts: the same
``ServingEngine`` as ``paged_engine``, handed the program's config for that
family. Only how the program config is built from the configuration FILE
differs; driving, counters and sizes are inherited.

The program's record of the family is imported here at the top, so that a
program without it fails the cell at once, before any weight is made."""

from __future__ import annotations

import jax.numpy as jnp

from benchmark.adapters.paged_engine import Adapter as PagedAdapter
from triton_dist_tpu.models.window_moe import (SINK_WINDOW_MOE,
                                               WindowMoEConfig, bind)


class Adapter(PagedAdapter):
    def _program_config(self):
        c = self.cfg
        L = c["num_hidden_layers"]
        kinds = tuple("window" if x else "full"
                      for x in c["hybrid_layer_pattern"][:L])
        sparse = [bool(x) for x in c["moe_layer_freq"][:L]]
        n_dense = sparse.index(True)
        if c["scoring_func"] != "sigmoid" or not c["norm_topk_prob"] \
                or c["n_group"] != 1 or c["topk_group"] != 1 \
                or c["topk_method"] != "noaux_tc" or c["n_shared_experts"] \
                or c["routed_scaling_factor"] or c["tie_word_embeddings"] \
                or c["attention_bias"] or c["add_full_attention_sink_bias"] \
                or not c["add_swa_attention_sink_bias"] \
                or not all(sparse[n_dense:]) or "window" in kinds[:n_dense] \
                or (c["swa_head_dim"], c["swa_v_head_dim"],
                    c["swa_num_attention_heads"]) != (
                    c["head_dim"], c["v_head_dim"], c["num_attention_heads"]):
            raise ValueError(
                "the program has an ungrouped normalised sigmoid router with "
                "a selection bias, no shared expert or scaling factor, an "
                "untied head, sinks on the window layers alone, leading dense "
                "full-attention layers, and heads of one width in both kinds")
        period = kinds[n_dense:]
        rot = int(c["partial_rotary_factor"] * c["head_dim"])
        pc = WindowMoEConfig(
            vocab_size=c["vocab_size"], d_model=c["hidden_size"], n_layers=L,
            n_heads=c["num_attention_heads"],
            n_kv_heads=c["swa_num_key_value_heads"],
            full_kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
            v_head_dim=c["v_head_dim"],
            k_pool_width=c["cache"]["k_pool_width"],
            window=c["sliding_window"], layer_kinds=period,
            rope_dims=rot - rot % 2, rope_theta=float(c["swa_rope_theta"]),
            full_rope_theta=float(c["rope_theta"]), sinks=True,
            value_scale=float(c["attention_value_scale"]),
            n_dense_layers=n_dense, d_ff=c["intermediate_size"],
            moe_d_ff=c["moe_intermediate_size"],
            n_routed_experts=c["published"]["n_routed_experts"],
            n_experts_held=c["n_routed_experts"],
            first_held_expert=c["share"]["first_expert"],
            topk=c["num_experts_per_tok"], n_shared_experts=0,
            selection_bias=True, sequential=True,
            norm_eps=float(c["layernorm_epsilon"]),
            max_seq_len=self.max_context, dtype=jnp.dtype(c["torch_dtype"]))
        assert pc.paged is SINK_WINDOW_MOE
        # sized for the engine's slots and chunk, as the engine itself does
        # (tools that ask the family for its pool get the engine's)
        e = self.eng_cfg
        pc = bind(pc, e["num_slots"], e["prefill_chunk"])
        if pc.ring_pages(e["page_size"]) != c["cache"]["ring_pages"]:
            raise ValueError("the program's ring is not the file's")
        return pc
