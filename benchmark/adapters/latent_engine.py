"""The system under test for latent-attention, shared-plus-routed-expert
configurations: the same ``ServingEngine`` as ``paged_engine``, handed the
program's config for that family. Only how the program config is built from
the configuration FILE differs; driving, counters and sizes are inherited.

The program's module is imported here at the top, so that a program without
the family fails the cell at once, before any weight is made."""

from __future__ import annotations

import jax.numpy as jnp

from benchmark.adapters.paged_engine import Adapter as PagedAdapter
from triton_dist_tpu.models.mla import LatentMoEConfig


class Adapter(PagedAdapter):
    def _program_config(self):
        c = self.cfg
        rs = c["rope_scaling"]
        if rs["type"] != "yarn" or c["scoring_func"] != "sigmoid" \
                or c["n_group"] != 1 or c["topk_group"] != 1:
            raise ValueError("the program has YaRN rope and an ungrouped "
                             "sigmoid router only")
        pc = LatentMoEConfig(
            vocab_size=c["vocab_size"], d_model=c["hidden_size"],
            n_layers=c["num_hidden_layers"],
            n_dense_layers=c["first_k_dense_replace"],
            n_heads=c["num_attention_heads"], q_lora_rank=c["q_lora_rank"],
            kv_lora_rank=c["kv_lora_rank"],
            qk_nope_head_dim=c["qk_nope_head_dim"],
            qk_rope_head_dim=c["qk_rope_head_dim"],
            v_head_dim=c["v_head_dim"], d_ff=c["intermediate_size"],
            moe_d_ff=c["moe_intermediate_size"],
            n_routed_experts=c["published"]["n_routed_experts"],
            n_experts_held=c["n_routed_experts"],
            first_held_expert=c["share"]["first_expert"],
            topk=c["num_experts_per_tok"],
            n_shared_experts=c["n_shared_experts"],
            routed_scaling_factor=float(c["routed_scaling_factor"]),
            rope_theta=float(c["rope_theta"]),
            rope_factor=float(rs["factor"]),
            rope_original_max_pos=rs["original_max_position_embeddings"],
            rope_beta_fast=float(rs["beta_fast"]),
            rope_beta_slow=float(rs["beta_slow"]),
            rope_mscale=float(rs["mscale"]),
            rope_mscale_all_dim=float(rs["mscale_all_dim"]),
            norm_eps=float(c["rms_norm_eps"]), max_seq_len=self.max_context,
            dtype=jnp.dtype(c["torch_dtype"]))
        if pc.cache_width != c["cache"]["stored_width"]:
            raise ValueError("the program stores another width than the "
                             "file states")
        return pc
