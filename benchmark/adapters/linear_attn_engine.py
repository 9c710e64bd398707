"""The system under test for configurations of gated-delta-rule
linear-attention layers (a matrix state and no pages) with a gated
full-attention layer (pages and no state) closing every period, and a
softmax-routed share of experts beside a gated shared expert (the
``qwen3_next`` layer): the same ``ServingEngine`` as ``paged_engine``, handed
the program's config for that family. Only how the program config is built
from the configuration FILE differs; driving, counters and sizes are
inherited.

The program's module is imported here at the top, so that a program without
the family fails the cell at once, before any weight is made; no other file of
the benchmark imports it, so the other cells' set-up does not grow."""

from __future__ import annotations

import jax.numpy as jnp

from benchmark.adapters.paged_engine import Adapter as PagedAdapter
from triton_dist_tpu.models.linear_attn_moe import (LinearAttnMoEConfig, bind,
                                                    layer_state_bytes)


class Adapter(PagedAdapter):
    def _program_config(self):
        c = self.cfg
        every = c["full_attention_interval"]
        if c["decoder_sparse_step"] != 1 or c["mlp_only_layers"] \
                or c["tie_word_embeddings"] or c["rope_scaling"] is not None \
                or not c["norm_topk_prob"] or c["hidden_act"] != "silu" \
                or c["use_sliding_window"] \
                or c["num_hidden_layers"] % every:
            raise ValueError(
                "the program has whole periods of linear layers closed by a "
                "full one, an expert FFN in every layer, a renormalised "
                "softmax router, an untied head, plain rope, silu, and no "
                "sliding window only")
        rot = int(c["partial_rotary_factor"] * c["head_dim"])
        pc = LinearAttnMoEConfig(
            vocab_size=c["vocab_size"], d_model=c["hidden_size"],
            n_layers=c["num_hidden_layers"],
            layer_kinds=("linear",) * (every - 1) + ("full",),
            n_heads=c["num_attention_heads"],
            n_kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
            rope_dims=rot - rot % 2, rope_theta=float(c["rope_theta"]),
            lin_key_heads=c["linear_num_key_heads"],
            lin_value_heads=c["linear_num_value_heads"],
            lin_key_dim=c["linear_key_head_dim"],
            lin_value_dim=c["linear_value_head_dim"],
            lin_conv=c["linear_conv_kernel_dim"],
            gdn_chunk=c["cache"]["scan_block_tokens"],
            moe_d_ff=c["moe_intermediate_size"],
            shared_d_ff=c["shared_expert_intermediate_size"],
            n_routed_experts=c["published"]["num_experts"],
            n_experts_held=c["num_experts"],
            first_held_expert=c["share"]["first_expert"],
            topk=c["num_experts_per_tok"],
            norm_eps=float(c["rms_norm_eps"]),
            max_seq_len=self.max_context, dtype=jnp.dtype(c["torch_dtype"]))
        # sized for the engine's slots, as the engine itself does (tools that
        # ask the family for its pool get the engine's)
        pc = bind(pc, self.eng_cfg["num_slots"],
                  self.eng_cfg["prefill_chunk"])
        if layer_state_bytes(pc) != \
                c["cache"]["state_bytes_per_slot_per_linear_layer"]:
            raise ValueError("the program's state is not the file's: "
                             f"{layer_state_bytes(pc)} B a slot and layer")
        return pc
