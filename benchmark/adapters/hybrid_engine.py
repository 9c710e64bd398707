"""The system under test for configurations with a state-space mixer beside
attention in every block (the ``falcon_h1`` layer): the same ``ServingEngine``
as ``paged_engine``, handed the program's config for that family. Only how the
program config is built from the configuration FILE differs; driving, counters
and sizes are inherited.

The program's module is imported here at the top, so that a program without
the family fails the cell at once, before any weight is made; no other file of
the benchmark imports it, so the other cells' set-up does not grow."""

from __future__ import annotations

import jax.numpy as jnp

from benchmark.adapters.paged_engine import Adapter as PagedAdapter
from triton_dist_tpu.models.hybrid_ssm import (HybridSSMConfig, bind,
                                               slot_state_bytes)


class Adapter(PagedAdapter):
    def _program_config(self):
        c = self.cfg
        if not c["mamba_rms_norm"] or c["mamba_norm_before_gate"] \
                or not c["mamba_conv_bias"] or c["mamba_proj_bias"] \
                or c["attention_bias"] or c["mlp_bias"] \
                or c["projectors_bias"] or c["tie_word_embeddings"] \
                or c["rope_scaling"] is not None \
                or c["attn_layer_indices"] is not None \
                or c["hidden_act"] != "silu" or not c["mamba_use_mlp"] \
                or c["mamba_d_ssm"] != c["mamba_n_heads"] * c["mamba_d_head"]:
            raise ValueError(
                "the program has a gated grouped norm after the gate, a conv "
                "bias, no other bias, an untied head, plain rope, a mixer "
                "and an attention in every block and a silu MLP only")
        pc = HybridSSMConfig(
            vocab_size=c["vocab_size"], d_model=c["hidden_size"],
            n_layers=c["num_hidden_layers"],
            n_heads=c["num_attention_heads"],
            n_kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
            d_ff=c["intermediate_size"], ssm_heads=c["mamba_n_heads"],
            ssm_head_dim=c["mamba_d_head"], ssm_state=c["mamba_d_state"],
            ssm_groups=c["mamba_n_groups"], ssm_conv=c["mamba_d_conv"],
            ssm_chunk=c["mamba_chunk_size"],
            rope_theta=float(c["rope_theta"]),
            norm_eps=float(c["rms_norm_eps"]),
            embedding_multiplier=float(c["embedding_multiplier"]),
            lm_head_multiplier=float(c["lm_head_multiplier"]),
            attention_in_multiplier=float(c["attention_in_multiplier"]),
            attention_out_multiplier=float(c["attention_out_multiplier"]),
            key_multiplier=float(c["key_multiplier"]),
            ssm_in_multiplier=float(c["ssm_in_multiplier"]),
            ssm_out_multiplier=float(c["ssm_out_multiplier"]),
            ssm_multipliers=tuple(float(m) for m in c["ssm_multipliers"]),
            mlp_multipliers=tuple(float(m) for m in c["mlp_multipliers"]),
            max_seq_len=self.max_context, dtype=jnp.dtype(c["torch_dtype"]))
        # sized for the engine's slots, as the engine itself does (tools that
        # ask the family for its pool get the engine's)
        pc = bind(pc, self.eng_cfg["num_slots"],
                  self.eng_cfg["prefill_chunk"])
        per_layer = slot_state_bytes(pc) // pc.n_layers
        if per_layer != c["cache"]["state_bytes_per_slot_per_layer"]:
            raise ValueError("the program's state is not the file's: "
                             f"{per_layer} B a slot and layer")
        return pc
