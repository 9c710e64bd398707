"""The system under test for configurations that mix sliding-window and full
attention, with a parallel block and shared-plus-routed experts: the same
``ServingEngine`` as ``paged_engine``, handed the program's config for that
family. Only how the program config is built from the configuration FILE
differs; driving, counters and sizes are inherited.

The program's module is imported here at the top, so that a program without
the family fails the cell at once, before any weight is made."""

from __future__ import annotations

import jax.numpy as jnp

from benchmark.adapters.paged_engine import Adapter as PagedAdapter
from triton_dist_tpu.models.window_moe import WindowMoEConfig, bind

KINDS = {"sliding_attention": "window", "full_attention": "full"}


class Adapter(PagedAdapter):
    def _program_config(self):
        c = self.cfg
        if c["expert_selection_fn"] != "sigmoid" or not c["norm_topk_prob"] \
                or c["shared_expert_combination_strategy"] != "average" \
                or not c["use_parallel_block"] or c["use_qk_norm"] \
                or not c["tie_word_embeddings"] or c["attention_bias"] \
                or c["first_k_dense_replace"] or float(c["rotary_pct"]) != 1:
            raise ValueError(
                "the program has a parallel block, a normalised sigmoid "
                "router, averaged shared experts, a tied head, whole-head "
                "rope and no leading dense layers, biases or q/k norm only")
        period = c["layer_switch"]
        kinds = c["layer_types"][:c["num_hidden_layers"]]
        if any(kinds[i] != kinds[i % period] for i in range(len(kinds))):
            raise ValueError("layer_types does not repeat with layer_switch")
        pc = WindowMoEConfig(
            vocab_size=c["vocab_size"], d_model=c["hidden_size"],
            n_layers=c["num_hidden_layers"],
            n_heads=c["num_attention_heads"],
            n_kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
            window=c["sliding_window"],
            layer_kinds=tuple(KINDS[k] for k in kinds[:period]),
            moe_d_ff=c["intermediate_size"],
            n_routed_experts=c["published"]["num_experts"],
            n_experts_held=c["num_experts"],
            first_held_expert=c["share"]["first_expert"],
            topk=c["num_experts_per_tok"],
            n_shared_experts=c["num_shared_experts"],
            rope_theta=float(c["rope_theta"]),
            norm_eps=float(c["layer_norm_eps"]),
            logit_scale=float(c["logit_scale"]),
            max_seq_len=self.max_context, dtype=jnp.dtype(c["torch_dtype"]))
        # sized for the engine's slots and chunk, as the engine itself does
        # (tools that ask the family for its pool get the engine's)
        e = self.eng_cfg
        pc = bind(pc, e["num_slots"], e["prefill_chunk"])
        if pc.ring_pages(e["page_size"]) != c["cache"]["ring_pages"]:
            raise ValueError("the program's ring is not the file's")
        return pc
