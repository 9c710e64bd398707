"""The system under test for configurations of doubly gated short-convolution
layers (two carried rows a slot and no pages) with a GQA layer of narrow heads
opening every period, leading dense layers and a bias-selected sigmoid-routed
expert layer held WHOLE (the ``lfm2_moe`` layer): the same ``ServingEngine`` as
``paged_engine``, handed the program's config for that family. Only how the
program config is built from the configuration FILE differs; driving, counters
and sizes are inherited.

The program's module is imported here at the top, so that a program without
the family fails the cell at once, before any weight is made; no other file of
the benchmark imports it, so the other cells' set-up does not grow."""

from __future__ import annotations

import jax.numpy as jnp

from benchmark.adapters.paged_engine import Adapter as PagedAdapter
from triton_dist_tpu.models.short_conv_moe import (ShortConvMoEConfig, bind,
                                                   kv_bytes_per_token,
                                                   layer_state_bytes)

KINDS = {"conv": "conv", "full_attention": "full"}


def period_of(kinds: tuple) -> tuple:
    """The shortest period ``kinds`` is a whole number of."""
    for n in range(1, len(kinds) + 1):
        if len(kinds) % n == 0 and kinds == kinds[:n] * (len(kinds) // n):
            return kinds[:n]
    return kinds


class Adapter(PagedAdapter):
    def _program_config(self):
        c = self.cfg
        L, nd = c["num_hidden_layers"], c["num_dense_layers"]
        kinds = tuple(KINDS[k] for k in c["layer_types"][:L])
        if c["conv_bias"] or not c["norm_topk_prob"] \
                or not c["use_expert_bias"] \
                or c["rope_parameters"]["rope_type"] != "default" \
                or not c["assumed"]["tie_word_embeddings"] \
                or len(kinds) != L or set(kinds[:nd]) != {"conv"}:
            raise ValueError(
                "the program has leading dense conv layers, then whole "
                "periods of conv and full layers, a bias-selected "
                "renormalised sigmoid router, a tied head, plain rope, and "
                "no conv bias only")
        pc = ShortConvMoEConfig(
            vocab_size=c["vocab_size"], d_model=c["hidden_size"],
            n_layers=L, n_dense_layers=nd, layer_kinds=period_of(kinds[nd:]),
            d_ff=c["intermediate_size"], n_heads=c["num_attention_heads"],
            n_kv_heads=c["num_key_value_heads"],
            head_dim=c["hidden_size"] // c["num_attention_heads"],
            rope_theta=float(c["rope_parameters"]["rope_theta"]),
            conv_taps=c["conv_L_cache"], moe_d_ff=c["moe_intermediate_size"],
            n_routed_experts=c["num_experts"], n_experts_held=c["num_experts"],
            topk=c["num_experts_per_tok"],
            routed_scale=float(c["routed_scaling_factor"]),
            norm_eps=float(c["norm_eps"]), max_seq_len=self.max_context,
            dtype=jnp.dtype(c["torch_dtype"]))
        # sized for the engine's slots, as the engine itself does (tools that
        # ask the family for its pool get the engine's)
        pc = bind(pc, self.eng_cfg["num_slots"],
                  self.eng_cfg["prefill_chunk"])
        held = {"state_bytes_per_slot_per_conv_layer": layer_state_bytes(pc),
                "kv_bytes_per_token_per_full_layer": kv_bytes_per_token(pc)}
        for key, got in held.items():
            if got != c["cache"][key]:
                raise ValueError(f"the program holds {got} B, the file's "
                                 f"{key} is {c['cache'][key]}")
        return pc
