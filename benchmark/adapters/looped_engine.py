"""The system under test for LOOPED dense decoders (one stack of layers that
every token walks several times over the same weights, each walk with cache
planes of its own, sandwich norms, a norm and an exit gate at every walk's
end: the ``ouro`` layer): the same ``ServingEngine`` as ``paged_engine``,
handed the program's config for that family. Only how the program config is
built from the configuration FILE differs; driving, counters and sizes are
inherited.

The program's module is imported here at the top, so that a program without
the family fails the cell at once, before any weight is made; no other file of
the benchmark imports it, so the other cells' set-up does not grow."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark.adapters.paged_engine import Adapter as PagedAdapter
from triton_dist_tpu.models.looped import LoopedConfig, kv_bytes_per_token


class Adapter(PagedAdapter):
    def _program_config(self):
        c = self.cfg
        L = c["num_hidden_layers"]
        if c["model_type"] != "ouro" or c["hidden_act"] != "silu" \
                or c["tie_word_embeddings"] or c["rope_scaling"] is not None \
                or c["sliding_window"] is not None or c["use_sliding_window"] \
                or set(c["layer_types"][:L]) != {"full_attention"} \
                or len(c["layer_types"]) < L:
            raise ValueError(
                "the program has full-attention layers walked "
                "total_ut_steps times, silu-gated FFNs, plain rope, no "
                "window and an untied head only")
        pc = LoopedConfig(
            vocab_size=c["vocab_size"], d_model=c["hidden_size"], n_layers=L,
            n_walks=c["total_ut_steps"], n_heads=c["num_attention_heads"],
            n_kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
            d_ff=c["intermediate_size"], rope_theta=float(c["rope_theta"]),
            norm_eps=float(c["rms_norm_eps"]),
            exit_threshold=float(c["early_exit_threshold"]),
            max_seq_len=self.max_context, dtype=jnp.dtype(c["torch_dtype"]))
        # the file's cache against the program's own leaves: planes, bytes a
        # key and plane, bytes a token over all planes, bytes a page
        e, want = self.eng_cfg, c["cache"]
        pool = jax.eval_shape(lambda: pc.paged.init_pool(pc, 2,
                                                         e["page_size"]))
        leaves = jax.tree_util.tree_leaves(pool)
        a_page = sum(a.size * a.dtype.itemsize for a in leaves) // 2
        planes = {a.shape[0] for a in leaves}
        got = {"planes": planes.pop() if len(planes) == 1 else sorted(planes),
               "kv_bytes_per_key_and_plane":
                   a_page // (e["page_size"] * leaves[0].shape[0]),
               "kv_bytes_per_token": a_page // e["page_size"],
               "kv_bytes_per_page": a_page}
        assert got["kv_bytes_per_token"] == kv_bytes_per_token(pc)
        for key, have in got.items():
            if have != want[key]:
                raise ValueError(f"the program holds {key} = {have}, the "
                                 f"file's cache says {want[key]}")
        return pc
