"""The system under test: the package's paged serving engines, driven through
their public API (``ServingEngine`` on one device, ``ShardedServingEngine`` on
a TP x SP x EP mesh when the configuration's ``engine.mesh`` is given).

This is the only file of the benchmark that imports the program. It builds the
program's config dataclass from the configuration FILE (never from the
program's presets), hands the engine weights the benchmark made, and exposes
what the load generator needs: submit, step, a request's state and token count,
and the program's counters. It times nothing.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

WIRE = {"auto": "auto", "bf16": None, "fp8": jnp.float8_e4m3fn}


class Adapter:
    def __init__(self, cfg: dict):
        self.cfg = cfg
        self.eng_cfg = dict(cfg["engine"])
        self.mesh_shape = self.eng_cfg.pop("mesh", None)
        self.wire = self.eng_cfg.pop("wire", None)
        self.wire_resolves_to = self.eng_cfg.pop("wire_resolves_to", None)
        self.eng = None
        self.ctx = None

    # -- sizes the harness asks for ------------------------------------
    @property
    def devices_needed(self) -> int:
        if self.mesh_shape is None:
            return 1
        n = 1
        for d in self.mesh_shape:
            n *= int(d)
        return n

    @property
    def max_context(self) -> int:
        return self.eng_cfg["pages_per_seq"] * self.eng_cfg["page_size"]

    @property
    def decode_horizon(self) -> int:
        return int(self.eng_cfg.get("decode_horizon", 1))

    # -- building --------------------------------------------------------
    def _program_config(self):
        from triton_dist_tpu.models.llama import LlamaConfig
        from triton_dist_tpu.models.moe import MoEConfig
        c = self.cfg
        base = LlamaConfig(
            vocab_size=c["vocab_size"], d_model=c["hidden_size"],
            n_layers=c["num_hidden_layers"], n_heads=c["num_attention_heads"],
            n_kv_heads=c["num_key_value_heads"], d_ff=c["intermediate_size"],
            rope_theta=float(c["rope_theta"]),
            norm_eps=float(c["rms_norm_eps"]),
            max_seq_len=self.max_context, dtype=jnp.dtype(c["torch_dtype"]))
        if base.head_dim != c.get("head_dim", base.head_dim):
            raise ValueError("head_dim of the file is not hidden / heads")
        if not c.get("num_local_experts"):
            return base
        return MoEConfig(base=base, num_experts=c["num_local_experts"],
                         topk=c["num_experts_per_tok"],
                         moe_d_ff=c["intermediate_size"])

    def open_mesh(self, rehearsal: bool) -> None:
        """The mesh comes first: weights are then born sharded on it."""
        if self.mesh_shape is None:
            return
        from triton_dist_tpu.serving import serving_mesh
        if rehearsal:
            from triton_dist_tpu.utils.env import force_virtual_cpu_devices
            force_virtual_cpu_devices(self.devices_needed)
        self.ctx = serving_mesh(*(int(d) for d in self.mesh_shape))

    def weight_shardings(self):
        if self.ctx is None:
            return None
        from triton_dist_tpu.serving import serving_param_shardings
        return serving_param_shardings(self.ctx)

    def build(self, weights: dict) -> None:
        from triton_dist_tpu.serving import (ServingEngine,
                                             ShardedServingEngine)
        pc = self._program_config()
        if self.ctx is None:
            self.eng = ServingEngine(weights, pc, **self.eng_cfg)
        else:
            self.eng = ShardedServingEngine(weights, pc, self.ctx,
                                            wire_dtype=WIRE[self.wire],
                                            **self.eng_cfg)

    def set_weights(self, weights: dict) -> None:
        """Swap the weights of an idle engine (same shapes and placement, so
        nothing recompiles). Used by ``tools/sweep.py`` to read many seeds in
        one process; a benchmark run never calls it."""
        assert self.eng.sched.idle
        self.eng.params = weights

    def close(self) -> None:
        """Drop the engine and its page pool, so that the reference runs in
        the memory they held."""
        self.eng = None

    # -- what the configuration says the program must have resolved to ----
    def resolution(self) -> tuple[dict, bool]:
        """(what the program resolved, whether it is what the file states)."""
        if self.ctx is None:
            return {}, True
        got = {"decode": self.eng.wire_dtype, "chunk": self.eng.wire_dtype_chunk,
               "mesh": self.eng.mesh_desc}
        want = self.wire_resolves_to
        ok = want is None or all(got[k] == want[k] for k in want)
        return got, ok

    # -- driving ---------------------------------------------------------
    def submit(self, prompt, max_new_tokens: int):
        """Returns the program's request object, or None if it was refused."""
        rid = self.eng.submit(prompt, max_new_tokens)
        q = self.eng.sched.queue
        if q and q[-1].rid == rid:
            return q[-1]
        return None

    def step(self) -> bool:
        return self.eng.step()

    @property
    def idle(self) -> bool:
        return self.eng.sched.idle

    @property
    def queue_depth(self) -> int:
        return self.eng.sched.queue_depth

    @staticmethod
    def n_tokens(req) -> int:
        return len(req.generated)

    @staticmethod
    def tokens(req) -> list[int]:
        return list(req.generated)

    @staticmethod
    def finished(req) -> bool:
        return req.state.value == "finished"

    @staticmethod
    def failed(req) -> bool:
        return req.failure is not None or req.state.value in ("failed",
                                                              "rejected")

    @staticmethod
    def admit_clock(req) -> float | None:
        """The program's own ``time.perf_counter()`` stamp of first admission
        (the same clock the benchmark reads)."""
        return req.prefill_start_time

    def counters(self) -> dict:
        """A snapshot of the program's counters and histogram TOTALS (exact);
        never its thinned percentiles."""
        m = self.eng.metrics
        out = {k: v for k, v in m.counters.items()
               if isinstance(v, (int, float))}
        for name, h in m.hist.items():
            out[name + ".total"] = h.total
            out[name + ".count"] = h.count
        for k, v in self.eng.compile_stats.items():
            out["compile." + k] = v
        return out

    def decode_steps(self) -> int:
        return self.eng.metrics.counters["decode_steps"]
