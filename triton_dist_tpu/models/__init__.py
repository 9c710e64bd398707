import dataclasses

from triton_dist_tpu.models.llama import (  # noqa: F401
    LlamaConfig, init_params, forward, forward_tp_overlap)
from triton_dist_tpu.models.moe import (  # noqa: F401
    MoEConfig, init_moe_params, moe_forward)

_FAMILIES = {"llama": LlamaConfig, "moe": MoEConfig}


def preset_config(name: str, model: str | None = None,
                  n_layers: int | None = None):
    """Resolve a preset NAME to ``(family, config)`` through the classmethods
    on ``LlamaConfig`` / ``MoEConfig`` (``mistral_7b``, ``mixtral_8x7b``,
    ``tiny``, ...). ``model`` ("llama"/"moe") pins the family; None takes
    the first family that defines the name, dense first — ``tiny`` exists
    in both. ``n_layers`` overrides the depth and nothing else: widths stay
    the published ones."""
    families = (model,) if model is not None else tuple(_FAMILIES)
    for family in families:
        cls = _FAMILIES[family]
        if isinstance(cls.__dict__.get(name), classmethod):
            cfg = getattr(cls, name)()
            if n_layers is not None:
                cfg = (dataclasses.replace(cfg, n_layers=n_layers)
                       if family == "llama" else dataclasses.replace(
                           cfg, base=dataclasses.replace(
                               cfg.base, n_layers=n_layers)))
            return family, cfg
    known = sorted(n for f in families
                   for n, v in _FAMILIES[f].__dict__.items()
                   if isinstance(v, classmethod))
    raise ValueError(f"unknown preset {name!r} for model "
                     f"{model or 'llama/moe'}; known: {', '.join(known)}")
