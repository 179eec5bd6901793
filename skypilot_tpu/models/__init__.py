"""Model families: llama / mixtral / gemma share one attention,
KV-cache, and serving-decode stack (models/llama.py); deepseek brings
its own attention and latent paged pool (models/deepseek.py) and brumby
its power retention and a pool of whole-sequence states
(models/brumby.py), phi4flash its five mixers and three kinds of pool
behind one slot (models/phi4flash.py) to the same engine."""
from __future__ import annotations


def model_api(cfg):
    """Config-type -> model module (init/forward/decode/cache fns).

    Static dispatch on the (static-argnum) config dataclass, shared by
    the serving recipe, the decode engine, and the benches so a fifth
    family plugs in at exactly one place.
    """
    from skypilot_tpu.models import (brumby, deepseek, gemma, llama,
                                     mixtral, phi4flash)
    if isinstance(cfg, mixtral.MixtralConfig):
        return mixtral
    if isinstance(cfg, deepseek.DeepseekV3Config):
        return deepseek
    if isinstance(cfg, brumby.BrumbyConfig):
        return brumby
    if isinstance(cfg, phi4flash.Phi4FlashConfig):
        return phi4flash
    if isinstance(cfg, gemma.GemmaConfig):
        return gemma
    return llama


def family_name(cfg) -> str:
    """Config-type -> family string ("llama" / "mixtral" / "gemma" /
    "deepseek" / "brumby" / "phi4flash").

    The stable identifier the tuning manifest keys engine constants
    by (skypilot_tpu/tune/) — the same dispatch as model_api, reduced
    to a name that can live in a JSON file."""
    return model_api(cfg).__name__.rsplit(".", 1)[-1]
