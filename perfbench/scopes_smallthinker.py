"""What the SmallThinker cell's readers share: whether a run is of that
family (by the configuration's ``model_type``, which no other family
states), its counter ``pairs_held``, read as the other routed cells'
readers read it, and the device time of the routed layer and of the flash
kernels by layer type. The routed layer's time is
``scopes_sdar.moe_seconds``'s, everything under ``layer/moe/*``: the router,
whose span (``layer/moe/route``) runs in the attention sublayer ahead of
``attn/qkv`` and ``swa/qkv``, is counted in it with the rest of the layer."""

from __future__ import annotations

from typing import Dict, Optional

import scopes
import scopes_sdar
from scopes_sdar import pairs_held  # noqa: F401  (the kind's counter, read the same way)

MODEL_TYPE = "smallthinker"
SWA = "layer/swa"       # the window layers' attention sublayer
ATTN = "layer/attn"     # the full (NoPE) layers'
FLASH = ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv")


def is_smallthinker(facts: Dict) -> bool:
    return (facts.get("kind") == "train"
            and facts.get("model", {}).get("model_type") == MODEL_TYPE)


def moe_seconds(facts: Dict, trace) -> Optional[Dict[str, float]]:
    return scopes_sdar.moe_seconds(facts, trace) if is_smallthinker(facts) else None


def flash_seconds(facts: Dict, trace, sublayer: str) -> Optional[float]:
    """Device seconds a step of the flash kernels under ``<sublayer>/core``
    (every phase: a forward replayed costs time too); ``None`` where there
    is no scope table or no such kernel."""
    if trace is None or not is_smallthinker(facts):
        return None
    got = scopes.train_step_scopes(facts, trace)
    if got is None:
        return None
    core = sublayer + "/core/"
    seconds = sum(s for (_, scope, _), s in got["rows"].items()
                  if scope.startswith(core) and scope[len(core):] in FLASH)
    return seconds or None
