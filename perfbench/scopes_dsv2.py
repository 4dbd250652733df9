"""What the latent-attention cell's readers share: whether a run is of that
family (by the configuration's ``model_type``, which no other family states),
and its counters (``pairs_held``, read as the block-diffusion cell's readers
read it). The routed layer's device time is ``scopes_sdar.moe_seconds``'s
(the rows under ``layer/moe/*`` plus XLA's unscoped ``ragged-dot-*`` by
name): the layer is the same code. The shared expert runs under
``layer/shared/*`` and is no part of it."""

from __future__ import annotations

from typing import Dict, Optional

import scopes_sdar
from scopes_sdar import pairs_held  # noqa: F401  (the kind's counter, read the same way)


MODEL_TYPE = "deepseek_v2"


def is_dsv2(facts: Dict) -> bool:
    return facts.get("kind") == "train" and facts.get("model", {}).get("model_type") == MODEL_TYPE


def moe_seconds(facts: Dict, trace) -> Optional[Dict[str, float]]:
    return scopes_sdar.moe_seconds(facts, trace) if is_dsv2(facts) else None
