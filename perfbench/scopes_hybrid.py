"""Whether a run is of the hybrid decoder's family (gated delta-rule layers
among full-attention ones): by the configuration's ``model_type``, which no
other family states. ``layer_types`` alone does not tell: a decoder of
windowed and full attention layers lists them under the same key."""

from __future__ import annotations

from typing import Dict

MODEL_TYPE = "olmo_hybrid"


def is_hybrid(facts: Dict) -> bool:
    return facts.get("kind") == "train" and facts.get("model", {}).get("model_type") == MODEL_TYPE
