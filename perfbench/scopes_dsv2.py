"""What the latent-attention cell's readers share: whether a run is of that
family, and its counters (``pairs_held``, read as the block-diffusion cell's
readers read it). The routed layer's device time is
``scopes_sdar.moe_seconds``'s (the rows under ``layer/moe/*`` plus XLA's
unscoped ``ragged-dot-*`` by name): the layer is the same code. The shared
expert runs under ``layer/shared/*`` and is no part of it.

The families' predicates overlap: ``scopes_sdar.is_sdar`` asks for
``experts_held`` alone, which this configuration has too, so it is true of
this cell as well. No accepted reader lists this cell, so none reads it with
``counts_sdar``'s keys, and the ``benchmark`` PR that folds the twins
(``PERF.md`` section 7) has to make the predicates exclude one another
before one does."""

from __future__ import annotations

from typing import Dict, Optional

import scopes_sdar
from scopes_sdar import pairs_held  # noqa: F401  (the kind's counter, read the same way)


def is_dsv2(facts: Dict) -> bool:
    return facts.get("kind") == "train" and "kv_lora_rank" in facts.get("model", {})


def moe_seconds(facts: Dict, trace) -> Optional[Dict[str, float]]:
    return scopes_sdar.moe_seconds(facts, trace) if is_dsv2(facts) else None
