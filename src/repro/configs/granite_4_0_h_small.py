"""Per-arch config module (assignment deliverable f): exposes CONFIG."""
from .registry import GRANITE_4_0_H_SMALL as CONFIG
from .base import reduced

SMOKE = reduced(CONFIG)

# The published scalar multipliers (config.json).  The program has no
# place for them: `repro.models.model.fold_multipliers` folds them into
# the weights they multiply, which is exact for inference.
MULTIPLIERS = {"embedding": 12.0, "residual": 0.22,
               "attention": 1.0 / 128, "logits": 16.0}
