"""Per-arch config module (assignment deliverable f): exposes CONFIG."""
from .registry import DEEPSEEK_V2_LITE as CONFIG
from .base import reduced

SMOKE = reduced(CONFIG)
