"""Overload control & graceful degradation (docs/RESILIENCE.md).

The closed loop from observability to behavior: a deterministic,
hysteresis-guarded shed ladder (controller.py) driven by the SLO
engine's burn-rate alerts and the runtime-stats pressure providers,
priority-aware (priority.py) and cost-model-informed (costmodel.py).
"""

from .controller import (
    DegradationController,
    Disposition,
    L0_NORMAL,
    L1_SHED_OPTIONAL,
    L2_BROWNOUT,
    L3_ADMISSION,
    L4_FAIL_STATIC,
    LEVEL_NAMES,
    TokenBucket,
    default_degradation_controller,
    level_name,
)
from .costmodel import CostModel
from .upstream import (
    DEADLINE_HEADER,
    UpstreamHealth,
    attempt_timeout,
    parse_deadline,
)
from .priority import (
    PRIORITY_CLASSES,
    PRIORITY_HEADER,
    PriorityResolver,
    rank_of,
)

__all__ = [
    "DegradationController", "Disposition", "TokenBucket", "CostModel",
    "PriorityResolver", "PRIORITY_CLASSES", "PRIORITY_HEADER",
    "default_degradation_controller", "rank_of",
    "level_name", "LEVEL_NAMES",
    "L0_NORMAL", "L1_SHED_OPTIONAL", "L2_BROWNOUT", "L3_ADMISSION",
    "L4_FAIL_STATIC",
    "UpstreamHealth", "parse_deadline", "attempt_timeout",
    "DEADLINE_HEADER",
]
