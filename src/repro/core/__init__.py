# The paper's primary contribution: distributed readability evaluation for
# 2-D graph layouts — five metrics, exact (all-pairs) and enhanced
# (grid/strip divide-and-conquer) algorithms, TPU-adapted (DESIGN.md S2).
#
# The public front door is repro.api (EvalConfig + Evaluator ->
# ReadabilityScores); these re-exports are the building blocks it is
# made of, plus the deprecated evaluate_layout shim.
from repro.core.crossing import (count_crossings_enhanced,  # noqa: F401
                                 count_crossings_exact, count_crossings_strips)
from repro.core.crossing_angle import (crossing_angle_enhanced,  # noqa: F401
                                       crossing_angle_exact,
                                       crossing_angle_strips)
from repro.core.edge_length import edge_length_variation  # noqa: F401
from repro.core.engine import (EngineResult, ReadabilityPlan,  # noqa: F401
                               evaluate_layouts, evaluate_once,
                               evaluate_planned, plan_readability,
                               replan_on_overflow)
from repro.core.keys import (EvalConfig, pow2_bucket,  # noqa: F401
                             topology_hash)
from repro.core.metrics import (ALL_METRICS, ReadabilityReport,  # noqa: F401
                                evaluate_exact, evaluate_layout,
                                report_from_result, reports_from_batch)
from repro.core.min_angle import minimum_angle  # noqa: F401
from repro.core.occlusion import (count_occlusions_enhanced,  # noqa: F401
                                  count_occlusions_exact,
                                  count_occlusions_gridded_batched)
from repro.core.scores import (ReadabilityScores,  # noqa: F401
                               scores_from_batch, scores_from_result)
