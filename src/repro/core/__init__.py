"""Core reproduction of "WWW: What, When, Where to Compute-in-Memory".

Public surface:
  GEMM, CiMPrimitive + the four Table-IV prototypes, CiMSystemConfig,
  priority_map (the paper's mapping algorithm), evaluate / evaluate_baseline
  (the analytical cost model), random_search (heuristic mapper baseline),
  decide / plan_workload (the what/when/where planner).
"""
from .baseline import evaluate_baseline
from .campaign import (CampaignResult, CampaignSpec, Constraint,
                       build_config, certify_front, certify_point,
                       parse_precision, run_campaign)
from .cost_model import Metrics, evaluate, evaluate_cim
from .gemm import GEMM, attention_gemms, conv2d_gemm, fc_gemm
from .heuristic import random_search
from .mapping import CiMMapping, priority_map
from .pareto import (ParetoAccumulator, dominates, pareto_mask,
                     pareto_mask_np)
from .memory import (DRAM, LEVELS, RF, SMEM, CiMSystemConfig, configb_count,
                     iso_area_primitive_count)
from .planner import (Decision, decide, make_decision, plan_workload,
                      plan_workload_by_phase, standard_configs, summarize)
from .sweep import (SweepEngine, decide_batched, plan_workload_batched,
                    sweep_evaluate, sweep_evaluate_baseline)
from .primitives import (ANALOG_6T, ANALOG_8T, DIGITAL_6T, DIGITAL_8T,
                         PRIMITIVES, SUPPORTED_BITS, TENSOR_CORE,
                         CiMPrimitive, TensorCoreSpec,
                         mac_energy_pj_from_tops_w, precision_factors,
                         tech_scale_ratio)
from .vectorized import evaluate_batch, exhaustive_best
from .workloads import (BERT_LARGE, DLRM, GPT_J, REAL_WORKLOADS, RESNET50,
                        square_sweep, synthetic_dataset)

__all__ = [
    "GEMM", "CiMPrimitive", "CiMSystemConfig", "CiMMapping", "Metrics",
    "priority_map", "evaluate", "evaluate_cim", "evaluate_baseline",
    "random_search", "decide", "plan_workload", "standard_configs",
    "summarize", "Decision", "plan_workload_by_phase",
    "ANALOG_6T", "ANALOG_8T", "DIGITAL_6T", "DIGITAL_8T", "PRIMITIVES",
    "TENSOR_CORE", "TensorCoreSpec", "DRAM", "SMEM", "RF", "LEVELS",
    "iso_area_primitive_count", "configb_count", "SUPPORTED_BITS",
    "mac_energy_pj_from_tops_w", "precision_factors", "tech_scale_ratio",
    "attention_gemms", "conv2d_gemm", "fc_gemm",
    "BERT_LARGE", "GPT_J", "DLRM", "RESNET50", "REAL_WORKLOADS",
    "synthetic_dataset", "square_sweep",
    "evaluate_batch", "exhaustive_best", "make_decision",
    "SweepEngine", "decide_batched", "plan_workload_batched",
    "sweep_evaluate", "sweep_evaluate_baseline",
    "CampaignSpec", "CampaignResult", "Constraint", "build_config",
    "run_campaign", "certify_point", "certify_front", "parse_precision",
    "ParetoAccumulator", "dominates", "pareto_mask", "pareto_mask_np",
]
