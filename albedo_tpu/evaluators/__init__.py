"""Evaluation layer: ranking metrics (NDCG@k, Precision@k, MAP) and AUC.

Reference parity: ``evaluators/RankingEvaluator.scala`` (a Spark ``Evaluator``
over ``mllib.RankingMetrics``) and the AUC check at
``LogisticRegressionRanker.scala:354-364``.
"""

from albedo_tpu.evaluators.classification import area_under_roc
from albedo_tpu.evaluators.normal_equations import normal_eq_residual
from albedo_tpu.evaluators.ranking import (
    RankingEvaluator,
    UserItems,
    mean_average_precision,
    ndcg_at_k,
    precision_at_k,
    user_actual_items,
    user_items_from_pairs,
)

__all__ = [
    "RankingEvaluator",
    "UserItems",
    "area_under_roc",
    "mean_average_precision",
    "ndcg_at_k",
    "normal_eq_residual",
    "precision_at_k",
    "user_actual_items",
    "user_items_from_pairs",
]
