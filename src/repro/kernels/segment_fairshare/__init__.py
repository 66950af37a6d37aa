"""COO segment reductions for the water-filling solver (Pallas)."""

from .kernel import segment_min, segment_sum
from .ref import segment_min_ref, segment_sum_ref

__all__ = ["segment_sum", "segment_min", "segment_sum_ref",
           "segment_min_ref"]
