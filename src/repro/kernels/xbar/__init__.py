from .ops import SWEEP_CAP, xbar_contend, xbar_contend_sweep

__all__ = ["SWEEP_CAP", "xbar_contend", "xbar_contend_sweep"]
