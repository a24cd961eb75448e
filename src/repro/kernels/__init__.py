"""Pallas TPU kernels (compiled on a TPU, interpreted on the CPU) + the
XLA reference path.

Dispatch is declarative: ``plan.ApplyPlan`` names a staged-table
computation and compiles it to one cached program (DESIGN.md §13);
``autotune`` persists the Pallas tile choices the plans resolve.  The
pre-plan ``ops`` wrapper shims are gone — construct plans directly.
"""
from . import autotune, plan, ref, butterfly, shear, spectral
from .plan import ApplyPlan
