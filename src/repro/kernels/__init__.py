"""Pallas TPU kernels for BiCompFL hot-spots (compiled on a TPU; run in
the Pallas interpreter on the CPU, see ``ops.resolve_interpret``)."""
from . import ops, ref  # noqa: F401
