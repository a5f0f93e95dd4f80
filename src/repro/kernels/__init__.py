"""Pallas TPU kernels: compiled by Mosaic on a TPU, interpreted
elsewhere and checked there against ``ref.py`` (see ops.py)."""
from repro.kernels import ops, ref
