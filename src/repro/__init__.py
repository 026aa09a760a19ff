"""Hoard-on-TPU: distributed data caching + multi-pod JAX training framework.

Reproduction and extension of Pinto et al., "Hoard: A Distributed Data
Caching System to Accelerate Deep Learning Training on the Cloud" (2018).
See docs/architecture.md for the system map and PERF.md for measured results.
"""

__version__ = "1.0.0"
