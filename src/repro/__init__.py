"""repro — Distributed synchronous-SGD training/inference framework in JAX.

Reproduction (and TPU adaptation) of Das et al. 2016, "Distributed Deep
Learning Using Synchronous Stochastic Gradient Descent" (Intel PCL-DNN):
hybrid data/model parallelism, part-reduce/part-broadcast collectives,
balance-equation-driven placement, and blocking-solver-driven Pallas kernels
— extended to ten modern architectures across dense/MoE/SSM/hybrid/VLM/audio
families.  See DESIGN.md.
"""
__version__ = "1.0.0"
