"""repro: CuAsmRL (CGO'25) on TPU — RL-optimized instruction schedules as a
compiler service inside a multi-pod JAX training/serving framework."""

__version__ = "1.0.0"
