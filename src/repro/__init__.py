"""repro — ALPT (AAAI 2023) reproduction + mesh-parallel LM/CTR training.

The package never picks a platform: JAX uses its default backend (the TPU
where one is attached).  Tests and CPU runs choose the CPU themselves with
``JAX_PLATFORMS=cpu``.
"""
