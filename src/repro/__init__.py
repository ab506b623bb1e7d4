"""Reproduction of *Lightning: Scaling the GPU Programming Model Beyond a
Single GPU*, grown into a multi-device jax system."""
