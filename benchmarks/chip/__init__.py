"""Chip benchmark of the distributed-GAN training path (see run.py)."""
