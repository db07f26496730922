"""Chip benchmark of the serving engine: one cell (a model configuration
under one traffic mix) per run.  ``python bench/run.py --help``."""
