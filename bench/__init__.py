"""Chip benchmark for the serve path: one cell = one model configuration
under one traffic mix, driven through ``Server.serve`` (see ``run.py``)."""
