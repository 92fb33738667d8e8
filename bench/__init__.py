"""Chip benchmark of the federated round: see ``bench/run.py``."""
