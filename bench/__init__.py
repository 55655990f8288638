"""The chip benchmark of the mining service: see ``bench/run.py``."""
