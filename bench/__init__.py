"""The benchmark of the SOAR placement service: ``python3 bench/run.py``."""
