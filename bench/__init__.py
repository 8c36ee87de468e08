"""The benchmark: run one cell with ``python3 bench/run.py`` (see run.py)."""
