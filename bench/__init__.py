"""On-chip benchmark of the EFL-FG system: ``python bench/run.py --help``."""
