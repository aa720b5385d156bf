"""On-chip benchmark of the FFT engine: ``python3 bench/run.py --help``."""
